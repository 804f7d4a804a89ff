"""Tier-1 wiring for benchmarks/bench_e2e.py (--smoke shape), mirroring
test_bench_st_smoke: the ordering path — including the new
dispatcher↔executor execution-lane handoff — gets a collection-time
guard (the bench module must import) and a runtime guard (the lane
must order real traffic).

TPUBFT_THREADCHECK=1 arms utils/racecheck across the run: every
make_lock in the handoff (execution lane condition, blockchain staging,
clients manager) becomes a CheckedLock feeding the global lock-order
graph, so an inversion between the dispatcher and executor threads
raises inside this test instead of deadlocking production. The stall
watchdog must also stay quiet."""
import os

import pytest


@pytest.fixture
def threadcheck(monkeypatch):
    monkeypatch.setenv("TPUBFT_THREADCHECK", "1")
    from tpubft.utils import racecheck
    assert racecheck.enabled()
    yield


def test_bench_e2e_smoke(threadcheck):
    from benchmarks.bench_e2e import smoke
    out = smoke(secs=2.0, clients=2)
    assert out["lane"]["ok"], out
    # racecheck: no dispatcher/executor stall was reported during the
    # run (lock-order inversions raise inside the run itself)
    assert out["stall_reports"] == 0, out
    # the instrumentation really fired across the handoff: a lane run
    # holds the blockchain staging lock while consulting the clients
    # manager (at-most-once check), so that nesting edge MUST be in the
    # recorded lock-order graph — if it is absent, the CheckedLock
    # plumbing silently stopped covering the dispatcher↔executor paths
    from tpubft.utils.racecheck import get_checker
    edges = get_checker()._edges
    assert "clients_manager" in edges.get("kvbc.staging", set()), edges
