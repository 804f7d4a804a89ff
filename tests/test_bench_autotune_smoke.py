"""Tier-1 wiring for benchmarks/bench_autotune.py (--smoke shape): the
autotuner A/B harness must order real traffic on both legs — cold
static knobs, and the same cold knobs with the controllers live at
full cadence against the in-process cluster — under TPUBFT_THREADCHECK
so the tuner-thread ⇄ actuator (batcher/lane/admission) lock orders
ride the runtime checker. Timing gates (the 0.9x acceptance ratio)
stay out of tier-1 — host noise."""
import pytest


@pytest.fixture
def threadcheck(monkeypatch):
    monkeypatch.setenv("TPUBFT_THREADCHECK", "1")
    from tpubft.utils import racecheck
    assert racecheck.enabled()
    yield


def test_bench_autotune_smoke(threadcheck):
    from tpubft.utils.racecheck import get_watchdog
    before = get_watchdog().stall_reports
    from benchmarks.bench_autotune import smoke
    out = smoke()
    assert out["cold"]["ok"], out
    assert out["autotune"]["ok"], out
    # no stall / lock-order report with the controllers poking live
    # actuators mid-traffic (inversions raise inside the run itself)
    assert get_watchdog().stall_reports == before, out
    assert out["stall_reports"] == 0, out
