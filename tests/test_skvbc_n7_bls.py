"""The 7-replica threshold-BLS SimpleKVBC cluster (`skvbc_n7_bls`) on
the normal path, tiny: `InProcessCluster(f=2, c=0)`,
`SkvbcHandler(merkle=True)` over `KeyValueBlockchain` on the native
kvlog engine, `SkvbcClient`, every `ReplicaConfig` field but the
configuration's five at its default. Writes read back against a
dictionary, the seven ledgers end byte-identical, and the
Prepare/Commit/full-commit-proof certificates the replicas hold — in
their windows and in their stores — equal the plain-integer reference's
(`cellbench/reference/certs.py`) on both commit paths. Each case has a
time limit of its own."""
import functools
import os
import sys
import threading
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from cellbench.drivers import served_bls  # noqa: E402
from cellbench.reference import bls as ref_bls  # noqa: E402
from cellbench.reference.certs import (ThresholdSystem,  # noqa: E402
                                       interpolate_at_zero)
from tpubft.apps.skvbc import SkvbcClient, SkvbcHandler  # noqa: E402
from tpubft.kvbc import KeyValueBlockchain  # noqa: E402
from tpubft.kvbc.replica import open_db  # noqa: E402
from tpubft.storage.metadata import (CONSENSUS_META_FAMILIES,  # noqa: E402
                                     DBPersistentStorage)
from tpubft.testing import InProcessCluster  # noqa: E402
from tpubft.utils import flight  # noqa: E402

CONFIG = dict(threshold_scheme="threshold-bls", client_sig_scheme="ed25519",
              view_change_timer_ms=60000)
TIMEOUT_MS = 45000


def within(seconds: float):
    """The test's own time limit: its body runs on a thread, and a body
    still running after `seconds` fails the test."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            box = {}

            def body():
                try:
                    fn(*args, **kwargs)
                except BaseException as e:  # noqa: BLE001 — re-raised below
                    box["error"] = e
            t = threading.Thread(target=body, daemon=True, name=fn.__name__)
            t.start()
            t.join(seconds)
            assert not t.is_alive(), f"still running after {seconds} s"
            if "error" in box:
                raise box["error"]
        return run
    return wrap


def _wait(pred, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline and not pred():
        time.sleep(0.05)
    return pred()


class Run:
    """One tiny run of the cluster: three closed-loop writers (two of
    single writes, one of `write_batch`), then what the served cell's
    comparison holds, on the live cluster."""

    def __init__(self, tmp_path, backend: str, byzantine=None,
                 messages: int = 4) -> None:
        self.dbs, self.written = {}, {}
        flight.reset()

        def handler_factory(r):
            self.dbs[r] = open_db(
                str(tmp_path / f"replica-{r}.kvlog"), sync_writes=False,
                sync_families=CONSENSUS_META_FAMILIES)
            return SkvbcHandler(
                KeyValueBlockchain(self.dbs[r],
                                   use_device_hashing=backend == "tpu"),
                merkle=True)

        self.cluster = InProcessCluster(
            f=2, c=0, num_clients=3, handler_factory=handler_factory,
            storage_factory=lambda r: DBPersistentStorage(self.dbs[r]),
            cfg_overrides=dict(CONFIG, crypto_backend=backend),
            byzantine=byzantine, seed=b"skvbc-n7-bls-test")
        self.messages = messages

    def __enter__(self) -> "Run":
        cl = self.cluster.start()
        self.kvs = [SkvbcClient(cl.client(i)) for i in range(3)]
        errors = []

        def writer(i):
            try:
                for j in range(self.messages):
                    pairs = [(b"key-%d-%d-%d" % (i, j, w),
                              b"value-%d-%d" % (j, w))
                             for w in range(4 if i == 2 else 1)]
                    if i == 2:
                        replies = self.kvs[i].write_batch(
                            [[p] for p in pairs], timeout_ms=TIMEOUT_MS)
                    else:
                        replies = [self.kvs[i].write(
                            pairs, timeout_ms=TIMEOUT_MS)]
                    assert all(r.success for r in replies)
                    self.written.update(pairs)
            except BaseException as e:  # noqa: BLE001 — reported below
                errors.append(e)
        threads = [threading.Thread(target=writer, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(4 * TIMEOUT_MS / 1e3)
        assert not any(t.is_alive() for t in threads) and not errors, errors
        self.blocks = len(self.written)     # one pair a write, one a block
        return self

    def __exit__(self, *exc) -> None:
        self.cluster.stop()
        for db in self.dbs.values():
            db.close()

    def assert_ledgers_and_reads(self) -> None:
        cl = self.cluster
        chains = [cl.handlers[r].blockchain for r in range(cl.n)]
        assert _wait(lambda: all(bc.last_block_id == self.blocks
                                 for bc in chains)), \
            [bc.last_block_id for bc in chains]
        heads = {(bc.last_block_id, bc.state_digest(), bc.merkle_root("kv"))
                 for bc in chains}
        assert len(heads) == 1, heads
        got = self.kvs[0].read(sorted(self.written), timeout_ms=TIMEOUT_MS)
        assert got == self.written
        assert [cl.metric(r, "gauges", "view") for r in range(cl.n)] \
            == [0] * cl.n

    def certificates(self) -> dict:
        """Every certificate every replica holds, compared."""
        held = served_bls.held_certificates(self.cluster, self.dbs)
        got = served_bls.compare_certificates(self.cluster, held,
                                              sorted(held))
        assert got["compared"] > 0 and got["unsound_systems"] == 0
        assert got["mismatches"] == 0 and got["unverified"] == 0, got
        # both places: the windows and the stores
        places = {where for rows in held.values()
                  for _r, where, *_ in rows}
        assert places == {"window", "persisted"}
        return got["by_kind"]


def test_reference_interpolates_the_dealers_secret():
    poly = ref_bls.Polynomial([11, 22, 33, 44, 55])
    points = [(i, poly.at(i)) for i in range(1, 8)]
    assert interpolate_at_zero(points[:5]) == 11
    assert interpolate_at_zero(points[2:]) == 11
    system = ThresholdSystem(5, [poly.at(i) for i in range(1, 8)])
    assert system.consistent() and system.secret == 11
    assert system.certificate(b"digest") \
        == ref_bls.expected_certificate(poly, b"digest")
    assert not ThresholdSystem(
        5, [poly.at(i) + (i == 7) for i in range(1, 8)]).consistent()


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@within(150)
def test_n7_threshold_bls_cluster_serves_and_certifies(tmp_path, backend):
    """The fast path (7 of 7) as the cluster takes it unhindered; `tpu`
    is the XLA-CPU rehearsal of the device backend."""
    with Run(tmp_path, backend) as run:
        run.assert_ledgers_and_reads()
        by_kind = run.certificates()
        assert by_kind.get("fast", 0) > 0, by_kind


@within(150)
def test_a_replica_held_back_takes_the_slow_path(tmp_path):
    """Replica 6 sends everything 600 ms late: no slot gathers 7 of 7
    inside `fast_path_timeout_ms`, so each is demoted and commits on
    Prepare and Commit certificates of 5 of 7."""
    with Run(tmp_path, "cpu", byzantine={6: "delay-600"},
             messages=2) as run:
        run.assert_ledgers_and_reads()
        by_kind = run.certificates()
        assert by_kind.get("prepare", 0) > 0 and by_kind.get("commit", 0) > 0
        cl = run.cluster
        assert sum(cl.metric(r, "counters", "slow_path_commits")
                   for r in range(cl.n)) > 0


@within(150)
def test_a_junk_share_offered_to_a_collector_is_dropped(tmp_path):
    """Replica 3's shares are junk: a 7-of-7 combine cannot land, the
    slow path's collectors identify and drop the share, and the
    certificates are still the reference's."""
    with Run(tmp_path, "cpu", byzantine={3: "corrupt-shares"},
             messages=2) as run:
        run.assert_ledgers_and_reads()
        by_kind = run.certificates()
        assert by_kind.get("commit", 0) > 0, by_kind
        assert "fast" not in by_kind


@within(150)
def test_the_certificate_path_writes_its_spans_and_the_commit_path(tmp_path):
    with Run(tmp_path, "cpu") as run:
        run.assert_ledgers_and_reads()
        rows = flight.slot_tracker().recent(limit=flight.SlotTracker.KEEP)
        assert rows and all(r["path"] in ("fast", "slow") for r in rows)
        paths = {(r["rid"], r["seq"]): r["path"] for r in rows}
        for seq, held in served_bls.held_certificates(
                run.cluster, run.dbs).items():
            for r, _where, kind, _pp, _cert in held:
                if kind != "prepare" and (r, seq) in paths:
                    assert paths[r, seq] == \
                        ("fast" if kind == "fast" else "slow")
        slots = {r["seq"] for r in rows}
        for name in ("share_sign", "bls_share_decompress", "bls_combine",
                     "bls_pairing_verify"):
            spans, from_ns = flight.span_events_tail(name)
            assert spans and from_ns == 0, name
            assert all(us >= 0 for _t, _seq, us in spans)
        # a share span names its slot; the fused path's spans say how
        # many slots or certificates they covered, never one a share
        assert {seq for _t, seq, _us
                in flight.span_events("share_sign")} >= slots
        for name in ("bls_share_decompress", "bls_combine",
                     "bls_pairing_verify"):
            covered = [seq for _t, seq, _us in flight.span_events(name)]
            assert min(covered) >= 1
        flushes = len(flight.span_events("bls_combine"))
        assert len(flight.span_events("bls_share_decompress")) == flushes
        assert flushes <= 3 * len(slots)
