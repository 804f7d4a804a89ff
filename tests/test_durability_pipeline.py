"""Group-commit durability pipeline (ISSUE 15 acceptance).

Covers: PendingStore overlay semantics (last-writer-wins staging,
apply-ordered removal, merged visibility through the blockchain's
pending view incl. range scans), group formation (group_max cut,
window expiry, flush), watermark monotonicity and reply gating (a held
pipeline means NO reply, NO last_executed advance — release unblocks
both), drain-barrier discipline, seal backpressure, ledger
byte-equivalence across admission shapes, the `dur.group_fsync` crash drill
(exactly-once replay, `last_executed` monotone across the restart),
and the autotuner seed write-back round trip (ROADMAP 8d)."""
import json
import os
import threading
import time

from tpubft.apps import skvbc
from tpubft.consensus.persistent import FilePersistentStorage
from tpubft.durability import DurabilityPipeline, PendingStore, SealedRun
from tpubft.kvbc import KeyValueBlockchain
from tpubft.storage.interfaces import WriteBatch
from tpubft.storage.memorydb import MemoryDB
from tpubft.testing.cluster import InProcessCluster


def _wait(pred, timeout=25.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


def _kv_cluster(tmp_path, dbs, **overrides):
    def handler_factory(r):
        db = dbs.setdefault(r, MemoryDB())
        return skvbc.SkvbcHandler(
            KeyValueBlockchain(db, use_device_hashing=False))

    def storage_factory(r):
        return FilePersistentStorage(str(tmp_path / f"r{r}.wal"))

    return InProcessCluster(f=1, handler_factory=handler_factory,
                            storage_factory=storage_factory,
                            cfg_overrides=overrides or None)


# ---------------------------------------------------------------------
# PendingStore unit semantics
# ---------------------------------------------------------------------

def test_pending_store_stage_lookup_apply():
    st = PendingStore("t")
    n1 = st.stage({b"\x01ak1": b"v1", b"\x01ak2": None})
    n2 = st.stage({b"\x01ak1": b"v2"})      # later run overwrites
    assert n2 == n1 + 1
    assert st.lookup(b"\x01ak1") == (n2, b"v2")
    assert st.lookup(b"\x01ak2") == (n1, None)   # pending delete
    assert st.lookup(b"\x01ak3") is None
    # applying run 1 must NOT drop k1 (run 2's value still pending)
    st.mark_applied(n1)
    assert st.lookup(b"\x01ak1") == (n2, b"v2")
    assert st.lookup(b"\x01ak2") is None
    st.mark_applied(n2)
    assert st.empty
    assert st.wait_empty(0.1)


def test_pending_view_point_and_range_merge():
    """The blockchain-side read view: point gets consult the overlay,
    range scans MERGE pending keys into the base iteration (pending
    overwrites win, pending deletes hide base rows, pure-pending keys
    appear in order)."""
    from tpubft.kvbc.blockchain import _PendingView
    base = MemoryDB()
    base.put(b"a", b"base-a", b"fam")
    base.put(b"c", b"base-c", b"fam")
    base.put(b"d", b"base-d", b"fam")
    store = PendingStore("t")
    view = _PendingView(base, store)
    from tpubft.storage.interfaces import fkey
    store.stage({fkey(b"fam", b"b"): b"pend-b",      # pure pending
                 fkey(b"fam", b"c"): b"pend-c",      # overwrite
                 fkey(b"fam", b"d"): None})          # pending delete
    assert view.get(b"b", b"fam") == b"pend-b"
    assert view.get(b"c", b"fam") == b"pend-c"
    assert view.get(b"d", b"fam") is None
    assert view.get(b"a", b"fam") == b"base-a"
    assert list(view.range_iter(b"fam")) == [
        (b"a", b"base-a"), (b"b", b"pend-b"), (b"c", b"pend-c")]
    # empty overlay falls straight through
    st2 = PendingStore("t2")
    v2 = _PendingView(base, st2)
    assert list(v2.range_iter(b"fam")) == [
        (b"a", b"base-a"), (b"c", b"base-c"), (b"d", b"base-d")]


# ---------------------------------------------------------------------
# pipeline unit semantics (stub replica)
# ---------------------------------------------------------------------

class _Run:
    def __init__(self, last):
        self.first = last
        self.last = last


class _Clients:
    def __init__(self):
        self.executed = []

    def on_request_executed(self, c, s, r):
        self.executed.append((c, s))


class _Lane:
    def __init__(self):
        self.completed = []

    def complete_durable(self, run):
        self.completed.append(run.last)


class _Incoming:
    def __init__(self):
        self.pushes = 0

    def push_internal_once(self, _key):
        self.pushes += 1


class _SyncDB(MemoryDB):
    def __init__(self):
        super().__init__()
        self.syncs = 0
        self.group_writes = []

    def sync(self):
        self.syncs += 1

    def write_group(self, batches):
        self.group_writes.append(len(batches))
        super().write_group(batches)


class _StubReplica:
    def __init__(self):
        self.id = 0
        self.last_executed = 0
        self.clients = _Clients()
        self.exec_lane = _Lane()
        self.incoming = _Incoming()
        self.aggregator = None
        self.health = None


def _seal(pipe, seq, db=None, store=None, key=None, val=b"v"):
    batch = run_no = None
    if db is not None and store is not None:
        batch = WriteBatch().put(key or b"k%d" % seq, val, b"blk")
        run_no = store.stage(dict(batch.ops))
    pipe.seal(SealedRun(run=_Run(seq), executed_now=[(9, seq, None)],
                        batch=batch, run_no=run_no, db=db,
                        sync_dbs=(db,) if db is not None and batch is None
                        else ()))


def test_group_formation_and_watermark():
    """group_max cuts a full group immediately; the watermark, the
    completions, the at-most-once visibility and ONE concatenated
    write_group + ONE sync per group all land together."""
    r = _StubReplica()
    db = _SyncDB()
    pipe = DurabilityPipeline(r, group_max=4, window_us=60_000_000)
    store = pipe.pending
    pipe.hold()
    pipe.start()
    try:
        for seq in range(1, 5):
            _seal(pipe, seq, db=db, store=store)
        assert pipe.watermark == 0 and not r.exec_lane.completed
        pipe.release()
        assert _wait(lambda: pipe.watermark == 4, 10)
        assert r.exec_lane.completed == [1, 2, 3, 4]
        assert r.clients.executed == [(9, s) for s in range(1, 5)]
        assert db.group_writes == [4]     # ONE concatenated apply
        assert db.syncs == 1              # ONE fsync for the group
        assert store.empty                # overlay fully retired
        assert r.incoming.pushes == 1
        assert db.get(b"k3", b"blk") == b"v"
    finally:
        pipe.stop()


def test_window_expiry_forms_partial_group():
    r = _StubReplica()
    db = _SyncDB()
    pipe = DurabilityPipeline(r, group_max=64, window_us=20_000)
    pipe.start()
    try:
        _seal(pipe, 1, db=db, store=pipe.pending)
        _seal(pipe, 2, db=db, store=pipe.pending)
        # nowhere near group_max: the 20ms window must cut the group
        assert _wait(lambda: pipe.watermark == 2, 10)
        assert db.syncs == 1 and db.group_writes == [2]
    finally:
        pipe.stop()


def test_drain_flushes_and_seal_backpressure():
    r = _StubReplica()
    pipe = DurabilityPipeline(r, group_max=2, window_us=60_000_000)
    pipe.hold()
    pipe.start()
    try:
        for seq in range(1, 4):
            _seal(pipe, seq)
        assert not pipe.drain(timeout=0.3)      # held: cannot drain
        # fill the queue to the bound: the next seal must BLOCK (lane
        # backpressure), then complete once the io thread resumes
        for seq in range(4, pipe._queue_max + 1):
            _seal(pipe, seq)
        blocked = threading.Event()

        def late_seal():
            _seal(pipe, pipe._queue_max + 1)
            blocked.set()

        t = threading.Thread(target=late_seal, daemon=True)
        t.start()
        assert not blocked.wait(0.3), "seal did not backpressure"
        pipe.release()
        assert blocked.wait(10)
        assert pipe.drain(timeout=10)
        assert pipe.idle() and pipe.watermark == pipe._queue_max + 1
    finally:
        pipe.stop()


def test_group_commit_failure_retries_never_completes_early():
    """A failing fsync requeues the WHOLE group: nothing completes,
    nothing reaches the reply cache, the watermark holds — and the
    group lands once the disk recovers."""
    r = _StubReplica()

    class _FlakyDB(_SyncDB):
        def __init__(self):
            super().__init__()
            self.fail = True

        def sync(self):
            if self.fail:
                raise OSError("injected fsync failure")
            super().sync()

    db = _FlakyDB()
    pipe = DurabilityPipeline(r, group_max=2, window_us=0)
    pipe.RETRY_DELAY_S = 0.05
    pipe.start()
    try:
        _seal(pipe, 1, db=db, store=pipe.pending)
        assert _wait(lambda: pipe.m_retries.value >= 1, 10)
        assert pipe.watermark == 0 and not r.exec_lane.completed
        assert not r.clients.executed
        db.fail = False
        assert _wait(lambda: pipe.watermark == 1, 10)
        assert r.exec_lane.completed == [1]
    finally:
        pipe.stop()


def test_drain_on_idle_does_not_poison_window():
    """A barrier drain against an already-idle pipeline must not leave
    a stale flush request behind — the next sealed run would commit as
    an unamortized group of one, once per barrier event."""
    r = _StubReplica()
    db = _SyncDB()
    pipe = DurabilityPipeline(r, group_max=64, window_us=60_000_000)
    pipe.start()
    try:
        assert pipe.drain(timeout=2)       # idle drain: nothing to do
        _seal(pipe, 1, db=db, store=pipe.pending)
        time.sleep(0.4)
        assert pipe.watermark == 0, \
            "stale flush bypassed the group window"
        pipe.flush()
        assert _wait(lambda: pipe.watermark == 1, 10)
    finally:
        pipe.stop()


def test_pending_barrier_waits_for_durability_not_just_overlay():
    """The direct-write barrier must see an applied-but-unsynced group
    parked for an fsync retry (overlay already empty!) as NOT drained:
    a direct head write in that window would be overwritten by the
    retry's re-apply of an older head."""
    from tpubft.kvbc.blockchain import BlockchainError

    class _FlakyDB(_SyncDB):
        fail = True

        def sync(self):
            if self.fail:
                raise OSError("injected fsync failure")
            super().sync()

    bc = KeyValueBlockchain(MemoryDB(), use_device_hashing=False)
    r = _StubReplica()
    db = _FlakyDB()
    pipe = DurabilityPipeline(r, group_max=1, window_us=0)
    pipe.RETRY_DELAY_S = 0.05
    bc.attach_durability(pipe.pending, drain_fn=pipe.drain)
    pipe.start()
    try:
        _seal(pipe, 1, db=db, store=pipe.pending)
        assert _wait(lambda: pipe.m_retries.value >= 1, 10)
        # the group APPLIED (overlay retired) but never fsynced: the
        # overlay alone looks clear, yet the barrier must refuse
        assert pipe.pending.empty
        assert not pipe.idle()
        try:
            bc._pending_barrier(timeout=0.3)
            raise AssertionError("barrier passed with an unsynced "
                                 "group parked for retry")
        except BlockchainError:
            pass
        db.fail = False
        assert _wait(lambda: pipe.watermark == 1, 10)
        bc._pending_barrier(timeout=5)     # durable now: barrier opens
    finally:
        pipe.stop()


def test_inflight_dedup_across_sealed_runs():
    """Exactly-once across back-to-back runs while durability is
    pending (the bug the `spec-abort-equivocation` chaos seed 20260804
    caught): a request executed in a SEALED-but-not-yet-fsynced run
    must NOT execute again when a later slot re-proposes it (view
    change after an equivocation) — the ClientsManager entry is
    deliberately invisible until the group fsync, so the lane's
    in-flight map is the only thing standing between one write and a
    duplicate block."""
    from tpubft.consensus.execution import CompletedRun, ExecutionLane

    class _Reply:
        def pack(self):
            return b"stashed-wire"

    class _Cl:
        def was_executed(self, c, s):
            return False

        def cached_reply(self, c, s):
            return None

    class _Cfg:
        time_service_enabled = False

    class _Slow:
        enabled = False

    class _Rep:
        id = 0
        clients = _Cl()
        cfg = _Cfg()
        _slowdown = _Slow()
        executions = 0

        def _execute_request(self, req, seq):
            _Rep.executions += 1
            return b"payload"

        def _build_reply(self, client, req_seq, payload, pages_wb,
                         defer_sign=False):
            return _Reply(), b"wire"

        class m_exec_lane_depth:  # noqa: N801 — gauge stub
            @staticmethod
            def set(v):
                pass

    class _Req:
        sender_id = 9
        req_seq_num = 5

    class _PP:
        time = None

        def client_requests(self):
            return [_Req()]

    r = _Rep()
    lane = ExecutionLane(r, 16, 150)      # thread never started
    pp = _PP()
    # run A executes the request
    lane._run_seen = set()
    res_a = CompletedRun(first=1, last=1, n_requests=0)
    executed_a = []
    lane._execute_slot(1, pp, WriteBatch(), res_a, executed_a)
    assert _Rep.executions == 1 and executed_a
    # seal publication (what _apply_run does before pipe.seal)
    with lane._cond:
        for client, req_seq, reply in executed_a:
            lane._inflight[(client, req_seq)] = reply
    # run B re-proposes the SAME request before the group fsync landed
    lane._run_seen = set()
    res_b = CompletedRun(first=2, last=2, n_requests=0)
    lane._execute_slot(2, pp, WriteBatch(), res_b, [])
    assert _Rep.executions == 1, "request executed twice pre-durability"
    assert res_b.replies == [(9, b"stashed-wire")]
    # completion (post-fsync, post-on_request_executed) erases the entry
    done = CompletedRun(first=1, last=1, n_requests=1,
                        reply_keys=[(9, 5)])
    lane.complete_durable(done)
    assert (9, 5) not in lane._inflight
    assert lane.pop_completed() == [done]


# ---------------------------------------------------------------------
# reply gating on a live cluster
# ---------------------------------------------------------------------

def test_reply_never_precedes_group_fsync(tmp_path):
    """Hold every replica's io thread: executed runs stay sealed, no
    reply reaches the client and last_executed never advances past the
    watermark; releasing the pipelines delivers the SAME write."""
    dbs = {}
    with _kv_cluster(tmp_path, dbs, durability_window_us=0) as cluster:
        kv = skvbc.SkvbcClient(cluster.client(0))
        assert kv.write([(b"warm", b"w")], timeout_ms=15000).success
        # quiesce: the ack needs only f+1 replies — laggards integrate
        # their (already-durable) warm group a beat later, which must
        # not read as a gating violation below
        assert _wait(lambda: all(
            cluster.replicas[r].last_executed >= 1
            and cluster.replicas[r].durability.idle()
            for r in range(4)))
        base = [cluster.replicas[r].last_executed for r in range(4)]
        for r in range(4):
            cluster.replicas[r].durability.hold()
        box = {}

        def bg_write():
            box["r"] = kv.write([(b"gated", b"g")], timeout_ms=30000)

        t = threading.Thread(target=bg_write, daemon=True)
        t.start()
        time.sleep(1.5)
        # executed (sealed) but NOT durable: no ack, no watermark move
        assert "r" not in box, "reply preceded its group's fsync"
        for r in range(4):
            rep = cluster.replicas[r]
            assert rep.last_executed == base[r], \
                "last_executed advanced past the durability watermark"
            assert rep.last_executed <= rep.durability.watermark
        for r in range(4):
            cluster.replicas[r].durability.release()
        t.join(30)
        assert box.get("r") is not None and box["r"].success
        for r in range(4):
            rep = cluster.replicas[r]
            assert _wait(lambda rep=rep:
                         rep.last_executed <= rep.durability.watermark
                         and rep.durability.idle(), 10)


def test_status_and_flight_surface(tmp_path):
    dbs = {}
    with _kv_cluster(tmp_path, dbs) as cluster:
        kv = skvbc.SkvbcClient(cluster.client(0))
        for i in range(3):
            assert kv.write([(b"s%d" % i, b"v")],
                            timeout_ms=15000).success
        rep = cluster.replicas[0]
        assert _wait(lambda: rep.durability.m_groups.value > 0)
        payload = json.loads(rep.durability.render())
        assert payload["watermark"] >= 1
        assert payload["groups"] >= 1 and payload["runs"] >= 1
        assert payload["group_max"] == rep.cfg.durability_group_max
        # the dur_wm_lag gauge exists and reads 0 once idle
        assert _wait(lambda: cluster.metric(
            0, "gauges", "dur_wm_lag", component="durability") == 0)


# ---------------------------------------------------------------------
# ledger byte-equivalence across admission shapes (the pipeline's own
# shapes are held to a plain sequential apply in the lane's tests)
# ---------------------------------------------------------------------

def _run_workload(tmp_path, sub, n_writes=6, **overrides):
    dbs = {}
    subdir = tmp_path / sub
    subdir.mkdir()
    with _kv_cluster(subdir, dbs, **overrides) as cluster:
        cl = cluster.client(0)
        cl._req_seq = 1_000_000     # pin reply-ring page comparability
        kv = skvbc.SkvbcClient(cl)
        for i in range(n_writes):
            assert kv.write([(b"k%d" % i, b"v%d" % i)],
                            timeout_ms=15000).success
        assert _wait(lambda:
                     cluster.handlers[0].blockchain.last_block_id
                     == n_writes)
        bc = cluster.handlers[0].blockchain
        assert _wait(lambda: cluster.metric(
            0, "counters", "dur_groups", component="durability") > 0)
        pages = cluster.replicas[0].res_pages
        ring = sorted((k, v) for k, v in pages.all_pages()
                      if k[2:].startswith((b"clientreplies", b"clients")))
        return {
            "state_digest": bc.state_digest(),
            "reply_pages": ring,
            "blocks": [bc.get_raw_block(b)
                       for b in range(1, n_writes + 1)],
        }


def test_sharded_admission_ledger_equivalence(tmp_path):
    """ISSUE 19 key-sharded admission, the durable half of the
    equivalence claim: the same workload through sharded vs shared-
    buffer admission (same worker count) lands byte-identical ledger
    blocks, state digest, and reply-ring / at-most-once pages."""
    on = _run_workload(tmp_path, "shard_on", admission_workers=2)
    off = _run_workload(tmp_path, "shard_off", admission_workers=2,
                        admission_key_sharding=False)
    assert on["state_digest"] == off["state_digest"]
    assert on["blocks"] == off["blocks"]
    assert on["reply_pages"] and on["reply_pages"] == off["reply_pages"]


# ---------------------------------------------------------------------
# crash-restart at dur.group_fsync: exactly-once, watermark monotone
# ---------------------------------------------------------------------

def test_crash_restart_at_group_fsync_exactly_once(tmp_path):
    """Park a replica's io thread AT dur.group_fsync (group applied,
    fsync never issued, watermark unpublished), then recover it
    standalone from its durable state: the committed suffix replays
    exactly once (at-most-once pages dedup), last_executed is monotone
    across the crash-restart, and the recovered ledger digest matches
    the cluster's."""
    from tpubft.comm.loopback import LoopbackBus
    from tpubft.consensus.replica import Replica
    from tpubft.testing import crashpoints as cp
    from tpubft.utils.config import ReplicaConfig
    victim = 2
    dbs = {}
    hit = threading.Event()

    def crash_here():
        hit.set()
        cp.park()

    try:
        with _kv_cluster(tmp_path, dbs) as cluster:
            kv = skvbc.SkvbcClient(cluster.client(0))
            assert kv.write([(b"pre", b"1")], timeout_ms=15000).success
            assert _wait(lambda:
                         cluster.replicas[victim].last_executed >= 1)
            frozen_at = cluster.replicas[victim].last_executed
            cp.arm("dur.group_fsync", rid=victim, action=crash_here)
            assert kv.write([(b"boom", b"2")], timeout_ms=15000).success
            assert hit.wait(15)
            assert cluster.replicas[victim].last_executed == frozen_at
            target_digest = \
                cluster.handlers[0].blockchain.state_digest()
            keys = cluster.keys
            pages = cluster._pages_dbs[victim]
            cp.disarm_all()
            cp.release_parked()
        # ---- standalone recovery from the victim's durable state ----
        cfg = ReplicaConfig(replica_id=victim, f_val=1,
                            num_of_client_proxies=2)
        recovered = Replica(
            cfg, keys.for_node(victim), LoopbackBus().create(victim),
            skvbc.SkvbcHandler(
                KeyValueBlockchain(dbs[victim],
                                   use_device_hashing=False)),
            storage=FilePersistentStorage(
                str(tmp_path / f"r{victim}.wal")),
            reserved_pages=pages)
        assert recovered.last_executed >= frozen_at, \
            "last_executed regressed across the crash-restart"
        assert recovered.handler.blockchain.state_digest() \
            == target_digest, "replay diverged after group-fsync crash"
    finally:
        cp.disarm_all()
        cp.release_parked()


# ---------------------------------------------------------------------
# autotuner seed write-back round trip (ROADMAP 8d)
# ---------------------------------------------------------------------

def test_autotune_seed_writeback_round_trip(tmp_path):
    """A controller's converged operating point written on clean
    shutdown re-baselines a fresh registry: values AND degraded-reset
    defaults match the converged point, frozen pins survive."""
    from tpubft.tuning.controller import TuningController
    from tpubft.tuning.knobs import Knob, KnobRegistry, load_seed
    path = str(tmp_path / "seed.json")
    reg = KnobRegistry(name="t-src")
    reg.register(Knob(name="durability_group_max", value=8, default=8,
                      lo=1, hi=64))
    reg.register(Knob(name="combine_flush_us", value=300, default=300,
                      lo=0, hi=20000))
    ctl = TuningController(reg, name="t-src")
    reg.set("durability_group_max", 24, source="policy")
    reg.freeze("combine_flush_us", 1200)
    assert ctl.write_seed(path) == path
    # fresh boot: seed re-baselines values AND defaults
    reg2 = KnobRegistry(name="t-dst")
    reg2.register(Knob(name="durability_group_max", value=8, default=8,
                       lo=1, hi=64))
    reg2.register(Knob(name="combine_flush_us", value=300, default=300,
                       lo=0, hi=20000))
    assert load_seed(reg2, path) == 2
    assert reg2.get("durability_group_max") == 24
    assert reg2.knob("durability_group_max").default == 24
    assert reg2.get("combine_flush_us") == 1200
    assert reg2.knob("combine_flush_us").frozen
    # converged point survives a second round trip unchanged
    ctl2 = TuningController(reg2, name="t-dst")
    path2 = str(tmp_path / "seed2.json")
    ctl2.write_seed(path2)
    with open(path2) as fh:
        payload = json.load(fh)
    assert payload["knobs"]["durability_group_max"] == 24
    assert payload["knobs"]["combine_flush_us"] == {
        "value": 1200, "frozen": True}


def test_replica_stop_writes_seed(tmp_path):
    """Clean replica shutdown with autotune_seed_file configured writes
    the converged operating point back (the warm-boot handoff)."""
    path = str(tmp_path / "replica-seed.json")
    dbs = {}
    with _kv_cluster(tmp_path, dbs, autotune_enabled=True,
                     autotune_seed_file=path) as cluster:
        assert cluster.replicas[0].tuning is not None
    assert os.path.exists(path)
    with open(path) as fh:
        payload = json.load(fh)
    assert "durability_group_max" in payload["knobs"]
    assert "combine_flush_us" in payload["knobs"]
