"""Execution-lane fault matrix (ISSUE 3 acceptance): crash between
commit and apply, view change with a non-empty lane, wedge drain,
accumulation=1 degeneration, and lane-on/off state equivalence."""
import time

import pytest

from tpubft.apps import counter, skvbc
from tpubft.consensus.persistent import FilePersistentStorage
from tpubft.kvbc import KeyValueBlockchain
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
    """Cluster whose blockchains + WAL + reserved pages all survive an
    in-process restart (the crash-recovery shape)."""
    def handler_factory(r):
        db = dbs.setdefault(r, MemoryDB())
        return skvbc.SkvbcHandler(
            KeyValueBlockchain(db, use_device_hashing=False))

    def storage_factory(r):
        return FilePersistentStorage(str(tmp_path / f"r{r}.wal"))

    return InProcessCluster(f=1, handler_factory=handler_factory,
                            storage_factory=storage_factory,
                            cfg_overrides=overrides or None)


def test_crash_between_commit_and_apply_replays_exactly_once(tmp_path):
    """Kill a replica AFTER commit certificates persist but BEFORE the
    lane applies them: restart must re-execute the suffix exactly once —
    same blocks as the rest of the cluster, reply ring intact."""
    dbs = {}
    with _kv_cluster(tmp_path, dbs) as cluster:
        kv = skvbc.SkvbcClient(cluster.client(0))
        # freeze replica 2's lane: commits persist, apply doesn't
        held = cluster.replicas[2]
        held.exec_lane.hold()
        for i in range(5):
            r = kv.write([(b"k%d" % i, b"v%d" % i)], timeout_ms=15000)
            assert r.success
        # replica 2 must have COMMITTED slots in its WAL while its
        # handler state is behind (apply frozen)
        assert _wait(lambda: any(
            e.commit_full or e.full_commit_proof
            for e in held.storage.load().seq_states.values())), \
            "no committed slot persisted on the held replica"
        assert held.last_executed < 5
        bc_before = dbs[2]
        # crash (stop() is crash-equivalent for the lane: no drain)
        cluster.kill(2)
        rep = cluster.restart(2)
        # recovery replays the committed-but-unexecuted suffix inline
        assert _wait(lambda: cluster.handlers[2].blockchain.last_block_id
                     >= 5), "restarted replica did not replay the suffix"
        assert dbs[2] is bc_before
        # exactly once: state digest converges to a live replica's
        assert _wait(lambda: cluster.handlers[2].blockchain.state_digest()
                     == cluster.handlers[0].blockchain.state_digest())
        # reply ring intact across the crash: the restarted replica
        # reloaded executed-request records from the persisted ring
        cid = cluster.client(0).cfg.client_id
        # the paged table starts empty after a restart and holds a client
        # only once something has touched it: page the record in from the
        # persisted ring, as the replica itself would, instead of racing
        # the client's next retransmission for it
        with rep.clients._mu:
            info = rep.clients._resident(cid)
        assert info.replies, "reply ring lost across restart"
        assert all(rep.clients.was_executed(cid, s) for s in info.replies)
        # cluster keeps committing with the recovered replica
        assert kv.write([(b"post", b"crash")], timeout_ms=15000).success
        assert _wait(lambda: cluster.handlers[2].blockchain.state_digest()
                     == cluster.handlers[0].blockchain.state_digest())


def test_view_change_with_pending_lane_drains_first(tmp_path):
    """Primary dies while execution lags (slowdown on the execute
    phase): backups complain, the view changes, and the lane's pending
    slots are fully applied before the new view — no replica loses or
    duplicates a committed write."""
    from tpubft.testing.slowdown import (SlowdownPolicy, PHASE_EXECUTE,
                                         get_slowdown_manager)
    dbs = {}
    mgr = get_slowdown_manager()
    with _kv_cluster(tmp_path, dbs,
                     view_change_timer_ms=2500) as cluster:
        kv = skvbc.SkvbcClient(cluster.client(0))
        assert kv.write([(b"w", b"0")], timeout_ms=15000).success
        mgr.install(PHASE_EXECUTE, SlowdownPolicy(delay_ms=40))
        try:
            for i in range(4):
                assert kv.write([(b"k%d" % i, b"v")],
                                timeout_ms=15000).success
            # kill the primary; clients keep the cluster under load so
            # the liveness clock arms and a real view change happens
            cluster.kill(0)
            deadline = time.monotonic() + 30
            entered = False
            while time.monotonic() < deadline and not entered:
                try:
                    kv.write([(b"vc", b"x")], timeout_ms=3000)
                except Exception:
                    pass
                entered = any(cluster.replicas[r].view > 0
                              for r in (1, 2, 3))
            assert entered, "no view change happened"
        finally:
            mgr.clear()
        assert kv.write([(b"post-vc", b"1")], timeout_ms=40000).success
        # invariant the drain protects: every live replica applied every
        # slot it committed — states converge, nothing stuck in a lane
        def converged():
            views = [cluster.replicas[r] for r in (1, 2, 3)]
            if any(rep.exec_lane is not None
                   and not rep.exec_lane.idle() for rep in views):
                return False
            ds = {cluster.handlers[r].blockchain.state_digest()
                  for r in (1, 2, 3)}
            return len(ds) == 1
        assert _wait(converged, timeout=30), "replicas diverged after VC"


def test_wedge_drains_lane_before_restart_proof(tmp_path):
    """Operator wedge with execution lagging behind ordering: every
    replica must finish applying up to the wedge point (lane drained)
    before the n/n restart proof can form."""
    from tpubft.testing.slowdown import (SlowdownPolicy, PHASE_EXECUTE,
                                         get_slowdown_manager)
    dbs = {}
    mgr = get_slowdown_manager()
    with _kv_cluster(tmp_path, dbs,
                     checkpoint_window_size=10,
                     work_window_size=20) as cluster:
        kv = skvbc.SkvbcClient(cluster.client(0))
        assert kv.write([(b"pre", b"w")], timeout_ms=15000).success
        mgr.install(PHASE_EXECUTE, SlowdownPolicy(delay_ms=30))
        try:
            op = cluster.operator_client()
            assert op.wedge(timeout_ms=20000).success
        finally:
            mgr.clear()
        # all replicas reach the stop point and the full restart proof
        # forms — impossible unless each lane drained to the wedge point
        def proven():
            reps = cluster.replicas.values()
            return all(r.control.wedge_point is not None
                       and r.last_executed >= r.control.wedge_point
                       for r in reps) \
                and all(r.control.restart_proof for r in reps)
        assert _wait(proven, timeout=30), [
            (r.control.wedge_point, r.last_executed,
             r.control.restart_proof)
            for r in cluster.replicas.values()]
        # post-wedge: no replica executed past the stop point
        for r in cluster.replicas.values():
            assert r.last_executed == r.control.wedge_point


@pytest.mark.parametrize("overrides", [
    dict(execution_max_accumulation=1),
    dict(execution_lane=False),
])
def test_degenerate_modes_order_and_converge(tmp_path, overrides):
    """execution_max_accumulation=1 (per-slot runs, still off the
    dispatcher) and execution_lane=False (legacy inline) must both order
    traffic and converge to identical state."""
    dbs = {}
    with _kv_cluster(tmp_path, dbs, **overrides) as cluster:
        kv = skvbc.SkvbcClient(cluster.client(0))
        for i in range(6):
            assert kv.write([(b"k%d" % i, b"v%d" % i)],
                            timeout_ms=15000).success
        assert _wait(lambda: len(
            {cluster.handlers[r].blockchain.state_digest()
             for r in range(4)}) == 1, timeout=25)
        assert cluster.handlers[0].blockchain.last_block_id == 6


def test_lane_and_inline_reach_identical_state(tmp_path):
    """Same workload under execution_lane on vs off ends in the same
    blockchain state digest (block-for-block equivalence)."""
    digests = {}
    for lane in (True, False):
        dbs = {}
        sub = tmp_path / str(lane)
        sub.mkdir()
        with _kv_cluster(sub, dbs, execution_lane=lane) as cluster:
            kv = skvbc.SkvbcClient(cluster.client(0))
            for i in range(5):
                assert kv.write([(b"k%d" % i, b"v")],
                                timeout_ms=15000).success
            assert _wait(
                lambda: cluster.handlers[0].blockchain.last_block_id == 5)
            digests[lane] = \
                cluster.handlers[0].blockchain.state_digest()
    assert digests[True] == digests[False]


def test_oversize_reply_marker_still_written(tmp_path):
    """The reply-dedup keeps the oversize-reply at-most-once marker on
    the legacy "clients" page (the one record the ring cannot hold)."""
    from tpubft.consensus.replica import IRequestsHandler

    class BigReplyHandler(IRequestsHandler):
        def __init__(self):
            self.count = 0

        def execute(self, client_id, req_seq, flags, request):
            self.count += 1
            return b"x" * 5000          # > PAGE_SIZE once framed

        def state_digest(self):
            return b"\x00" * 32

    with InProcessCluster(f=1, handler_factory=lambda r=None:
                          BigReplyHandler()) as cluster:
        cl = cluster.client(0)
        cl.start()
        reply = cl.send_write(b"hello")
        assert reply == b"x" * 5000
        rep0 = cluster.replicas[0]
        cid = cl.cfg.client_id
        page = rep0.res_pages.load("clients", cid)
        assert page is not None and page[:1] == b"\x01"
        marked_seq = int.from_bytes(page[1:9], "big")
        assert rep0.clients.was_executed(cid, marked_seq)
