"""Execution-lane fault matrix: crash between commit and apply, view
change with a non-empty lane, wedge drain, and the served ledger held
to a plain sequential apply in every shape the lane's runs take."""
import struct
import threading
import time

import pytest

from tpubft.apps import skvbc
from tpubft.consensus import messages as m
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


def _restore_standalone(tmp_path, cluster, dbs, victim):
    """The victim's durable state (its WAL, and the db that holds its
    ledger and its reserved pages) under a replica that is built and
    never started. The pages are read from the db afresh: the crashed
    replica's own object still reads through its pending overlay, which
    died with it."""
    from tpubft.comm.loopback import LoopbackBus
    from tpubft.consensus.replica import Replica
    from tpubft.consensus.reserved_pages import ReservedPages
    from tpubft.utils.config import ReplicaConfig
    return Replica(
        ReplicaConfig(replica_id=victim, f_val=1, num_of_client_proxies=2),
        cluster.keys.for_node(victim), LoopbackBus().create(victim),
        skvbc.SkvbcHandler(
            KeyValueBlockchain(dbs[victim], use_device_hashing=False)),
        storage=FilePersistentStorage(str(tmp_path / f"r{victim}.wal")),
        reserved_pages=ReservedPages(dbs[victim]))


def _hold_until_two_slots_wait(cluster, victim, kv):
    """Hold the victim's lane and order two single-slot writes on the
    other three: both wait, committed, in the victim's lane."""
    held = cluster.replicas[victim]
    held.exec_lane.hold()
    for i in range(2):
        assert kv.write([(b"held%d" % i, b"v")], timeout_ms=15000).success
    assert _wait(lambda: held.exec_lane.depth >= 2), \
        "two committed slots never waited in the held lane"
    return held


def test_crash_after_the_apply_of_a_coalesced_run_replays_exactly_once(
        tmp_path):
    """SIGKILL at `exec.post_apply` inside a run of TWO slots — sealed
    into the pending overlay, nothing handed to the io thread, nothing
    durable: recovery replays both slots from the WAL, once."""
    from tpubft.testing import crashpoints as cp
    dbs = {}
    victim = 2
    hit = threading.Event()

    def crash_here():
        hit.set()
        cp.park()                 # SIGKILL analog: not one more statement

    try:
        with _kv_cluster(tmp_path, dbs) as cluster:
            kv = skvbc.SkvbcClient(cluster.client(0))
            assert kv.write([(b"pre", b"1")], timeout_ms=15000).success
            assert _wait(lambda:
                         cluster.replicas[victim].last_executed >= 1)
            runs0 = cluster.metric(victim, "counters", "exec_runs")
            held = _hold_until_two_slots_wait(cluster, victim, kv)
            cp.arm("exec.post_apply", rid=victim, action=crash_here)
            held.exec_lane.release()
            assert hit.wait(15), "the run never reached exec.post_apply"
            # one run took both slots, and none of it is durable
            assert held.exec_lane.depth == 0
            assert cluster.metric(victim, "counters", "exec_runs") == runs0
            assert KeyValueBlockchain(
                dbs[victim], use_device_hashing=False).last_block_id == 1
            recovered = _restore_standalone(tmp_path, cluster, dbs, victim)
            assert recovered.last_executed >= 3, \
                "recovery did not replay the committed suffix"
            bc = recovered.handler.blockchain
            assert bc.last_block_id == 3, (
                f"replay divergence: {bc.last_block_id} blocks (expected "
                f"3 — double-applied or lost)")
            assert bc.state_digest() == \
                cluster.handlers[0].blockchain.state_digest()
            # release the parked lane thread BEFORE teardown so the
            # victim's stop() doesn't eat its full join timeout
            cp.disarm_all()
            cp.release_parked()
    finally:
        cp.disarm_all()
        cp.release_parked()


def test_never_started_replica_replays_in_init_and_stops_clean(tmp_path):
    """A replica restored from a WAL with a committed, unapplied suffix
    replays it while it is built — before any thread exists — and a
    stop() without a start() finds a lane and a pipeline that never ran
    and leaves nothing behind."""
    dbs = {}
    victim = 2
    with _kv_cluster(tmp_path, dbs) as cluster:
        kv = skvbc.SkvbcClient(cluster.client(0))
        held = _hold_until_two_slots_wait(cluster, victim, kv)
        assert held.last_executed == 0
        cluster.kill(victim)      # crash-equivalent: the lane never drains
        want = cluster.handlers[0].blockchain.state_digest()
        before = set(threading.enumerate())
        recovered = _restore_standalone(tmp_path, cluster, dbs, victim)
        # (the verify and combine batchers start theirs when built)
        assert not [t.name for t in set(threading.enumerate()) - before
                    if t.name.startswith(("exec-", "dur-"))], \
            "building a replica started the lane or the io thread"
        assert recovered.last_executed == 2
        assert recovered.handler.blockchain.last_block_id == 2
        assert recovered.handler.blockchain.state_digest() == want
        assert recovered.exec_lane.idle() and recovered.durability.idle()
        t0 = time.monotonic()
        recovered.stop()
        assert time.monotonic() - t0 < 5.0, "stop() waited on a thread"
        assert recovered.exec_lane._thread is None
        # stopped for good: the ledger is where the replay left it
        assert recovered.handler.blockchain.last_block_id == 2


def test_a_run_that_fails_is_rolled_back_and_retried_once(tmp_path):
    """A handler that raises in mid-run: the run's staged blocks are
    dropped with its accumulation, the slots go back to the head of the
    lane, and the retry applies them exactly once — the replica ends
    where the others do."""
    dbs = {}
    victim = 2
    raised = []

    class FailsOnce(skvbc.SkvbcHandler):
        def execute(self, client_id, req_seq, flags, request):
            msg = skvbc.unpack(request)
            if not raised and getattr(msg, "writeset", None) \
                    and msg.writeset[0][0] == b"held1":
                raised.append(self.blockchain.last_block_id)
                raise RuntimeError("injected: the handler failed")
            return super().execute(client_id, req_seq, flags, request)

    def handler_factory(r):
        db = dbs.setdefault(r, MemoryDB())
        cls = FailsOnce if r == victim else skvbc.SkvbcHandler
        return cls(KeyValueBlockchain(db, use_device_hashing=False))

    with InProcessCluster(
            f=1, handler_factory=handler_factory,
            storage_factory=lambda r: FilePersistentStorage(
                str(tmp_path / f"r{r}.wal"))) as cluster:
        kv = skvbc.SkvbcClient(cluster.client(0))
        held = _hold_until_two_slots_wait(cluster, victim, kv)
        held.exec_lane.release()
        bc = cluster.handlers[victim].blockchain
        assert _wait(lambda: bc.last_block_id == 2
                     and held.last_executed == 2)
        # it failed on the run's SECOND slot, with the first one staged
        assert raised == [1]
        assert cluster.metric(victim, "counters", "exec_runs") == 1
        assert bc.state_digest() == \
            cluster.handlers[0].blockchain.state_digest()
        assert kv.write([(b"after", b"retry")], timeout_ms=15000).success
        assert _wait(lambda: bc.last_block_id == 3)


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


# ---------------------------------------------------------------------
# the served ledger against a plain sequential apply
# ---------------------------------------------------------------------

def _plain_ledger(writesets):
    """The reference: the ordered writes put one block at a time into a
    fresh ledger through the handler — no replica, no lane, no
    accumulation, no pipeline."""
    bc = KeyValueBlockchain(MemoryDB(), use_device_hashing=False)
    handler = skvbc.SkvbcHandler(bc, merkle=True)
    for n, ws in enumerate(writesets, 1):
        reply = skvbc.unpack(handler.execute(
            0, n, 0, skvbc.pack(skvbc.WriteRequest(writeset=ws))))
        assert reply.success and reply.latest_block == n
    return bc.last_block_id, bc.state_digest(), bc.merkle_root("kv")


class _Traffic:
    """One closed-loop client. The order of the writes is the order they
    were sent in (a message is answered before the next leaves); inside
    one batch message it is the blocks its replies name, which have to
    follow the message before it without a gap."""

    def __init__(self, cluster) -> None:
        self.kv = skvbc.SkvbcClient(cluster.client(0))
        self.ordered = []

    def write(self, *pairs, timeout_ms=20000):
        ws = list(pairs)
        reply = self.kv.write(ws, timeout_ms=timeout_ms)
        assert reply.success
        assert reply.latest_block == len(self.ordered) + 1, \
            "a block was dropped, repeated or reordered"
        self.ordered.append(ws)

    def write_batch(self, writesets, timeout_ms=30000):
        replies = self.kv.write_batch(writesets, timeout_ms=timeout_ms)
        assert all(r.success for r in replies)
        base = len(self.ordered)
        assert sorted(r.latest_block for r in replies) == list(
            range(base + 1, base + len(writesets) + 1)), \
            "a block was dropped, repeated or reordered"
        for _block, ws in sorted(zip((r.latest_block for r in replies),
                                     writesets)):
            self.ordered.append(ws)


def _singles(cluster, t):
    for i in range(6):
        t.write((b"k%d" % i, b"v%d" % i))


def _batches_of_16(cluster, t):
    """Slots of at most 4 requests, so one message fills several; the
    lanes are held until each has two committed slots waiting, which it
    then takes as ONE coalesced run."""
    for b in range(2):
        for rep in cluster.replicas.values():
            rep.exec_lane.hold()
        failure = []

        def drive(b=b):
            try:
                t.write_batch([[(b"b%d.%d" % (b, i), b"v%d" % i)]
                               for i in range(16)])
            except BaseException as e:  # noqa: BLE001 — reported below
                failure.append(e)

        th = threading.Thread(target=drive, daemon=True)
        th.start()
        try:
            assert _wait(lambda: all(rep.exec_lane.depth >= 2
                                     for rep in cluster.replicas.values()))
        finally:
            for rep in cluster.replicas.values():
                rep.exec_lane.release()
        th.join(40)
        assert not th.is_alive() and not failure, failure
    assert all(cluster.metric(r, "counters", "exec_run_slots")
               > cluster.metric(r, "counters", "exec_runs")
               for r in range(4)), "no run coalesced two slots"


def _barrier_in_mid_stream(cluster, t):
    op = cluster.operator_client()
    for i in range(3):
        t.write((b"pre%d" % i, b"v"))
    # an ordered RECONFIG request: the dispatcher drains the lane and
    # executes the batch inline, between two lane runs
    assert op.status(timeout_ms=20000).success
    for i in range(3):
        t.write((b"post%d" % i, b"v"))


def _across_a_checkpoint(cluster, t):
    for i in range(4):
        t.write((b"c%d" % i, b"v%d" % i))
    t.write_batch([[(b"cb%d" % i, b"v")] for i in range(12)])
    assert _wait(lambda: all(r.last_stable >= 4
                             for r in cluster.replicas.values())), \
        "no checkpoint became stable"
    t.write((b"after", b"ckpt"))


def _view_changes_in_mid_traffic(cluster, t):
    """A commit-certificate blackout leaves an accepted slot that cannot
    commit anywhere; the view change re-orders it."""
    certs = {int(c) for c in (
        m.MsgCode.PreparePartial, m.MsgCode.PrepareFull,
        m.MsgCode.CommitPartial, m.MsgCode.CommitFull,
        m.MsgCode.PartialCommitProof, m.MsgCode.FullCommitProof)}
    blackout = threading.Event()

    def drop_certs(_s, _d, data):
        if blackout.is_set() and len(data) >= 2 \
                and struct.unpack_from("<H", data)[0] in certs:
            return None
        return data

    cluster.bus.add_hook(drop_certs)
    t.write((b"k0", b"v0"))
    blackout.set()
    done = threading.Event()
    failure = []

    def drive():
        try:
            t.write((b"k1", b"v1"), timeout_ms=60000)
        except BaseException as e:  # noqa: BLE001 — reported below
            failure.append(e)
        done.set()

    threading.Thread(target=drive, daemon=True).start()
    assert _wait(lambda: any(rep.view >= 1
                             for rep in cluster.replicas.values()),
                 timeout=30), "the blackout never forced a view change"
    blackout.clear()
    assert done.wait(60) and not failure, failure
    for i in range(2, 5):
        t.write((b"k%d" % i, b"v%d" % i), timeout_ms=30000)


@pytest.mark.parametrize("drive, overrides", [
    (_singles, {}),
    (_batches_of_16, dict(max_num_of_requests_in_batch=4)),
    (_barrier_in_mid_stream, {}),
    (_across_a_checkpoint, dict(checkpoint_window_size=4,
                                work_window_size=8,
                                max_num_of_requests_in_batch=2)),
    (_singles, dict(execution_max_accumulation=1)),
    (_singles, dict(durability_group_max=1, durability_window_us=0)),
    (_view_changes_in_mid_traffic, dict(view_change_timer_ms=1200)),
], ids=["single_writes", "write_batch_16", "barrier_request",
        "checkpoint_boundary", "max_accumulation_1",
        "durability_group_max_1", "view_changes"])
def test_served_ledger_equals_plain_sequential_apply(tmp_path, drive,
                                                     overrides):
    """Whatever shape the lane's runs take, the four served ledgers end
    where the plain reference does: block count, state digest and merkle
    root."""
    def handler_factory(r):
        return skvbc.SkvbcHandler(
            KeyValueBlockchain(MemoryDB(), use_device_hashing=False),
            merkle=True)

    with InProcessCluster(
            f=1, handler_factory=handler_factory,
            storage_factory=lambda r: FilePersistentStorage(
                str(tmp_path / f"r{r}.wal")),
            cfg_overrides=overrides or None) as cluster:
        traffic = _Traffic(cluster)
        drive(cluster, traffic)
        want = _plain_ledger(traffic.ordered)

        def heads():
            return [(bc.last_block_id, bc.state_digest(),
                     bc.merkle_root("kv"))
                    for bc in (cluster.handlers[r].blockchain
                               for r in range(4))]
        assert _wait(lambda: heads() == [want] * 4, timeout=30), \
            (want, heads())


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
