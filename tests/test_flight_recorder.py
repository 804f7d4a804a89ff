"""Flight recorder: per-thread rings, slot-lifecycle folding, kernel
profiling, the diagnostics surfaces (`status get slots|kernels|flight`,
`perf show` snapshot shape), the stalled-health dump artifact +
tools/tpuprof rendering, and the chaos-campaign red-verdict attachment.
"""
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from tpubft.diagnostics import DiagnosticsServer, Registrar, TimeRecorder
from tpubft.tools import ctl
from tpubft.utils import flight
from tpubft.utils.flight import SlotTracker


def _slot_events(seq, rid=0, step_ns=1_000_000):
    """Record one full synthetic slot lifecycle for `seq`."""
    flight.set_thread_rid(rid)
    for code in (flight.EV_ADM_ADMIT, flight.EV_PP_DISPATCH,
                 flight.EV_PP_ACCEPT, flight.EV_PREPARED,
                 flight.EV_COMMITTED, flight.EV_EXEC_ENQ,
                 flight.EV_EXEC_APPLY, flight.EV_REPLY):
        flight.record(code, seq=seq, view=0)


# ---------------- rings ----------------

def test_ring_bounded_and_ordered():
    flight.reset()
    n = flight.RING_SIZE + 57
    for i in range(n):
        flight.record(flight.EV_ADM_INGEST, arg=i)
    snap = flight.snapshot()
    me = threading.current_thread().name
    ring = next(r for r in snap["rings"] if r["thread"] == me)
    evs = [e for e in ring["events"] if e[1] == flight.EV_ADM_INGEST]
    assert len(evs) <= flight.RING_SIZE          # bounded
    # oldest-to-newest, and the newest events survived the wrap
    ts = [e[0] for e in evs]
    assert ts == sorted(ts)
    assert evs[-1][4] == n - 1


def test_disabled_recorder_is_a_noop():
    from tpubft.ops.dispatch import device_section
    flight.reset()
    flight._set_enabled(False)
    try:
        assert not flight.enabled()
        flight.record(flight.EV_ADM_INGEST, arg=1)
        _slot_events(seq=999)
        # the off switch covers the device seam too: no kernel profile
        with device_section("disabledkind", batch=2):
            pass
        snap = flight.snapshot()
        assert all(not r["events"] for r in snap["rings"])
        assert flight.stage_summary()["completed"] == 0
        assert "disabledkind" not in flight.kernel_profiler().snapshot()
    finally:
        flight._set_enabled(True)
    assert flight.enabled()


def test_dead_ring_retention_bounded():
    flight.reset()

    def emit():
        flight.record(flight.EV_ADM_INGEST, arg=1)

    for i in range(flight.DEAD_RING_KEEP + 12):
        t = threading.Thread(target=emit, name=f"churn-{i}")
        t.start()
        t.join()
    # one more live registration triggers the prune pass
    emit()
    snap = flight.snapshot()
    alive = {t.name for t in threading.enumerate()}
    dead = [r for r in snap["rings"] if r["thread"] not in alive]
    assert len(dead) <= flight.DEAD_RING_KEEP
    # the NEWEST dead rings were the ones kept
    kept = {r["thread"] for r in dead if r["thread"].startswith("churn-")}
    assert f"churn-{flight.DEAD_RING_KEEP + 11}" in kept


def test_thread_rid_attribution():
    flight.reset()
    done = threading.Event()

    def other():
        flight.set_thread_rid(3)
        flight.record(flight.EV_ADM_DRAIN, arg=8)
        done.set()

    t = threading.Thread(target=other, name="flight-test-thread")
    t.start()
    t.join()
    assert done.is_set()
    snap = flight.snapshot()
    ring = next(r for r in snap["rings"]
                if r["thread"] == "flight-test-thread")
    assert ring["rid"] == 3 and ring["events"]


# ---------------- slot lifecycle ----------------

def test_fold_stage_math():
    t0 = 1_000_000_000
    slot = {"admit": t0, "handler": t0 + 2_000_000,
            "accept": t0 + 3_000_000, "prepared": t0 + 10_000_000,
            "committed": t0 + 15_000_000, "applied": t0 + 25_000_000,
            "replied": t0 + 26_000_000}
    stages = SlotTracker.fold(slot)
    assert stages == {"adm_wait": 2.0, "dispatch": 1.0, "prepare": 7.0,
                      "commit": 5.0, "exec": 10.0, "reply": 1.0,
                      "cert_lag": 0.0,
                      # no lane start / queue stamp / group on record:
                      # exec reads as all service, the rest as nothing
                      "order_wait": 0.0, "exec_wait": 0.0,
                      "exec_run": 10.0, "dur_wait": 0.0,
                      # nor the loop's end: the run reads as all seal
                      "exec_app": 0.0, "exec_reply": 0.0,
                      "exec_seal": 10.0, "dur_queue": 0.0,
                      "dur_apply": 0.0, "dur_fsync": 0.0}
    # fast path: no prepare quorum — prepare reads 0, commit runs from
    # accept; a primary self-proposal has no admit/handler anchors
    fast = {"accept": t0, "committed": t0 + 4_000_000,
            "applied": t0 + 5_000_000, "replied": t0 + 5_500_000}
    stages = SlotTracker.fold(fast)
    assert stages["adm_wait"] == 0.0 and stages["dispatch"] == 0.0
    assert stages["prepare"] == 0.0 and stages["commit"] == 4.0
    assert stages["exec"] == 1.0 and stages["reply"] == 0.5


def test_slot_tracker_folds_recorded_lifecycle():
    flight.reset()
    for seq in (10, 11, 12):
        _slot_events(seq, rid=5)
    s = flight.stage_summary()
    assert s["completed"] == 3 and s["live"] == 0
    assert set(s["stages"]) == set(flight.STAGES)
    recent = flight.slot_tracker().recent(rid=5)
    assert [r["seq"] for r in recent] == [10, 11, 12]
    assert all(r["total_ms"] >= 0 for r in recent)
    # a replay of EV_REPLY for an already-folded slot is ignored
    flight.record(flight.EV_REPLY, seq=10)
    assert flight.stage_summary()["completed"] == 3


def test_late_commit_after_reply_does_not_resurrect_slot():
    """Optimistic replies reorder the lifecycle: the slot finalizes on
    EV_REPLY and the verified-commit EV_COMMITTED (plus any straggler
    stage event) lands afterwards. Late events on a folded slot must be
    dropped, not spawn a ghost live entry that never finalizes."""
    flight.reset()
    tr = flight.slot_tracker()
    t0 = 1_000_000_000
    tr.on_event(7, flight.EV_PP_ACCEPT, 9, 0, 0, t0)
    tr.on_event(7, flight.EV_EXEC_APPLY, 9, 0, 1, t0 + 1_000_000)
    tr.on_event(7, flight.EV_REPLY, 9, 0, 0, t0 + 2_000_000)
    assert tr.summary(rid=7)["completed"] == 1
    # the deferred certificate verifies after the client already replied
    tr.on_event(7, flight.EV_COMMITTED, 9, 0, 0, t0 + 9_000_000)
    tr.on_event(7, flight.EV_PREPARED, 9, 0, 0, t0 + 9_100_000)
    s = tr.summary(rid=7)
    assert s["live"] == 0 and s["completed"] == 1
    # a slot never seen before still opens a live entry as usual
    tr.on_event(7, flight.EV_COMMITTED, 10, 0, 0, t0 + 9_200_000)
    assert tr.summary(rid=7)["live"] == 1
    tr.reset()


def test_slot_tracker_live_bound():
    flight.reset()
    tr = flight.slot_tracker()
    for seq in range(SlotTracker.MAX_LIVE + 40):
        flight.record(flight.EV_PP_ACCEPT, seq=seq)
    assert flight.stage_summary()["live"] <= SlotTracker.MAX_LIVE
    tr.reset()


# ---------------- request accounting inside the replica ----------------

MS = 1_000_000
T0 = 1_000_000_000


GROUP_EVENTS = (flight.EV_DUR_TAKE, flight.EV_DUR_WRITTEN,
                flight.EV_DUR_GROUP)


def _fold_events(events, rid=7, seq=5):
    """Feed (code, arg, offset_ms) rows for one slot through the live
    tracker; returns the finalized record. A group event's `arg` is its
    watermark."""
    flight.reset()
    tr = flight.slot_tracker()
    for code, arg, at_ms in events:
        group = code in GROUP_EVENTS
        tr.on_event(rid, code, arg if group else seq, 0,
                    0 if group else arg, T0 + int(at_ms * MS))
    return tr.recent(rid=rid)[-1]


def _assert_parts_sum(st):
    """The two splits, to the rows' 0.001 ms rounding."""
    assert st["exec_app"] + st["exec_reply"] + st["exec_seal"] \
        == pytest.approx(st["exec_run"], abs=0.0015)
    assert st["dur_queue"] + st["dur_apply"] + st["dur_fsync"] \
        == pytest.approx(st["dur_wait"], abs=0.0015)
    assert all(st[k] >= 0 for k in flight.STAGES)


def test_order_wait_folds_from_pp_create_on_the_primary_only():
    primary = _fold_events([
        (flight.EV_PP_CREATE, 3_250_000, 0),      # oldest waited 3.25 s
        (flight.EV_PP_ACCEPT, 31, 0.1),           # 31 requests
        (flight.EV_COMMITTED, 0, 5), (flight.EV_EXEC_APPLY, 1, 9),
        (flight.EV_REPLY, 0, 10)])
    assert primary["stages_ms"]["order_wait"] == 3250.0
    assert primary["reqs"] == 31
    # an overlay: the wait came before the slot existed
    assert primary["total_ms"] == pytest.approx(
        sum(primary["stages_ms"][s] for s in flight.PIPELINE_STAGES))
    backup = _fold_events([
        (flight.EV_PP_ACCEPT, 31, 0.1), (flight.EV_COMMITTED, 0, 5),
        (flight.EV_EXEC_APPLY, 1, 9), (flight.EV_REPLY, 0, 10)], rid=8)
    assert backup["stages_ms"]["order_wait"] == 0.0
    assert backup["reqs"] == 31


@pytest.mark.parametrize("name,events,wait,run", [
    # committed at 5, the lane reached it at 45, applied at 50
    ("normal", [(flight.EV_COMMITTED, 0, 5), (flight.EV_EXEC_START, 4, 45),
                (flight.EV_EXEC_APPLY, 4, 50)], 40.0, 5.0),
    # released on the structural certificate (optimistic replies): the
    # lane began ahead of the verified commit — no wait, and the run is
    # what was left of it after the commit
    ("released_ahead", [(flight.EV_EXEC_START, 1, 2),
                        (flight.EV_COMMITTED, 0, 5),
                        (flight.EV_EXEC_APPLY, 1, 6)], 0.0, 1.0),
    # a run that failed and was retried: the first start stands, so the
    # failed attempt and the back-off count as the lane's run
    ("retried_run", [(flight.EV_COMMITTED, 0, 5),
                     (flight.EV_EXEC_START, 2, 10),
                     (flight.EV_EXEC_START, 2, 25),
                     (flight.EV_EXEC_APPLY, 2, 30)], 5.0, 20.0),
    # no lane start on record: all of exec is service
    ("no_start", [(flight.EV_COMMITTED, 0, 5),
                  (flight.EV_EXEC_APPLY, 1, 30)], 0.0, 25.0),
])
def test_exec_splits_into_wait_and_run(name, events, wait, run):
    rec = _fold_events([(flight.EV_PP_ACCEPT, 1, 0)] + events
                       + [(flight.EV_REPLY, 0, 60)])
    st = rec["stages_ms"]
    assert st["exec_wait"] == pytest.approx(wait)
    assert st["exec_run"] == pytest.approx(run)
    assert st["exec_wait"] + st["exec_run"] == pytest.approx(st["exec"])
    assert sum(st[s] for s in flight.PIPELINE_STAGES) \
        == pytest.approx(rec["total_ms"], abs=0.01)


def test_dur_wait_folds_from_the_group_watermark():
    rec = _fold_events([
        (flight.EV_PP_ACCEPT, 1, 0), (flight.EV_COMMITTED, 0, 5),
        (flight.EV_EXEC_START, 1, 6), (flight.EV_EXEC_APPLY, 1, 10),
        (flight.EV_DUR_GROUP, 4, 11),     # watermark 4: not this slot's
        (flight.EV_DUR_GROUP, 9, 17),     # watermark 9 covers seq 5
        (flight.EV_DUR_GROUP, 12, 30),    # a later group moves nothing
        (flight.EV_REPLY, 0, 19)])
    st = rec["stages_ms"]
    assert st["dur_wait"] == pytest.approx(7.0)
    assert st["dur_wait"] <= st["reply"] == pytest.approx(9.0)
    # a sibling replica's group is not this replica's
    flight.reset()
    tr = flight.slot_tracker()
    tr.on_event(1, flight.EV_EXEC_APPLY, 5, 0, 1, T0)
    tr.on_event(2, flight.EV_DUR_GROUP, 9, 0, 1, T0 + MS)
    tr.on_event(1, flight.EV_REPLY, 5, 0, 0, T0 + 2 * MS)
    assert tr.recent(rid=1)[-1]["stages_ms"]["dur_wait"] == 0.0
    # without the pipeline there is no group: 0, and a group that lands
    # after the reply is clamped into it
    late = SlotTracker.fold({"applied": T0, "replied": T0 + MS,
                             "durable": T0 + 5 * MS})
    assert late["dur_wait"] == late["reply"] == 1.0


@pytest.mark.parametrize("name,events,parts", [
    # committed at 5, the lane began at 10, the loop returned at 18
    # after 6 ms of application calls, applied at 20
    ("one_slot_run", [(flight.EV_COMMITTED, 0, 5),
                      (flight.EV_EXEC_START, 1, 10),
                      (flight.EV_EXEC_HANDLED, 6000, 18),
                      (flight.EV_EXEC_APPLY, 1, 20)], (6.0, 2.0, 2.0)),
    # released ahead of its verified commit: the loop was over before
    # the commit, so the run after it is all seal
    ("released_ahead", [(flight.EV_EXEC_START, 1, 2),
                        (flight.EV_EXEC_HANDLED, 1500, 4),
                        (flight.EV_COMMITTED, 0, 5),
                        (flight.EV_EXEC_APPLY, 1, 6)], (0.0, 0.0, 1.0)),
    # the application's sum is capped at the loop it was summed in
    ("app_over_the_loop", [(flight.EV_COMMITTED, 0, 5),
                           (flight.EV_EXEC_START, 1, 10),
                           (flight.EV_EXEC_HANDLED, 9000, 12),
                           (flight.EV_EXEC_APPLY, 1, 13)], (2.0, 0.0, 1.0)),
    # a retried run: the first loop's end stands, the retry is seal
    ("retried_run", [(flight.EV_COMMITTED, 0, 5),
                     (flight.EV_EXEC_START, 1, 10),
                     (flight.EV_EXEC_HANDLED, 1000, 12),
                     (flight.EV_EXEC_START, 1, 20),
                     (flight.EV_EXEC_HANDLED, 1000, 22),
                     (flight.EV_EXEC_APPLY, 1, 24)], (1.0, 1.0, 12.0)),
    # no EV_EXEC_HANDLED on record: the whole run reads as seal
    ("no_handled", [(flight.EV_COMMITTED, 0, 5),
                    (flight.EV_EXEC_START, 1, 10),
                    (flight.EV_EXEC_APPLY, 1, 20)], (0.0, 0.0, 10.0)),
])
def test_exec_run_splits_into_app_reply_and_seal(name, events, parts):
    rec = _fold_events([(flight.EV_PP_ACCEPT, 1, 0)] + events
                       + [(flight.EV_REPLY, 0, 60)])
    st = rec["stages_ms"]
    assert (st["exec_app"], st["exec_reply"], st["exec_seal"]) \
        == pytest.approx(parts)
    _assert_parts_sum(st)


def test_a_run_of_several_slots_seals_at_its_last():
    """Three slots in one run: each slot's loop is its own, and the seal
    of a slot that is not the run's last holds the later slots."""
    flight.reset()
    tr = flight.slot_tracker()
    for seq in (1, 2, 3):
        tr.on_event(4, flight.EV_COMMITTED, seq, 0, 1, T0)
    at = 10
    for seq in (1, 2, 3):
        tr.on_event(4, flight.EV_EXEC_START, seq, 0, 3, T0 + at * MS)
        tr.on_event(4, flight.EV_EXEC_HANDLED, seq, 0, 1000,
                    T0 + (at + 2) * MS)
        at += 2
    for seq in (1, 2, 3):
        tr.on_event(4, flight.EV_EXEC_APPLY, seq, 0, 3, T0 + 20 * MS)
        tr.on_event(4, flight.EV_REPLY, seq, 0, 0, T0 + 21 * MS)
    rows = {r["seq"]: r["stages_ms"] for r in tr.recent(rid=4)}
    assert [rows[s]["exec_seal"] for s in (1, 2, 3)] == [8.0, 6.0, 4.0]
    assert all(rows[s]["exec_app"] == rows[s]["exec_reply"] == 1.0
               for s in (1, 2, 3))
    for st in rows.values():
        _assert_parts_sum(st)


def test_dur_wait_splits_at_the_take_and_the_write():
    """A group of three runs on replica 7: each slot queues from its own
    apply to the one take, then shares the group's write and fsync. A
    sibling replica's group moves nothing here, a slot no group covers
    reads 0 in every part, and each row carries its group's runs."""
    flight.reset()
    tr = flight.slot_tracker()
    for seq, applied in ((5, 10), (6, 11), (7, 12), (8, 12)):
        tr.on_event(7, flight.EV_COMMITTED, seq, 0, 1, T0)
        tr.on_event(7, flight.EV_EXEC_APPLY, seq, 0, 1, T0 + applied * MS)
    for code, at in ((flight.EV_DUR_TAKE, 13), (flight.EV_DUR_WRITTEN, 14),
                     (flight.EV_DUR_GROUP, 15)):
        tr.on_event(8, code, 9, 0, 1, T0 + at * MS)   # replica 8's group
    tr.on_event(7, flight.EV_DUR_TAKE, 7, 0, flight.DUR_CUT_DEADLINE,
                T0 + 14 * MS)
    tr.on_event(7, flight.EV_DUR_WRITTEN, 7, 0, 3, T0 + 17 * MS)
    tr.on_event(7, flight.EV_DUR_GROUP, 7, 0, 3, T0 + 20 * MS)
    for seq in (5, 6, 7, 8):
        tr.on_event(7, flight.EV_REPLY, seq, 0, 0, T0 + 22 * MS)
    rows = {r["seq"]: r for r in tr.recent(rid=7)}
    split = {seq: tuple(rows[seq]["stages_ms"][k]
                        for k in ("dur_queue", "dur_apply", "dur_fsync"))
             for seq in rows}
    assert split == {5: (4.0, 3.0, 3.0), 6: (3.0, 3.0, 3.0),
                     7: (2.0, 3.0, 3.0), 8: (0.0, 0.0, 0.0)}
    assert [rows[s]["group_runs"] for s in (5, 6, 7, 8)] == [3, 3, 3, 0]
    assert rows[5]["dur_cut"] == flight.DUR_CUT_DEADLINE
    for r in rows.values():
        _assert_parts_sum(r["stages_ms"])
    # a group that lands after the reply: clamped into it, part by part
    late = SlotTracker.fold({"applied": T0, "replied": T0 + MS,
                             "taken": T0 + MS // 2, "written": T0 + 2 * MS,
                             "durable": T0 + 5 * MS})
    assert (late["dur_wait"], late["dur_queue"], late["dur_apply"],
            late["dur_fsync"]) == (1.0, 0.5, 0.5, 0.0)
    _assert_parts_sum(late)
    # a group retried after a failed write: the first take stands
    rec = _fold_events([
        (flight.EV_COMMITTED, 0, 5), (flight.EV_EXEC_APPLY, 1, 10),
        (flight.EV_DUR_TAKE, 5, 11), (flight.EV_DUR_TAKE, 5, 16),
        (flight.EV_DUR_WRITTEN, 5, 17), (flight.EV_DUR_GROUP, 5, 18),
        (flight.EV_REPLY, 0, 19)])
    st = rec["stages_ms"]
    assert (st["dur_queue"], st["dur_apply"], st["dur_fsync"]) \
        == pytest.approx((1.0, 6.0, 1.0))


def test_group_events_scan_only_their_replica_s_live_slots():
    flight.reset()
    tr = flight.slot_tracker()
    for rid in (1, 2):
        tr.on_event(rid, flight.EV_EXEC_APPLY, 5, 0, 1, T0)
    tr.on_event(2, flight.EV_DUR_GROUP, 5, 0, 1, T0 + MS)
    assert "durable" in tr._live[(2, 5)]
    assert "durable" not in tr._live[(1, 5)]
    assert set(tr._live_by_rid[1]) == {5}
    # finalizing and evicting keep the index equal to the live set
    tr.on_event(2, flight.EV_REPLY, 5, 0, 0, T0 + 2 * MS)
    assert tr._live_by_rid[2] == {}
    for seq in range(SlotTracker.MAX_LIVE + 3):
        tr.on_event(3, flight.EV_PP_ACCEPT, seq, 0, 0, T0)
    assert sum(len(d) for d in tr._live_by_rid.values()) \
        == len(tr._live) == SlotTracker.MAX_LIVE
    tr.reset()


def test_recent_holds_4096_slots():
    flight.reset()
    tr = flight.slot_tracker()
    assert SlotTracker.KEEP == 4096
    for seq in range(1, SlotTracker.KEEP + 11):
        tr.on_event(3, flight.EV_PP_ACCEPT, seq, 0, 0, T0 + seq)
        tr.on_event(3, flight.EV_REPLY, seq, 0, 0, T0 + seq + 1)
    rows = tr.recent(limit=SlotTracker.KEEP)
    assert len(rows) == 4096
    assert rows[0]["seq"] == 11 and rows[-1]["seq"] == SlotTracker.KEEP + 10
    tr.reset()


def test_tpuprof_replays_the_new_events_like_the_live_tracker():
    from tools import tpuprof
    flight.reset()
    flight.set_thread_rid(6)
    for code, seq, arg in (
            (flight.EV_PP_CREATE, 21, 1500), (flight.EV_PP_ACCEPT, 21, 7),
            (flight.EV_COMMITTED, 21, 0), (flight.EV_EXEC_START, 21, 1),
            (flight.EV_EXEC_HANDLED, 21, 0),
            (flight.EV_EXEC_APPLY, 21, 1),
            (flight.EV_DUR_TAKE, 20, flight.DUR_CUT_FULL),  # not 21's
            (flight.EV_DUR_TAKE, 21, flight.DUR_CUT_QUIET),
            (flight.EV_DUR_WRITTEN, 21, 1), (flight.EV_DUR_GROUP, 21, 1),
            (flight.EV_REPLY, 21, 0)):
        flight.record(code, seq=seq, arg=arg)
        time.sleep(0.0002)            # every part > 0 on both sides
    with flight.span("tpuprof_span_case", 21):
        pass
    row = flight.slot_tracker().recent(rid=6)[-1]
    live = row["stages_ms"]
    dump = flight.snapshot()
    slot = tpuprof.fold_slots(dump)[(6, 21)]
    replayed = SlotTracker.fold(slot)
    assert {k: round(v, 3) for k, v in replayed.items()} == live
    assert live["order_wait"] == 1.5 and slot["reqs"] == 7
    assert {"handled", "taken", "written", "durable"} <= set(slot)
    assert slot["cut"] == row["dur_cut"] == flight.DUR_CUT_QUIET
    assert slot["group_runs"] == row["group_runs"] == 1
    assert all(live[k] > 0 for k in ("exec_reply", "exec_seal",
                                     "dur_queue", "dur_apply",
                                     "dur_fsync"))
    _assert_parts_sum(live)
    dump["_path"] = "live"
    assert any("tpuprof_span_case" in line
               for line in tpuprof.span_table([dump]))
    assert any("order_wait" in line for line in tpuprof.stage_table([dump]))


def test_live_cluster_write_accounts_for_order_and_lane():
    """A real write through an f=1 cluster: the primary's row carries
    the queue wait and the lane's own run; every row's split sums."""
    from tpubft.apps import counter
    from tpubft.testing import InProcessCluster
    flight.reset()
    with InProcessCluster(f=1) as cluster:
        cl = cluster.client()
        for _ in range(3):
            cl.send_write(counter.encode_add(1), timeout_ms=20000)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            rows = flight.slot_tracker().recent(limit=SlotTracker.KEEP)
            if [r for r in rows if r["stages_ms"]["order_wait"] > 0]:
                break
            time.sleep(0.05)
    primary = [r for r in rows if r["stages_ms"]["order_wait"] > 0]
    assert primary, rows
    assert len({r["rid"] for r in primary}) == 1      # one primary
    assert all(r["stages_ms"]["exec_run"] > 0 for r in primary)
    assert all(r["reqs"] >= 1 for r in primary)
    for r in rows:
        st = r["stages_ms"]
        assert st["exec_wait"] + st["exec_run"] \
            == pytest.approx(st["exec"], abs=0.002)
        assert st["dur_wait"] <= st["reply"]
    # the lane's run and the durability group are flight spans now
    names = {n for n in ("exec_run", "dur_group")
             if flight.span_events(n)}
    assert "exec_run" in names


def test_live_ledger_write_splits_the_run_and_the_group():
    """Real writes through an f=1 SKVBC cluster whose ledgers defer to
    the durability pipeline: every replica has a row with all six parts
    above 0, and on every row the parts sum to `exec_run` and
    `dur_wait`."""
    from tpubft.apps import skvbc
    from tpubft.kvbc import KeyValueBlockchain
    from tpubft.storage.memorydb import MemoryDB
    from tpubft.testing import InProcessCluster

    def handler_factory(_r):
        return skvbc.SkvbcHandler(
            KeyValueBlockchain(MemoryDB(), use_device_hashing=False))

    parts = ("exec_app", "exec_reply", "exec_seal", "dur_queue",
             "dur_apply", "dur_fsync")
    flight.reset()
    with InProcessCluster(f=1, handler_factory=handler_factory) as cluster:
        kv = skvbc.SkvbcClient(cluster.client(0))
        for i in range(4):
            assert kv.write([(b"split%d" % i, b"v")],
                            timeout_ms=15000).success
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            rows = flight.slot_tracker().recent(limit=SlotTracker.KEEP)
            whole = {r["rid"] for r in rows if r["reqs"]
                     and all(r["stages_ms"][k] > 0 for k in parts)}
            if len(whole) == cluster.n:
                break
            time.sleep(0.05)
    assert len(whole) == cluster.n, rows
    for r in rows:
        _assert_parts_sum(r["stages_ms"])
    assert all(r["group_runs"] >= 1 and r["dur_cut"] in flight.DUR_CUT_NAMES
               for r in rows if r["reqs"])


def _xplane_events(trace_dir, prefix="tpubft:"):
    """{name: [(line key, start ns, duration ns)]} of the host's `prefix`
    events in the profiler's trace."""
    import glob
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(prefix):
                    out.setdefault(e.name[len(prefix):], []).append(
                        (f"{plane.name}#{i}", int(e.start_ns),
                         int(e.duration_ns)))
    return out


def _ring_intervals(start_code, end_code, thread_prefix):
    """Durations in ns, start event -> the next end event, on the rings
    of threads named `thread_prefix*`."""
    out = []
    for ring in flight.snapshot()["rings"]:
        if not ring["thread"].startswith(thread_prefix):
            continue
        t0 = None
        for t, code, _seq, _view, _arg in ring["events"]:
            if code == start_code:
                t0 = t if t0 is None else t0
            elif code == end_code and t0 is not None:
                out.append(t - t0)
                t0 = None
    return out


def test_the_profiler_s_trace_and_the_rings_name_one_interval(tmp_path):
    """A real `jax.profiler` trace on the CPU backend holds a worker's
    `flight.span` and the io thread's `tpubft:dur_apply` /
    `tpubft:dur_fsync`, each as long as its ring interval, from threads
    other than the one that started the profiler. (The trace's lines
    are not asserted one a thread: in a process that has run many
    threads the host tracer has put two threads' events on one line.)"""
    import jax
    from tpubft.durability import DurabilityPipeline, SealedRun
    from tpubft.storage.interfaces import WriteBatch

    class SlowDB:
        """write_group takes 20 ms, sync 35 ms."""
        syncs_on_write = False

        def write_group(self, batches):
            time.sleep(0.020)

        def sync(self):
            time.sleep(0.035)

    class Stub:
        id = 0
        last_executed = 0
        aggregator = health = exec_lane = comm = None

        class clients:
            @staticmethod
            def on_request_executed(*_a):
                pass

        class incoming:
            @staticmethod
            def push_internal_once(_key):
                pass

    class Run:
        def __init__(self, seq):
            self.first = self.last = seq

    def probe():
        with flight.span("xplane_probe", 1):
            time.sleep(0.015)

    flight.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with flight.annotate("main_thread"):
            pass
        worker = threading.Thread(target=probe, name="xplane-probe")
        worker.start()
        worker.join()
        pipe = DurabilityPipeline(Stub(), group_max=1, window_us=0)
        pipe.start()
        try:
            for seq in (1, 2):
                pipe.seal(SealedRun(
                    run=Run(seq), executed_now=[],
                    batch=WriteBatch().put(b"k%d" % seq, b"v", b"blk"),
                    run_no=pipe.pending.stage({}), db=SlowDB()))
            assert pipe.drain(10)
        finally:
            pipe.stop()
    finally:
        jax.profiler.stop_trace()
    trace = _xplane_events(str(tmp_path))
    assert len(trace["main_thread"]) == 1
    # the span: its ring half inside its profiler half
    (_line, _start, span_ns), = trace["xplane_probe"]
    (_t, _seq, span_us), = flight.span_events("xplane_probe")
    assert 0 <= span_ns - span_us * 1000 < 2_000_000
    # the io thread's two intervals, one a group: the annotation inside
    # its ring interval, and as long to within 2 ms
    for name, start, end, sleep_ns in (
            ("dur_apply", flight.EV_DUR_TAKE, flight.EV_DUR_WRITTEN,
             20_000_000),
            ("dur_fsync", flight.EV_DUR_WRITTEN, flight.EV_DUR_GROUP,
             35_000_000)):
        ann = sorted(d for _k, _s, d in trace[name])
        ring = sorted(_ring_intervals(start, end, "dur-"))
        assert len(ann) == len(ring) == 2, (name, ann, ring)
        for a, r in zip(ann, ring):
            assert sleep_ns <= a <= r + 50_000
            assert r - a < 2_000_000, (name, a, r)
    # each nested in one of the group spans, on that span's line
    groups = trace["dur_group"]
    assert len(groups) == 2
    for name in ("dur_apply", "dur_fsync"):
        for line, start, dur in trace[name]:
            assert any(gl == line and gs <= start
                       and start + dur <= gs + gd
                       for gl, gs, gd in groups), (name, groups)


# ---------------- flight.span ----------------

def _my_events(code):
    me = threading.current_thread().name
    ring = next(r for r in flight.snapshot()["rings"] if r["thread"] == me)
    return [e for e in ring["events"] if e[1] == code]


def test_span_writes_one_event_with_its_name_and_duration():
    flight.reset()
    with flight.span("unit_span", seq=12):
        time.sleep(0.003)
    evs = _my_events(flight.EV_SPAN)
    assert len(evs) == 1
    t, _code, seq, view, us = evs[0]
    assert seq == 12 and us >= 2500
    snap = flight.snapshot()
    assert snap["span_names"][str(view)] == "unit_span"
    assert snap["event_names"][str(flight.EV_SPAN)] == "span"
    assert flight.span_events("unit_span") == [(t, 12, us)]
    assert flight.span_events("unit_span", since_ns=t + 1) == []
    assert flight.span_events("never_opened") == []
    # summed time written once, by the caller
    flight.record_span("unit_sum", 1234, seq=3)
    assert [(s, u) for _t, s, u in flight.span_events("unit_sum")] \
        == [(3, 1234)]
    # an exception leaves through the span and is still recorded
    with pytest.raises(KeyError):
        with flight.span("unit_span"):
            raise KeyError("x")
    assert len(flight.span_events("unit_span")) == 2


def test_span_events_refuses_a_ring_that_wrapped_past_the_window():
    flight.reset()
    with flight.span("wrap_span"):
        pass
    t_first = flight.span_events("wrap_span")[0][0]
    for i in range(flight.RING_SIZE):
        flight.record(flight.EV_ADM_INGEST, arg=i)
    with flight.span("wrap_span"):
        pass
    # the window reaches back past what the ring still holds
    assert flight.span_events("wrap_span", since_ns=t_first) is None
    later = flight.span_events("wrap_span",
                               since_ns=time.monotonic_ns() - 1000)
    assert later is not None
    flight.reset()


def test_span_is_nothing_when_the_recorder_is_off():
    flight.reset()
    flight._set_enabled(False)
    try:
        cm = flight.span("off_span")
        assert cm is flight.span("other")         # one shared no-op
        assert flight.annotate("off_annotation") is cm
        with cm:
            pass
        flight.record_span("off_sum", 5)
        assert not _my_events(flight.EV_SPAN)
    finally:
        flight._set_enabled(True)
    assert not flight.span_events("off_span")


def test_span_annotates_the_profiler_only_once_jax_is_imported(
        monkeypatch):
    seen = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(flight, "_trace_annotation", Ann)
    with flight.span("annotated"):
        seen.append("body")
    assert seen == [("enter", "tpubft:annotated"), "body",
                    ("exit", "tpubft:annotated")]
    # the profiler half alone: no ring event of its own
    flight.reset()
    with flight.annotate("exec_seal"):
        seen.append("seal")
    assert seen[3:] == [("enter", "tpubft:exec_seal"), "seal",
                        ("exit", "tpubft:exec_seal")]
    assert not _my_events(flight.EV_SPAN)


def test_bls_share_decompression_is_one_span_per_combine():
    from tpubft.crypto.interfaces import Cryptosystem
    cs = Cryptosystem("threshold-bls", threshold=2, num_signers=3,
                      seed=b"flight-bls")
    digest = b"d" * 32
    verifier = cs.create_threshold_verifier()
    acc = verifier.new_accumulator(with_share_verification=False)
    acc.set_expected_digest(digest)
    shares = [(i, cs.create_threshold_signer(i).sign_share(digest))
              for i in (1, 2)]
    flight.reset()
    for i, share in shares:
        acc.add(i, share)
    assert not flight.span_events("bls_share_decompress")   # not per share
    acc.get_full_signed_data()
    acc.get_full_signed_data()                  # nothing new to report
    spans = flight.span_events("bls_share_decompress")
    assert len(spans) == 1 and spans[0][2] > 0


# ---------------- kernel profiler ----------------

def test_device_section_profiles_kernels():
    from tpubft.ops.dispatch import device_section
    flight.reset()
    for i in range(3):
        with device_section("flighttest", batch=16 * (i + 1)):
            time.sleep(0.002)
    snap = flight.kernel_profiler().snapshot()
    st = snap["flighttest"]
    assert st["calls"] == 3
    assert st["first_call_ms"] >= 1.5            # the "compile" call
    assert st["warm_avg_ms"] >= 1.5              # the two warm calls
    assert st["batch_min"] == 16 and st["batch_max"] == 48
    assert st["breaker_states"].get("closed") == 3
    # the ring carries the enter/exit annotations too
    me = threading.current_thread().name
    ring = next(r for r in flight.snapshot()["rings"]
                if r["thread"] == me)
    codes = [e[1] for e in ring["events"]]
    assert flight.EV_DEV_ENTER in codes and flight.EV_DEV_EXIT in codes


def test_gate_wait_is_recorded_and_stays_off_the_breaker_s_clock():
    from tpubft.ops.dispatch import (device_breaker, device_dispatch,
                                     device_section)
    flight.reset()
    br = device_breaker()
    br.reset()
    slo0 = br.latency_slo_s
    br.latency_slo_s = 0.030        # the wait alone would breach it
    holding, release = threading.Event(), threading.Event()

    def holder():
        with device_dispatch():         # the raw gate: no attempt of
            holding.set()               # its own on the breaker's books
            release.wait(5)

    t = threading.Thread(target=holder, name="gate-holder")
    t.start()
    try:
        assert holding.wait(5)
        threading.Timer(0.05, release.set).start()
        before = br.snapshot()
        with device_section("gatewaiter", batch=2):
            pass
        t.join(5)
        row = flight.kernel_profiler().call_rows("gatewaiter")[-1]
        assert row["gate_wait_us"] >= 40_000
        assert row["device_us"] < 20_000 and row["prep_us"] == 0
        after = br.snapshot()
        # queueing behind a healthy thread is not a slow device
        assert after["state"] == "closed"
        assert after["slo_breaches"] == before["slo_breaches"]
        assert after["failures"] == before["failures"]
        snap = flight.kernel_profiler().snapshot()["gatewaiter"]
        assert snap["gate_wait_ms"] >= 40 and snap["prep_ms"] == 0
    finally:
        release.set()
        br.latency_slo_s = slo0
        br.reset()


def test_call_rows_have_dense_ordinals_and_survive_snapshot():
    from tpubft.ops.dispatch import device_section
    flight.reset()
    prof = flight.kernel_profiler()
    for i in range(5):
        with device_section("rowkind_a", batch=i + 1):
            pass
        if i % 2:
            with device_section("rowkind_b", batch=10, shards=2):
                pass
    mid = prof.snapshot()
    for _ in range(2):
        with device_section("rowkind_a", batch=9):
            pass
    a, b = prof.call_rows("rowkind_a"), prof.call_rows("rowkind_b")
    assert [r["ordinal"] for r in a] == [1, 2, 3, 4, 5, 6, 7]
    assert [r["ordinal"] for r in b] == [1, 2]
    assert [r["batch"] for r in a] == [1, 2, 3, 4, 5, 9, 9]
    # the shard view of a launch has totals, and no row of its own
    assert prof.snapshot()["rowkind_b.shard"]["calls"] == 2
    assert not prof.call_rows("rowkind_b.shard")
    # a reader cuts a window by the `calls` it snapshotted, no clock
    end = prof.snapshot()
    cut = [r for r in a if mid["rowkind_a"]["calls"] < r["ordinal"]
           <= end["rowkind_a"]["calls"]]
    assert [r["batch"] for r in cut] == [9, 9]
    assert set(a[0]) == {"kind", "ordinal", "batch", "t_enter_ns",
                         "prep_us", "gate_wait_us", "device_us"}
    assert all(x["t_enter_ns"] < y["t_enter_ns"] for x, y in zip(a, a[1:]))
    # the dump carries them, and taking it changes nothing
    dumped = [r for r in flight.snapshot()["kernel_calls"]
              if r["kind"] == "rowkind_a"]
    assert dumped == a == prof.call_rows("rowkind_a")
    # bounded
    for _ in range(flight.KernelProfiler.CALL_ROWS + 5):
        prof.record("rowkind_c", 1, 1000, "closed")
    assert len(prof.call_rows()) == flight.KernelProfiler.CALL_ROWS
    flight.reset()
    assert prof.call_rows() == []


def test_tier_accounts_prep_round_its_sections():
    from tpubft.ops.dispatch import device_section, device_tier
    flight.reset()
    with device_tier("tierkind"):
        time.sleep(0.004)                          # prep before
        with device_section("tierkind", batch=3):
            time.sleep(0.002)
            with device_section("tierkind.inner", batch=1):   # re-entrant
                pass
        time.sleep(0.003)                          # between two launches
        with device_tier("nested"):                # passes through
            with device_section("tierkind", batch=4):
                pass
        time.sleep(0.005)                          # tail
    first, second = flight.kernel_profiler().call_rows("tierkind")
    assert 3500 <= first["prep_us"] < 20_000
    assert first["device_us"] >= 1500
    # the second launch owns the gap before it and the tier's tail
    assert 7000 <= second["prep_us"] < 30_000
    inner = flight.kernel_profiler().call_rows("tierkind.inner")[0]
    assert inner["prep_us"] == 0        # ran inside its parent's device
    snap = flight.kernel_profiler().snapshot()["tierkind"]
    assert snap["prep_ms"] == pytest.approx(
        (first["prep_us"] + second["prep_us"]) / 1e3, abs=0.01)
    # outside any tier a section has no prep to report
    with device_section("tierkind", batch=1):
        pass
    assert flight.kernel_profiler().call_rows("tierkind")[-1][
        "prep_us"] == 0


def test_device_seam_imports_leave_jax_out_and_off_means_off():
    """A host-only replica must never pay the jax import for its
    telemetry; with TPUBFT_FLIGHT=0 the new seams keep nothing."""
    code = (
        "import sys\n"
        "from tpubft.ops import dispatch\n"
        "from tpubft.utils import flight\n"
        "with dispatch.device_tier('k'):\n"
        "    with dispatch.device_section('k', batch=2):\n"
        "        pass\n"
        "with flight.span('s'):\n"
        "    pass\n"
        "flight.record_span('t', 7)\n"
        "flight.record(flight.EV_EXEC_START, seq=1)\n"
        "print('jax' in sys.modules, flight.enabled(),\n"
        "      len(flight.kernel_profiler().call_rows()),\n"
        "      len(flight.span_events('s') or []),\n"
        "      flight.stage_summary()['live'])\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for flag, want in (("1", "False True 1 1 1"), ("0", "False False 0 0 0")):
        env = dict(os.environ, TPUBFT_FLIGHT=flag, PYTHONPATH=root)
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert out.stdout.split() == want.split(), (flag, out.stdout)


# ---------------- diagnostics surfaces ----------------

def test_status_endpoints_empty_recorder():
    flight.reset()
    reg = Registrar()
    flight.install_diagnostics(reg)
    slots = json.loads(reg.get_status("slots"))
    assert slots["summary"]["completed"] == 0
    assert slots["recent"] == []
    assert set(slots["summary"]["stages"]) == set(flight.STAGES)
    assert json.loads(reg.get_status("kernels")) == {}
    snap = json.loads(reg.get_status("flight"))
    assert snap["enabled"] and snap["ring_size"] == flight.RING_SIZE


def test_status_endpoints_over_the_server():
    flight.reset()
    _slot_events(seq=42, rid=1)
    from tpubft.ops.dispatch import device_section
    with device_section("srvtest", batch=4):
        pass
    reg = Registrar()
    flight.install_diagnostics(reg)
    with TimeRecorder(reg.histogram("op")):
        time.sleep(0.001)
    srv = DiagnosticsServer(reg)
    srv.start()
    try:
        keys = ctl.query(srv.port, "status list").split("\n")
        assert {"flight", "slots", "kernels"} <= set(keys)
        slots = json.loads(ctl.query(srv.port, "status get slots"))
        assert slots["summary"]["completed"] >= 1
        assert any(r["seq"] == 42 for r in slots["recent"])
        kernels = json.loads(ctl.query(srv.port, "status get kernels"))
        assert kernels["srvtest"]["calls"] == 1
        snap = json.loads(ctl.query(srv.port, "status get flight"))
        assert snap["rings"] and snap["event_names"]
        # histogram snapshot shape (`perf show`): the full percentile
        # contract every stage histogram also serves
        hist = json.loads(ctl.query(srv.port, "perf show op"))
        assert set(hist) == {"count", "avg", "max", "p50", "p95", "p99",
                             "unit"}
        assert hist["count"] == 1 and hist["unit"] == "us"
        # the slot stages registered their histograms on the GLOBAL
        # registrar (process-wide diagnostics)
        from tpubft.diagnostics import get_registrar
        gsnap = get_registrar().histogram_snapshot("slot.commit")
        assert gsnap is not None and gsnap["count"] >= 1
    finally:
        srv.stop()


# ---------------- dump plane + tpuprof ----------------

def test_stalled_health_transition_writes_dump_tpuprof_renders(tmp_path):
    from tools import tpuprof
    from tpubft.consensus.health import HealthMonitor
    from tpubft.utils.breaker import all_breakers
    for b in all_breakers().values():
        b.reset()
    flight.reset()
    flight.configure(dump_dir=str(tmp_path))
    try:
        _slot_events(seq=77, rid=2)
        clk = [100.0]
        hm = HealthMonitor("flighttest", clock=lambda: clk[0])
        hm.register_probe("dispatcher", 1.0,
                          detail_fn=lambda: {"external_q": 0})
        v = hm.poll_once()
        assert v["verdict"] == "healthy"
        assert hm.last_flight_dump is None
        clk[0] = 105.0                      # probe age 5s > 1s threshold
        v = hm.poll_once()
        assert v["verdict"] == "stalled"
        path = hm.last_flight_dump
        assert path and os.path.exists(path)
        assert hm.m_flight_dumps.value == 1
        # same episode: no second artifact
        clk[0] = 106.0
        hm.poll_once()
        assert hm.m_flight_dumps.value == 1
        dump = json.load(open(path))
        assert dump["reason"].endswith("stalled")
        assert dump["extra"]["stalled"] == ["dispatcher"]
        # the offline analyzer renders a timeline for the recorded slot
        out = tpuprof.render([path])
        assert "stage histogram" in out
        assert "slot timeline" in out
        assert "    77 " in out             # seq 77's timeline row
        assert "kernel profile" in out
        # recovery re-arms: beat + healthy poll, then a fresh stall
        # writes a NEW artifact
        hm.beat("dispatcher")
        assert hm.poll_once()["verdict"] == "healthy"
        clk[0] = 120.0
        assert hm.poll_once()["verdict"] == "stalled"
        assert hm.m_flight_dumps.value == 2
    finally:
        flight.configure(dump_dir=flight._default_dump_dir())


def test_chaos_red_verdict_attaches_flight_dump(tmp_path):
    from tpubft.testing.campaign import ChaosCampaign, ScenarioSpec
    flight.configure(dump_dir=str(tmp_path))
    try:
        def red(ctx):
            raise AssertionError("injected red verdict")

        def green(ctx):
            return {"fine": True}

        art = ChaosCampaign(seed=7, specs=[
            ScenarioSpec("seeded-red", red, "inproc", 10.0),
            ScenarioSpec("seeded-green", green, "inproc", 10.0),
        ]).run()
        vr = next(s for s in art["scenarios"] if s["name"] == "seeded-red")
        vg = next(s for s in art["scenarios"]
                  if s["name"] == "seeded-green")
        assert not vr["ok"] and "injected red verdict" in vr["error"]
        assert vr["flight_dump"] and os.path.exists(vr["flight_dump"])
        dump = json.load(open(vr["flight_dump"]))
        assert dump["reason"] == "chaos-red-seeded-red"
        assert "injected red verdict" in dump["extra"]["error"]
        assert vg["ok"] and "flight_dump" not in vg
    finally:
        flight.configure(dump_dir=flight._default_dump_dir())


def test_dump_retention_prunes_oldest(tmp_path, monkeypatch):
    flight.configure(dump_dir=str(tmp_path))
    monkeypatch.setattr(flight, "MAX_DUMPS", 3)
    try:
        paths = [flight.dump(f"ret{i}") for i in range(7)]
        assert all(paths)
        files = sorted(f for f in os.listdir(tmp_path)
                       if f.endswith(".json"))
        # prune runs before each write: at most MAX_DUMPS + the fresh one
        assert len(files) <= 4
        assert os.path.basename(paths[-1]) in files      # newest kept
        assert os.path.basename(paths[0]) not in files   # oldest pruned
    finally:
        flight.configure(dump_dir=flight._default_dump_dir())


def test_health_dump_throttle(tmp_path):
    from tpubft.consensus.health import HealthMonitor
    from tpubft.utils.breaker import all_breakers
    for b in all_breakers().values():
        b.reset()
    flight.configure(dump_dir=str(tmp_path))
    try:
        clk = [0.0]
        hm = HealthMonitor("flaptest", clock=lambda: clk[0])
        hm.register_probe("dispatcher", 1.0)

        def flap(at):
            clk[0] = at
            v = hm.poll_once()
            assert v["verdict"] == "stalled"
            hm.beat("dispatcher")
            assert hm.poll_once()["verdict"] == "healthy"

        flap(5.0)
        assert hm.m_flight_dumps.value == 1
        flap(8.0)                       # within dump_min_interval_s
        assert hm.m_flight_dumps.value == 1      # throttled, no artifact
        flap(30.0)
        assert hm.m_flight_dumps.value == 2
    finally:
        flight.configure(dump_dir=flight._default_dump_dir())


def test_dump_survives_unwritable_dir(tmp_path):
    target = tmp_path / "nope"
    target.write_text("a file, not a directory")
    flight.configure(dump_dir=str(target))
    try:
        assert flight.dump("unwritable") is None   # never raises
    finally:
        flight.configure(dump_dir=flight._default_dump_dir())
