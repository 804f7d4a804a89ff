"""The gap resend (`Replica._on_replica_status` (b)): a fresh gap is
answered by the primary and the f+1 ring followers of the lagging peer,
nobody else and nobody twice in two beacon periods; a gap that stands
is answered by everyone, so a replica whose followers and primary it
cannot hear still catches up. And the two process-wide hot spots PR 33
took the locks off: the stall watchdog's beat and the loopback bus."""
import threading
import time

import pytest

from tpubft.apps import counter
from tpubft.testing import InProcessCluster

PERIOD_MS = 100
CFG = {"status_report_timer_ms": PERIOD_MS, "view_change_timer_ms": 60000}
LAGGING = 3             # n=4, view 0: primary 0; 3's followers are 0 and 1
OUTSIDER = 2            # neither primary nor follower of 3


def _executed(cluster, r):
    return cluster.replicas[r].last_executed


def _lag_replica_3(cluster, writes=6):
    """Replica 3 is cut off both ways (nobody sees its beacons, so
    nobody has noticed a gap yet) while the other three commit
    `writes`."""
    cut = {"on": True}
    cluster.bus.add_hook(
        lambda s, d, data: None if cut["on"] and LAGGING in (s, d)
        else data)
    cl = cluster.client()
    for i in range(writes):
        cl.send_write(counter.encode_add(1), timeout_ms=15000)
    deadline = time.time() + 10
    while time.time() < deadline and min(
            _executed(cluster, r) for r in (0, 1, 2)) < writes:
        time.sleep(0.02)
    assert _executed(cluster, LAGGING) == 0
    return cut


def _wait_caught_up(cluster, bound_s):
    target = _executed(cluster, 0)
    deadline = time.monotonic() + bound_s
    while time.monotonic() < deadline \
            and _executed(cluster, LAGGING) < target:
        time.sleep(0.02)
    return _executed(cluster, LAGGING), target


def test_a_fresh_gap_is_answered_by_followers_and_primary_only():
    # a long beacon period: the gap closes well inside
    # GAP_EVERYONE_AFTER of them
    with InProcessCluster(f=1, cfg_overrides=dict(
            CFG, status_report_timer_ms=500)) as cluster:
        cut = _lag_replica_3(cluster)
        cut["on"] = False
        got, target = _wait_caught_up(cluster, 5.0)
        assert got == target
        resends = {r: cluster.metric(r, "counters", "gap_resends")
                   for r in (0, 1, 2)}
        assert resends[0] >= 1 and resends[1] >= 1
        # the gap closed within GAP_EVERYONE_AFTER periods: the
        # outsider kept silent
        assert resends[OUTSIDER] == 0, resends


def test_a_replica_deaf_to_followers_and_primary_still_catches_up():
    """Both ring followers of replica 3 (one of them the primary) are
    down as far as it can tell; the outsider answers once the gap has
    stood for GAP_EVERYONE_AFTER beacon periods."""
    with InProcessCluster(f=1, cfg_overrides=CFG) as cluster:
        cut = _lag_replica_3(cluster)
        cluster.bus.add_hook(
            lambda s, d, data: None if d == LAGGING and s in (0, 1)
            else data)
        cut["on"] = False
        every = cluster.replicas[OUTSIDER].GAP_EVERYONE_AFTER
        # 6 slots at up to MAX_GAP_RESEND a beacon: one answer does it
        got, target = _wait_caught_up(
            cluster, (every + 6) * PERIOD_MS / 1e3 + 3.0)
        assert got == target
        assert cluster.metric(OUTSIDER, "counters", "gap_resends") >= 1


def test_nobody_answers_one_gap_twice_in_two_beacon_periods():
    from tpubft.consensus import messages as m
    with InProcessCluster(f=1, cfg_overrides=dict(
            CFG, status_report_timer_ms=60000)) as cluster:
        cl = cluster.client()
        for _ in range(3):
            cl.send_write(counter.encode_add(1), timeout_ms=15000)
        rep = cluster.replicas[0]
        deadline = time.time() + 5
        while time.time() < deadline and rep.last_executed < 3:
            time.sleep(0.02)
        beacon = m.ReplicaStatusMsg(
            sender_id=LAGGING, view=0, last_stable_seq=0,
            last_executed_seq=1, in_view_change=False)
        before = rep.m_gap_resends.value
        rep._on_replica_status(beacon)
        rep._on_replica_status(beacon)
        assert rep.m_gap_resends.value == before + 1
        # the peer moved on: a new gap is answered at once
        beacon.last_executed_seq = 2
        rep._on_replica_status(beacon)
        assert rep.m_gap_resends.value == before + 2
        # and the outsider is silent on both
        out = cluster.replicas[OUTSIDER]
        silent = out.m_gap_resends.value
        out._on_replica_status(beacon)
        assert out.m_gap_resends.value == silent


def test_watchdog_beats_and_unregisters_race_free():
    """`beat` takes no lock (PR 33): threads beating, unregistering and
    the monitor's scan must neither raise nor lose a live name."""
    from tpubft.utils.racecheck import StallWatchdog
    dog = StallWatchdog(threshold_s=30.0)
    errors, stop = [], threading.Event()

    def churn(i):
        try:
            while not stop.is_set():
                dog.beat(f"loop-{i}")
                dog.beat(f"brief-{i}")
                dog.unregister(f"brief-{i}")
        except Exception as e:      # noqa: BLE001 — the test's finding
            errors.append(e)

    def scan():
        try:
            while not stop.is_set():
                list(dog._beats.items())
        except Exception as e:      # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=churn, args=(i,)) for i in range(8)]
    threads.append(threading.Thread(target=scan))
    for t in threads:
        t.start()
    time.sleep(0.5)
    stop.set()
    for t in threads:
        t.join(5)
    dog.stop()
    assert not errors, errors
    assert {f"loop-{i}" for i in range(8)} <= set(dog._beats)
    assert not [n for n in dog._beats if n.startswith("brief-")]


def test_a_stalled_then_beating_loop_is_reported_again():
    """The lock-free beat still clears `_reported`, so a second stall
    of the same loop is reported as the first was."""
    from tpubft.utils.racecheck import StallWatchdog
    dog = StallWatchdog(threshold_s=30.0)
    dog._reported.add("loop")
    dog.beat("loop")
    dog.stop()
    assert "loop" not in dog._reported


@pytest.mark.parametrize("posters", [1, 16])
def test_the_bus_delivers_every_message_in_each_sender_s_order(posters):
    """The bus queue is a SimpleQueue (PR 33): many threads posting at
    once lose nothing and keep each sender's order."""
    from tpubft.comm.loopback import LoopbackBus
    bus = LoopbackBus()
    got = []

    class Sink:
        def on_new_message(self, sender, data):
            got.append((sender, int(data)))

    dest = bus.create(99)
    dest.start(Sink())
    each = 200
    threads = [threading.Thread(
        target=lambda s=s: [bus.post(s, 99, b"%d" % i) for i in range(each)])
        for s in range(posters)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    deadline = time.time() + 5
    while time.time() < deadline and len(got) < posters * each:
        time.sleep(0.01)
    bus.shutdown()
    assert len(got) == posters * each
    for s in range(posters):
        assert [i for snd, i in got if snd == s] == list(range(each))
