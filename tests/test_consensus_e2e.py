"""End-to-end consensus tests: 4-replica counter cluster (the reference's
simpleTest scenario) over the in-process loopback bus."""
import time

import pytest

from tpubft.apps import counter
from tpubft.testing import InProcessCluster


def test_single_write_commits_and_replies():
    with InProcessCluster(f=1) as cluster:
        cl = cluster.client()
        reply = cl.send_write(counter.encode_add(5))
        assert counter.decode_reply(reply) == 5


def test_sequential_writes_accumulate():
    with InProcessCluster(f=1) as cluster:
        cl = cluster.client()
        total = 0
        for delta in (3, 10, -4, 100):
            total += delta
            reply = cl.send_write(counter.encode_add(delta))
            assert counter.decode_reply(reply) == total
        # all replicas converge on the same state
        deadline = time.time() + 5
        while time.time() < deadline:
            values = [cluster.handlers[r].value for r in range(cluster.n)]
            if all(v == total for v in values):
                break
            time.sleep(0.05)
        assert all(cluster.handlers[r].value == total
                   for r in range(cluster.n))


def test_read_only_request_fast_path():
    with InProcessCluster(f=1) as cluster:
        cl = cluster.client()
        cl.send_write(counter.encode_add(42))
        reply = cl.send_read(counter.encode_read())
        assert counter.decode_reply(reply) == 42


def test_duplicate_request_gets_cached_reply():
    with InProcessCluster(f=1) as cluster:
        cl = cluster.client()
        def executed(at_least):
            # a reply quorum is 2f+1 and the replies leave before the
            # dispatcher books the run: give replica 0 a moment to count
            deadline = time.monotonic() + 5
            while cluster.metric(0, "counters", "executed_requests") \
                    < at_least and time.monotonic() < deadline:
                time.sleep(0.01)
            return cluster.metric(0, "counters", "executed_requests")

        r1 = cl.send_write(counter.encode_add(7))
        # metrics: executed once per replica; a client retransmission of an
        # executed request must not re-execute (reply cache)
        executed_before = executed(1)
        r2 = cl.send_write(counter.encode_add(7))
        assert counter.decode_reply(r2) == 14  # new request executes
        assert executed(executed_before + 1) == executed_before + 1


def test_two_clients_interleaved():
    with InProcessCluster(f=1, num_clients=2) as cluster:
        c0, c1 = cluster.client(0), cluster.client(1)
        counter.decode_reply(c0.send_write(counter.encode_add(1)))
        counter.decode_reply(c1.send_write(counter.encode_add(2)))
        v0 = counter.decode_reply(c0.send_write(counter.encode_add(3)))
        assert v0 == 6


def test_f2_seven_replicas():
    with InProcessCluster(f=2) as cluster:
        assert cluster.n == 7
        cl = cluster.client()
        assert counter.decode_reply(cl.send_write(counter.encode_add(9))) == 9


def test_metrics_advance():
    with InProcessCluster(f=1) as cluster:
        cl = cluster.client()
        cl.send_write(counter.encode_add(1))
        assert cluster.metric(0, "counters", "sent_preprepares") >= 1
        # the client reply proves a quorum (3) executed; the 4th replica
        # finishes its async verification moments later — poll for it
        deadline = time.time() + 5
        while time.time() < deadline:
            if all(cluster.metric(r, "gauges", "last_executed_seq") >= 1
                   for r in range(4)):
                break
            time.sleep(0.02)
        for r in range(4):
            assert cluster.metric(r, "gauges", "last_executed_seq") >= 1


def test_progress_with_one_crashed_backup():
    """n=4, f=1: consensus must survive one crashed non-primary replica."""
    with InProcessCluster(f=1) as cluster:
        cl = cluster.client()
        assert counter.decode_reply(cl.send_write(counter.encode_add(1))) == 1
        cluster.kill(3)  # backup, not the view-0 primary
        assert counter.decode_reply(cl.send_write(counter.encode_add(2))) == 3
