"""The client's retry timer (ISSUE 33): the first retry of a write —
which is the broadcast to every replica — follows the client's own
measured reply latency, never below `retry_timeout_ms` and never above
a third of the request's budget; and a dead primary is still passed
within the old bound. A stub transport stands for the cluster: it keeps
every send and answers as a cluster of the given latency would."""
import threading
import time

import pytest

from tpubft.bftclient import BftClient, ClientConfig
from tpubft.bftclient.client import ReplyLatency
from tpubft.comm.interfaces import ConnectionStatus, ICommunication
from tpubft.consensus import messages as m
from tpubft.consensus.keys import ClusterKeys
from tpubft.utils.config import ReplicaConfig

N, F = 4, 1
CLIENT = N          # the first client id of a 4-replica cluster


class StubCluster(ICommunication):
    """Replies to a write `latency_s` after its first copy arrived —
    from every replica, or (`primary_dead`) only once the message has
    reached the backups, and then from them alone."""

    def __init__(self, latency_s: float, primary_dead: bool = False):
        self.latency_s, self.primary_dead = latency_s, primary_dead
        self.sends = {}             # req_seq -> [(t, dest)]
        self._receiver = None
        self._answered = set()

    def start(self, receiver) -> None:
        self._receiver = receiver

    def stop(self) -> None:
        self._receiver = None

    def is_running(self) -> bool:
        return self._receiver is not None

    def get_connection_status(self, node) -> ConnectionStatus:
        return ConnectionStatus.CONNECTED

    def send(self, dest, data) -> None:
        seq = m.unpack(data).req_seq_num
        self.sends.setdefault(seq, []).append((time.monotonic(), dest))
        reached = {d for _, d in self.sends[seq]}
        alive = reached - ({0} if self.primary_dead else set())
        if seq in self._answered or len(alive) < (3 if self.primary_dead
                                                  else 1):
            return
        self._answered.add(seq)
        repliers = [r for r in range(N)
                    if not (self.primary_dead and r == 0)]
        threading.Timer(self.latency_s, self._reply,
                        (seq, repliers)).start()

    def _reply(self, seq, repliers) -> None:
        for r in repliers:
            reply = m.ClientReplyMsg(
                sender_id=r, req_seq_num=seq, current_primary=0,
                reply=b"done", replica_specific_info=b"")
            if self._receiver is not None:
                self._receiver.on_new_message(r, reply.pack())


def client_on(comm, **cfg) -> BftClient:
    keys = ClusterKeys.generate(ReplicaConfig(f_val=F,
                                              num_of_client_proxies=1), 1)
    return BftClient(ClientConfig(client_id=CLIENT, f_val=F, **cfg),
                     keys.for_node(CLIENT), comm)


def counters(cl) -> dict:
    return {k: c.value for k, c in cl.metrics.counters.items()}


def test_estimate_is_mean_plus_deviations():
    est = ReplyLatency(2.0)
    assert est.upper_s() is None
    est.note(1.0)
    assert est.upper_s() == pytest.approx(1.0 + 2 * 0.5)
    for _ in range(60):
        est.note(3.0)               # the cluster slowed down: it follows
    assert est.mean_s == pytest.approx(3.0, abs=0.01)
    assert 3.0 <= est.upper_s() < 3.1
    assert ReplyLatency(0.0).upper_s() is None


def test_first_retry_follows_the_measured_latency():
    comm = StubCluster(latency_s=0.6)
    cl = client_on(comm)
    try:
        for i in range(3):
            assert cl.send_write(b"w%d" % i, timeout_ms=5000) == b"done"
    finally:
        cl.stop()
    first, second, third = (comm.sends[s] for s in sorted(comm.sends))
    # nothing measured yet: the old timer — primary, then all at 250 ms
    assert [d for _, d in first][:1] == [0] and len(first) >= 1 + N
    assert 0.2 <= first[1][0] - first[0][0] <= 0.45
    # measured 0.6 s: a write that is answered in 0.6 s is sent once
    assert second == second[:1] and third == third[:1]
    assert counters(cl) == {"client_sends": 3, "client_broadcasts": 1,
                            "client_retransmissions":
                                counters(cl)["client_retransmissions"]}
    assert counters(cl)["client_retransmissions"] >= 1


def test_a_dead_primary_is_passed_within_the_old_bound():
    comm = StubCluster(latency_s=0.01)
    cl = client_on(comm)
    try:
        for i in range(4):          # a fast cluster: estimate << 250 ms
            cl.send_write(b"w%d" % i, timeout_ms=5000)
        assert cl._latency.upper_s() < 0.25
        comm.primary_dead = True
        t0 = time.monotonic()
        assert cl.send_write(b"after", timeout_ms=5000) == b"done"
        took = time.monotonic() - t0
    finally:
        cl.stop()
    last = comm.sends[max(comm.sends)]
    assert last[0][1] == 0                      # the primary first
    gap = last[1][0] - last[0][0]
    assert 0.2 <= gap <= 0.45, gap              # all the others at 250 ms
    assert {d for _, d in last} == set(range(N))
    assert took < 0.25 + 0.45


def test_the_timer_is_bounded_by_a_third_of_the_budget():
    comm = StubCluster(latency_s=10.0, primary_dead=True)
    cl = client_on(comm)
    cl._latency.note(100.0)         # a cluster that took minutes once
    t0 = time.monotonic()
    with pytest.raises(Exception):
        cl.send_write(b"w", timeout_ms=1500)
    cl.stop()
    sends = comm.sends[max(comm.sends)]
    # budget 1.5 s: the broadcast at 0.5 s, not at 100 s
    assert 0.45 <= sends[1][0] - t0 <= 0.8
    assert len({d for _, d in sends}) == N


def test_reads_keep_the_fixed_timer_and_count_nothing():
    comm = StubCluster(latency_s=0.05)
    cl = client_on(comm)
    try:
        cl._latency.note(5.0)
        cl.send_read(b"r", timeout_ms=3000)
    finally:
        cl.stop()
    assert counters(cl) == {"client_sends": 0, "client_broadcasts": 0,
                            "client_retransmissions": 0}
    assert cl._latency.mean_s == 5.0
