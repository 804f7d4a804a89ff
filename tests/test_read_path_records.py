"""What the program records of the read path and of overwrites: the ring
spans `ro_read` (a replica's whole answer to a read-only request) and
`ro_read_wait` (SkvbcHandler.read's wait for the application's lock),
and the `kvbc` counter `smt_keys_overwritten` (leaves a merkle walk found
already stored) at both walks."""
import hashlib
import threading
import time

import pytest

from tpubft.apps import skvbc
from tpubft.kvbc import KeyValueBlockchain
from tpubft.kvbc.sparse_merkle import (_DEVICE_THRESHOLD, METRICS,
                                       SparseMerkleTree)
from tpubft.storage import MemoryDB
from tpubft.testing.cluster import InProcessCluster
from tpubft.utils import flight


def _counters():
    return dict(METRICS.snapshot()["counters"])


def _vh(i: int) -> bytes:
    return hashlib.sha256(b"value %d" % i).digest()


@pytest.mark.parametrize("width", [8, _DEVICE_THRESHOLD],
                         ids=["native", "levels"])
def test_overwritten_leaves_are_counted_at_both_walks(width):
    tree = SparseMerkleTree(MemoryDB(), use_device=False)
    keys = [b"key %d" % i for i in range(2 * width)]
    c0 = _counters()
    tree.update_batch({k: _vh(i) for i, k in enumerate(keys[:width])},
                      version=1)
    c1 = _counters()
    assert c1["smt_keys_overwritten"] == c0["smt_keys_overwritten"]
    # half of the next block overwrites or deletes a stored leaf, half
    # writes a fresh one
    half = width // 2
    ups = {k: _vh(100 + i) for i, k in enumerate(keys[:half - 1])}
    ups[keys[half - 1]] = None
    ups.update({k: _vh(200 + i) for i, k in enumerate(keys[width:
                                                          width + half])})
    tree.update_batch(ups, version=2)
    c2 = _counters()
    assert c2["smt_keys_updated"] - c1["smt_keys_updated"] == 2 * half
    assert c2["smt_keys_overwritten"] - c1["smt_keys_overwritten"] == half
    native = c2["smt_keys_native"] - c1["smt_keys_native"]
    assert native == (2 * half if 2 * half < _DEVICE_THRESHOLD else 0)


def test_ro_read_wait_is_the_wait_for_the_application_lock():
    h = skvbc.SkvbcHandler(KeyValueBlockchain(MemoryDB()), merkle=True)
    h.execute(1, 1, 0, skvbc.pack(skvbc.WriteRequest(
        writeset=[(b"k", b"v")])))
    request = skvbc.pack(skvbc.ReadRequest(keys=[b"k"]))
    since = time.monotonic_ns()
    assert skvbc.unpack(h.read(9, request)).reads == [(b"k", b"v")]
    # a lane holding the lock for 0.2 s: the read waits for it
    held = threading.Event()

    def lane():
        with h._lock:
            held.set()
            time.sleep(0.2)

    t = threading.Thread(target=lane)
    t.start()
    held.wait()
    h.read(9, request)
    t.join()
    spans = flight.span_events("ro_read_wait", since_ns=since)
    assert len(spans) == 2
    assert spans[0][2] < 100_000 and spans[1][2] >= 150_000


def test_every_replica_records_ro_read_for_a_read_only_request():
    def handler(_r=None):
        return skvbc.SkvbcHandler(KeyValueBlockchain(MemoryDB()))

    since = time.monotonic_ns()
    with InProcessCluster(f=1, handler_factory=handler) as cluster:
        client = cluster.client(0)
        client.start()
        kv = skvbc.SkvbcClient(client)
        assert kv.read([b"nothing"], timeout_ms=20_000) == {}
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            spans = flight.span_events("ro_read", since_ns=since) or []
            if len(spans) >= cluster.n:
                break
            time.sleep(0.05)
    assert len(spans) >= cluster.n
    waits = flight.span_events("ro_read_wait", since_ns=since) or []
    assert len(waits) >= cluster.n
