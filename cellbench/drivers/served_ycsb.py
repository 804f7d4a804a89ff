"""Driver `served_ycsb`: YCSB's core workload over the `served` driver's
cluster (`ycsb_n4`), its reads and its final state held to the plain
reference of `cellbench/reference/ycsb.py`.

The cluster is `served`'s, built the same way from the configuration's
file — InProcessCluster, SkvbcHandler over the merkle KeyValueBlockchain
on the native kvlog engine, bftclient, SkvbcClient — and the window's
rules are its rules (late is late, not wrong; the drain waits for what
is in flight). What differs:

before anything is built, a check that the program counts what the
cell reads (`missing_capabilities`), and a refusal in one line if not;

set-up loads the records before the cluster starts,
`ledger_blocks_at_start` blocks through `KeyValueBlockchain.add_blocks`
over replica 0's store, one block a call (wide enough for the level
walk and its device hashing), each batch applied as the same engine
record to every replica's store, so that the four logs are byte for
byte the same, as replicas restored from one checkpoint;

the clients are YCSB's (`cellbench/ycsb.py`): each operation a
read-only `SkvbcClient.read` of one key (f+1 matching replies) or a
blind `SkvbcClient.write` of one whole record (2f+c+1); `attempted` and
`failed` count operations, and the write latencies are the updates';

the program's `kvbc` counters are snapshotted at the window's open and
close, and the read path's ring spans (`ro_read`, `ro_read_wait`) are
read as it closes;

the check: the ledgers as `served` compares them, the preload counted;
every read completed in the run against the version history
(`reads_not_linearizable`); every key updated in the run and a seeded
sample of untouched records read back through the client
(`final_state_wrong`); the `kv` merkle root against a plain root of the
reference's final state (`merkle_root_wrong`); and that the window
launched the ed25519 kernel (`ed25519_device_items_missing`).
"""
from __future__ import annotations

import os
import random
import statistics
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from cellbench import ycsb
from cellbench.drivers.served import Driver as Served
from cellbench.drivers.served_bls import _AtClose
from cellbench.harness import (breaker_events, breaker_snapshot, say,
                               single_device_programs, warm)
from cellbench.reference import ycsb as ref

# the read path's ring spans, read when the window closes
READ_SPANS = ("ro_read", "ro_read_wait")
# what the cell reads of the program, beyond what `served` reads
NEEDED_COUNTERS = ("smt_keys_updated", "smt_keys_overwritten")


def missing_capabilities() -> list:
    """What the cell reads and the program lacks, one string each."""
    from tpubft.kvbc import sparse_merkle
    from tpubft.utils import flight
    counters = sparse_merkle.METRICS.snapshot()["counters"]
    missing = [f"tpubft.kvbc.sparse_merkle: the kvbc counter {name}"
               for name in NEEDED_COUNTERS if name not in counters]
    if not hasattr(flight, "span_events_tail"):
        missing.append("tpubft.utils.flight.span_events_tail")
    return missing


class _Tee:
    """Replica 0's store while the records load: a batch written to it
    is encoded once and applied to it, and then, as the same engine
    record, to each other store on a thread of that store's own (the
    engine's apply leaves the interpreter lock)."""

    def __init__(self, first, others) -> None:
        self._first, self._others = first, others
        self._pools = [ThreadPoolExecutor(1) for _ in others]
        self._applied = []

    def __getattr__(self, name):
        return getattr(self._first, name)

    def write(self, batch) -> None:
        payload, families = batch.encode(), batch.families
        self._first._apply(payload, families)
        self._applied += [pool.submit(db._apply, payload, families)
                          for pool, db in zip(self._pools, self._others)]

    def join(self) -> None:
        for done in self._applied:
            done.result()
        for pool in self._pools:
            pool.shutdown()


class Driver(Served):
    def __init__(self, cell, seed: int, log) -> None:
        missing = missing_capabilities()
        if missing:
            raise SystemExit(
                f"{cell.name}: this program cannot serve "
                f"{cell.config['name']}; it lacks " + "; ".join(missing))
        self.cell, self.seed, self.log = cell, seed, log
        self.cfg = cell.config
        self.params = cell.workload
        self.load = ycsb.Records(self.cfg, seed)
        self.clients = ycsb.clients(cell.traffic, self.load, seed)
        self.preload_blocks = self.cfg["ledger_blocks_at_start"]
        self.records = []            # one row per finished operation
        self.read_spans = None
        self._mu = threading.Lock()
        self._stop = threading.Event()
        self._threads = []
        self.workdir = None
        self.cluster = None

    # -----------------------------------------------------------------
    def setup(self) -> None:
        from tpubft.apps.skvbc import SkvbcClient, SkvbcHandler
        from tpubft.kvbc import KeyValueBlockchain
        from tpubft.storage.metadata import DBPersistentStorage
        from tpubft.testing import InProcessCluster

        warm(single_device_programs(**self.params["programs"]), self.log)
        overrides = dict(self.cfg["replica_config"])
        device = overrides["crypto_backend"] == "tpu"
        self.workdir = tempfile.mkdtemp(prefix="cellbench-")
        self.dbs = self._preload(device)

        def handler_factory(r):
            return SkvbcHandler(
                KeyValueBlockchain(self.dbs[r], use_device_hashing=device),
                merkle=True)

        self.cluster = InProcessCluster(
            f=self.cfg["cluster"]["f"], c=self.cfg["cluster"]["c"],
            num_clients=len(self.clients), handler_factory=handler_factory,
            storage_factory=lambda r: DBPersistentStorage(self.dbs[r]),
            cfg_overrides=overrides, seed=b"cellbench-%d" % self.seed)
        self.kvs = [SkvbcClient(self.cluster.client(c.index))
                    for c in self.clients]
        self.cluster.start()
        self._breaker0 = breaker_snapshot()
        self._threads = [threading.Thread(target=self._loop, args=(c,),
                                          name=f"client-{c.index}")
                         for c in self.clients]
        for t in self._threads:
            t.start()
        # the same traffic, unmeasured
        time.sleep(self.params["warmup_s"])

    def _log(self, r: int) -> str:
        return os.path.join(self.workdir, f"replica-{r}.kvlog")

    def _open(self, r: int):
        from tpubft.kvbc.replica import open_db
        from tpubft.storage.metadata import CONSENSUS_META_FAMILIES
        # what KvbcReplica opens for a deployment: the native kvlog
        # engine at the config's durability defaults
        return open_db(self._log(r), sync_writes=False,
                       sync_families=CONSENSUS_META_FAMILIES)

    def _preload(self, device: bool) -> dict:
        """Every replica's store with the records in it; {replica: its
        store, open}. The load runs once, on replica 0's store, and each
        batch it writes is applied to the other replicas' stores as the
        same engine record (`_Tee`), so the four logs come out byte for
        byte the same, as replicas restored from one checkpoint, and
        their indexes build while the load computes the next block."""
        from tpubft.kvbc import BLOCK_MERKLE, BlockUpdates, KeyValueBlockchain
        n = self.cfg["cluster"]["n"]
        per_block = -(-self.load.count // self.preload_blocks)
        t0 = time.monotonic()
        dbs = {r: self._open(r) for r in range(n)}
        tee = _Tee(dbs[0], [dbs[r] for r in range(1, n)])
        chain = KeyValueBlockchain(tee, use_device_hashing=device)
        for pairs in self.load.blocks(per_block):
            bu = BlockUpdates()
            for key, value in pairs:
                bu.put("kv", key, value, cat_type=BLOCK_MERKLE)
            # one block a call: a call of several takes the cross-block
            # walk, which is several times slower on the host
            chain.add_blocks([bu])
        t1 = time.monotonic()
        tee.join()
        heads = {(KeyValueBlockchain(db, use_device_hashing=False)
                  .last_block_id, os.path.getsize(self._log(r)))
                 for r, db in dbs.items()}
        root = chain.merkle_root("kv")
        log_bytes = os.path.getsize(self._log(0))
        if heads != {(self.preload_blocks, log_bytes)}:
            raise RuntimeError(f"the load left the stores at {heads}, not "
                               f"{self.preload_blocks} blocks alike")
        say(phase="preload", records=self.load.count,
            blocks=self.preload_blocks, log_bytes=log_bytes,
            load_s=round(t1 - t0, 2),
            others_s=round(time.monotonic() - t1, 2), root=root.hex())
        return dbs

    def _loop(self, client) -> None:
        from tpubft.bftclient.client import TimeoutError_
        kv = self.kvs[client.index]
        timeout_ms = self.params["request_timeout_ms"]
        keys = self.load.keys
        i = 0
        while not self._stop.is_set():
            kind, rec, value = client.op(i)
            key = keys[rec]
            digest, block, ok = None, 0, False
            t0 = time.monotonic()
            try:
                if kind == "read":
                    got = kv.read([key], timeout_ms=timeout_ms).get(key)
                    digest, ok = (ref.digest(got) if got is not None
                                  else None), True
                else:
                    reply = kv.write([(key, value)], timeout_ms=timeout_ms)
                    ok, block = reply.success, reply.latest_block
            except TimeoutError_:
                pass
            t1 = time.monotonic()
            if kind == "update":
                digest = ref.digest(value)
            with self._mu:
                self.records.append(dict(
                    client=client.index, op=i, kind=kind, key=key,
                    sent=t0, done=t1, ok=ok, digest=digest, block=block))
            i += 1

    # -----------------------------------------------------------------
    def _counters(self) -> dict:
        from tpubft.kvbc.sparse_merkle import METRICS
        out = super()._counters()
        out["kvbc"] = dict(METRICS.snapshot()["counters"])
        return out

    def measure(self, seconds: float, tracer) -> None:
        super().measure(seconds, _AtClose(tracer, self._read_spans))

    def _read_spans(self) -> None:
        """The read path's ring spans since the window opened, read as
        it closes: a dispatcher's ring holds its last seconds only.
        {name: (spans, from where on every ring that holds them is
        whole)}."""
        from tpubft.utils import flight
        since = int(self.t_open * 1e9)
        self.read_spans = {name: flight.span_events_tail(name, since)
                           for name in READ_SPANS}

    def finish(self) -> None:
        """Wait for what was in flight at the close: an answer that
        comes late is late, not wrong."""
        for t in self._threads:
            t.join(self.params["request_timeout_ms"] / 1e3 + 30)
        alive = [t.name for t in self._threads if t.is_alive()]
        if alive:
            raise RuntimeError(f"clients never returned: {alive[:4]}")
        win = [r for r in self.records
               if self.t_open <= r["done"] <= self.t_close]
        self.window = win
        self.attempted = len(win)
        self.failed = sum(not r["ok"] for r in win)

        def lat(kind):
            return sorted((r["done"] - r["sent"]) * 1e3 for r in win
                          if r["ok"] and r["kind"] == kind)
        self.latencies_ms = lat("update")
        self.read_latencies_ms = lat("read")
        self.updates_acked = len(self.latencies_ms)
        kv0, kv1 = self.before["kvbc"], self.after["kvbc"]
        views = [self.cluster.metric(r, "gauges", "view")
                 for r in range(self.cluster.n)]
        say(phase="window", seconds=round(self.t_close - self.t_open, 3),
            operations=self.attempted, failed=self.failed,
            updates_acked=self.updates_acked,
            reads=len(self.read_latencies_ms),
            update_p50_ms=(round(statistics.median(self.latencies_ms), 1)
                           if self.latencies_ms else None),
            read_p50_ms=(round(statistics.median(self.read_latencies_ms), 1)
                         if self.read_latencies_ms else None),
            samples_beyond_p95=(len(self.latencies_ms)
                                - int(0.95 * len(self.latencies_ms))),
            slots_finalized=len(self.slots),
            kvbc={k: kv1.get(k, 0) - kv0.get(k, 0) for k in kv1},
            read_spans={name: dict(spans=len(spans),
                                   from_s=round(from_ns / 1e9
                                                - self.t_open, 3))
                        for name, (spans, from_ns)
                        in (self.read_spans or {}).items()},
            views=views)

    def layer_context(self) -> dict:
        return dict(super().layer_context(),
                    writes_acked=self.updates_acked,
                    read_latencies_ms=self.read_latencies_ms,
                    read_spans=self.read_spans, t_close=self.t_close)

    # -----------------------------------------------------------------
    def check(self, cmp) -> None:
        """Everything acknowledged in the run (warm-up, window and the
        drain after it) against the plain reference: the ledgers, every
        read, the final state through the client, the merkle root."""
        cl = self.cluster
        ups = [r for r in self.records if r["kind"] == "update"]
        acked = [r for r in ups if r["ok"]]
        unacked = len(ups) - len(acked)
        per_block = -(-self.load.count // self.preload_blocks)
        writes = [(1 + rec // per_block, self.load.keys[rec],
                   ref.digest(self.load.value(rec)))
                  for rec in range(self.load.count)]
        writes += [(r["block"], r["key"], r["digest"]) for r in acked]
        history = ref.History()
        # in block order; a reply that names a block it cannot have
        # (`reply_block_conflicts`) still finds its place
        for block, key, digest in sorted(writes, key=lambda w: w[0]):
            history.apply(block, key, digest)
        expected = self.preload_blocks + len(acked)

        # a reply quorum is 2f+1: give the last replica time to apply
        chains = [cl.handlers[r].blockchain for r in range(cl.n)]
        deadline = time.monotonic() + 120
        quiet_s = self.params.get("settle_quiet_s", 10)
        seen, moved = None, time.monotonic()
        while (any(bc.last_block_id < expected for bc in chains)
               and time.monotonic() < min(deadline, moved + quiet_s)):
            now = [bc.last_block_id for bc in chains]
            if now != seen:
                seen, moved = now, time.monotonic()
            time.sleep(0.05)
        heads = [(bc.last_block_id, bc.state_digest(), bc.merkle_root("kv"))
                 for bc in chains]
        cmp.add("ledgers_divergent",
                sum(h != heads[0] for h in heads[1:]), 0)
        blocks = max(h[0] for h in heads)
        cmp.add("acked_writes_without_block",
                max(0, expected - min(h[0] for h in heads)), 0)
        cmp.add("blocks_nobody_wrote",
                max(0, blocks - expected - unacked), 0)
        replied = [r["block"] for r in acked]
        cmp.add("reply_block_conflicts",
                len(replied) - len(set(replied))
                + sum(not self.preload_blocks < b <= blocks
                      for b in replied), 0)

        # every read completed in the run
        t0 = time.monotonic()
        reads = [r for r in self.records if r["kind"] == "read" and r["ok"]]
        spans = ref.bounds([(r["sent"], r["done"], r["block"])
                            for r in acked],
                           [(r["sent"], r["done"]) for r in reads])
        floor = self.preload_blocks
        wrong_reads = sum(
            not history.read_is_linearizable(r["key"], r["digest"],
                                              max(lo, floor),
                                              max(hi, floor))
            for r, (lo, hi) in zip(reads, spans))
        cmp.add("reads_not_linearizable", wrong_reads, 0)
        t1 = time.monotonic()

        # the final state: every key updated in the run, and a seeded
        # sample of the records nobody updated, through the client
        updated = sorted({r["key"] for r in ups})
        untouched = sorted(set(self.load.keys) - set(updated))
        sample = random.Random(f"{self.seed}/ycsb/untouched").sample(
            untouched, min(self.params["untouched_keys_read"],
                           len(untouched)))
        keys = updated + sorted(sample)
        asked = set(keys)
        got = {}
        timeout_ms = self.params["request_timeout_ms"]
        for i in range(0, len(keys), 64):
            got.update(self.kvs[0].read(keys[i:i + 64],
                                        timeout_ms=timeout_ms))
        cmp.add("final_state_wrong",
                sum(ref.digest(got[k]) != history.latest(k) if k in got
                    else 1 for k in keys)
                + sum(k not in asked for k in got), 0)
        t2 = time.monotonic()
        root = ref.merkle_root(history.state())
        cmp.add("merkle_root_wrong", int(root != heads[0][2]), 0)
        cmp.add("degraded_verifies", self._counters()["degraded_verifies"], 0)
        cmp.add("breaker_events", breaker_events(self._breaker0), 0)
        items = [s["kernels"].get("ed25519", (0, 0))[1]
                 for s in (self.before, self.after)]
        cmp.add("ed25519_device_items_missing", int(items[1] <= items[0]), 0)
        say(phase="check", updates_acked=len(acked), updates_unacked=unacked,
            reads_checked=len(reads), keys_read_back=len(keys),
            blocks=blocks, ed25519_items_in_window=items[1] - items[0],
            reads_s=round(t1 - t0, 2), read_back_s=round(t2 - t1, 2),
            root_s=round(time.monotonic() - t2, 2),
            state_root=heads[0][2].hex())
