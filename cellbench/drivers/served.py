"""Driver `served`: an in-process BFT cluster serving SimpleKVBC
writes, read from the clients' side.

It is chip_smoke.served() (PR 21, ran on the chip) with a clock: the
cluster is built the same way — InProcessCluster, SkvbcHandler over the
merkle KeyValueBlockchain on the native kvlog engine, bftclient,
SkvbcClient — from the configuration's file, the clients are the mix's
closed loops, and a window is laid over their completions. The window
drives `SkvbcClient.write` / `write_batch` and nothing else.
"""
from __future__ import annotations

import os
import shutil
import statistics
import tempfile
import threading
import time

from cellbench import generate
from cellbench.harness import (breaker_events, breaker_snapshot,
                               kernel_profile, say, single_device_programs,
                               warm)
from cellbench.reference.skvbc import Ledger

SIG_COUNTERS = ("sigs_device_dispatched", "batched_verifies",
                "scalar_fallbacks", "degraded_verifies", "memo_hits")


def quantile(sorted_vals, q: float) -> float:
    """The q-quantile by nearest rank over all the values."""
    return sorted_vals[min(len(sorted_vals) - 1,
                           int(q * len(sorted_vals)))]


class Driver:
    def __init__(self, cell, seed: int, log) -> None:
        self.cell, self.seed, self.log = cell, seed, log
        self.cfg = cell.config
        self.params = cell.workload
        self.clients = generate.kv_clients(cell.traffic, seed)
        self.records = []            # one row per finished message
        self._mu = threading.Lock()
        self._stop = threading.Event()
        self._threads = []
        self.workdir = None
        self.cluster = None

    # -----------------------------------------------------------------
    def setup(self) -> None:
        from tpubft.apps.skvbc import SkvbcClient, SkvbcHandler
        from tpubft.kvbc import KeyValueBlockchain
        from tpubft.kvbc.replica import open_db
        from tpubft.storage.metadata import (CONSENSUS_META_FAMILIES,
                                             DBPersistentStorage)
        from tpubft.testing import InProcessCluster

        warm(single_device_programs(**self.params["programs"]), self.log)
        overrides = dict(self.cfg["replica_config"])
        device = overrides["crypto_backend"] == "tpu"
        self.workdir = tempfile.mkdtemp(prefix="cellbench-")
        self.dbs = {}

        def handler_factory(r):
            # what KvbcReplica builds for a deployment: the native kvlog
            # engine at the config's durability defaults, the merkle
            # SKVBC layout, device hashing when the backend is the device
            self.dbs[r] = open_db(
                os.path.join(self.workdir, f"replica-{r}.kvlog"),
                sync_writes=False, sync_families=CONSENSUS_META_FAMILIES)
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
        # the same traffic, unmeasured: the first device call, the
        # ledgers' first blocks and the pipeline's fill are set-up
        time.sleep(self.params["warmup_s"])

    def _loop(self, client) -> None:
        from tpubft.bftclient.client import TimeoutError_
        kv = self.kvs[client.index]
        timeout_ms = self.params["request_timeout_ms"]
        i = 0
        while not self._stop.is_set():
            writes = client.message(i)
            blocks, ok = [], False
            t0 = time.monotonic()
            try:
                if client.writes_per_message == 1:
                    replies = [kv.write(writes[0], timeout_ms=timeout_ms)]
                else:
                    replies = kv.write_batch(writes, timeout_ms=timeout_ms)
                ok = all(r.success for r in replies)
                blocks = [r.latest_block for r in replies]
            except TimeoutError_:
                pass
            t1 = time.monotonic()
            with self._mu:
                self.records.append(dict(
                    client=client.index, cls=client.cls, message=i,
                    sent=t0, done=t1, ok=ok, writes=writes,
                    blocks=blocks))
            i += 1

    # -----------------------------------------------------------------
    def _counters(self) -> dict:
        from tpubft.utils import flight
        cl = self.cluster
        out = {name: sum(cl.metric(r, "counters", name,
                                   component="signature_manager")
                         for r in range(cl.n))
               for name in SIG_COUNTERS}
        out["slots_finalized"] = flight.stage_summary()["finalized_total"]
        out["kernels"] = kernel_profile()
        return out

    def measure(self, seconds: float, tracer) -> None:
        from cellbench.run import span
        self.before = self._counters()
        self.t_open = time.monotonic()
        self.t_close = self.t_open + seconds
        while not tracer.due(self.t_close) and tracer.on:
            time.sleep(0.05)
        self.traced_from = self._counters()
        tracer.start()
        # the profiler keeps host spans of the thread that started it:
        # the clients' threads wait for replies all through this one
        with span("clients_await_replies"):
            time.sleep(max(0.0, self.t_close - time.monotonic()))
        self.t_close = time.monotonic()
        self.after = self._counters()
        from tpubft.utils import flight
        keep = flight.SlotTracker.KEEP
        n = self.after["slots_finalized"] - self.before["slots_finalized"]
        self.slots = flight.slot_tracker().recent(limit=keep)[-min(n, keep):] \
            if n > 0 else []
        tracer.stop()
        self._stop.set()

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
        self.attempted = sum(len(r["writes"]) for r in win)
        self.failed = sum(len(r["writes"]) for r in win if not r["ok"])
        lat = sorted((r["done"] - r["sent"]) * 1e3 for r in win if r["ok"])
        self.latencies_ms = lat
        by_cls = {}
        for r in win:
            if r["ok"]:
                by_cls.setdefault(r["cls"], []).append(
                    (r["done"] - r["sent"]) * 1e3)
        views = [self.cluster.metric(r, "gauges", "view")
                 for r in range(self.cluster.n)]
        say(phase="window", seconds=round(self.t_close - self.t_open, 3),
            messages=len(win), writes_attempted=self.attempted,
            writes_failed=self.failed,
            samples_beyond_p95=len(lat) - int(0.95 * len(lat)),
            by_class={c: dict(messages=len(v),
                              p50_ms=round(statistics.median(v), 1))
                      for c, v in by_cls.items()},
            slots_finalized=len(self.slots),
            reqs_per_slot=round((self.attempted - self.failed)
                                / max(1, len(self.slots))
                                * self.cluster.n, 2), views=views)

    def end_to_end(self) -> dict:
        window_s = self.t_close - self.t_open
        acked = self.attempted - self.failed
        out = {"writes_per_s": acked / window_s}
        if self.latencies_ms:
            out["write_p50_ms"] = quantile(self.latencies_ms, 0.50)
            out["write_p95_ms"] = quantile(self.latencies_ms, 0.95)
        return out

    def layer_context(self) -> dict:
        return dict(before=self.before, after=self.after,
                    traced_from=self.traced_from,
                    slots=self.slots, window=self.window,
                    writes_acked=self.attempted - self.failed,
                    window_s=self.t_close - self.t_open)

    # -----------------------------------------------------------------
    def check(self, cmp) -> None:
        """Every write acknowledged in the run (warm-up, window and the
        drain after it) against the plain reference, through the
        client; and the four ledgers against each other."""
        cl = self.cluster
        acked = [r for r in self.records if r["ok"]]
        unacked_writes = sum(len(r["writes"]) for r in self.records
                             if not r["ok"])
        ledger = Ledger()
        for r in acked:
            for ws in r["writes"]:
                ledger.write(ws)
        # a reply quorum is 2f+1: give the last replica time to apply
        chains = [cl.handlers[r].blockchain for r in range(cl.n)]
        # (up to two minutes, while any ledger still grows)
        deadline = time.monotonic() + 120
        quiet_s = self.params.get("settle_quiet_s", 10)
        seen, moved = None, time.monotonic()
        while (any(bc.last_block_id < ledger.blocks for bc in chains)
               and time.monotonic() < min(deadline, moved + quiet_s)):
            now = [bc.last_block_id for bc in chains]
            if now != seen:
                seen, moved = now, time.monotonic()
            time.sleep(0.05)
        heads = [(bc.last_block_id, bc.state_digest(), bc.merkle_root("kv"))
                 for bc in chains]
        cmp.add("ledgers_divergent",
                sum(h != heads[0] for h in heads[1:]), 0)
        # the reference gives every acknowledged write one block; a
        # write that was sent and never acknowledged may have one too
        blocks = max(h[0] for h in heads)
        cmp.add("acked_writes_without_block",
                max(0, ledger.blocks - min(h[0] for h in heads)), 0)
        cmp.add("blocks_nobody_wrote",
                max(0, blocks - ledger.blocks - unacked_writes), 0)
        replied = [b for r in acked for b in r["blocks"]]
        cmp.add("reply_block_conflicts",
                len(replied) - len(set(replied))
                + sum(not 1 <= b <= blocks for b in replied), 0)
        keys = sorted(ledger.state)
        reads = {}
        timeout_ms = self.params["request_timeout_ms"]
        for i in range(0, len(keys), 256):
            reads.update(self.kvs[0].read(keys[i:i + 256],
                                          timeout_ms=timeout_ms))
        want = ledger.read(keys)
        cmp.add("reads_wrong",
                sum(reads.get(k) != v for k, v in want.items())
                + sum(k not in want for k in reads), 0)
        cmp.add("degraded_verifies", self._counters()["degraded_verifies"], 0)
        cmp.add("breaker_events", breaker_events(self._breaker0), 0)
        cmp.add("device_saw_no_signature",
                int(self.after["sigs_device_dispatched"]
                    == self.before["sigs_device_dispatched"]), 0)
        say(phase="check", writes_acked=ledger.blocks,
            writes_unacked=unacked_writes, blocks=blocks,
            keys_read=len(reads), state_root=heads[0][2].hex())

    def close(self) -> None:
        self._stop.set()
        if self.cluster is not None:
            self.cluster.stop()
        for db in getattr(self, "dbs", {}).values():
            db.close()
        if self.workdir:
            shutil.rmtree(self.workdir, ignore_errors=True)
