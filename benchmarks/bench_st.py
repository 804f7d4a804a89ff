"""State-transfer catch-up benchmark: stop-and-wait vs pipelined.

Measures destination catch-up blocks/sec over the in-process transport
with INJECTED PER-MESSAGE LATENCY — the regime that motivated the
pipelined fetch loop: with one range in flight (window=1, the old
behavior) catch-up is bounded by a single source's RTT; with a sliding
window of ranges striped across several sources the RTTs overlap and
throughput approaches aggregate-link speed (the aggregated-gossip
insight of arXiv 1911.04698 applied to block dissemination).

Topology: `--sources` source replicas share one pre-built chain; one
empty destination transfers the whole thing. Every message (request,
chunk, reject) is delayed `--latency-ms` by a scheduler thread; all
protocol handling is serialized under one dispatch lock, emulating each
node's single consensus dispatcher (and keeping the comparison honest on
a 1-core host: the pipeline may only overlap LATENCY, not compute).

`--smoke` runs a small shape for the tier-1 wiring test
(tests/test_bench_st_smoke.py).

Usage:
  python -m benchmarks.bench_st [--blocks 256] [--range 16] [--window 4]
      [--sources 4] [--latency-ms 10] [--device] [--smoke] [--json]
"""
from __future__ import annotations

import argparse
import heapq
import json
import threading
import time
from typing import Dict, Optional

from tpubft.kvbc import BlockUpdates, KeyValueBlockchain
from tpubft.statetransfer import StateTransferManager
from tpubft.statetransfer.manager import StConfig
from tpubft.storage import MemoryDB


class LatencyNet:
    """In-process message router with a fixed per-message delivery delay.
    One scheduler thread pops messages in deliver-time order; every
    handle_message runs under a single dispatch lock."""

    def __init__(self, latency_s: float) -> None:
        self.latency = latency_s
        self.nodes: Dict[int, StateTransferManager] = {}
        self._q: list = []
        self._cv = threading.Condition()
        self._seq = 0
        self._stop = False
        self.dispatch_lock = threading.Lock()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="latency-net")

    def add(self, node_id: int, mgr) -> None:
        self.nodes[node_id] = mgr

    def sender(self, from_id: int):
        def send(dest: int, payload: bytes) -> None:
            with self._cv:
                self._seq += 1
                heapq.heappush(self._q, (time.monotonic() + self.latency,
                                         self._seq, from_id, dest, payload))
                self._cv.notify()
        return send

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify()
        self._thread.join(timeout=5)

    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._stop and (
                        not self._q
                        or self._q[0][0] > time.monotonic()):
                    timeout = None
                    if self._q:
                        timeout = max(self._q[0][0] - time.monotonic(), 0)
                    self._cv.wait(timeout=timeout if timeout != 0 else 1e-4)
                if self._stop:
                    return
                _, _, sender, dest, payload = heapq.heappop(self._q)
            mgr = self.nodes.get(dest)
            if mgr is not None:
                with self.dispatch_lock:
                    mgr.handle_message(sender, payload)


def _build_chain(n_blocks: int, value_bytes: int) -> KeyValueBlockchain:
    bc = KeyValueBlockchain(MemoryDB(), use_device_hashing=False)
    payload = b"v" * value_bytes
    for i in range(n_blocks):
        bc.add_block(BlockUpdates()
                     .put("ver", f"k{i}".encode(), payload)
                     .put("ver", b"seq", str(i).encode()))
    return bc


def run(n_blocks: int, range_blocks: int, window: int, n_sources: int,
        latency_s: float, device: bool = False,
        value_bytes: int = 256, timeout_s: float = 120.0) -> dict:
    """One catch-up transfer; returns blocks/sec + manager counters."""
    chain = _build_chain(n_blocks, value_bytes)
    net = LatencyNet(latency_s)
    dest_id = n_sources
    for r in range(n_sources):
        src = StateTransferManager(r, chain)
        net.add(r, src)
        src.bind(net.sender(r), lambda s, d: None,
                 replica_ids=list(range(n_sources)) + [dest_id], f_val=1)
        src.on_checkpoint_stable(10, chain.state_digest())
    dest_bc = KeyValueBlockchain(MemoryDB(), use_device_hashing=False)
    dest = StateTransferManager(
        dest_id, dest_bc,
        StConfig(fetch_batch_blocks=range_blocks, window_ranges=window,
                 retry_timeout_s=5.0,
                 device_digest_threshold=(range_blocks if device
                                          else 10 ** 9),
                 use_device_digests=device))
    net.add(dest_id, dest)
    done = threading.Event()
    dest.bind(net.sender(dest_id), lambda s, d: done.set(),
              replica_ids=list(range(n_sources)), f_val=n_sources - 1)

    if device:
        # warm the XLA sha256 program so compile time doesn't pollute the
        # measured transfer
        from tpubft.ops.sha256 import sha256_batch_mixed
        sha256_batch_mixed([b"x" * value_bytes] * range_blocks)

    net.start()
    t0 = time.monotonic()
    with net.dispatch_lock:
        dest.start_collecting(10, {10: (chain.state_digest(), b"")})
    while not done.is_set() and time.monotonic() - t0 < timeout_s:
        done.wait(0.02)
        with net.dispatch_lock:
            dest.tick()
    elapsed = time.monotonic() - t0
    net.stop()
    ok = done.is_set() and dest_bc.last_block_id == n_blocks
    snap = dest.metrics.snapshot()["counters"]
    return {
        "ok": ok,
        "blocks": n_blocks,
        "range_blocks": range_blocks,
        "window": window,
        "sources": n_sources,
        "latency_ms": latency_s * 1000,
        "elapsed_s": round(elapsed, 4),
        "blocks_per_sec": round(n_blocks / elapsed, 1) if elapsed else 0.0,
        "device": device,
        "device_digest_batches": snap["device_digest_batches"],
        "scalar_digests": snap["scalar_digests"],
        "source_failovers": snap["source_failovers"],
    }


def compare(n_blocks: int, range_blocks: int, window: int, n_sources: int,
            latency_s: float, device: bool = False) -> dict:
    base = run(n_blocks, range_blocks, 1, n_sources, latency_s,
               device=device)
    piped = run(n_blocks, range_blocks, window, n_sources, latency_s,
                device=device)
    speedup = (piped["blocks_per_sec"] / base["blocks_per_sec"]
               if base["blocks_per_sec"] else 0.0)
    return {"baseline": base, "pipelined": piped,
            "speedup": round(speedup, 2)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--blocks", type=int, default=256)
    ap.add_argument("--range", type=int, default=16, dest="range_blocks")
    ap.add_argument("--window", type=int, default=4)
    ap.add_argument("--sources", type=int, default=4)
    ap.add_argument("--latency-ms", type=float, default=20.0)
    ap.add_argument("--device", action="store_true",
                    help="route window digests through the batched "
                         "device SHA-256 kernel (counter-visible)")
    ap.add_argument("--smoke", action="store_true",
                    help="small fast shape for the tier-1 wiring test")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    if args.smoke:
        args.blocks, args.range_blocks = 64, 8
        args.latency_ms = 5.0
    out = compare(args.blocks, args.range_blocks, args.window,
                  args.sources, args.latency_ms / 1000.0,
                  device=args.device)
    if args.json:
        print(json.dumps(out))
    else:
        for name in ("baseline", "pipelined"):
            r = out[name]
            print(f"{name:9s} window={r['window']} sources={r['sources']} "
                  f"latency={r['latency_ms']:.0f}ms "
                  f"blocks={r['blocks']} range={r['range_blocks']} -> "
                  f"{r['blocks_per_sec']:.1f} blocks/sec "
                  f"({r['elapsed_s']:.3f}s, ok={r['ok']}, "
                  f"device_batches={r['device_digest_batches']})")
        print(f"speedup: {out['speedup']}x")
    ok = out["baseline"]["ok"] and out["pipelined"]["ok"]
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
