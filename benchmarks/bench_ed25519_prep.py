"""What the ed25519 batch's host half costs on this host:
`ops/ed25519.prepare_batch` alone, and the whole `sig_verify` tier round
`crypto/tpu.verify_batch_mixed` as SigManager runs it, with the kernel
replaced by a stub that sleeps (the device's time, the interpreter lock
released), for 64 and 1,000 items. Each twice: alone, and beside a
thread that signs 1,000 ed25519 messages a slot into a 4-deep queue, the
shape of the flood's sign-ahead producer (`cellbench/drivers/flood.py`),
the loop taking one slot from it a round and then sleeping `--rest-ms`
for the rest of a slot. Host arithmetic only — nothing runs on a device.

Usage: python -m benchmarks.bench_ed25519_prep [--rounds 60]
           [--kernel-ms 2.2] [--rest-ms 45]
Prints one JSON line a setting: milliseconds a call (median and 90th
percentile) of `prepare_batch` or of the tier, each measured in rounds
of its own, and for the tier its host prep as the kernel profiler's call
rows record it (`prep_us`, what `flood_verify_prep_ms` reads).
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import queue
import statistics
import sys
import threading
import time

SLOT = 1000


def _slot(signers, j: int):
    """One slot's (scheme, key, message, signature) entries: ≈ 45-byte
    messages that carry the slot's number, as the flood's."""
    out = []
    for i in range(SLOT):
        s = signers[i % len(signers)]
        msg = b"slot %08d item %04d " % (j, i) + bytes(16)
        out.append(("ed25519", s.public_bytes(), msg, s.sign(msg)))
    return out


class Signer(threading.Thread):
    def __init__(self, signers) -> None:
        super().__init__(name="sign-ahead", daemon=True)
        self.signers, self.q = signers, queue.Queue(maxsize=4)
        self.stop = threading.Event()

    def run(self) -> None:
        j = 0
        while not self.stop.is_set():
            item = _slot(self.signers, j)
            while not self.stop.is_set():
                try:
                    self.q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    pass
            j += 1


def _ms(samples_ns):
    ms = sorted(x / 1e6 for x in samples_ns)
    return {"p50": round(statistics.median(ms), 3),
            "p90": round(ms[int(0.9 * (len(ms) - 1))], 3)}


def measure(what: str, n: int, rounds: int, rest_s: float, fixed,
            signer):
    """`rounds` calls of `prepare_batch` (what="prep") or of the tier
    (what="tier"), each on a fresh slot from the signer where one runs.
    Returns the calls' times and, for the tier, its call rows' prep."""
    from tpubft.crypto.tpu import verify_batch_mixed
    from tpubft.ops import ed25519 as ops
    from tpubft.ops.dispatch import device_tier
    from tpubft.utils import flight
    took_ns, row_ns = [], []
    waits = 0
    for _ in range(rounds):
        if signer is None:
            entries = fixed
        else:
            t = time.perf_counter()
            entries = signer.q.get()
            waits += time.perf_counter() - t > 0.001
        entries = entries[:n]
        if what == "prep":
            items = [(m, s, pk) for _, pk, m, s in entries]
            t0 = time.perf_counter_ns()
            ops.prepare_batch(items)
            took_ns.append(time.perf_counter_ns() - t0)
        else:
            t0 = time.perf_counter_ns()
            with device_tier("sig_verify"):
                verdicts = verify_batch_mixed(entries)
            took_ns.append(time.perf_counter_ns() - t0)
            assert len(verdicts) == n
            row_ns.append(flight.kernel_profiler().call_rows("ed25519")[-1]
                          ["prep_us"] * 1e3)
        time.sleep(rest_s)
    out = {"ms": _ms(took_ns), "input_waits": waits}
    if row_ns:
        out["prep_us_row_ms"] = _ms(row_ns)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rounds", type=int, default=60)
    ap.add_argument("--kernel-ms", type=float, default=2.2,
                    help="the stub kernel's sleep (the chip's "
                         "verify_kernel for 1,000 lanes: 2.2 ms)")
    ap.add_argument("--rest-ms", type=float, default=45.0,
                    help="sleep after each round: the rest of a slot")
    args = ap.parse_args(argv)
    import numpy as np

    from tpubft.crypto import cpu
    from tpubft.ops import ed25519 as ops

    def stub_kernel(s_win, *_):
        time.sleep(args.kernel_ms / 1e3)
        return np.ones(s_win.shape[1], bool)

    ops._use_pallas = lambda: False
    ops.verify_kernel = stub_kernel
    signers = [cpu.Ed25519Signer.generate(seed=b"bench%d" % i)
               for i in range(64)]
    fixed = _slot(signers, 0)
    machine = {"node": platform.node(), "cpus": os.cpu_count(),
               "python": platform.python_version()}
    for with_signer in (False, True):
        signer = Signer(signers) if with_signer else None
        if signer is not None:
            signer.start()
            time.sleep(0.5)
        try:
            for n in (64, SLOT):
                for what in ("prep", "tier"):
                    measure(what, n, 3, 0.0, fixed, signer)      # warm
                    row = measure(what, n, args.rounds,
                                  args.rest_ms / 1e3, fixed, signer)
                    print(json.dumps(dict(
                        call=("prepare_batch" if what == "prep"
                              else "sig_verify_tier"),
                        items=n, signer=with_signer,
                        kernel_ms=args.kernel_ms, rest_ms=args.rest_ms,
                        **row, machine=machine)), flush=True)
        finally:
            if signer is not None:
                signer.stop.set()
                signer.join(5)
    return 0


if __name__ == "__main__":
    sys.exit(main())
