"""Fused cross-slot combine plane + certificate-scheme crossover bench.

Two questions from ISSUE 11 / ROADMAP item 3 ("kill the
threshold-combine tax"):

  1. `--sweep` — combines/sec of the FUSED plane
     (`IThresholdVerifier.combine_batch`: one segmented MSM + one RLC
     pairing check per flush for BLS, one batched ed25519 verify for the
     multisig vector) vs the per-slot reference loop, across in-flight
     slot counts. This is the microbench of what
     consensus/collectors.CombineBatcher drains per flush.
  2. `--crossover` — per-combine cost of the Ed25519 multisig vector vs
     BLS threshold at committee sizes n ∈ {4, 7, 16, 32}: the measured
     basis for `crypto/systems.ADAPTIVE_SCHEME_CROSSOVER_N` (the
     "adaptive" certificate scheme's configure-time pick; EdDSA-vs-BLS
     committee framing: arXiv 2302.00418).

Every row re-checks that fused and per-slot verdicts (combined bytes,
ok flags, bad-share ids) are identical (`verdicts_match`) — a speed row
from a wrong combine would be worse than no row. Rows produced through
the device backend on a CPU/XLA host carry the `degraded` +
`probe_error` convention (PR 4): they validate plumbing, not speed.

Usage: python -m benchmarks.bench_combine [--sweep] [--crossover]
           [--backend cpu|tpu] [--slots 1,2,4,8,16] [--secs 0.5]
           [--smoke]
Prints one JSON line per row.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import List

from tpubft.utils.jaxcache import setup_cache
from tpubft.crypto.interfaces import Cryptosystem, IThresholdVerifier

# slow-path quorum 2f+c+1 for c=0, f=(n-1)//3 — the preset --cases
# (4, 7, 16, 32) bracket the adaptive crossover's default boundary and
# the aggregation-gossip target size, but any n calibrates
def quorum_k(n: int) -> int:
    if n < 4:
        raise SystemExit(f"--cases: n={n} below the minimum BFT "
                         f"committee (n >= 3f+1 with f >= 1)")
    return 2 * ((n - 1) // 3) + 1


def _verifier(scheme: str, k: int, n: int, backend: str, system=None):
    system = system or Cryptosystem(scheme, k, n,
                                    seed=b"bench-combine-%d" % n)
    if backend == "tpu":
        from tpubft.crypto.tpu import make_threshold_verifier
        return system, make_threshold_verifier(
            scheme, k, n, system.public_key, system.share_public_keys)
    return system, system.create_threshold_verifier()


def _jobs(system, k: int, slots: int):
    signers = {i: system.create_threshold_signer(i)
               for i in range(1, k + 1)}
    out = []
    for s in range(slots):
        d = s.to_bytes(4, "big") * 8
        out.append((d, {i: signers[i].sign_share(d)
                        for i in range(1, k + 1)}))
    return out


def _rate(fn, secs: float) -> float:
    """Calls/sec of fn over a ~secs window (>=2 calls)."""
    fn()                                   # warmup / compile
    t0 = time.perf_counter()
    n = 0
    while True:
        fn()
        n += 1
        dt = time.perf_counter() - t0
        if dt >= secs and n >= 2:
            return n / dt


def _annotate_device(row: dict, backend: str) -> dict:
    if backend != "tpu":
        return row
    import jax
    row["platform"] = jax.default_backend()
    if row["platform"] == "cpu":
        row["degraded"] = True
        row["probe_error"] = ("device path executed on the XLA CPU "
                              "backend: validates the fused kernel "
                              "plumbing, not device speed")
    return row


def sweep_row(scheme: str, n: int, k: int, slots: int, backend: str,
              secs: float) -> dict:
    system, v = _verifier(scheme, k, n, backend)
    jobs = _jobs(system, k, slots)
    fused = v.combine_batch(jobs)
    perslot = IThresholdVerifier.combine_batch(v, jobs)
    fused_rate = _rate(lambda: v.combine_batch(jobs), secs)
    loop_rate = _rate(
        lambda: IThresholdVerifier.combine_batch(v, jobs), secs)
    row = {
        "bench": "combine_sweep", "scheme": scheme, "backend": backend,
        "n": n, "k": k, "in_flight_slots": slots,
        "fused_combines_per_sec": round(fused_rate * slots, 1),
        "per_slot_combines_per_sec": round(loop_rate * slots, 1),
        "fused_speedup": round(fused_rate / loop_rate, 2),
        "verdicts_match": fused == perslot,
    }
    return _annotate_device(row, backend)


def autotune_row(scheme: str, n: int, k: int, slots: int, backend: str,
                 secs: float) -> dict:
    """--sweep --autotune leg (ISSUE 14 satellite): the combine flush
    knobs now feed through the knob registry, so this leg drives a LIVE
    CombineBatcher end-to-end through that seam — a pipelined producer
    replays `slots` collectors per round while a measured-rate hill
    climb votes the `combine_batch_max` knob through the registry's
    hysteresis/step machinery (the in-replica controller votes from
    kernel/stage telemetry instead; the actuator path is identical).
    Reports the static-default rate vs the converged operating point,
    with verdict correctness asserted on every flush."""
    import threading
    from tpubft.consensus.collectors import CombineBatcher, ShareCollector
    from tpubft.tuning.knobs import GROW, SHRINK, Knob, KnobRegistry
    system, v = _verifier(scheme, k, n, backend)
    jobs = _jobs(system, k, slots)
    reference = IThresholdVerifier.combine_batch(v, jobs)
    collectors = [ShareCollector(0, i, "commit", d, v)
                  for i, (d, _s) in enumerate(jobs)]
    done = threading.Semaphore(0)
    bad = []

    def post(res):
        ok, combined, shares = reference[res.seq_num]
        if bool(res.ok) != bool(ok) or res.combined_sig != combined:
            bad.append(res.seq_num)
        done.release()

    batcher = CombineBatcher(post, flush_us=300, max_batch=64)
    registry = KnobRegistry("bench-combine")
    registry.register(Knob(
        name="combine_batch_max", value=64, default=64, lo=1, hi=512,
        cooldown_s=0.0, hysteresis=1,
        apply_fn=lambda val: batcher.reconfigure(max_batch=val)))
    registry.register(Knob(
        name="combine_flush_us", value=300, default=300, lo=0, hi=5000,
        cooldown_s=0.0, hysteresis=1,
        apply_fn=lambda val: batcher.reconfigure(flush_us=val)))

    def pump(window_s: float) -> float:
        t0 = time.perf_counter()
        rounds = 0
        while True:
            for c, (_d, shares) in zip(collectors, jobs):
                batcher.submit(c, shares)
            for _ in jobs:
                done.acquire()
            rounds += 1
            dt = time.perf_counter() - t0
            if dt >= window_s and rounds >= 2:
                return rounds * slots / dt

    try:
        pump(0.05)                              # warmup / compile
        default_rate = pump(secs / 2)
        best_rate, stale = default_rate, 0
        for _ in range(10):                     # bounded hill climb
            if stale >= 2:
                break
            direction = GROW if stale == 0 else SHRINK
            if registry.vote("combine_batch_max", direction):
                registry.step("combine_batch_max", direction)
            rate = pump(secs / 6)
            if rate > best_rate * 1.02:
                best_rate, stale = rate, 0
            else:
                stale += 1
        tuned_rate = max(best_rate, default_rate)
    finally:
        batcher.stop()
    row = {
        "bench": "combine_autotune", "scheme": scheme,
        "backend": backend, "n": n, "k": k, "in_flight_slots": slots,
        "default_combines_per_sec": round(default_rate, 1),
        "tuned_combines_per_sec": round(tuned_rate, 1),
        "tuned_over_default": round(tuned_rate / default_rate, 2),
        "converged_batch_max": registry.get("combine_batch_max"),
        "converged_flush_us": registry.get("combine_flush_us"),
        "verdicts_match": not bad,
    }
    return _annotate_device(row, backend)


def crossover_row(n: int, k: int, slots: int, backend: str,
                  secs: float) -> dict:
    """Per-combine µs of both certificate schemes at committee size n:
    the adaptive scheme should pick the cheaper column's scheme."""
    row = {"bench": "scheme_crossover", "backend": backend, "n": n,
           "k": k, "in_flight_slots": slots}
    rates = {}
    for scheme in ("multisig-ed25519", "threshold-bls"):
        system, v = _verifier(scheme, k, n, backend)
        jobs = _jobs(system, k, slots)
        assert v.combine_batch(jobs) \
            == IThresholdVerifier.combine_batch(v, jobs), \
            f"{scheme} fused/per-slot verdict divergence"
        r = _rate(lambda: v.combine_batch(jobs), secs)
        rates[scheme] = r * slots
        key = ("multisig_us_per_combine" if scheme == "multisig-ed25519"
               else "bls_us_per_combine")
        row[key] = round(1e6 / (r * slots), 1)
    row["winner"] = max(rates, key=rates.get)
    row["multisig_over_bls"] = round(
        rates["multisig-ed25519"] / rates["threshold-bls"], 1)
    # wire/proof size is the BLS column's compensation: the vector
    # certificate grows with k, the threshold certificate never does
    row["multisig_cert_bytes"] = 2 + 66 * k
    row["bls_cert_bytes"] = 48
    return _annotate_device(row, backend)


def main(argv: List[str] = None) -> int:
    setup_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--crossover", action="store_true")
    ap.add_argument("--backend", default="cpu", choices=("cpu", "tpu"))
    ap.add_argument("--slots", default="1,2,4,8,16")
    ap.add_argument("--cases", default="4,7,16,32",
                    help="committee sizes for --crossover")
    ap.add_argument("--secs", type=float, default=0.5,
                    help="measurement window per point")
    ap.add_argument("--smoke", action="store_true",
                    help="tier-1 shape: tiny sizes, correctness gates")
    ap.add_argument("--autotune", action="store_true",
                    help="with --sweep: add the knob-registry leg — a "
                         "live CombineBatcher hill-climbed through the "
                         "registry seam vs the static default")
    args = ap.parse_args(argv)
    if args.smoke:
        rows = [sweep_row("threshold-bls", 4, 3, 4, "cpu", 0.1),
                sweep_row("multisig-ed25519", 4, 3, 4, "cpu", 0.1),
                crossover_row(4, 3, 4, "cpu", 0.1)]
        for row in rows:
            print(json.dumps(row), flush=True)
        return 0 if all(r.get("verdicts_match", True) for r in rows) else 1
    if not args.sweep and not args.crossover:
        args.sweep = args.crossover = True
    rc = 0
    if args.sweep:
        for scheme in ("threshold-bls", "multisig-ed25519"):
            for slots in [int(x) for x in args.slots.split(",")]:
                row = sweep_row(scheme, 4, 3, slots, args.backend,
                                args.secs)
                rc |= 0 if row["verdicts_match"] else 1
                print(json.dumps(row), flush=True)
        if args.autotune:
            for scheme in ("threshold-bls", "multisig-ed25519"):
                slots = max(int(x) for x in args.slots.split(","))
                row = autotune_row(scheme, 4, 3, slots, args.backend,
                                   args.secs)
                rc |= 0 if row["verdicts_match"] else 1
                print(json.dumps(row), flush=True)
    if args.crossover:
        for n in [int(x) for x in args.cases.split(",")]:
            print(json.dumps(crossover_row(n, quorum_k(n), 8,
                                           args.backend, args.secs)),
                  flush=True)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
