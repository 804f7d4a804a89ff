"""Verified crypto-offload tier bench (ISSUE 20).

Four questions about renting untrusted MSM helpers:

  1. `--ab` — combines/sec of the fused combine plane with the offload
     tier OFF vs ON (one honest in-process helper): what a leased
     combine costs end-to-end INCLUDING the constant-size soundness
     check the replica runs on every response. Every row re-checks that
     the two paths' verdicts (ok flags, combined bytes, bad-share ids)
     are byte-identical — the tier's core contract.
  2. `--soundness` — the check itself: µs per 2-pairing RLC combine
     check vs µs per local combine, across flush sizes. The claim being
     measured is CONSTANT-SIZE: the check cost must stay flat while the
     combine cost grows with shares.
  3. `--kill` — liveness drill: one of two helpers crashes mid-run; the
     lease retries onto the survivor / falls local inside the same
     flush, throughput continues, NOBODY is quarantined (a crash is
     sick, not Byzantine).
  4. `--lie` — eviction drill: a helper turns Byzantine mid-run
     (wrong-but-on-curve points — the hardest lie); the soundness check
     catches it on the FIRST lying lease, the helper is quarantined,
     verdicts never diverge from the local path.

In-process helpers (no socket hop) isolate the protocol + soundness
cost from transport noise; rows produced through the device backend on
a CPU/XLA host carry the `degraded` + `probe_error` convention (PR 4):
they validate the seam's plumbing and safety, not speed.

Usage: python -m benchmarks.bench_offload [--ab] [--soundness]
           [--kill] [--lie] [--backend cpu|tpu]
           [--slots 1,4,16] [--secs 0.5] [--smoke]
Prints one JSON line per row.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import List

from tpubft.utils.jaxcache import setup_cache
from tpubft.crypto.interfaces import Cryptosystem

# the bench IS the external harness the offload-seam baseline speaks
# of: it instantiates helper engines directly to drive fault drills
from tpubft.offload.helper import HelperServer
from tpubft.offload.pool import (InprocHelper, get_offload_pool,
                                 reset_offload_pool)


def _verifier(k: int, n: int, backend: str):
    system = Cryptosystem("threshold-bls", k, n,
                          seed=b"bench-offload-%d" % n)
    if backend == "tpu":
        from tpubft.crypto.tpu import make_threshold_verifier
        return system, make_threshold_verifier(
            "threshold-bls", k, n, system.public_key,
            system.share_public_keys)
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
                              "backend: validates the offload seam "
                              "and soundness plumbing, not speed")
    return row


def _pool_with(*servers, timeout_ms=30000):
    reset_offload_pool()
    pool = get_offload_pool()
    pool.configure(enabled=True, lease_timeout_ms=timeout_ms,
                   max_inflight=8)
    for s in servers:
        pool.add_helper(InprocHelper(s.helper_id, s))
    return pool


def ab_row(n: int, k: int, slots: int, backend: str,
           secs: float) -> dict:
    """Offload-off vs offload-on (honest helper) combine_batch rate;
    verdicts byte-identical; per-lease soundness cost from the pool's
    own telemetry."""
    system, v = _verifier(k, n, backend)
    jobs = _jobs(system, k, slots)
    reset_offload_pool()                       # OFF leg
    local = v.combine_batch(jobs)
    local_rate = _rate(lambda: v.combine_batch(jobs), secs)
    pool = _pool_with(HelperServer("bench-honest"))    # ON leg
    leased = v.combine_batch(jobs)
    leased_rate = _rate(lambda: v.combine_batch(jobs), secs)
    snap = pool.snapshot()
    verified = max(1, snap["counters"]["lease_verified"])
    row = {
        "bench": "offload_ab", "scheme": "threshold-bls",
        "backend": backend, "n": n, "k": k, "in_flight_slots": slots,
        "local_combines_per_sec": round(local_rate * slots, 1),
        "leased_combines_per_sec": round(leased_rate * slots, 1),
        "leased_over_local": round(leased_rate / local_rate, 2),
        "soundness_us_per_lease": round(
            snap["soundness_us_total"] / verified, 1),
        "lease_us_per_item": round(
            snap["lease_us_total"] / max(1, snap["lease_items_total"]),
            1),
        "leases_verified": snap["counters"]["lease_verified"],
        "leases_rejected": snap["counters"]["lease_rejected"],
        "verdicts_match": leased == local,
    }
    reset_offload_pool()
    return _annotate_device(row, backend)


def soundness_row(n: int, k: int, slots: int, backend: str,
                  secs: float) -> dict:
    """µs per soundness check vs µs per local combine at this flush
    size — the constant-size claim in one row: check_over_combine
    should FALL as slots grow."""
    from tpubft.crypto import bls12381 as bls
    from tpubft.offload import soundness
    system, v = _verifier(k, n, backend)
    jobs = _jobs(system, k, slots)
    digests = [d for d, _s in jobs]
    pts = [bls.g1_decompress(
        bls.g1_compress(bls.combine_shares(
            sorted(shares),
            [bls.g1_decompress(shares[i]) for i in sorted(shares)])))
        for _d, shares in jobs]
    assert soundness.check_bls_combine(system.public_key, digests, pts)
    check_rate = _rate(
        lambda: soundness.check_bls_combine(system.public_key,
                                            digests, pts), secs)
    reset_offload_pool()
    combine_rate = _rate(lambda: v.combine_batch(jobs), secs)
    row = {
        "bench": "offload_soundness", "backend": backend,
        "n": n, "k": k, "in_flight_slots": slots,
        "check_us_per_flush": round(1e6 / check_rate, 1),
        "combine_us_per_flush": round(1e6 / combine_rate, 1),
        "check_over_combine": round(combine_rate / check_rate, 2),
    }
    return _annotate_device(row, backend)


def kill_row(n: int, k: int, slots: int, backend: str,
             secs: float) -> dict:
    """Helper-kill drill: flush continuously, crash one of two helpers
    mid-window. Liveness = throughput continues, verdicts never
    diverge; the dead helper is SICK (breaker cooldown), not
    quarantined."""
    system, v = _verifier(k, n, backend)
    jobs = _jobs(system, k, slots)
    reset_offload_pool()
    want = v.combine_batch(jobs)
    victim = HelperServer("bench-victim")
    survivor = HelperServer("bench-survivor")
    pool = _pool_with(victim, survivor, timeout_ms=2000)
    flushes = [0, 0]                # [before, after] the kill
    bad = 0
    t0 = time.perf_counter()
    killed = False
    while time.perf_counter() - t0 < secs or flushes[1] < 2:
        if not killed and time.perf_counter() - t0 >= secs / 2:
            victim.set_strategy("crash")
            killed = True
        if v.combine_batch(jobs) != want:
            bad += 1
        flushes[int(killed)] += 1
    dt = time.perf_counter() - t0
    snap = pool.snapshot()
    row = {
        "bench": "offload_helper_kill", "backend": backend,
        "n": n, "k": k, "in_flight_slots": slots,
        "combines_per_sec": round(sum(flushes) * slots / dt, 1),
        "flushes_before_kill": flushes[0],
        "flushes_after_kill": flushes[1],
        "lease_timeouts": snap["counters"]["lease_timeouts"],
        "local_fallbacks": snap["counters"]["local_fallbacks"],
        "quarantined": snap["quarantined"],   # must stay empty: sick
        "verdicts_match": bad == 0,
        "liveness_held": flushes[1] >= 2 and not snap["quarantined"],
    }
    reset_offload_pool()
    return _annotate_device(row, backend)


def lie_row(n: int, k: int, slots: int, backend: str,
            secs: float) -> dict:
    """Lying-helper drill: a helper flips to wrong-but-on-curve points
    mid-window. Safety = verdicts never diverge (the lie dies at the
    soundness check, one local re-run); the liar is quarantined on its
    FIRST lying lease and never re-admitted within the window."""
    system, v = _verifier(k, n, backend)
    jobs = _jobs(system, k, slots)
    reset_offload_pool()
    want = v.combine_batch(jobs)
    liar = HelperServer("bench-liar")
    honest = HelperServer("bench-honest")
    pool = _pool_with(liar, honest)
    flushes = [0, 0]
    bad = 0
    t0 = time.perf_counter()
    flipped = False
    while time.perf_counter() - t0 < secs or flushes[1] < 2:
        if not flipped and time.perf_counter() - t0 >= secs / 2:
            liar.set_strategy("wrong-on-curve")
            flipped = True
        if v.combine_batch(jobs) != want:
            bad += 1
        flushes[int(flipped)] += 1
    dt = time.perf_counter() - t0
    snap = pool.snapshot()
    row = {
        "bench": "offload_lying_helper", "backend": backend,
        "n": n, "k": k, "in_flight_slots": slots,
        "combines_per_sec": round(sum(flushes) * slots / dt, 1),
        "flushes_before_flip": flushes[0],
        "flushes_after_flip": flushes[1],
        "leases_verified": snap["counters"]["lease_verified"],
        "leases_rejected": snap["counters"]["lease_rejected"],
        "quarantined": snap["quarantined"],
        "verdicts_match": bad == 0,
        # one lying lease, one rejection, immediate quarantine
        "caught_on_first_lie": (
            snap["quarantined"] == ["bench-liar"]
            and snap["counters"]["lease_rejected"] == 1),
    }
    reset_offload_pool()
    return _annotate_device(row, backend)


def main(argv: List[str] = None) -> int:
    setup_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--ab", action="store_true")
    ap.add_argument("--soundness", action="store_true")
    ap.add_argument("--kill", action="store_true")
    ap.add_argument("--lie", action="store_true")
    ap.add_argument("--backend", default="tpu", choices=("cpu", "tpu"),
                    help="tpu = the device-backed verifier (the only "
                         "one with the offload hook)")
    ap.add_argument("--slots", default="1,4,16")
    ap.add_argument("--n", type=int, default=4)
    ap.add_argument("--secs", type=float, default=0.5,
                    help="measurement window per point")
    ap.add_argument("--smoke", action="store_true",
                    help="tier-1 shape: tiny sizes, correctness gates")
    args = ap.parse_args(argv)
    k = 2 * ((args.n - 1) // 3) + 1
    if args.smoke:
        rows = [ab_row(4, 3, 4, "tpu", 0.1),
                soundness_row(4, 3, 4, "tpu", 0.1),
                kill_row(4, 3, 2, "tpu", 0.4),
                lie_row(4, 3, 2, "tpu", 0.4)]
        ok = all(r.get("verdicts_match", True) for r in rows) \
            and rows[2]["liveness_held"] and rows[3]["caught_on_first_lie"]
        for row in rows:
            print(json.dumps(row), flush=True)
        return 0 if ok else 1
    if not (args.ab or args.soundness or args.kill or args.lie):
        args.ab = args.soundness = args.kill = args.lie = True
    rc = 0
    slot_list = [int(x) for x in args.slots.split(",")]
    if args.ab:
        for slots in slot_list:
            row = ab_row(args.n, k, slots, args.backend, args.secs)
            rc |= 0 if row["verdicts_match"] else 1
            print(json.dumps(row), flush=True)
    if args.soundness:
        for slots in slot_list:
            print(json.dumps(soundness_row(args.n, k, slots,
                                           args.backend, args.secs)),
                  flush=True)
    if args.kill:
        row = kill_row(args.n, k, max(slot_list), args.backend,
                       max(args.secs, 1.0))
        rc |= 0 if (row["verdicts_match"] and row["liveness_held"]) else 1
        print(json.dumps(row), flush=True)
    if args.lie:
        row = lie_row(args.n, k, max(slot_list), args.backend,
                      max(args.secs, 1.0))
        rc |= 0 if (row["verdicts_match"]
                    and row["caught_on_first_lie"]) else 1
        print(json.dumps(row), flush=True)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
