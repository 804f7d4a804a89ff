"""Measure CPU↔device crossovers (TPUBFT_MSM_CROSSOVER_K and
TPUBFT_ECDSA_CROSSOVER_B).

Default mode — BLS combine: for each quorum size k, build a
threshold-BLS certificate through both accumulators — the CPU native
path (Lagrange + Pippenger MSM, tpubft/native/bls12381.cpp) and the
device path (host Lagrange + the batched curve MSM kernel,
ops/bls12_381.combine_shares) — and report ms per combine. The
crossover is the smallest k where the device wins; export it as
TPUBFT_MSM_CROSSOVER_K (consumed by
crypto/tpu.TpuBlsThresholdAccumulator). Reference counterpart:
threshsign/bench/BenchThresholdBls.cpp:208 + FastMultExp.cpp:27.

`--ecdsa` mode: for each batch size B, A/B three ECDSA verification
tiers over a realistic multi-principal corpus — the per-item
`scalar.ecdsa_verify` loop (the 30-34/s-class degraded cliff BENCH_r05
recorded), the batched host engine (`scalar.ecdsa_verify_batch`:
Montgomery batch inversion + comb tables + lockstep affine walk), and
the device RLC kernel (`ops/ecdsa.rlc_verify_batch`: one MSM-shaped
launch per batch). The crossover is the smallest B where the device
beats the batched host; export it as TPUBFT_ECDSA_CROSSOVER_B
(consumed by crypto/tpu.verify_batch_mixed, i.e. the SigManager device
ride). Rows carry the `degraded`/`probe_error` convention: on the
XLA-CPU fallback the "device" column is not a device number and says
so machine-readably.

Usage: python -m benchmarks.bench_msm_crossover [--ks 8,32,128,512,667]
       python -m benchmarks.bench_msm_crossover --ecdsa \
           [--batches 16,64,256,1024] [--curve secp256k1] [--principals 8]
"""
from __future__ import annotations

import argparse
import json
import time


def bench_k(n: int, k: int, reps: int) -> dict:
    from tpubft.crypto.interfaces import Cryptosystem
    from tpubft.crypto.tpu import make_threshold_verifier
    cs = Cryptosystem("threshold-bls", k, n, seed=b"xover-%d" % k)
    digest = b"x" * 32
    shares = [(i, cs.create_threshold_signer(i).sign_share(digest))
              for i in range(1, k + 1)]
    cpu_v = cs.create_threshold_verifier()
    dev_v = make_threshold_verifier("threshold-bls", k, n, cs.public_key,
                                    cs.share_public_keys)

    def combine(v):
        acc = v.new_accumulator(with_share_verification=False)
        acc.set_expected_digest(digest)
        for i, s in shares:
            acc.add(i, s)
        return acc.get_full_signed_data()

    import os
    os.environ["TPUBFT_MSM_CROSSOVER_K"] = "1"   # force device path
    try:
        assert combine(dev_v) == combine(cpu_v)
        best_cpu = best_dev = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            combine(cpu_v)
            best_cpu = min(best_cpu, time.perf_counter() - t0)
            t0 = time.perf_counter()
            combine(dev_v)
            best_dev = min(best_dev, time.perf_counter() - t0)
    finally:
        del os.environ["TPUBFT_MSM_CROSSOVER_K"]
    return {"k": k, "cpu_ms": round(best_cpu * 1e3, 1),
            "device_ms": round(best_dev * 1e3, 1),
            "device_wins": best_dev < best_cpu}


def _ecdsa_corpus(curve: str, batch: int, principals: int):
    from tpubft.crypto import cpu
    # fresh principals PER ROW (seed includes the batch size): the
    # scalar engine's pubkey/comb caches are module-level, so reusing
    # keys across rows would turn every later row's "cold" column into
    # a warm measurement
    signers = [cpu.EcdsaSigner.generate(
        curve, seed=b"xover-ec-%d-%d" % (batch, j))
               for j in range(max(1, min(principals, batch)))]
    items = []
    for i in range(batch):
        s = signers[i % len(signers)]
        msg = b"xover-msg-%d" % i
        items.append((s.public_bytes(), msg, s.sign(msg)))
    return items


def bench_ecdsa_batch(curve: str, batch: int, principals: int,
                      reps: int) -> dict:
    """One row of the three-tier A/B at a fixed batch size. The batched
    host is measured WARM (per-principal combs hot): BFT principals are
    long-lived, so steady state is the honest number — the one-time
    comb build cost is reported separately."""
    from tpubft.crypto import scalar
    from tpubft.ops import ecdsa as ops_ecdsa
    # fresh cache per row: earlier rows' principals must not hold the
    # TPUBFT_ECDSA_HOT_COMBS slots (a sweep wide enough to exhaust the
    # cap would silently measure the cold tier as "warm")
    scalar.reset_ecdsa_caches()
    items = _ecdsa_corpus(curve, batch, principals)
    kernel_items = [(m, s, pk) for pk, m, s in items]

    # per-item scalar loop — the degraded-mode baseline being rescued
    loop_n = min(batch, 32)
    t0 = time.perf_counter()
    for pk, m, s in items[:loop_n]:
        assert scalar.ecdsa_verify(pk, m, s, curve)
    loop_s = (time.perf_counter() - t0) / loop_n

    # batched host: first call builds cold combs; heat to the hot tier
    t0 = time.perf_counter()
    assert all(scalar.ecdsa_verify_batch(items, curve))
    cold_s = time.perf_counter() - t0
    for _ in range(max(1, (scalar._COMB_HOT_AFTER * len(
            {pk for pk, _, _ in items}) // max(1, batch)) + 1)):
        scalar.ecdsa_verify_batch(items, curve)
    best_host = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        assert all(scalar.ecdsa_verify_batch(items, curve))
        best_host = min(best_host, time.perf_counter() - t0)

    # device RLC kernel (one launch per batch; compile excluded)
    assert ops_ecdsa.rlc_verify_batch(curve, kernel_items).all()
    best_dev = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        ops_ecdsa.rlc_verify_batch(curve, kernel_items)
        best_dev = min(best_dev, time.perf_counter() - t0)

    return {"curve": curve, "batch": batch,
            "principals": len({pk for pk, _, _ in items}),
            "scalar_loop_per_s": round(1.0 / loop_s, 1),
            "host_batch_per_s": round(batch / best_host, 1),
            "host_cold_first_ms": round(cold_s * 1e3, 1),
            "device_rlc_per_s": round(batch / best_dev, 1),
            "host_vs_loop": round(loop_s * batch / best_host, 1),
            "device_wins": best_dev < best_host}


def main_ecdsa(args) -> None:
    import jax
    probe_error = None
    platform = jax.devices()[0].platform
    if platform == "cpu":
        from bench import _device_probe_once
        ok, probe_error = _device_probe_once()
        if ok:
            probe_error = None
    rows = []
    for batch in [int(x) for x in args.batches.split(",")]:
        row = bench_ecdsa_batch(args.curve, batch, args.principals,
                                args.reps)
        row["platform"] = platform
        if platform == "cpu":
            row["degraded"] = True      # "device" column = XLA-CPU
            row["probe_error"] = probe_error or (
                "default backend is cpu: the device_rlc column measures "
                "the XLA-CPU fallback, not an accelerator")
        rows.append(row)
        print(json.dumps(row), flush=True)
    crossover = min((r["batch"] for r in rows if r["device_wins"]),
                    default=None)
    summary = {"crossover_b": crossover}
    if args.seed_out:
        # knob-registry seed file, the ISSUE-14 handoff: the autotuner
        # loads it at replica wiring (ReplicaConfig.autotune_seed_file)
        # and re-baselines the knob's default to the measured value —
        # replacing the old copy-an-env-export workflow. No measured
        # crossover (host always wins, this container's XLA-CPU case)
        # seeds the always-host sentinel instead of omitting the knob,
        # so the seed still overrides a stale env export.
        from tpubft.tuning.knobs import write_seed
        value = crossover if crossover is not None else 1 << 20
        summary["seed_file"] = write_seed(
            args.seed_out, {"ecdsa_crossover_b": value},
            note="bench_msm_crossover --ecdsa (%s): device RLC vs "
                 "batched host, batches %s" % (args.curve, args.batches))
        summary["recommend"] = (
            "--config-override autotune_seed_file=%s" % args.seed_out)
    else:
        summary["recommend"] = (
            "rerun with --seed-out <path> to emit a knob-registry seed "
            "file (autotune_seed_file)"
            if crossover is not None
            else "batched host always wins here; SigManager routes "
                 "ECDSA to ecdsa_verify_batch (--seed-out pins it)")
    print(json.dumps(summary), flush=True)


def main() -> None:
    from tpubft.utils.jaxcache import setup_cache
    setup_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--ks", default="8,32,128,512,667")
    ap.add_argument("--n", type=int, default=1000)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--ecdsa", action="store_true",
                    help="measure the ECDSA device-vs-batched-host "
                         "crossover instead of the BLS combine")
    ap.add_argument("--batches", default="16,64,256,1024")
    ap.add_argument("--curve", default="secp256k1",
                    choices=("secp256k1", "secp256r1"))
    ap.add_argument("--principals", type=int, default=8)
    ap.add_argument("--seed-out", default=None,
                    help="with --ecdsa: write the measured crossover as "
                         "a knob-registry seed file (load via "
                         "ReplicaConfig.autotune_seed_file) instead of "
                         "an env-export line")
    args = ap.parse_args()
    if args.ecdsa:
        main_ecdsa(args)
        return
    import jax
    rows = []
    for k in [int(x) for x in args.ks.split(",")]:
        row = bench_k(max(args.n, k), k, args.reps)
        row["platform"] = jax.devices()[0].platform
        rows.append(row)
        print(json.dumps(row), flush=True)
    crossover = min((r["k"] for r in rows if r["device_wins"]),
                    default=None)
    print(json.dumps({"crossover_k": crossover,
                      "recommend": "TPUBFT_MSM_CROSSOVER_K=%s"
                      % (crossover or "unset (CPU always wins here)")}),
          flush=True)


if __name__ == "__main__":
    main()
