"""Benchmark: batched signature verification throughput on the chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "device": {...}, "kernels": {...}, "secondary": {...}}

Metric: Ed25519 signature verifications/sec through the kernel the
served path selects on a TPU (the fused Pallas kernel — the framework's
SigManager hot path). Baseline: single-thread OpenSSL CPU verification
measured in the same process (the reference's crypto path is
one-at-a-time CPU verify on the dispatcher/request threads —
SigManager.cpp:197).

One process, one chip: everything runs here, nothing is spawned. It
fails when `jax.devices()[0].platform` is not "tpu" — a number from any
other platform is not this benchmark's number — and a kernel that does
not compile is an error, not a skipped row. (ROADMAP A1 replaces this
file with the cell benchmark.)
"""
from __future__ import annotations

import json
import os
import time


def _secondary_metrics() -> dict:
    """Kernel rows for the OTHER hot crypto paths (configs 3/5's client
    sigs and every threshold-bls config's certificate combine), so the
    driver artifact carries the full device story, not just Ed25519.
    TPUBFT_BENCH_ECDSA_BATCH sweeps amortization."""
    out: dict = {}

    # ECDSA batch verification — both deployed curves (reference
    # crypto_utils.hpp secp256k1/secp256r1 via OpenSSL, one-at-a-time)
    from tpubft.crypto import cpu as ccpu
    from tpubft.ops import ecdsa as eops
    eb = max(1, int(os.environ.get("TPUBFT_BENCH_ECDSA_BATCH", "512")))
    for curve in ("secp256r1", "secp256k1"):
        signer = ccpu.EcdsaSigner.generate(
            curve=curve, seed=b"bench-" + curve.encode())
        pk = signer.public_bytes()
        items = []
        for i in range(eb):
            msg = b"ecdsa-bench-%d" % (i % 64)
            items.append((msg, signer.sign(msg), pk))
        verdict = eops.verify_batch(curve, items)         # compile
        assert eb and bool(verdict.all()), curve
        reps = 3
        t0 = time.perf_counter()
        for _ in range(reps):
            eops.verify_batch(curve, items)
        dt = (time.perf_counter() - t0) / reps
        out["ecdsa-%s-verifies/sec" % curve] = round(eb / dt, 1)

        # RLC batch kernel (one MSM-shaped launch per flush) and the
        # batched host fallback — the two tiers of the rescued path
        verdict = eops.rlc_verify_batch(curve, items)     # compile
        assert bool(verdict.all()), curve
        t0 = time.perf_counter()
        for _ in range(reps):
            eops.rlc_verify_batch(curve, items)
        dt = (time.perf_counter() - t0) / reps
        out["ecdsa-%s-rlc-verifies/sec" % curve] = round(eb / dt, 1)

        from tpubft.crypto import scalar as _scalar
        host_items = [(item_pk, m, s) for m, s, item_pk in items]
        # heat the per-principal comb past the hot threshold so the
        # timed reps measure warm steady state at ANY eb
        for _ in range(_scalar._COMB_HOT_AFTER // eb + 2):
            _scalar.ecdsa_verify_batch(host_items, curve)
        t0 = time.perf_counter()
        for _ in range(reps):
            assert all(_scalar.ecdsa_verify_batch(host_items, curve))
        dt = (time.perf_counter() - t0) / reps
        out["ecdsa-%s-host-batch/sec" % curve] = round(eb / dt, 1)

    # BLS threshold combine — Lagrange + k-point G1 MSM, the per-slot
    # certificate cost of every threshold-bls config (reference
    # FastMultExp.cpp role). k=3 quorum of config 2's n=7 shape.
    from tpubft.crypto.digest import digest as sha256d
    from tpubft.crypto.systems import Cryptosystem
    k, n = (3, 7)
    system = Cryptosystem("threshold-bls", k, n, seed=b"bench-bls")
    dg = sha256d(b"bls-bench")
    shares = [system.create_threshold_signer(i).sign_share(dg)
              for i in range(1, k + 1)]
    verifier = system.create_threshold_verifier()

    def combine():
        acc = verifier.new_accumulator(with_share_verification=False)
        acc.set_expected_digest(dg)
        for sid, share in enumerate(shares, start=1):
            acc.add(sid, share)
        return acc.get_full_signed_data()

    combined = combine()                                  # warm
    assert verifier.verify(dg, combined)
    reps = 8
    t0 = time.perf_counter()
    for _ in range(reps):
        combine()
    out["bls-combine-ms (k=%d/n=%d)" % (k, n)] = round(
        (time.perf_counter() - t0) / reps * 1e3, 2)
    return out


def main() -> None:
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures the chip: the default JAX platform here "
            f"is {dev.platform!r}, not 'tpu'")
    # the verify kernels are large programs (~1 min compile each);
    # repeated runs hit the cache
    from tpubft.utils.jaxcache import setup_cache
    setup_cache()

    from tpubft.crypto import cpu as ccpu
    from tpubft.ops import ed25519 as ops
    from tpubft.ops import ed25519_pallas as opsp

    # ---- CPU baseline: OpenSSL single-thread verify loop ----
    signer = ccpu.Ed25519Signer.generate(seed=b"bench")
    pk = signer.public_bytes()
    verifier = ccpu.Ed25519Verifier(pk)
    msgs = [f"bench-message-{i}".encode() for i in range(512)]
    sigs = [signer.sign(m) for m in msgs]
    t0 = time.perf_counter()
    n_base = 0
    while time.perf_counter() - t0 < 1.0:
        i = n_base % 512
        verifier.verify(msgs[i], sigs[i])
        n_base += 1
    cpu_rate = n_base / (time.perf_counter() - t0)

    # ---- batched kernels: fused Pallas and the XLA formulation ----
    # TPUBFT_BENCH_BATCH sweeps amortization points without code edits,
    # rounded up to a multiple of the fused kernel's TILE (the kernel
    # requires a tile multiple — callers pad)
    batch = max(1, int(os.environ.get("TPUBFT_BENCH_BATCH", "16384")))
    batch = (batch + opsp.TILE - 1) // opsp.TILE * opsp.TILE
    items = [(msgs[i % 512], sigs[i % 512], pk) for i in range(batch)]
    prep = ops.prepare_batch(items)
    args = (prep.s_win, prep.h_win, prep.a_y, prep.a_sign,
            prep.r_y, prep.r_sign)

    def measure(kernel) -> float:
        out = kernel(*args)
        out.block_until_ready()                   # compile
        assert bool(out.all()), "kernel rejected valid signatures"
        reps = 3
        t0 = time.perf_counter()
        for _ in range(reps):
            out = kernel(*args)
        out.block_until_ready()
        return batch / ((time.perf_counter() - t0) / reps)

    rates = {"pallas-fused": measure(opsp.verify_kernel),
             "xla": measure(ops.verify_kernel)}
    record = {
        "metric": "ed25519-verifies/sec (batch=%d, %s, pallas-fused)" % (
            batch, dev.platform),
        "value": round(rates["pallas-fused"], 1),
        "unit": "verifies/sec",
        "vs_baseline": round(rates["pallas-fused"] / cpu_rate, 3),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "kernels": {k: round(v, 1) for k, v in rates.items()},
        "secondary": _secondary_metrics(),
    }
    print(json.dumps(record))


if __name__ == "__main__":
    main()
