"""BLS threshold-signature microbenchmark.

Rebuild of the reference's threshsign bench harness
(/root/reference/threshsign/bench/BenchThresholdBls.cpp:36,208 +
bench/lib/IThresholdSchemeBenchmark.h): per-op latency for share signing,
share verification, accumulation+combine (Lagrange + MSM — the TPU-target
op, FastMultExp.cpp:27), combined-signature pairing verification, and the
batch-verification tree (BlsBatchVerifier.cpp:44) at SBFT cluster sizes
n ∈ {4, 7, 31, 501, 1000} (reference cases stop at 501; 1000 is the
BASELINE.json north-star scale).

Usage: python -m benchmarks.bench_bls [--cases 4,7,31] [--json]
Each case prints one JSON line.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict

from tpubft.crypto import bls12381 as bls
from tpubft.crypto.digest import digest as sha256
from tpubft.crypto.interfaces import Cryptosystem

# (n, threshold): threshold = 2f+c+1 slow-path quorum of the largest f
# with n = 3f+2c+1, c=0 (SBFT; ReplicasInfo quorum arithmetic)
CASES = {4: 3, 7: 5, 31: 21, 501: 335, 1000: 667}


def _timeit(fn, reps: int) -> float:
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps


def bench_case(n: int, k: int, seed: bytes = b"bls-bench") -> Dict:
    t0 = time.perf_counter()
    system = Cryptosystem("threshold-bls", k, n, seed=seed)
    keygen_s = time.perf_counter() - t0
    digest = sha256(b"bls-bench-message")

    signers = [system.create_threshold_signer(i) for i in range(1, k + 1)]
    verifier = system.create_threshold_verifier()

    # share signing (hash-to-G1 + one G1 mul)
    sign_s = _timeit(lambda: signers[0].sign_share(digest),
                     reps=8 if n >= 501 else 32)
    t0 = time.perf_counter()
    shares = [s.sign_share(digest) for s in signers]
    all_sign_s = time.perf_counter() - t0

    # single share verification (2 pairings)
    share_verify_s = _timeit(
        lambda: verifier.verify_share(1, digest, shares[0]), reps=4)

    # accumulate + combine (Lagrange coefficients + k-point G1 MSM)
    def combine():
        acc = verifier.new_accumulator(with_share_verification=False)
        acc.set_expected_digest(digest)
        for sid, share in enumerate(shares, start=1):
            acc.add(sid, share)
        return acc.get_full_signed_data()

    combine_s = _timeit(combine, reps=2 if n >= 501 else 8)
    combined = combine()

    # combined-signature verification (2 pairings)
    verify_s = _timeit(lambda: verifier.verify(digest, combined), reps=4)
    assert verifier.verify(digest, combined)

    # batch share verification: all-good root check, then isolation cost
    # with one bad share (O(log k) pairing checks)
    h = bls.hash_to_g1(digest)
    pks = [verifier.share_pk(i) for i in range(1, k + 1)]
    pts = [bls.g1_decompress(s) for s in shares]
    tree = bls.BlsBatchVerifier(pks, h)
    t0 = time.perf_counter()
    verdicts = tree.batch_verify(pts)
    batch_good_s = time.perf_counter() - t0
    assert all(verdicts)
    good_checks = tree.checks

    bad = list(pts)
    bad[k // 2] = bls.G1_GEN                    # forged share
    tree = bls.BlsBatchVerifier(pks, h)
    t0 = time.perf_counter()
    verdicts = tree.batch_verify(bad)
    batch_onebad_s = time.perf_counter() - t0
    assert verdicts.count(False) == 1
    return {
        "n": n, "k": k, "native": bls.bls_native.available()
        if hasattr(bls, "bls_native") else None,
        "keygen_s": round(keygen_s, 4),
        "sign_share_us": round(sign_s * 1e6, 1),
        "sign_all_k_s": round(all_sign_s, 4),
        "verify_share_us": round(share_verify_s * 1e6, 1),
        "accumulate_combine_ms": round(combine_s * 1e3, 2),
        "verify_combined_us": round(verify_s * 1e6, 1),
        "batch_verify_all_good_ms": round(batch_good_s * 1e3, 2),
        "batch_good_pairing_checks": good_checks,
        "batch_verify_one_bad_ms": round(batch_onebad_s * 1e3, 2),
        "batch_onebad_pairing_checks": tree.checks,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cases", default="4,7,31,501,1000")
    args = ap.parse_args()
    from tpubft.crypto import bls_native
    for n in [int(x) for x in args.cases.split(",")]:
        row = bench_case(n, CASES[n])
        row["native"] = bls_native.available()
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
