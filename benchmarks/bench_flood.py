"""Config 4 — the n=1000 synthetic PrePrepare/share flood.

BASELINE.json's fourth config at a scale no single-host cluster can
reach: 1000 distinct principals' signatures flooding ONE replica's
verification plane, and a 1000-signer threshold-BLS certificate built
through the product accumulator classes. This measures the actual
product path — SigManager's cross-principal batch (the role of the
reference's per-message SigManager::verifySig loop, SigManager.cpp:197,
fed by a PrePrepare flood) and IThresholdAccumulator combine (the
fastMultExp role, BlsThresholdAccumulator.cpp:42-56) — not the raw BLS
microbench (that's benchmarks/bench_bls.py).

Phases reported (one JSON line each):
  A. sigmanager-flood: verify 1000 distinct-principal ed25519 sigs
     through SigManager.verify_batch — per-principal CPU loop vs the
     cross-principal device batch (sharded verify on a mesh).
  B. threshold-1000: sign k=667 shares; accumulate+combine through the
     CPU accumulator vs the device-MSM accumulator; verify; batch
     share-verification tree root.

Usage: python -m benchmarks.bench_flood [--n 1000] [--reps 3]
"""
from __future__ import annotations

import argparse
import json
import time


def _mean_best(fn, reps: int) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def phase_a_sigmanager_flood(n: int, reps: int) -> None:
    """PrePrepare-shaped flood: n messages from n distinct principals."""
    from tpubft.consensus.keys import ClusterKeys
    from tpubft.consensus.sig_manager import SigManager
    from tpubft.utils.config import ReplicaConfig

    f = (n - 1) // 3
    cfg = ReplicaConfig(f_val=f, num_of_client_proxies=0)
    assert cfg.n_val == 3 * f + 1
    t0 = time.perf_counter()
    keys = ClusterKeys.generate(cfg, 0, seed=b"flood")
    keygen_s = time.perf_counter() - t0

    items = []
    for r in range(cfg.n_val):
        signer = keys.for_node(r).my_signer()
        msg = b"preprepare-digest-%d" % r
        items.append((r, msg, signer.sign(msg)))

    # per-principal CPU loop (the reference's shape); memo disabled so
    # the reps loop measures the engine, not the duplicate cache
    sm_cpu = SigManager(keys.for_node(0), memo_capacity=0)
    cpu_s = _mean_best(lambda: sm_cpu.verify_batch(items), reps)
    assert all(sm_cpu.verify_batch(items))

    # cross-principal device batch (one dispatch; sharded over the mesh)
    from tpubft.crypto.tpu import verify_batch_mixed
    sm_dev = SigManager(keys.for_node(0), batch_fn=verify_batch_mixed,
                        device_min_batch=1, memo_capacity=0)
    dev_s = _mean_best(lambda: sm_dev.verify_batch(items), reps)
    assert all(sm_dev.verify_batch(items))

    import jax
    print(json.dumps({
        "phase": "sigmanager-flood", "n_principals": cfg.n_val,
        "platform": jax.devices()[0].platform,
        "keygen_s": round(keygen_s, 2),
        "cpu_loop_verifies_per_sec": round(len(items) / cpu_s, 1),
        "device_batch_verifies_per_sec": round(len(items) / dev_s, 1),
        "device_vs_cpu": round(cpu_s / dev_s, 2),
        "device_dispatched":
            sm_dev.sigs_device_dispatched.value,
    }), flush=True)


def phase_b_threshold(n: int, reps: int) -> None:
    """1000-signer threshold certificate through the product classes."""
    from tpubft.crypto.interfaces import Cryptosystem
    from tpubft.crypto.tpu import make_threshold_verifier

    k = 2 * ((n - 1) // 3) + 1
    t0 = time.perf_counter()
    cs = Cryptosystem("threshold-bls", k, n, seed=b"flood-bls")
    keygen_s = time.perf_counter() - t0
    digest = b"f" * 32

    t0 = time.perf_counter()
    shares = [(i, cs.create_threshold_signer(i).sign_share(digest))
              for i in range(1, k + 1)]
    sign_s = time.perf_counter() - t0

    cpu_v = cs.create_threshold_verifier()
    dev_v = make_threshold_verifier("threshold-bls", k, n, cs.public_key,
                                    cs.share_public_keys)

    def combine(verifier):
        acc = verifier.new_accumulator(with_share_verification=False)
        acc.set_expected_digest(digest)
        for i, s in shares:
            acc.add(i, s)
        return acc.get_full_signed_data()

    import os
    cpu_s = _mean_best(lambda: combine(cpu_v), reps)
    os.environ["TPUBFT_MSM_CROSSOVER_K"] = "1"   # force the device MSM
    try:
        dev_s = _mean_best(lambda: combine(dev_v), reps)
        combined = combine(cpu_v)
        assert combine(dev_v) == combined, "device combine != CPU combine"
    finally:
        del os.environ["TPUBFT_MSM_CROSSOVER_K"]

    t0 = time.perf_counter()
    ok = cpu_v.verify(digest, combined)
    verify_s = time.perf_counter() - t0
    assert ok

    # batch share-verification tree (root check over all k shares)
    from tpubft.crypto import bls12381 as bls
    h = bls.hash_to_g1(digest)
    pks = [cpu_v.share_pk(i) for i, _ in shares]
    pts = [bls.g1_decompress(s) for _, s in shares]
    tree_s = _mean_best(
        lambda: bls.batch_verify_shares(pks, h, pts), reps)

    import jax
    print(json.dumps({
        "phase": "threshold-1000", "n": n, "k": k,
        "platform": jax.devices()[0].platform,
        "keygen_s": round(keygen_s, 2),
        "sign_all_shares_s": round(sign_s, 2),
        "accumulate_combine_cpu_ms": round(cpu_s * 1e3, 1),
        "accumulate_combine_device_ms": round(dev_s * 1e3, 1),
        "verify_combined_ms": round(verify_s * 1e3, 1),
        "batch_share_tree_root_ms": round(tree_s * 1e3, 1),
    }), flush=True)


def phase_c_memo_coalesce(n: int, reps: int) -> None:
    """The admission-plane win this repo's PR 1 claims: retransmit /
    duplicate verifies short-circuit on the verified-signature memo, and
    cold mixed-scheme traffic coalesces into per-curve kernel calls in
    one dispatch. Reported against the pre-change shape (per-principal
    scalar loop, no memo)."""
    from tpubft.consensus.keys import ClusterKeys
    from tpubft.consensus.sig_manager import SigManager
    from tpubft.crypto.tpu import verify_batch_mixed
    from tpubft.utils.config import ReplicaConfig

    f = max((n - 1) // 3, 1)
    cfg = ReplicaConfig(f_val=f, num_of_client_proxies=0,
                        client_sig_scheme="ecdsa-secp256k1")
    keys = ClusterKeys.generate(cfg, 0, seed=b"flood-memo")
    items = []
    for r in range(cfg.n_val):
        signer = keys.for_node(r).my_signer()
        msg = b"preprepare-digest-%d" % r
        items.append((r, msg, signer.sign(msg)))

    # pre-change shape: per-principal scalar loop, memo off
    sm_loop = SigManager(keys.for_node(0), memo_capacity=0)
    loop_s = _mean_best(lambda: sm_loop.verify_batch(items), reps)

    # coalesced batch plane, memo off: cold-traffic throughput
    sm_cold = SigManager(keys.for_node(0), batch_fn=verify_batch_mixed,
                         device_min_batch=1, memo_capacity=0)
    sm_cold.verify_batch(items)                    # compile warmup
    cold_s = _mean_best(lambda: sm_cold.verify_batch(items), reps)

    # memoized plane: one cold pass, then pure retransmit traffic
    sm_memo = SigManager(keys.for_node(0), batch_fn=verify_batch_mixed,
                         device_min_batch=1, memo_capacity=4 * len(items))
    assert all(sm_memo.verify_batch(items))        # cold: fills the memo
    memo_s = _mean_best(lambda: sm_memo.verify_batch(items), reps)
    total = (sm_memo.memo_hits.value + sm_memo.batched_verifies.value
             + sm_memo.scalar_fallbacks.value)

    import jax
    print(json.dumps({
        "phase": "memo-coalesce", "n_sigs": len(items),
        "platform": jax.devices()[0].platform,
        "scalar_loop_verifies_per_sec": round(len(items) / loop_s, 1),
        "coalesced_verifies_per_sec": round(len(items) / cold_s, 1),
        "memo_hit_verifies_per_sec": round(len(items) / memo_s, 1),
        "coalesced_vs_scalar_loop": round(loop_s / cold_s, 2),
        "memo_vs_scalar_loop": round(loop_s / memo_s, 2),
        "memo_hit_rate": round(sm_memo.memo_hits.value / total, 4),
        "counters": {
            "memo_hits": sm_memo.memo_hits.value,
            "batched_verifies": sm_memo.batched_verifies.value,
            "scalar_fallbacks": sm_memo.scalar_fallbacks.value,
        },
    }), flush=True)


def main() -> None:
    from tpubft.utils.jaxcache import setup_cache
    setup_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1000)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--phases", default="a,b,c")
    args = ap.parse_args()
    if "a" in args.phases:
        phase_a_sigmanager_flood(args.n, args.reps)
    if "b" in args.phases:
        phase_b_threshold(args.n, args.reps)
    if "c" in args.phases:
        phase_c_memo_coalesce(args.n, args.reps)


if __name__ == "__main__":
    main()
