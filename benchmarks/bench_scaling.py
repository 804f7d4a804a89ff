"""Multi-chip scaling sweep: sharded verify + MSM at 1/2/4/8 devices.

Usage:  python -m benchmarks.bench_scaling [--devices 1,2,4,8]
        [--batch 2048] [--msm-k 64]

Each width runs in a fresh SUBPROCESS (the virtual-device count is a
process-level XLA flag) and prints one JSON row:
  {"devices": D, "verify_rate": r, "msm_ms": m,
   "verify_shards": D, "shard_rows": batch/D}

What the sweep proves depends on the platform:
- on a REAL multi-chip TPU mesh the rows give the scaling slope
  (verifies/sec should grow toward linear; combine-ms should stay flat
  as the all_gather payload is tiny);
- on the virtual CPU mesh of a 1-core host every "device" multiplexes
  the same core, so wall-clock CANNOT improve — there the sweep
  validates that the sharded programs compile and execute at every
  width, that the partitioner actually splits the batch (shard_rows
  = batch/D on each device), and that going wide costs bounded
  overhead (the regression test's bound).

Reference point: the reference runs both loops on one CPU thread
(SigManager.cpp:197 verify loop; FastMultExp.cpp:27 accumulation) —
its scaling story ends at one core, which is the gap this module's
mesh design exists to beat.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time


def _require_platform(platform: str) -> None:
    """`--platform native` is a chip measurement: refuse to produce a
    "native" row on anything but a TPU."""
    import jax
    found = jax.devices()[0].platform
    if platform == "native" and found != "tpu":
        raise SystemExit(f"--platform native needs a TPU; the default "
                         f"JAX platform here is {found!r}")


def run_width(d: int, batch: int, msm_k: int,
              platform: str = "cpu") -> dict:
    """One width, current process. The sweep's parent set this
    process's platform and device count through the environment:
    platform="cpu" is the virtual CPU mesh (the 1-host validation
    mode); "native" is a real chip mesh, and a child that finds no TPU
    there fails."""
    import jax
    _require_platform(platform)
    from tpubft.utils.jaxcache import setup_cache
    setup_cache()
    import numpy as np

    from tpubft.crypto import cpu as ccpu
    from tpubft.ops import ed25519 as ops
    from tpubft.parallel import sharding as sh

    mesh = sh.make_mesh(d)
    assert mesh.devices.size == d

    # ---- data-parallel verify ----
    signer = ccpu.Ed25519Signer.generate(seed=b"scale")
    pk = signer.public_bytes()
    msgs = [b"scale-%d" % (i % 64) for i in range(batch)]
    items = [(m, signer.sign(m), pk) for m in msgs]
    prep = ops.prepare_batch(items)
    kernel = sh.sharded_verify_ed25519(mesh)
    args = (prep.s_win, prep.h_win, prep.a_y, prep.a_sign,
            prep.r_y, prep.r_sign)
    out = kernel(*args)
    out.block_until_ready()                     # compile
    assert bool(np.asarray(out).all())
    shards = out.addressable_shards
    shard_rows = shards[0].data.shape[0]
    reps = 3
    t0 = time.perf_counter()
    for _ in range(reps):
        out = kernel(*args)
    out.block_until_ready()
    verify_rate = batch / ((time.perf_counter() - t0) / reps)

    # ---- sharded MSM (threshold-share accumulation shape) ----
    from tpubft.crypto import bls12381 as bls
    pts = [bls.g1_mul(bls.G1_GEN, i + 1) for i in range(msm_k)]
    scalars = [(7 * i + 3) % bls.R for i in range(msm_k)]
    t0 = time.perf_counter()
    acc = sh.sharded_msm(pts, scalars, mesh)
    compile_and_first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    acc = sh.sharded_msm(pts, scalars, mesh)
    msm_ms = (time.perf_counter() - t0) * 1e3
    # correctness anchor vs the host golden model
    assert acc == bls.g1_msm(pts, scalars), "sharded MSM result mismatch"

    return {"devices": d, "batch": batch,
            "platform": jax.default_backend(),
            "verify_rate": round(verify_rate, 1),
            "verify_shards": len(shards), "shard_rows": int(shard_rows),
            "msm_k": msm_k, "msm_ms": round(msm_ms, 1),
            "msm_first_s": round(compile_and_first_s, 1)}


def run_dispatch_ab(d: int, batch: int, platform: str = "cpu") -> dict:
    """Sharded-vs-single A/B through the PRODUCTION dispatch plane
    (ISSUE 16): the same ed25519 flood routed twice by the live mesh
    tier — once with the CryptoMesh capped at one chip (the pre-mesh
    single-device path) and once at full width. Correctness-gated: the
    two verdict vectors must be byte-identical before any rate is
    reported. On a real mesh the acceptance bar is >= 1.6x at 2 shards;
    on the virtual CPU host mesh every shard multiplexes one core, so
    only the byte-identity + the bounded sharding overhead are the
    signal (the row names its platform)."""
    import jax
    _require_platform(platform)
    from tpubft.utils.jaxcache import setup_cache
    setup_cache()
    import numpy as np

    from tpubft.crypto import cpu as ccpu
    from tpubft.ops import dispatch
    from tpubft.ops import ed25519 as ops

    signer = ccpu.Ed25519Signer.generate(seed=b"scale-ab")
    pk = signer.public_bytes()
    items = [(b"ab-%d" % i, signer.sign(b"ab-%d" % i), pk)
             for i in range(batch)]
    mgr = dispatch.crypto_mesh()
    mgr.reset()

    def leg(cap: int):
        mgr.set_shard_count(cap)
        out = np.asarray(ops.verify_batch(items))       # compile + warm
        reps = 3
        t0 = time.perf_counter()
        for _ in range(reps):
            out = np.asarray(ops.verify_batch(items))
        return out, batch / ((time.perf_counter() - t0) / reps)

    single, single_rate = leg(1)
    shards = 0
    try:
        mgr.set_shard_count(0)
        shards = dispatch.mesh_shards()
        sharded, sharded_rate = leg(0)
    finally:
        mgr.set_shard_count(0)
    assert single.tobytes() == sharded.tobytes(), \
        "A/B verdict vectors diverged between shard widths"
    assert bool(single.all()), "valid flood failed to verify"
    return {"mode": "dispatch-ab", "devices": d, "batch": batch,
            "platform": jax.default_backend(), "shards": shards,
            "single_rate": round(single_rate, 1),
            "sharded_rate": round(sharded_rate, 1),
            "speedup": round(sharded_rate / max(single_rate, 1e-9), 3),
            "verdicts_identical": True}


def run_agg_ab(f: int = 10, fanout: int = 4, writes: int = 10,
               mode: str = "tree", min_reduction: float = 4.0,
               min_goodput_ratio: float = 0.9) -> dict:
    """Aggregation-gossip on/off A/B through a full in-process cluster
    (ISSUE 17): the same skvbc write flood ordered twice by n = 3f+1
    replicas — once with every Prepare/Commit share sent direct to the
    collector (the all-to-all baseline) and once climbing the
    aggregation overlay. One replica is killed in both legs so the
    optimistic fast path can never complete and every slot takes the
    aggregated share path. Gated on the facts the mode claims:

      * per-replica share-datagram reduction — the busiest replica's
        received Prepare/Commit share count drops >= `min_reduction`x
        (O(n) collector fan-in -> O(fanout) per overlay node);
      * byte-identical ledgers — every live replica in BOTH legs ends
        with the same state digest and raw block bytes (aggregation is
        transport, never semantics);
      * goodput — the aggregated leg sustains >= `min_goodput_ratio`
        of baseline write throughput (asserted on real accelerator
        rows; CPU rows only report it, under their platform's name).
    """
    import jax

    from tpubft.apps import skvbc
    from tpubft.kvbc import KeyValueBlockchain
    from tpubft.storage.memorydb import MemoryDB
    from tpubft.testing.cluster import InProcessCluster

    def leg(agg_mode: str) -> dict:
        def handler_factory(_r):
            return skvbc.SkvbcHandler(
                KeyValueBlockchain(MemoryDB(), use_device_hashing=False))

        overrides = dict(threshold_scheme="multisig-bls",
                         share_aggregation=agg_mode,
                         # 50ms quiescence window: on a CPU host child
                         # shares trickle in with >10ms gaps, and every
                         # premature flush is an extra datagram up the
                         # tree — the A/B wants ~one flush per subtree
                         # per slot
                         agg_fanout=fanout, agg_flush_ms=50,
                         # sized per the OPERATIONS.md guidance: above
                         # the full CPU-host slow-path slot latency
                         # INCLUDING the first slot's JAX compile stall,
                         # so the A/B measures the overlay, not fallback
                         # churn from a timeout tuned for device hosts
                         agg_parent_timeout_ms=10000,
                         fast_path_timeout_ms=80,
                         view_change_timer_ms=60000)
        cluster = InProcessCluster(f=f, num_clients=1,
                                   handler_factory=handler_factory,
                                   cfg_overrides=overrides)
        n = cluster.n
        try:
            cluster.start()
            cluster.kill(n - 1)
            live = range(n - 1)
            cl = cluster.client(0)
            cl._req_seq = 1_000_000
            kv = skvbc.SkvbcClient(cl)
            t0 = time.perf_counter()
            for i in range(writes):
                assert kv.write([(b"k%d" % i, b"v%d" % i)],
                                timeout_ms=120000).success
            elapsed = time.perf_counter() - t0
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline and not all(
                    cluster.handlers[r].blockchain.last_block_id == writes
                    for r in live):
                time.sleep(0.05)
            digests = {cluster.handlers[r].blockchain.state_digest()
                       for r in live}
            assert len(digests) == 1, "live replicas diverged in-leg"
            return {
                "rate": writes / elapsed,
                "rcvd": [cluster.metric(r, "counters",
                                        "share_msgs_received")
                         for r in live],
                "absorbed": cluster.metric(0, "counters",
                                           "agg_partials_absorbed"),
                "fallbacks": sum(
                    cluster.metric(r, "counters", "agg_fallbacks")
                    for r in live),
                "digest": digests.pop(),
                "blocks": [cluster.handlers[0].blockchain.get_raw_block(i)
                           for i in range(1, writes + 1)],
            }
        finally:
            cluster.stop()

    off = leg("off")
    on = leg(mode)
    assert on["digest"] == off["digest"] and on["blocks"] == off["blocks"], \
        "aggregation changed ledger BYTES; it may only change transport"
    assert on["absorbed"] > 0, "overlay never delivered a partial"
    reduction = max(off["rcvd"]) / max(max(on["rcvd"]), 1)
    assert reduction >= min_reduction, (
        f"per-replica share fan-in reduction {reduction:.2f}x under the "
        f"{min_reduction}x bar (off={max(off['rcvd'])}, "
        f"on={max(on['rcvd'])})")
    goodput_ratio = on["rate"] / max(off["rate"], 1e-9)
    platform = jax.default_backend()
    if platform != "cpu":
        assert goodput_ratio >= min_goodput_ratio, (
            f"aggregated goodput ratio {goodput_ratio:.3f} under "
            f"{min_goodput_ratio}")
    n = 3 * f + 1
    return {"mode": "agg-ab", "agg_mode": mode, "n": n, "f": f,
            "fanout": fanout, "writes": writes, "platform": platform,
            "off_rate": round(off["rate"], 2),
            "on_rate": round(on["rate"], 2),
            "goodput_ratio": round(goodput_ratio, 3),
            "off_max_rcvd": max(off["rcvd"]),
            "on_max_rcvd": max(on["rcvd"]),
            "off_collector_rcvd": off["rcvd"][0],
            "on_collector_rcvd": on["rcvd"][0],
            "reduction": round(reduction, 2),
            "fallbacks": on["fallbacks"],
            "ledgers_identical": True}


def agg_ab_smoke(writes: int = 4) -> dict:
    """Tier-1 shape: the smallest overlay whose interior nodes survive
    the fast-path-disabling kill (n=7, fanout 2 — at n=4 the seeded
    permutation seats the killed replica at the only non-root interior
    slot and no partial can ever flow). At this size the reduction is
    marginal by construction — the gates that matter are ledger
    byte-identity and that the overlay actually carried partials."""
    return run_agg_ab(f=2, fanout=2, writes=writes, mode="tree",
                      min_reduction=1.0, min_goodput_ratio=0.0)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", default="1,2,4,8")
    ap.add_argument("--batch", type=int, default=2048)
    ap.add_argument("--msm-k", type=int, default=64)
    ap.add_argument("--one-width", type=int, default=0,
                    help="internal: run this width in-process")
    ap.add_argument("--dispatch-ab", action="store_true",
                    help="sharded-vs-single A/B through the production "
                         "dispatch plane (mesh cap 1 vs full width), "
                         "correctness-gated on byte-identical verdicts")
    ap.add_argument("--agg-ab", action="store_true",
                    help="share-aggregation on/off A/B through a full "
                         "in-process cluster: per-replica share fan-in "
                         "reduction + byte-identical ledgers (ISSUE 17)")
    ap.add_argument("--agg-f", type=int, default=10,
                    help="f for the --agg-ab cluster (n = 3f+1; the "
                         "default is the 'n=32' row: f=10 -> n=31, the "
                         "closest n=3f+1 size)")
    ap.add_argument("--agg-fanout", type=int, default=4)
    ap.add_argument("--agg-writes", type=int, default=10)
    ap.add_argument("--agg-mode", default="tree",
                    choices=("tree", "gossip"))
    ap.add_argument("--platform", default="cpu",
                    choices=("cpu", "native"),
                    help="cpu = virtual host-device mesh (1-host "
                         "validation); native = real accelerator mesh "
                         "(the actual scaling slope)")
    args = ap.parse_args()
    if args.agg_ab:
        if args.platform == "cpu":
            os.environ.setdefault("JAX_PLATFORMS", "cpu")
        _require_platform(args.platform)
        row = run_agg_ab(f=args.agg_f, fanout=args.agg_fanout,
                         writes=args.agg_writes, mode=args.agg_mode)
        print(json.dumps(row))
        return
    if args.one_width:
        if args.dispatch_ab:
            print(json.dumps(run_dispatch_ab(args.one_width, args.batch,
                                             platform=args.platform)))
        else:
            print(json.dumps(run_width(args.one_width, args.batch,
                                       args.msm_k,
                                       platform=args.platform)))
        return
    # the parent stays off JAX: each width is one child, in turn, and a
    # chip belongs to whichever child is running
    for d in [int(x) for x in args.devices.split(",")]:
        env = dict(os.environ)
        if args.platform == "cpu":
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={d}").strip()
        cmd = [sys.executable, "-m", "benchmarks.bench_scaling",
               "--one-width", str(d), "--batch", str(args.batch),
               "--msm-k", str(args.msm_k), "--platform", args.platform]
        if args.dispatch_ab:
            cmd.append("--dispatch-ab")
        r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                           timeout=1800)
        if r.returncode != 0:
            raise SystemExit(f"width {d} child exited rc={r.returncode}:\n"
                             f"{r.stderr[-2000:]}")
        print(r.stdout.strip().splitlines()[-1], flush=True)


if __name__ == "__main__":
    main()
