"""BLS12-381 G1 kernels: batched scalar-mul and Lagrange-weighted MSM.

The TPU rebuild of the reference's hottest op — threshold-share accumulation
(BlsThresholdAccumulator::computeLagrangeCoeff + exponentiateLagrangeCoeff →
fastMultExp, threshsign/src/bls/relic/FastMultExp.cpp:27): combine k
signature shares into the threshold signature via sum_i [L_i(0)] S_i.

Split of labor:
  host   — Lagrange coefficients mod r (tiny: O(k²) int mulmods), point
           decompression (CPU reference impl; device decompress is a later
           round), final pairing verify (CPU for now).
  device — the MSM: batched constant-time ladders over all shares in
           parallel + a log₂(k) tree reduction. `tpubft.parallel` shards the
           same MSM across a device mesh for n=1000-scale accumulation.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpubft.crypto import bls12381 as ref
from tpubft.ops.field import get_field, pad_pow2 as _pad_pow2
from tpubft.ops.weierstrass import Curve, WPoint


@functools.lru_cache(maxsize=None)
def g1_curve() -> Curve:
    return Curve(get_field(ref.P), 0, ref.B1, ref.G1_GEN[0], ref.G1_GEN[1], ref.R)


SCALAR_BITS = 255
_SCALAR_MASK = (1 << SCALAR_BITS) - 1


def _bits_msb_batch(scalars: Sequence[int]) -> np.ndarray:
    """(255, B) int32; row i holds bit 254 - i of every scalar."""
    raw = b"".join((k & _SCALAR_MASK).to_bytes(32, "big") for k in scalars)
    bits = np.unpackbits(np.frombuffer(raw, np.uint8).reshape(-1, 32), axis=1)
    return np.ascontiguousarray(bits[:, 1:].T, dtype=np.int32)


@functools.partial(jax.jit, static_argnums=())
def msm_kernel(bits: jnp.ndarray, px: jnp.ndarray, py: jnp.ndarray,
               infinity: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """sum_i [k_i] P_i. bits (255,B), px/py (NL,B) Montgomery, infinity (B,)
    marks padding/identity slots. Returns projective result limbs (NL,1) x3."""
    cv = g1_curve()
    pts = cv.from_affine(px, py)
    # padding slots become the identity regardless of their (px,py) content
    pts = cv.select(infinity, cv.identity(px.shape[1:]), pts)
    acc = cv.scalar_mul_bits(bits, pts)
    out = cv.msm_reduce(acc)
    return out.x, out.y, out.z


def _prep_msm(points: Sequence, scalars: Sequence[int], m: int):
    """Pad an n-point MSM to m slots (identity padding) -> device arrays.
    Only the real points are converted; a padding slot (and a None
    point) is zero limbs, zero bits and `infinity` set. (This function
    keeps its length: the kernels below stay on their source lines.)"""
    cv = g1_curve()
    live = [i for i, p in enumerate(points) if p is not None]
    infinity = np.ones(m, bool)
    infinity[live] = False
    # the arrays are born zero, so the padding costs nothing
    px = np.zeros((cv.f.nl, m), np.int32)
    py = np.zeros((cv.f.nl, m), np.int32)
    px[:, live], py[:, live] = cv.affine_to_device(
        [points[i] for i in live])
    bits = np.zeros((SCALAR_BITS, m), np.int32)
    bits[:, live] = _bits_msb_batch(
        [scalars[i] % ref.R for i in live])
    return bits, px, py, infinity


def _msm_launch(plan, points: Sequence, scalars: Sequence[int]):
    """One MSM launch under a MeshPlan (None / meshless plan = the
    single-device kernel — also the post-eviction landing spot when
    the retry loop hands us a one-chip plan)."""
    from tpubft.ops.dispatch import device_section
    n = len(points)
    if plan is not None and plan.mesh is not None:
        from tpubft.parallel import sharding
        shards = plan.n
        m = sharding.shard_rows(n, shards) * shards
        kern = sharding.mesh_manager().cached_kernel(
            "bls_msm", plan, sharding.sharded_msm_kernel)
    else:
        shards, m = 1, _pad_pow2(n)
        kern = msm_kernel
    bits, px, py, infinity = _prep_msm(points, scalars, m)
    with device_section("bls_msm", batch=m, shards=shards):
        x, y, z = kern(jnp.asarray(bits), jnp.asarray(px),
                       jnp.asarray(py), jnp.asarray(infinity))
        x, y, z = np.asarray(x), np.asarray(y), np.asarray(z)
    # host-side affine conversion stays OUTSIDE the gate (dispatch.py rule)
    return _to_affine_host(x[:, 0], y[:, 0], z[:, 0])


def msm(points: Sequence, scalars: Sequence[int]):
    """Host-facing MSM: G1 affine int points + int scalars -> affine point.
    Drop-in for the reference fastMultExp (FastMultExp.cpp:27-59).
    Multi-chip hosts shard the points over the healthy mesh (each device
    ladders its shard; one tiny all_gather combines — SURVEY §5.7),
    with per-chip fault isolation via dispatch.mesh_launch."""
    n = len(points)
    if n == 0:
        return None
    from tpubft.ops import dispatch
    plan = dispatch.mesh_plan()
    if plan.mesh is not None and n >= 2 * plan.n:
        return dispatch.mesh_launch(
            "bls_msm", lambda p: _msm_launch(p, points, scalars))
    return _msm_launch(None, points, scalars)


def _to_affine_host(x_limbs, y_limbs, z_limbs):
    f = g1_curve().f
    z = f.to_int(z_limbs)
    if z == 0:
        return None
    zi = pow(z, -1, ref.P)
    return (f.to_int(x_limbs) * zi % ref.P, f.to_int(y_limbs) * zi % ref.P)


def combine_shares(ids: Sequence[int], shares_g1: Sequence) -> object:
    """Threshold combine: Lagrange coefficients (host) + MSM (device).
    Device-accelerated equivalent of bls12381.combine_shares."""
    coeffs = ref.lagrange_coeffs_at_zero(ids)
    return msm(list(shares_g1), coeffs)


@functools.partial(jax.jit, static_argnums=())
def msm_batch_kernel(bits: jnp.ndarray, px: jnp.ndarray, py: jnp.ndarray,
                     infinity: jnp.ndarray
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Segmented multi-MSM: S independent sum_i [k_ij] P_ij in ONE
    launch. bits (255, S, K), px/py (NL, S, K) Montgomery, infinity
    (S, K) marks padding/identity slots; K is the per-segment share
    width (padded to a power of two). Ladders all S·K points in
    parallel, then tree-reduces only the K axis — one projective
    result (NL, S, 1) per segment, never mixing segments."""
    cv = g1_curve()
    pts = cv.from_affine(px, py)
    pts = cv.select(infinity, cv.identity(px.shape[1:]), pts)
    acc = cv.scalar_mul_bits(bits, pts)
    out = cv.msm_reduce(acc)
    return out.x, out.y, out.z


def msm_batch(segments: Sequence[Tuple[Sequence, Sequence[int]]]) -> List:
    """Cross-slot fused MSM: each segment is (points, scalars) and the
    whole batch rides ONE `msm_kernel`-shaped device launch instead of
    one launch per segment (the per-slot combine tax the fused
    combine plane removes). Returns one affine point (or None for the
    identity) per segment. Segment count and width are padded to
    powers of two so the jit cache stays at O(log² sizes) programs.
    Wide segments (share width >= 2 per chip) shard the share axis
    over the healthy mesh."""
    s = len(segments)
    if s == 0:
        return []
    kwidth = max(1, max(len(p) for p, _ in segments))
    from tpubft.ops import dispatch
    plan = dispatch.mesh_plan()
    if plan.mesh is not None and kwidth >= 2 * plan.n:
        return dispatch.mesh_launch(
            "bls_msm", lambda p: _msm_batch_launch(p, segments))
    return _msm_batch_launch(None, segments)


def _msm_batch_launch(plan,
                      segments: Sequence[Tuple[Sequence, Sequence[int]]]
                      ) -> List:
    cv = g1_curve()
    s = len(segments)
    kwidth = max(1, max(len(p) for p, _ in segments))
    if plan is not None and plan.mesh is not None:
        from tpubft.parallel import sharding
        shards = plan.n
        kmax = sharding.shard_rows(kwidth, shards) * shards
        kern = sharding.mesh_manager().cached_kernel(
            "bls_msm.batch", plan, sharding.sharded_msm_batch_kernel)
    else:
        shards, kmax = 1, _pad_pow2(kwidth)
        kern = msm_batch_kernel
    smax = _pad_pow2(s)
    infinity = np.ones((smax, kmax), bool)
    pts: List[Tuple[int, int]] = []
    ks: List[int] = []
    total = 0
    for j in range(smax):
        points, scalars = segments[j] if j < s else ((), ())
        total += len(points)
        for i in range(kmax):
            if i < len(points) and points[i] is not None:
                pts.append(points[i])
                ks.append(scalars[i] % ref.R)
                infinity[j, i] = False
            else:
                pts.append((0, 0))
                ks.append(0)
    px, py = cv.affine_to_device(pts)           # (NL, smax*kmax)
    px = px.reshape(px.shape[0], smax, kmax)
    py = py.reshape(py.shape[0], smax, kmax)
    bits = _bits_msb_batch(ks).reshape(SCALAR_BITS, smax, kmax)
    from tpubft.ops.dispatch import device_section
    with device_section("bls_msm", batch=total, shards=shards):
        x, y, z = kern(jnp.asarray(bits), jnp.asarray(px),
                       jnp.asarray(py), jnp.asarray(infinity))
        x, y, z = np.asarray(x), np.asarray(y), np.asarray(z)
    return [_to_affine_host(x[:, j, 0], y[:, j, 0], z[:, j, 0])
            for j in range(s)]


def combine_shares_batch(jobs: Sequence[Tuple[Sequence[int], Sequence]]
                         ) -> List:
    """Fused threshold combine across slots: jobs of (ids, shares_g1)
    — Lagrange coefficients per job on host (tiny), then ONE segmented
    MSM device call for every job together. Element-wise identical to
    per-job `combine_shares`."""
    return msm_batch([(list(shares), ref.lagrange_coeffs_at_zero(ids))
                      for ids, shares in jobs])


def batch_scalar_mul(points: Sequence, scalars: Sequence[int]) -> List:
    """[k_i]P_i for each i (no reduction) — used by batched share verify."""
    cv = g1_curve()
    n = len(points)
    if n == 0:
        return []
    infinity = np.array([p is None for p in points], bool)
    pts = [(0, 0) if p is None else p for p in points]
    px, py = cv.affine_to_device(pts)
    bits = _bits_msb_batch([k % ref.R for k in scalars])

    @jax.jit
    def kern(bits, px, py, inf):
        p = cv.from_affine(px, py)
        p = cv.select(inf, cv.identity(px.shape[1:]), p)
        acc = cv.scalar_mul_bits(bits, p)
        return acc.x, acc.y, acc.z

    from tpubft.ops.dispatch import device_section
    with device_section("bls_mul", batch=n):
        x, y, z = kern(jnp.asarray(bits), jnp.asarray(px), jnp.asarray(py),
                       jnp.asarray(infinity))
        x, y, z = np.asarray(x), np.asarray(y), np.asarray(z)
    return [_to_affine_host(x[:, i], y[:, i], z[:, i]) for i in range(n)]
