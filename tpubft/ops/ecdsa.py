"""Batched ECDSA verification (secp256k1 / P-256) as JAX kernels.

Rebuild of the reference's per-message ECDSA verify path
(util/include/crypto_utils.hpp:57-73 ECDSAVerifier, Crypto++) as batched
kernels. Two device shapes:

  * `verify_batch` — per-item Shamir ladders R' = [u1]G + [u2]Q with a
    per-item affine x-compare (the original kernel; returns one verdict
    bit per item in one launch).
  * `rlc_verify_batch` — the random-linear-combination batch check (the
    2G2T MSM-outsourcing framing, arXiv 2602.23464): ONE MSM-shaped
    launch folds every item's verify equation into a single aggregate
    residual, checked against zero. Aggregate failure falls back to
    bisection identification (mirroring crypto/bls12381.BlsBatchVerifier)
    so a forged signature fails only itself while its siblings verify.

RLC formulation note: the textbook point-level fold
Sum a_i*u1_i*G + Sum a_i*u2_i*Q_i - Sum a_i*R_i = O needs each R_i's
y-coordinate, and a plain r||s ECDSA signature only determines x(R_i)
(both y-candidates are valid by the x-only acceptance rule, and the
wire format carries no recovery bit). Folding an arbitrary candidate
would reject ~half of all honest signatures. The sound x-only
equivalent implemented here keeps the per-item ladder T_i = [u1]G +
[u2]Q inside the launch and RLC-folds the PROJECTIVE X-RESIDUALS
instead: with T_i = (X_i : Y_i : Z_i),

    rho_i = (X_i - r_i*Z_i) * (X_i - (r_i+n)*Z_i)      (in F_p)
    check:  Sum a_i * rho_i == 0                        (in F_p)

rho_i == 0 exactly when x(T_i) is r_i or r_i+n (the wrap case the
per-item ladder already accepts), including both y-candidates at once,
and the fold needs no per-item field inversion (the per-item kernel's
to_affine pays a ~256-mul Fermat chain; the residual form pays 4 muls).
Coefficients a_i are 128-bit Fiat-Shamir draws bound to the whole batch
transcript, so a forged item survives the aggregate only with
probability ~2^-128 — and never survives bisection: a singleton launch
checks a_i*rho_i == 0 with invertible a_i, which is exact.
"""
from __future__ import annotations

import functools
import hashlib
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpubft.crypto import scalar as _scalar
from tpubft.ops.field import get_field, int_to_limbs
from tpubft.ops.weierstrass import Curve

CURVES = {
    "secp256k1": dict(
        p=0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEFFFFFC2F,
        a=0, b=7,
        gx=0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798,
        gy=0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8,
        n=0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141),
    "secp256r1": dict(
        p=0xFFFFFFFF00000001000000000000000000000000FFFFFFFFFFFFFFFFFFFFFFFF,
        a=-3, b=0x5AC635D8AA3A93E7B3EBBD55769886BC651D06B0CC53B0F63BCE3C3E27D2604B,
        gx=0x6B17D1F2E12C4247F8BCE6E563A440F277037D812DEB33A0F4A13945D898C296,
        gy=0x4FE342E2FE1A7F9B8EE7EB4A7C0F9E162BCE33576B315ECECBB6406837BF51F5,
        n=0xFFFFFFFF00000000FFFFFFFFFFFFFFFFBCE6FAADA7179E84F3B9CAC2FC632551),
}


@functools.lru_cache(maxsize=None)
def get_curve(name: str) -> Curve:
    c = CURVES[name]
    return Curve(get_field(c["p"]), c["a"], c["b"], c["gx"], c["gy"], c["n"],
                 fused=True)


class PreparedEcdsaBatch(NamedTuple):
    u1_bits: np.ndarray   # (256, B)
    u2_bits: np.ndarray
    qx: np.ndarray        # (NL, B) Montgomery
    qy: np.ndarray
    r_raw: np.ndarray     # (NL, B) tight non-Montgomery, r mod p for compare
    r_plus_n_raw: np.ndarray  # (NL, B) r+n (or invalid sentinel) for the wrap case
    host_valid: np.ndarray


class PreparedRlcBatch(NamedTuple):
    u1_bits: np.ndarray   # (256, B)
    u2_bits: np.ndarray
    qx: np.ndarray        # (NL, B) Montgomery
    qy: np.ndarray
    xr_m: np.ndarray      # (NL, B) Montgomery: r as a field element
    xrpn_m: np.ndarray    # (NL, B) Montgomery: r+n (wrap candidate)
    wrap_ok: np.ndarray   # (B,) bool: r+n < p, so the wrap candidate exists
    a_m: np.ndarray       # (NL, B) Montgomery: Fiat-Shamir RLC coefficients
    host_valid: np.ndarray


def _bits_msb(x: int, nbits: int = 256) -> np.ndarray:
    """256-bit big-endian bit vector via unpackbits (C-speed; the
    python shift loop this replaced was ~30us/item of host prep)."""
    if nbits == 256:
        return np.unpackbits(
            np.frombuffer(x.to_bytes(32, "big"), np.uint8)).astype(np.int32)
    return np.array([(x >> (nbits - 1 - i)) & 1 for i in range(nbits)],
                    dtype=np.int32)


class _Checked(NamedTuple):
    """Host prechecks shared by both kernel shapes."""
    u1: List[int]
    u2: List[int]
    r: List[int]
    q: List[Optional[Tuple[int, int]]]
    valid: np.ndarray


def _precheck(curve_name: str,
              items: Sequence[Tuple[bytes, bytes, bytes]]) -> _Checked:
    """Adapter over crypto/scalar.ecdsa_precheck_batch — ONE shared
    admission implementation (shape, 0 < r,s < n, memoized on-curve
    pubkey decode, batch-inverted s^-1) so kernel and host verdicts
    cannot drift on what they admit.  This module's item order is
    (msg, sig, pk); the scalar engine's is (pk, msg, sig)."""
    B = len(items)
    chk = _scalar.ecdsa_precheck_batch(
        [(pk, msg, sig) for msg, sig, pk in items], curve_name)
    u1 = [0] * B
    u2 = [0] * B
    valid = np.zeros(B, bool)
    qs: List[Optional[Tuple[int, int]]] = [None] * B
    for i in chk.live:
        u1[i] = chk.u1[i]
        u2[i] = chk.u2[i]
        qs[i] = chk.entries[i].pt
        valid[i] = True
    return _Checked(u1, u2, chk.r, qs, valid)


def prepare_batch(curve_name: str,
                  items: Sequence[Tuple[bytes, bytes, bytes]]) -> PreparedEcdsaBatch:
    """items: (message, raw_sig r||s 64B, pubkey SEC1-uncompressed 65B)."""
    cv = get_curve(curve_name)
    p, n = cv.f.p, cv.order
    nl = cv.f.nl
    B = len(items)
    chk = _precheck(curve_name, items)
    u1b = np.zeros((256, B), np.int32)
    u2b = np.zeros((256, B), np.int32)
    qx = np.zeros((nl, B), np.int32)
    qy = np.zeros((nl, B), np.int32)
    r_raw = np.zeros((nl, B), np.int32)
    rpn_raw = np.zeros((nl, B), np.int32)
    for i in range(B):
        if not chk.valid[i]:
            continue
        u1b[:, i] = _bits_msb(chk.u1[i])
        u2b[:, i] = _bits_msb(chk.u2[i])
        x, y = chk.q[i]
        qx[:, i] = cv.f.from_int(x)
        qy[:, i] = cv.f.from_int(y)
        r = chk.r[i]
        r_raw[:, i] = int_to_limbs(r, nl)
        # ECDSA accepts x(R') = r + n when r + n < p (wrap case)
        rpn = r + n if r + n < p else p  # p is never an affine x => no match
        rpn_raw[:, i] = int_to_limbs(rpn, nl)
    return PreparedEcdsaBatch(u1b, u2b, qx, qy, r_raw, rpn_raw, chk.valid)


def make_verify_kernel(curve_name: str):
    cv = get_curve(curve_name)

    @jax.jit
    def kernel(u1_bits, u2_bits, qx, qy, r_raw, r_plus_n_raw):
        batch = qx.shape[1:]
        q = cv.from_affine(qx, qy)
        g = cv.generator(batch)
        rp = cv.double_scalar_mul_bits(u1_bits, g, u2_bits, q)
        x_aff, _, is_id = cv.to_affine(rp)
        match = jnp.logical_or(jnp.all(x_aff == r_raw, axis=0),
                               jnp.all(x_aff == r_plus_n_raw, axis=0))
        return jnp.logical_and(match, jnp.logical_not(is_id))

    return kernel

_KERNELS = {}


def verify_batch(curve_name: str,
                 items: Sequence[Tuple[bytes, bytes, bytes]]) -> np.ndarray:
    if not items:
        return np.zeros(0, bool)
    if curve_name not in _KERNELS:
        _KERNELS[curve_name] = make_verify_kernel(curve_name)
    prep = prepare_batch(curve_name, items)
    from tpubft.ops.dispatch import device_section
    with device_section("ecdsa", batch=len(items)):
        out = _KERNELS[curve_name](prep.u1_bits, prep.u2_bits,
                                   prep.qx, prep.qy,
                                   prep.r_raw, prep.r_plus_n_raw)
        out = np.asarray(out)
        if out.shape[0] < len(items):
            raise RuntimeError(
                f"ecdsa kernel returned {out.shape[0]} verdicts "
                f"for a batch of {len(items)}")
        return out & prep.host_valid


# ---------------------------------------------------------------------------
# RLC batch verification (one aggregate check per flush + bisection)
# ---------------------------------------------------------------------------

def _rlc_coeffs(items: Sequence[Tuple[bytes, bytes, bytes]]) -> List[int]:
    """128-bit Fiat-Shamir coefficients bound to the FULL batch
    transcript (message digests, signatures, pubkeys): the adversary
    commits to every item before learning any coefficient, so
    engineering residuals that cancel inside the aggregate (or inside
    any bisection subtree, which reuses these coefficients) means
    inverting the hash. Odd => nonzero => invertible mod p."""
    h = hashlib.sha256(b"ecdsa-rlc")
    for msg, sig, pk in items:
        h.update(hashlib.sha256(msg).digest())
        h.update(bytes(sig))
        h.update(bytes(pk))
    ctx = h.digest()
    out = []
    for i in range(len(items)):
        hi = hashlib.sha256(ctx + i.to_bytes(4, "big"))
        out.append(int.from_bytes(hi.digest()[:16], "big") | 1)
    return out


def prepare_rlc_batch(curve_name: str,
                      items: Sequence[Tuple[bytes, bytes, bytes]]
                      ) -> PreparedRlcBatch:
    cv = get_curve(curve_name)
    p, n = cv.f.p, cv.order
    nl = cv.f.nl
    B = len(items)
    chk = _precheck(curve_name, items)
    coeffs = _rlc_coeffs(items)
    u1b = np.zeros((256, B), np.int32)
    u2b = np.zeros((256, B), np.int32)
    qx = np.zeros((nl, B), np.int32)
    qy = np.zeros((nl, B), np.int32)
    xr_m = np.zeros((nl, B), np.int32)
    xrpn_m = np.zeros((nl, B), np.int32)
    a_m = np.zeros((nl, B), np.int32)
    wrap_ok = np.zeros(B, bool)
    for i in range(B):
        if not chk.valid[i]:
            continue
        u1b[:, i] = _bits_msb(chk.u1[i])
        u2b[:, i] = _bits_msb(chk.u2[i])
        x, y = chk.q[i]
        qx[:, i] = cv.f.from_int(x)
        qy[:, i] = cv.f.from_int(y)
        r = chk.r[i]
        xr_m[:, i] = cv.f.from_int(r)
        if r + n < p:
            xrpn_m[:, i] = cv.f.from_int(r + n)
            wrap_ok[i] = True
        a_m[:, i] = cv.f.from_int(coeffs[i])
    return PreparedRlcBatch(u1b, u2b, qx, qy, xr_m, xrpn_m, wrap_ok,
                            a_m, chk.valid)


def rlc_fold_body(cv: Curve):
    """The RLC aggregate fold as a traceable body (no jit): shared by
    the single-device kernel below and the per-shard local function in
    tpubft/parallel/sharding.sharded_rlc_kernel, so the mesh path folds
    EXACTLY the arithmetic the bisection re-launches verify against."""
    f = cv.f

    def body(u1_bits, u2_bits, qx, qy, xr_m, xrpn_m, wrap_ok, active,
             a_m):
        batch = qx.shape[1:]
        q = cv.from_affine(qx, qy)
        g = cv.generator(batch)
        t = cv.double_scalar_mul_bits(u1_bits, g, u2_bits, q)
        one = f.one(batch)
        # projective x-residuals: zero iff x(T) == r (resp. r+n)
        d1 = f.norm(f.sub(t.x, f.mul(xr_m, t.z)))
        d2 = f.norm(f.sub(t.x, f.mul(xrpn_m, t.z)))
        d2 = f.select(wrap_ok, d2, one)
        rho = f.mul(d1, d2)                 # canonical [0, p)
        # the identity (Z=0) encodes as (0:1:0): X==0 would make d1
        # vanish spuriously, and identity is a reject — pin rho nonzero
        rho = f.select(f.is_zero(t.z), one, rho)
        # host-invalid and padding lanes must not poison the aggregate
        rho = f.select(active, rho, f.zero(batch))
        w = f.mul(a_m, rho)
        # weighted fold along the batch axis: log2(B) halving adds with
        # a norm per level keeps limbs tight; the value stays exact
        # (B*p < limb-vector capacity, bound in ops/field.canonical_raw)
        while w.shape[-1] > 1:
            h = w.shape[-1] // 2
            w = f.norm(f.add(w[..., :h], w[..., h:]))
        return jnp.all(f.canonical_raw(w) == 0)

    return body


def make_rlc_kernel(curve_name: str):
    """The fold as ONE named program, `ecdsa_rlc_kernel` for every
    curve and shape: a trace or a compile log finds it by that name
    (cellbench/kernels/ecdsa.json matches it)."""
    body = rlc_fold_body(get_curve(curve_name))

    def ecdsa_rlc_kernel(u1_bits, u2_bits, qx, qy, xr_m, xrpn_m, wrap_ok,
                         active, a_m):
        return body(u1_bits, u2_bits, qx, qy, xr_m, xrpn_m, wrap_ok,
                    active, a_m)

    return jax.jit(ecdsa_rlc_kernel)


@functools.lru_cache(maxsize=None)
def rlc_kernel(curve_name: str):
    """The process's one jitted single-device RLC kernel for a curve
    (every launch and every warm-up shares its compile cache)."""
    return make_rlc_kernel(curve_name)


# lanes of EVERY single-device launch: a batch is padded to exactly this
# many and a larger one split, so batches of any size — admission's, a
# PrePrepare's, a bisection's halves, whatever the autotuner does to the
# floor and the crossover — run ONE compiled program (a shape first met
# under traffic is a compile under traffic). A launch costs the same at
# 32, 64 and 128 lanes (PERF.md §6, PR 33); a power of two, because the
# fold halves the lanes, and whole vector registers for ops/field_pallas.
DEVICE_LANES = 128


def _rlc_launch(curve_name: str, prep: PreparedRlcBatch,
                idxs: Sequence[int]) -> bool:
    """One aggregate device launch over a subset of prepared columns,
    padded to DEVICE_LANES; inactive padding lanes contribute zero."""
    m = DEVICE_LANES
    sel = list(idxs) + [idxs[0]] * (m - len(idxs))
    active = np.zeros(m, bool)
    active[:len(idxs)] = prep.host_valid[list(idxs)]
    # the columns are gathered BEFORE the gate: host work under the
    # gate is a queue of replicas waiting for it (2-33 ms passed there
    # between the gate and the kernel's first step, PR 33)
    args = (prep.u1_bits[:, sel], prep.u2_bits[:, sel],
            prep.qx[:, sel], prep.qy[:, sel],
            prep.xr_m[:, sel], prep.xrpn_m[:, sel],
            prep.wrap_ok[sel], active, prep.a_m[:, sel])
    from tpubft.ops.dispatch import device_section
    with device_section("ecdsa", batch=len(idxs)):
        return bool(np.asarray(rlc_kernel(curve_name)(*args)))


# the RLC aggregate rides the mesh only past this per-shard lane
# count: each extra mesh width is another compiled ladder program, and
# small flushes amortize fine on one chip
_MESH_MIN_ROWS = 32


def _rlc_mesh_round(plan, curve_name: str, prep: PreparedRlcBatch,
                    idxs: Sequence[int]) -> List[List[int]]:
    """One sharded aggregate round: returns the list of index subsets
    (one per FAILING shard) that still need bisection — empty means
    every shard's partial sum was zero and the whole batch passes.
    The per-shard verdict bits replace the all-reduce: the aggregate
    verdict is their AND, and a failing aggregate names the guilty
    shard for free, so bisection re-launches only inside it. Falls
    back to the unsharded aggregate when eviction shrank the plan to
    one chip."""
    if plan is None or plan.mesh is None:
        return [] if _rlc_launch(curve_name, prep, idxs) else [list(idxs)]
    from tpubft.parallel import sharding
    d = plan.n
    rows = sharding.shard_rows(len(idxs), d)
    m = rows * d
    sel = list(idxs) + [idxs[0]] * (m - len(idxs))
    active = np.zeros(m, bool)
    active[:len(idxs)] = prep.host_valid[list(idxs)]
    kern = sharding.mesh_manager().cached_kernel(
        f"ecdsa_rlc.{curve_name}", plan,
        lambda mesh: sharding.sharded_rlc_kernel(curve_name, mesh))
    from tpubft.ops.dispatch import device_section
    with device_section("ecdsa", batch=len(idxs), shards=d):
        ok = np.asarray(kern(
            prep.u1_bits[:, sel], prep.u2_bits[:, sel],
            prep.qx[:, sel], prep.qy[:, sel],
            prep.xr_m[:, sel], prep.xrpn_m[:, sel],
            prep.wrap_ok[sel], jnp.asarray(active), prep.a_m[:, sel]))
        if ok.shape[0] < d:
            raise RuntimeError(
                f"sharded rlc kernel returned {ok.shape[0]} shard "
                f"verdicts for a mesh of {d}")
    failing = []
    for j in range(d):
        if not ok[j]:
            sub = [idxs[k] for k in range(j * rows,
                                          min((j + 1) * rows, len(idxs)))]
            if sub:
                failing.append(sub)
    return failing


def rlc_verify_batch(curve_name: str,
                     items: Sequence[Tuple[bytes, bytes, bytes]]
                     ) -> np.ndarray:
    """RLC batch verification: ONE MSM-shaped launch checks the whole
    flush; on aggregate failure, binary bisection re-launches halves
    (b forged items cost O(b*log B) launches, reference
    BlsBatchVerifier::batchVerifyRecursive) so only guilty items fail.
    Big flushes shard the aggregate over the chip mesh (per-shard
    partial sums + per-shard verdict bits; bisection only inside a
    failing shard). Verdicts are identical to `verify_batch` / the
    scalar loop on every path."""
    if not items:
        return np.zeros(0, bool)
    prep = prepare_rlc_batch(curve_name, items)
    out = prep.host_valid.copy()

    def descend(idxs: List[int]) -> None:
        live = [i for i in idxs if prep.host_valid[i]]
        if not live:
            return
        if len(live) > DEVICE_LANES:
            # more than one launch holds: each chunk is its own
            # aggregate (the coefficients bind the whole transcript, so
            # any subset folds soundly — bisection relies on the same)
            for at in range(0, len(live), DEVICE_LANES):
                descend(live[at:at + DEVICE_LANES])
            return
        if _rlc_launch(curve_name, prep, live):
            return
        if len(live) == 1:
            # singleton aggregate = a * rho with invertible a: exact
            out[live[0]] = False
            return
        mid = len(live) // 2
        descend(live[:mid])
        descend(live[mid:])

    live = [i for i in range(len(items)) if prep.host_valid[i]]
    if not live:
        return out
    from tpubft.ops import dispatch
    plan = dispatch.mesh_plan()
    if plan.mesh is not None and len(live) >= _MESH_MIN_ROWS * plan.n:
        for sub in dispatch.mesh_launch(
                "ecdsa",
                lambda p: _rlc_mesh_round(p, curve_name, prep, live)):
            descend(sub)
    else:
        descend(live)
    return out
