"""Specialized GF(2^255-19) engine with fully-parallel limb arithmetic.

The round-1 generic `Field` (tpubft/ops/field.py) ran Montgomery CIOS as a
`lax.scan` over limb steps — a long serial chain per field mul that left the
TPU VPU idle. This engine exploits the pseudo-Mersenne shape of the ed25519
prime so a field multiplication is a *scan-free* program:

  * representation: 24 signed int32 limbs, shape (24, ...batch); batch
    rides the trailing (lane) axis to fill the 8x128 VPU. The radix is
    NON-UNIFORM: limb i sits at bit W[i] = ceil(255*i/24) (limb sizes
    alternate 10/11 bits), so limb 24 lands exactly at 2^255 and high
    limbs fold back with a plain factor 19 (2^255 ≡ 19) — a uniform 2^11
    radix would need factor 19*2^9, which overflows int32 on worst-case
    carries. This is the ref10 "25.5-bit radix" idea re-derived for int32
    lanes instead of float64 mantissas.
  * values are redundant (any residue class); signs live in the limbs, so
    negation is literally `-a`.
  * mul: schoolbook convolution — 24 shifted multiply-accumulates over the
    whole batch with a per-(i,j) doubling correction for the non-uniform
    weights — then parallel carry passes (lo/hi splits, no scan) and
    factor-19 folding.
  * the only sequential pieces are the fixed squaring chains (inv/sqrt)
    and the cheap exact carry scans inside `canonical` (2 calls/verify).

Overflow budget (int32): normalized limbs satisfy |limb| <= 2^11 + eps.
With |a_i| <= m*2^11 and |b_j| <= k*2^11 the corrected convolution
accumulates at most 24 * m*k * 2^23, below 2^31 for m*k <= 10. The point
formulas in ed25519.py keep every product at m*k <= 6.

Replaces the hot-path role of the reference's per-message CPU bignum
(RELIC/Crypto++; SigManager.cpp:197's verify loop is the consumer being
rebuilt batch-parallel).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

P = 2**255 - 19
NL = 24
# bit position of limb i (i in 0..2*NL): W[NL] == 255 exactly
W = [(255 * i + NL - 1) // NL for i in range(2 * NL + 1)]
# bits held by position k (k in 0..2*NL-1) — 10 or 11
BITS = np.array([W[k + 1] - W[k] for k in range(2 * NL)], np.int32)
MASK = ((1 << BITS) - 1).astype(np.int32)
# doubling correction: product a_i*b_j contributes at weight 2^(W[i]+W[j])
# but position i+j has weight 2^W[i+j]; delta in {0,1}
_DBL = np.array([[W[i] + W[j] - W[i + j] for j in range(NL)]
                 for i in range(NL)], np.int32)
assert _DBL.min() == 0 and _DBL.max() == 1


def int_to_limbs(x: int) -> np.ndarray:
    """Host: python int (any residue; reduced mod p first) -> limb vector."""
    x %= P
    out = np.zeros(NL, dtype=np.int32)
    for i in range(NL):
        out[i] = x & int(MASK[i])
        x >>= int(BITS[i])
    return out


def limbs_to_int(limbs) -> int:
    """Host: limb vector (possibly loose/signed) -> canonical int mod p."""
    limbs = np.asarray(limbs)
    v = 0
    for i in range(limbs.shape[0]):
        v += int(limbs[i]) << W[i]
    return v % P


# ----------------------------------------------------------------------
# device ops — all (NL, ...batch) int32, fully data-parallel
# ----------------------------------------------------------------------

def add(a, b):
    return a + b


def sub(a, b):
    return a - b


def neg(a):
    """Signed limbs make negation free."""
    return -a


def _shape_const(arr: np.ndarray, ndim: int):
    return jnp.asarray(arr).reshape((-1,) + (1,) * (ndim - 1))


def _carry_pass(c, start: int = 0):
    """One parallel carry step with per-position widths: limb -> (lo, hi)
    split, hi shifted up one position. Exact for signed int32 (arithmetic
    >> is floor division, & MASK the matching non-negative remainder).
    `start` selects which slice of the global BITS table applies. Returns
    (same-length array, carry out of the top limb)."""
    n = c.shape[0]
    bits = _shape_const(BITS[start:start + n], c.ndim)
    mask = _shape_const(MASK[start:start + n], c.ndim)
    hi = c >> bits
    lo = c & mask
    shifted = jnp.concatenate([jnp.zeros_like(hi[:1]), hi[:-1]], axis=0)
    return lo + shifted, hi[-1]


def normalize(a):
    """Restore loose limbs (sums/differences of normalized values) to
    |limb| <= 2^11 + eps without changing the value mod p. Carry out of
    limb NL-1 has weight 2^W[NL] = 2^255 ≡ 19."""
    a, t = _carry_pass(a)
    a = a.at[0].add(t * 19)
    a, t = _carry_pass(a)
    return a.at[0].add(t * 19)


def mul(a, b):
    """Field multiply: corrected schoolbook convolution + factor-19
    pseudo-Mersenne reduction. Operand looseness budget: m*k <= 10 (see
    module docstring); output normalized."""
    batch = b.shape[1:]
    b2 = b + b
    # conv output: positions 0..46 + one pad position to absorb carries
    c = jnp.zeros((2 * NL,) + batch, dtype=jnp.int32)
    for i in range(NL):
        dbl_mask = _shape_const(_DBL[i], b.ndim).astype(bool)
        bs = jnp.where(dbl_mask, b2, b)
        c = c.at[i:i + NL].add(a[i] * bs)
    # two parallel passes: |limb| < 2^31 -> ~2^21 -> <= 2^12
    c, _ = _carry_pass(c)                    # pad limb absorbs; carry 0
    c, t2 = _carry_pass(c)                   # |t2| <= 2^11
    # fold: position NL+t ≡ 19 * position t; carry-out of position 2NL-1
    # has weight 2^W[2NL] = 2^510 ≡ 19*19 = 361
    lo = c[:NL] + c[NL:] * 19
    lo = lo.at[0].add(t2 * 361)
    # renormalize to |limb| <= 2^11 + eps
    lo, t = _carry_pass(lo)
    lo = lo.at[0].add(t * 19)
    lo, t = _carry_pass(lo)
    return lo.at[0].add(t * 19)


def sqr(a):
    return mul(a, a)


def zero(batch_shape: Tuple[int, ...]):
    return jnp.zeros((NL,) + batch_shape, dtype=jnp.int32)


def one(batch_shape: Tuple[int, ...]):
    o = jnp.zeros((NL,) + batch_shape, dtype=jnp.int32)
    return o.at[0].set(1)


def const(value: int, batch_shape: Tuple[int, ...]):
    limbs = jnp.asarray(int_to_limbs(value))
    return jnp.broadcast_to(
        limbs.reshape((NL,) + (1,) * len(batch_shape)),
        (NL,) + batch_shape).astype(jnp.int32)


def select(cond, a, b):
    """cond: (batch,) bool; a, b: (NL, batch)."""
    return jnp.where(cond[None], a, b)


# ---- canonicalization / comparison (off the hot path: 2 calls/verify) ----

# positive offset dominating any normalized-ish input (|limb| <= 2^12 ->
# |value| < 24 * 2^12 * 2^245 < 2^262); 2^10 * P ~ 2^265 dominates
_OFFSET = (1 << 10) * P


def _offset_limbs_np() -> np.ndarray:
    x = _OFFSET
    out = np.zeros(NL, dtype=np.int64)
    for i in range(NL - 1):
        out[i] = x & int(MASK[i])
        x >>= int(BITS[i])
    out[NL - 1] = x                    # top limb holds the overflow
    assert out[NL - 1] < 2**30
    return out.astype(np.int32)


_OFFSET_LIMBS = _offset_limbs_np()


def _p_tight_np() -> np.ndarray:
    # int_to_limbs reduces mod p (giving zeros), so build p's limbs directly
    x = P
    out = np.zeros(NL, dtype=np.int32)
    for i in range(NL):
        out[i] = x & int(MASK[i])
        x >>= int(BITS[i])
    assert x == 0
    return out


_P_TIGHT = _p_tight_np()


def _carry_scan(a):
    """Exact sequential carry propagation (NL steps, cheap): returns
    (tight limbs in [0, 2^BITS_k), carry_out at weight 2^255)."""
    bits = jnp.asarray(BITS[:NL])
    mask = jnp.asarray(MASK[:NL])

    def step(carry, xs):
        x, b_k, m_k = xs
        t = x + carry
        return t >> b_k, t & m_k

    c0 = jnp.zeros_like(a[0])
    carry, tight = jax.lax.scan(step, c0, (a, bits, mask))
    return tight, carry


def canonical(a):
    """Exact canonical residue in [0, p): (NL, B) tight non-negative limbs.
    Input must be normalized-ish (|limb| <= 2^12)."""
    off = _shape_const(_OFFSET_LIMBS, a.ndim)
    a = a + off                                  # value now in (0, 2^266)
    a, c = _carry_scan(a)
    a = a.at[0].add(c * 19)                      # c < 2^11 -> 19c < 2^16
    a, c = _carry_scan(a)
    a = a.at[0].add(c * 19)                      # c in {0, 1}
    a, _ = _carry_scan(a)                        # tight, value < 2^255
    # at most one subtraction of p left
    p_l = _shape_const(_P_TIGHT, a.ndim)
    d, borrow = _carry_scan(a - p_l)
    return jnp.where((borrow < 0)[None], a, d)


def eq(a, b):
    """Equality mod p of two normalized elements."""
    return jnp.all(canonical(a - b) == 0, axis=0)


def is_zero(a):
    return jnp.all(canonical(a) == 0, axis=0)


# ---- fixed-exponent powers (x^(p-2), x^((p-5)/8)) ----

def pow2k(x, k: int):
    """x^(2^k): k sequential squarings (lax.scan; with the squaring chains
    below these are the long serial parts of a verify, ~254 steps/chain)."""
    def body(c, _):
        return sqr(c), None
    out, _ = jax.lax.scan(body, x, None, length=k)
    return out


def _chain_250(x):
    """x^(2^250 - 1) and x^11 — shared core of the inversion and sqrt
    chains (standard curve25519 addition chain re-derived for batch JAX)."""
    z2 = sqr(x)                                  # 2
    z9 = mul(pow2k(z2, 2), x)                    # 9
    z11 = mul(z9, z2)                            # 11
    z_2_5 = mul(sqr(z11), z9)                    # 2^5 - 1
    z_2_10 = mul(pow2k(z_2_5, 5), z_2_5)         # 2^10 - 1
    z_2_20 = mul(pow2k(z_2_10, 10), z_2_10)      # 2^20 - 1
    z_2_40 = mul(pow2k(z_2_20, 20), z_2_20)      # 2^40 - 1
    z_2_50 = mul(pow2k(z_2_40, 10), z_2_10)      # 2^50 - 1
    z_2_100 = mul(pow2k(z_2_50, 50), z_2_50)     # 2^100 - 1
    z_2_200 = mul(pow2k(z_2_100, 100), z_2_100)  # 2^200 - 1
    z_2_250 = mul(pow2k(z_2_200, 50), z_2_50)    # 2^250 - 1
    return z_2_250, z11


def inv(x):
    """x^(p-2) = x^(2^255 - 21). inv(0) = 0 (callers guard)."""
    t250, z11 = _chain_250(x)
    return mul(pow2k(t250, 5), z11)              # (2^250-1)*2^5 + 11


def pow_p58(x):
    """x^((p-5)/8) = x^(2^252 - 3)."""
    t250, _ = _chain_250(x)
    return mul(pow2k(t250, 2), x)                # (2^250-1)*4 + 1
