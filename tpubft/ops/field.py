"""Big-integer modular arithmetic engine for TPU (JAX).

The foundation of the crypto data plane: prime-field arithmetic over
multi-limb integers, designed for TPU execution rather than translated from
the reference's RELIC/Crypto++ bignum code (FastMultExp.cpp etc.):

  * Limb representation: radix 2^11, signed int32 limbs, shape (NL, ...batch).
    Batch rides the trailing (lane) axis — large batches fill the 8x128 VPU;
    the limb axis is the leading (sublane) axis.
  * Montgomery multiplication (CIOS with lazy carries): a lax.scan over NL
    limb steps; each step is two scalar-vector MACs over the whole batch.
    Carries are left lazy inside the scan (exact int32 bookkeeping, bound
    analysis below) and resolved by one exact carry scan at the end.
  * No data-dependent control flow anywhere — everything is select-based,
    so the kernels are constant-time by construction and jit/vmap/shard_map
    compatible.

Bound analysis (why int32 never overflows):
  limbs are "loose": |limb| <= 2^12 (LOOSE_BOUND). CIOS step adds
  a_i*b + m_i*p with |a_i|,|b_k| <= 2^12, 0 <= m_i < 2^11, p_k < 2^11:
  per-step increment <= 2^24 + 2^22 per limb; NL <= 40 steps accumulate
  <= 40 * (2^24 + 2^22) < 2^29.4, plus the shifted-out carry (< 2^19)
  => every intermediate < 2^30 < int32 max.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

LIMB_BITS = 11
LIMB_MASK = (1 << LIMB_BITS) - 1


def pad_pow2(n: int) -> int:
    """Smallest power of two >= n — batch axes are padded to powers of
    two so the jit cache stays at O(log sizes) compiled programs."""
    m = 1
    while m < n:
        m *= 2
    return m


def int_to_limbs(x: int, n_limbs: int) -> np.ndarray:
    out = np.zeros(n_limbs, dtype=np.int32)
    for i in range(n_limbs):
        out[i] = x & LIMB_MASK
        x >>= LIMB_BITS
    if x:
        raise ValueError("integer does not fit in limb vector")
    return out


def limbs_to_int(limbs) -> int:
    limbs = np.asarray(limbs)
    v = 0
    for i in reversed(range(limbs.shape[0])):
        v = (v << LIMB_BITS) + int(limbs[i])
    return v


class Field:
    """Arithmetic mod a fixed prime p on (NL, ...batch) int32 limb arrays.

    All elements handed between public methods are in Montgomery form unless
    the method name says otherwise. Public API:
      to_mont / from_mont / from_int / to_int
      add, sub, norm  (value-preserving lazy-carry ops)
      mul, sqr, pow_const, inv, sqrt_candidate
      canonical, eq, is_zero
    """

    def __init__(self, p: int, n_limbs: Optional[int] = None):
        self.p = p
        bits = p.bit_length()
        # one headroom limb so 2*p and lazy sums still fit
        self.nl = n_limbs or (bits // LIMB_BITS + 2)
        if self.nl * LIMB_BITS < bits + 2:
            raise ValueError("n_limbs too small")
        self.R = 1 << (LIMB_BITS * self.nl)
        self.p_limbs = int_to_limbs(p, self.nl)
        # -p^-1 mod 2^LIMB_BITS (for the CIOS m quotient digit)
        self.pinv = (-pow(p, -1, 1 << LIMB_BITS)) % (1 << LIMB_BITS)
        self.r2_limbs = int_to_limbs(self.R * self.R % p, self.nl)
        self.one_limbs = int_to_limbs(1, self.nl)
        self.mont_one = int_to_limbs(self.R % p, self.nl)
        # canonicalization: p*2^j multiples, width nl+1 limbs
        self.max_shift = (LIMB_BITS * self.nl + 3) - bits + 1
        self._p_shifted = np.stack([
            int_to_limbs(p << j, self.nl + 1) for j in range(self.max_shift + 1)])
        # offset K*p making any loose value positive: K*p >= 2^(bits(nl)+2)
        K = ((1 << (LIMB_BITS * self.nl + 2)) + p - 1) // p
        self._kp_limbs = int_to_limbs(K * p, self.nl + 1)

    # ---------- host conversions ----------
    def from_int(self, x: int) -> np.ndarray:
        """Host: python int -> Montgomery limb vector (numpy)."""
        return int_to_limbs(x * self.R % self.p, self.nl)

    def to_int(self, limbs) -> int:
        """Host: Montgomery limb vector -> python int (canonical)."""
        return limbs_to_int(np.asarray(limbs)) * pow(self.R, -1, self.p) % self.p

    def raw_from_int(self, x: int) -> np.ndarray:
        """Host: python int -> non-Montgomery limb vector."""
        return int_to_limbs(x % self.p, self.nl)

    def raw_to_int(self, limbs) -> int:
        return limbs_to_int(np.asarray(limbs))

    # ---------- value-preserving limb ops ----------
    def add(self, a, b):
        return a + b

    def sub(self, a, b):
        return a - b

    def neg(self, a):
        # 2p - a keeps limbs loose-positive-ish; value-equivalent mod p
        two_p = jnp.asarray(int_to_limbs(2 * self.p, self.nl))
        return two_p.reshape((-1,) + (1,) * (a.ndim - 1)) - a

    def norm(self, a):
        """Two parallel carry passes: restores |limb| <= 2^11 + eps from
        |limb| <= 2^12-ish inputs, preserving value exactly. The TOP limb is
        never split (a negative value lives in a negative top limb; masking
        it would drop the sign carry), so the top limb absorbs carries
        unmasked — bounded because every mul() re-canonicalizes."""
        for _ in range(2):
            lo = a & LIMB_MASK
            hi = a >> LIMB_BITS
            a = (jnp.concatenate([lo[:-1], a[-1:]], axis=0)
                 + jnp.concatenate([jnp.zeros_like(hi[:1]), hi[:-1]], axis=0))
        return a

    def _carry_scan(self, a, out_limbs: Optional[int] = None):
        """Exact sequential carry propagation (floor semantics, signed-safe).
        Returns (tight_limbs, final_carry)."""
        n = a.shape[0]
        out_limbs = out_limbs or n

        def step(carry, x):
            t = x + carry
            return t >> LIMB_BITS, t & LIMB_MASK

        carry0 = jnp.zeros_like(a[0])
        final_carry, tight = jax.lax.scan(step, carry0, a)
        if out_limbs > n:
            # append carry limbs (carry may exceed one limb)
            extra = []
            c = final_carry
            for _ in range(out_limbs - n):
                extra.append(c & LIMB_MASK)
                c = c >> LIMB_BITS
            tight = jnp.concatenate([tight, jnp.stack(extra)], axis=0)
            final_carry = c
        return tight, final_carry

    # ---------- Montgomery multiplication (CIOS, lazy carries) ----------
    def mul(self, a, b):
        """mont_mul: a*b*R^-1 mod p, output canonical [0, p) tight limbs.

        Input contract: |limb| <= 2^12 and |integer value| <= c*p with
        c^2 * p < R (c ~ a few hundred; add/sub chains of canonical values
        stay far below). Values may be NEGATIVE (sub results) — REDC then
        lands in (-p, 2p], handled by the +p offset below."""
        p_l = jnp.asarray(self.p_limbs).reshape((-1,) + (1,) * (a.ndim - 1))
        pinv = jnp.int32(self.pinv)

        def step(t, a_i):
            # t: (NL, batch) accumulator; a_i: (batch,) current limb of a
            t0 = t[0] + a_i * b[0]
            m = ((t0 & LIMB_MASK) * pinv) & LIMB_MASK
            u0 = t0 + m * self.p_limbs[0].item()
            carry = u0 >> LIMB_BITS                     # exact: u0 ≡ 0 mod 2^11
            u_rest = t[1:] + a_i * b[1:] + m * p_l[1:]
            t_new = jnp.concatenate(
                [u_rest[:1] + carry, u_rest[1:],
                 jnp.zeros_like(t[:1])], axis=0)[: t.shape[0]]
            return t_new, None

        t0 = jnp.zeros_like(b)
        t, _ = jax.lax.scan(step, t0, a, unroll=4)
        # REDC of inputs with |value| <= c*p (c^2*p < R) yields t in (-p, 2p]:
        # sub chains make element values negative, so offset by +p before the
        # exact carry resolution, then reduce [0, 3p) -> [0, p).
        t = t + jnp.asarray(self.p_limbs).reshape((-1,) + (1,) * (t.ndim - 1))
        tight, carry = self._carry_scan(t)
        res = self._cond_sub_p(self._cond_sub_p(tight))
        return res

    def _cond_sub_p(self, a):
        p_l = jnp.asarray(self.p_limbs).reshape((-1,) + (1,) * (a.ndim - 1))
        d = a - p_l
        d_tight, d_carry = self._carry_scan(d)
        # d_carry < 0 iff a < p
        return jnp.where(d_carry < 0, a, d_tight)

    def sqr(self, a):
        return self.mul(a, a)

    def to_mont(self, x):
        r2 = jnp.asarray(self.r2_limbs).reshape((-1,) + (1,) * (x.ndim - 1))
        return self.mul(x, jnp.broadcast_to(r2, x.shape))

    def from_mont(self, x):
        one = jnp.asarray(self.one_limbs).reshape((-1,) + (1,) * (x.ndim - 1))
        return self.mul(x, jnp.broadcast_to(one, x.shape))

    def one(self, batch_shape: Tuple[int, ...]):
        m1 = jnp.asarray(self.mont_one).reshape((-1,) + (1,) * len(batch_shape))
        return jnp.broadcast_to(m1, (self.nl,) + batch_shape).astype(jnp.int32)

    def zero(self, batch_shape: Tuple[int, ...]):
        return jnp.zeros((self.nl,) + batch_shape, dtype=jnp.int32)

    # ---------- fixed-exponent power (inv, sqrt) ----------
    def pow_const(self, a, e: int):
        """a^e for a fixed public exponent (scan over bits, constant-time)."""
        nbits = max(e.bit_length(), 1)
        bits = jnp.asarray(
            np.array([(e >> (nbits - 1 - i)) & 1 for i in range(nbits)],
                     dtype=np.int32))

        def step(acc, bit):
            acc = self.mul(acc, acc)
            acc_mul = self.mul(acc, a)
            acc = jnp.where(bit, acc_mul, acc)
            return acc, None

        acc = self.one(a.shape[1:])
        acc, _ = jax.lax.scan(step, acc, bits)
        return acc

    def inv(self, a):
        """Fermat inversion a^(p-2). inv(0) = 0 (callers guard with flags)."""
        return self.pow_const(a, self.p - 2)

    # ---------- canonicalization / comparison ----------
    def canonical_raw(self, a):
        """Exact value mod p in tight limbs, for loose (possibly negative)
        inputs with |value| < 2^(11*nl + 2). NOT a Montgomery conversion."""
        kp = jnp.asarray(self._kp_limbs).reshape((-1,) + (1,) * (a.ndim - 1))
        ext = jnp.concatenate([a, jnp.zeros_like(a[:1])], axis=0) + kp
        v, carry = self._carry_scan(ext)
        # K*p chosen so value is positive and < 2^(11*(nl+1)) => carry 0
        for j in range(self.max_shift, -1, -1):
            pj = jnp.asarray(self._p_shifted[j]).reshape(
                (-1,) + (1,) * (a.ndim - 1))
            d = v - pj
            d_tight, d_carry = self._carry_scan(d)
            v = jnp.where(d_carry < 0, v, d_tight)
        return v[: self.nl]

    def eq(self, a, b):
        """Equality of two Montgomery elements (batch bool)."""
        diff = self.canonical_raw(a - b)
        return jnp.all(diff == 0, axis=0)

    def is_zero(self, a):
        return jnp.all(self.canonical_raw(a) == 0, axis=0)

    def select(self, cond, a, b):
        """cond: (batch,) bool; a,b: (NL, batch)."""
        return jnp.where(cond[None, :], a, b)

    # ---------- host: a batch of ints at once ----------
    # (below the traced methods: their source lines stay where they were)
    def from_ints(self, xs) -> np.ndarray:
        """Host: python ints -> Montgomery limb array (NL, B), column j
        equal to from_int(xs[j]); built as one array, not limb by limb."""
        nbytes = (self.nl * LIMB_BITS + 7) // 8
        raw = b"".join((x * self.R % self.p).to_bytes(nbytes, "little")
                       for x in xs)
        bits = np.unpackbits(
            np.frombuffer(raw, np.uint8).reshape(-1, nbytes),
            axis=1, bitorder="little")[:, :self.nl * LIMB_BITS]
        weights = 1 << np.arange(LIMB_BITS, dtype=np.int32)
        limbs = bits.reshape(-1, self.nl, LIMB_BITS).astype(np.int32) @ weights
        return np.ascontiguousarray(limbs.T)


@functools.lru_cache(maxsize=None)
def get_field(p: int, n_limbs: Optional[int] = None) -> Field:
    return Field(p, n_limbs)
