"""The double-scalar ladder of ops/weierstrass.Curve (a = 0) as ONE
Pallas TPU kernel over ops/field.Field's arithmetic.

Why this exists: Field.mul under XLA is three `lax.scan`s (the CIOS
steps, then an exact carry chain for each of two conditional
subtractions) — some 80 loop iterations of tiny operations a
multiplication. The 256-step ladder of an ECDSA verify runs 13,000
multiplications, so one launch was 6.6 million operations to the device:
144 ms whatever the lanes, and as many events in a profiler's trace, so
that no trace could hold a launch (PERF.md §6, PR 33). Here the whole
ladder is one custom call whose intermediates never leave the vector
registers and VMEM; the program round it keeps XLA's Field.

Same arithmetic as Field and Curve, limb for limb: `_Engine.mul` is
Field.mul with its scans unrolled (the CIOS step, the +p offset, the
carry chain, both conditional subtractions), `_add_a0` is Curve.add's
closed form with the terms in a dropped (multiplying by a = 0 gave an
exact 0), the loop is Curve.double_scalar_mul_bits'. Results are
bit-identical; tests/test_field_pallas.py holds them to that in
interpret mode. Layout inside the kernel: an element is (NL, 1, B), the
limb axis leading and untiled, so a limb is a row of vector registers
and shifting limbs is renaming rows. Mosaic takes no captured array
constant: the prime, 3b and Montgomery's one enter as inputs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tpubft.ops.field import LIMB_BITS, LIMB_MASK

LANES = 128     # the batch axis fills whole vector registers


def usable(x) -> bool:
    """Can a ladder over `x` (NL, B) go through the kernel: on a TPU,
    one batch axis of whole registers. Everything else keeps XLA's."""
    from tpubft.ops.ed25519 import _use_pallas
    return x.ndim == 2 and x.shape[1] % LANES == 0 and _use_pallas()


def _carry_chain(t):
    """Field._carry_scan, unrolled: (tight limbs, final carry)."""
    carry = jnp.zeros_like(t[0])
    rows = []
    for i in range(t.shape[0]):
        s = t[i] + carry
        carry = s >> LIMB_BITS
        rows.append((s & LIMB_MASK)[None])
    return jnp.concatenate(rows, 0), carry


class _Engine:
    """Field's add, sub, norm and mul on kernel-resident (NL, 1, B)
    values; `p` is the prime's limbs, broadcast to an element."""

    def __init__(self, nl: int, pinv: int, p) -> None:
        self.nl, self.pinv, self.p = nl, pinv, p

    @staticmethod
    def add(a, b):
        return a + b

    @staticmethod
    def sub(a, b):
        return a - b

    @staticmethod
    def norm(a):
        for _ in range(2):
            lo = a & LIMB_MASK
            hi = a >> LIMB_BITS
            a = (jnp.concatenate([lo[:-1], a[-1:]], 0)
                 + jnp.concatenate([jnp.zeros_like(hi[:1]), hi[:-1]], 0))
        return a

    def _cond_sub_p(self, a):
        d, carry = _carry_chain(a - self.p)
        return jnp.where((carry < 0)[None], a, d)

    def mul(self, a, b):
        p = self.p
        t = jnp.zeros_like(b)
        for i in range(self.nl):
            a_i = a[i]
            t0 = t[0] + a_i * b[0]
            m = ((t0 & LIMB_MASK) * self.pinv) & LIMB_MASK
            carry = (t0 + m * p[0]) >> LIMB_BITS    # exact: ≡ 0 mod 2^11
            u = t[1:] + a_i[None] * b[1:] + m[None] * p[1:]
            t = jnp.concatenate([u[:1] + carry[None], u[1:],
                                 jnp.zeros_like(t[:1])], 0)
        tight, _ = _carry_chain(t + p)
        return self._cond_sub_p(self._cond_sub_p(tight))


def _add_a0(f: _Engine, b3, p, q):
    """Curve.add (complete, projective) for a = 0: 14 multiplications."""
    (px, py, pz), (qx, qy, qz) = p, q
    xx, yy, zz = f.mul(px, qx), f.mul(py, qy), f.mul(pz, qz)
    xy = f.norm(f.sub(f.sub(f.mul(f.norm(f.add(px, py)),
                                  f.norm(f.add(qx, qy))), xx), yy))
    xz = f.norm(f.sub(f.sub(f.mul(f.norm(f.add(px, pz)),
                                  f.norm(f.add(qx, qz))), xx), zz))
    yz = f.norm(f.sub(f.sub(f.mul(f.norm(f.add(py, pz)),
                                  f.norm(f.add(qy, qz))), yy), zz))
    b3_zz, u = f.mul(b3, zz), f.mul(b3, xz)               # u = 3b XZ
    t_minus = f.norm(f.sub(yy, b3_zz))                    # Y1Y2 - 3bZZ
    t_plus = f.norm(f.add(yy, b3_zz))                     # Y1Y2 + 3bZZ
    v = f.norm(f.add(f.add(xx, xx), xx))                  # 3XX
    x3 = f.sub(f.mul(xy, t_minus), f.mul(yz, u))
    y3 = f.add(f.mul(v, u), f.mul(t_plus, t_minus))
    z3 = f.add(f.mul(yz, t_plus), f.mul(xy, v))
    return f.norm(x3), f.norm(y3), f.norm(z3)


def _ladder_kernel(x1, y1, z1, x2, y2, z2, bits1, bits2, p, b3, one,
                   ox, oy, oz, *, nl: int, pinv: int, nbits: int):
    f = _Engine(nl, pinv, p[...])
    b3v = b3[...]
    p1 = (x1[...], y1[...], z1[...])
    p2 = (x2[...], y2[...], z2[...])

    def step(i, acc):
        acc = _add_a0(f, b3v, acc, acc)
        for bits, pt in ((bits1, p1), (bits2, p2)):
            took = _add_a0(f, b3v, acc, pt)
            take = (bits[i] != 0)[None]
            acc = tuple(jnp.where(take, t, a) for t, a in zip(took, acc))
        return acc

    zero = jnp.zeros_like(p1[0])
    ox[...], oy[...], oz[...] = jax.lax.fori_loop(
        0, nbits, step, (zero, one[...], zero))


def double_scalar_mul_bits(cv, bits1, p1, bits2, p2,
                           interpret: bool = False):
    """cv.double_scalar_mul_bits on a curve with a = 0: bits (nbits, B)
    msb-first, points of (NL, B) coordinates -> (x, y, z)."""
    f = cv.f
    nl, lanes = p1[0].shape
    nbits = bits1.shape[0]

    def const(limbs):
        return jnp.broadcast_to(jnp.asarray(limbs)[:, None, None],
                                (nl, 1, lanes))

    el = pl.BlockSpec((nl, 1, lanes), lambda: (0, 0, 0))
    bits = pl.BlockSpec((nbits, 1, lanes), lambda: (0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_ladder_kernel, nl=nl, pinv=int(f.pinv),
                          nbits=nbits),
        in_specs=[el] * 6 + [bits] * 2 + [el] * 3,
        out_specs=[el] * 3,
        out_shape=[jax.ShapeDtypeStruct((nl, 1, lanes), jnp.int32)] * 3,
        interpret=interpret,
    )(*[c.reshape(nl, 1, lanes) for c in (*p1, *p2)],
      bits1.reshape(nbits, 1, lanes), bits2.reshape(nbits, 1, lanes),
      const(f.p_limbs), const(cv._b3_m), const(f.mont_one))
    return tuple(c.reshape(nl, lanes) for c in out)
