"""BLS12-381 G1's multi-scalar multiplication as ONE Pallas TPU kernel:
the single-scalar ladder of ops/weierstrass.Curve.scalar_mul_bits and,
in the same kernel, the tree reduce of Curve.msm_reduce.

Why this exists: under XLA the 255-step ladder is 7,140 field
multiplications, each three `lax.scan`s, so one launch was some ten
dependent loops of tiny operations: 254 ms a 1,024-point MSM, whatever
the lanes (PERF.md §5). The arithmetic is ops/field_pallas's, imported
unchanged (`_Engine`, `_add_a0`): results are bit-identical to
`scalar_mul_bits` followed by `msm_reduce`, every limb of (X : Y : Z).

Layout: a block is (NL, 8, 128), so a limb of a block is one whole
vector register: 1,024 lanes a block, a grid of blocks beyond. A batch
of fewer lanes fills the first B / 128 sublanes and the rest is never
read back (Mosaic rotates whole registers only). Lane b of a batch of nb
blocks lands in block b % nb, at flat position b // nb (sublane-major),
so the in-block tree pairs exactly the lanes msm_reduce pairs first and
the blocks' sums are msm_reduce's last levels, summed outside.

One loop runs the ladder and then the reduce, one addition a step (the
unrolled body is one `_add_a0`): bit i of the ladder is the steps 2i
(the accumulator plus itself) and 2i + 1 (plus the point, kept where the
bit is set); reduce step k adds to the accumulator its rotation that
brings position t + h to t, h being half of what is left to sum —
sublanes first, then lanes — and leaves the block's sum at (0, 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpubft.ops import field_pallas
from tpubft.ops.field_pallas import LANES, _add_a0, _Engine

SUBLANES = 8                  # a vector register is (8, 128) int32
BLOCK = SUBLANES * LANES      # lanes of one grid step


def _log2(n: int) -> int:
    return n.bit_length() - 1


def _kernel(x, y, z, bits, p, b3, one, ox, oy, oz, *, nl: int, pinv: int,
            nbits: int, rows: int, reduce: bool):
    f = _Engine(nl, pinv, p[...])
    b3v = b3[...]
    pt = (x[...], y[...], z[...])
    row_levels = _log2(rows)

    def partner(k, acc):
        """What reduce step k (< 0: a ladder step) adds: the rotation of
        the accumulator, by nothing before the reduce."""
        if not reduce:
            return acc
        lane = jnp.where(k >= row_levels, LANES - (
            LANES // 2 >> jnp.maximum(k - row_levels, 0)), 0)
        acc = tuple(pltpu.roll(c, lane, 2) for c in acc)
        if row_levels:
            sub = jnp.where((k >= 0) & (k < row_levels), SUBLANES - (
                rows // 2 >> jnp.maximum(k, 0)), 0)
            acc = tuple(pltpu.roll(c, sub, 1) for c in acc)
        return acc

    def step(s, acc):
        adds = (s < 2 * nbits) & (s % 2 == 1)
        other = partner(s - 2 * nbits, acc)
        other = tuple(jnp.where(adds, q, o) for q, o in zip(pt, other))
        took = _add_a0(f, b3v, acc, other)
        bit = bits[jnp.minimum(s // 2, nbits - 1)] != 0
        take = jnp.logical_or(bit, jnp.logical_not(adds))[None]
        return tuple(jnp.where(take, t, a) for t, a in zip(took, acc))

    steps = 2 * nbits + (row_levels + _log2(LANES) if reduce else 0)
    zero = jnp.zeros_like(pt[0])
    ox[...], oy[...], oz[...] = jax.lax.fori_loop(
        0, steps, step, (zero, one[...], zero))


def _launch(cv, bits, pt, reduce: bool, interpret: bool):
    """-> (x, y, z), each (nb, NL, 8, 128), blocked as the module's
    docstring says: the ladder's every lane, or where `reduce` each
    block's sum at (0, 0)."""
    f = cv.f
    nl, lanes = pt[0].shape
    nbits = bits.shape[0]
    width = min(lanes, BLOCK)
    nb, rows = lanes // width, width // LANES

    def blocked(a):
        n = a.shape[0]
        a = (a.reshape(n, width, nb).transpose(2, 0, 1)
             .reshape(nb, n, rows, LANES))
        return jnp.pad(a, ((0, 0), (0, 0), (0, SUBLANES - rows), (0, 0)))

    def const(limbs):
        return jnp.broadcast_to(jnp.asarray(limbs)[:, None, None],
                                (nl, SUBLANES, LANES))

    def block(n):
        return pl.BlockSpec((None, n, SUBLANES, LANES),
                            lambda j: (j, 0, 0, 0))

    cst = pl.BlockSpec((nl, SUBLANES, LANES), lambda j: (0, 0, 0))
    shape = jax.ShapeDtypeStruct((nb, nl, SUBLANES, LANES), jnp.int32)
    return pl.pallas_call(
        functools.partial(_kernel, nl=nl, pinv=int(f.pinv), nbits=nbits,
                          rows=rows, reduce=reduce),
        grid=(nb,),
        in_specs=[block(nl)] * 3 + [block(nbits)] + [cst] * 3,
        out_specs=[block(nl)] * 3,
        out_shape=[shape] * 3,
        interpret=interpret,
    )(*[blocked(c) for c in pt], blocked(bits),
      const(f.p_limbs), const(cv._b3_m), const(f.mont_one))


def usable(x, reduce: bool = False) -> bool:
    """Can a ladder over `x` (NL, B) — and its tree reduce, where
    `reduce` — go through the kernel: on a TPU, one batch axis of whole
    registers (ops/field_pallas's test), in whole blocks or one block
    (a power of two, where `reduce`). Everything else keeps XLA's."""
    lanes = x.shape[-1]
    whole = (lanes & (lanes - 1) == 0 if reduce
             else lanes <= BLOCK or lanes % BLOCK == 0)
    return whole and field_pallas.usable(x)


def scalar_mul_bits(cv, bits, pt, interpret: bool = False):
    """cv.scalar_mul_bits on a curve with a = 0: bits (nbits, B)
    msb-first, a point of (NL, B) coordinates -> (x, y, z), (NL, B)."""
    nl, lanes = pt[0].shape
    rows = min(lanes, BLOCK) // LANES
    return tuple(c[:, :, :rows].reshape(c.shape[0], nl, -1)
                 .transpose(1, 2, 0).reshape(nl, lanes)
                 for c in _launch(cv, bits, pt, False, interpret))


def msm_bits(cv, bits, pt, interpret: bool = False):
    """cv.msm_reduce(cv.scalar_mul_bits(bits, pt)) on a curve with
    a = 0, B a power of two -> (x, y, z), (NL, 1)."""
    from tpubft.ops.weierstrass import WPoint
    out = WPoint(*(c[:, :, 0, 0].T
                   for c in _launch(cv, bits, pt, True, interpret)))
    return tuple(cv.msm_reduce(out))
