"""Generic short-Weierstrass curve ops (y² = x³ + ax + b) over a Field.

Uses the complete projective addition law (Renes–Costello–Batina style closed
form): one branch-free formula valid for doubling, identity, and inverses —
exactly what a select-based constant-time ladder under lax.scan needs. Serves
secp256k1 (a=0), P-256 (a=-3), and BLS12-381 G1 (a=0, b=4).

Replaces the reference's per-curve CPU scalar multiplication
(Crypto++ ECDSA in util/src/crypto_utils.cpp:32-72 and RELIC G1 ops behind
threshsign/src/bls/relic/) with batched array programs.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpubft.ops.field import Field


class WPoint(NamedTuple):
    """Projective (X:Y:Z), Montgomery-form limbs, shape (NL, ...batch)."""
    x: jnp.ndarray
    y: jnp.ndarray
    z: jnp.ndarray


class Curve:
    def __init__(self, field: Field, a: int, b: int,
                 gx: int, gy: int, order: int, fused: bool = False):
        self.f = field
        # a = 0 only: on a TPU, ladders over whole-register batches run as
        # one Pallas kernel (double-scalar: ops/field_pallas; single: g1_pallas)
        self.fused = fused and a % field.p == 0
        self.a = a % field.p
        self.b = b % field.p
        self.order = order
        self.gx, self.gy = gx, gy
        self._a_m = field.from_int(self.a)
        self._b3_m = field.from_int(3 * self.b % field.p)
        self._gx_m = field.from_int(gx)
        self._gy_m = field.from_int(gy)

    def _c(self, limbs: np.ndarray, batch: Tuple[int, ...]) -> jnp.ndarray:
        return jnp.broadcast_to(
            jnp.asarray(limbs).reshape((-1,) + (1,) * len(batch)),
            (self.f.nl,) + batch)

    def identity(self, batch: Tuple[int, ...]) -> WPoint:
        return WPoint(self.f.zero(batch), self.f.one(batch), self.f.zero(batch))

    def generator(self, batch: Tuple[int, ...]) -> WPoint:
        return WPoint(self._c(self._gx_m, batch), self._c(self._gy_m, batch),
                      self.f.one(batch))

    def from_affine(self, x_m: jnp.ndarray, y_m: jnp.ndarray) -> WPoint:
        return WPoint(x_m, y_m, self.f.one(x_m.shape[1:]))

    def add(self, p: WPoint, q: WPoint) -> WPoint:
        """Complete projective addition (closed RCB form, ~16 field muls).

        X3 = (X1Y2+X2Y1)(Y1Y2 - a(X1Z2+X2Z1) - 3b Z1Z2)
             - (Y1Z2+Y2Z1)(a X1X2 + 3b(X1Z2+X2Z1) - a² Z1Z2)
        Y3 = (3X1X2 + a Z1Z2)(a X1X2 + 3b(X1Z2+X2Z1) - a² Z1Z2)
             + (Y1Y2 + a(X1Z2+X2Z1) + 3b Z1Z2)(Y1Y2 - a(X1Z2+X2Z1) - 3b Z1Z2)
        Z3 = (Y1Z2+Y2Z1)(Y1Y2 + a(X1Z2+X2Z1) + 3b Z1Z2)
             + (X1Y2+X2Y1)(3X1X2 + a Z1Z2)
        """
        f = self.f
        batch = p.x.shape[1:]
        a_m = self._c(self._a_m, batch)
        b3_m = self._c(self._b3_m, batch)

        xx = f.mul(p.x, q.x)
        yy = f.mul(p.y, q.y)
        zz = f.mul(p.z, q.z)
        # cross terms via (u+v)(s+t) - us - vt to save muls
        xy = f.norm(f.sub(f.sub(f.mul(f.norm(f.add(p.x, p.y)),
                                      f.norm(f.add(q.x, q.y))), xx), yy))
        xz = f.norm(f.sub(f.sub(f.mul(f.norm(f.add(p.x, p.z)),
                                      f.norm(f.add(q.x, q.z))), xx), zz))
        yz = f.norm(f.sub(f.sub(f.mul(f.norm(f.add(p.y, p.z)),
                                      f.norm(f.add(q.y, q.z))), yy), zz))

        a_xz = f.mul(a_m, xz)
        b3_zz = f.mul(b3_m, zz)
        t_minus = f.norm(f.sub(f.sub(yy, a_xz), b3_zz))       # Y1Y2 - aXZ - 3bZZ
        t_plus = f.norm(f.add(f.add(yy, a_xz), b3_zz))        # Y1Y2 + aXZ + 3bZZ
        a_xx = f.mul(a_m, xx)
        b3_xz = f.mul(b3_m, xz)
        a2_zz = f.mul(a_m, f.mul(a_m, zz))
        u = f.norm(f.sub(f.add(a_xx, b3_xz), a2_zz))          # aXX + 3bXZ - a²ZZ
        xx3 = f.norm(f.add(f.add(xx, xx), xx))
        a_zz = f.mul(a_m, zz)
        v = f.norm(f.add(xx3, a_zz))                          # 3XX + aZZ

        x3 = f.sub(f.mul(xy, t_minus), f.mul(yz, u))
        y3 = f.add(f.mul(v, u), f.mul(t_plus, t_minus))
        z3 = f.add(f.mul(yz, t_plus), f.mul(xy, v))
        return WPoint(f.norm(x3), f.norm(y3), f.norm(z3))

    def select(self, cond: jnp.ndarray, p: WPoint, q: WPoint) -> WPoint:
        f = self.f
        return WPoint(f.select(cond, p.x, q.x), f.select(cond, p.y, q.y),
                      f.select(cond, p.z, q.z))

    def neg(self, p: WPoint) -> WPoint:
        return WPoint(p.x, self.f.norm(self.f.neg(p.y)), p.z)

    def scalar_mul_bits(self, bits: jnp.ndarray, p: WPoint) -> WPoint:
        """[k]P for bit matrix (nbits, ...batch), msb-first, constant-time."""
        if self.fused:
            from tpubft.ops import g1_pallas
            if g1_pallas.usable(p.x):
                return WPoint(*g1_pallas.scalar_mul_bits(self, bits, p))
        def step(acc, bit):
            acc = self.add(acc, acc)
            acc = self.select(bit.astype(bool), self.add(acc, p), acc)
            return acc, None
        acc, _ = jax.lax.scan(step, self.identity(p.x.shape[1:]), bits)
        return acc

    def double_scalar_mul_bits(self, bits1, p1: WPoint, bits2, p2: WPoint) -> WPoint:
        """[k1]P1 + [k2]P2 with shared doublings (Shamir's trick)."""
        if self.fused:
            from tpubft.ops import field_pallas
            if field_pallas.usable(p1.x):
                return WPoint(*field_pallas.double_scalar_mul_bits(
                    self, bits1, p1, bits2, p2))
        def step(acc, bb):
            b1, b2 = bb
            acc = self.add(acc, acc)
            acc = self.select(b1.astype(bool), self.add(acc, p1), acc)
            acc = self.select(b2.astype(bool), self.add(acc, p2), acc)
            return acc, None
        acc, _ = jax.lax.scan(step, self.identity(p1.x.shape[1:]), (bits1, bits2))
        return acc

    def msm_reduce(self, p: WPoint) -> WPoint:
        """Tree-reduce a batch of points (NL, B) along the batch axis to a
        single point (NL, 1): log2(B) batched adds. B must be a power of 2
        (pad with identity)."""
        while p.x.shape[-1] > 1:
            h = p.x.shape[-1] // 2
            left = WPoint(p.x[..., :h], p.y[..., :h], p.z[..., :h])
            right = WPoint(p.x[..., h:2*h], p.y[..., h:2*h], p.z[..., h:2*h])
            p = self.add(left, right)
        return p

    def msm_bits(self, bits: jnp.ndarray, p: WPoint) -> WPoint:
        """msm_reduce(scalar_mul_bits(bits, p)) over a flat batch (NL, B),
        B a power of two. On a TPU, with a = 0, ladder and reduce are one
        kernel (ops/g1_pallas): bit-identical, every limb."""
        if self.fused:
            from tpubft.ops import g1_pallas
            if g1_pallas.usable(p.x, reduce=True):
                return WPoint(*g1_pallas.msm_bits(self, bits, p))
        return self.msm_reduce(self.scalar_mul_bits(bits, p))

    def to_affine(self, p: WPoint) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Returns (x_raw, y_raw, is_identity) with canonical non-Montgomery
        tight limbs. Identity maps to (0, 0, True)."""
        f = self.f
        zi = f.inv(p.z)
        x = f.from_mont(f.mul(p.x, zi))
        y = f.from_mont(f.mul(p.y, zi))
        is_id = f.is_zero(p.z)
        return x, y, is_id

    # ---- host helpers ----
    def affine_to_device(self, pts) -> Tuple[np.ndarray, np.ndarray]:
        """Host: list of (x, y) ints -> Montgomery limb arrays (NL, B)."""
        xs = self.f.from_ints([x for x, _ in pts])
        ys = self.f.from_ints([y for _, y in pts])
        return xs, ys
