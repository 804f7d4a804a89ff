"""The MSM's host-built operands, array code against the old loops.

`ops/bls12_381._bits_msb_batch`, `_prep_msm` and `Curve.affine_to_device`
build their arrays in one pass each; the loops they replaced are kept
here as the plain reference and the arrays must be the same bytes — the
device kernels take them unchanged. `lagrange_coeffs_at_zero` (its k²
small products now one native call) against the naive per-i formula.
"""
import random

import numpy as np
import pytest

from tpubft.crypto import bls12381 as bls
from tpubft.crypto import bls_native
from tpubft.ops import bls12_381 as dev

RNG = random.Random(0x31)


def _bits_loop(scalars):
    out = np.zeros((dev.SCALAR_BITS, len(scalars)), np.int32)
    for j, k in enumerate(scalars):
        for i in range(dev.SCALAR_BITS):
            out[i, j] = (k >> (dev.SCALAR_BITS - 1 - i)) & 1
    return out


def _affine_loop(pts):
    f = dev.g1_curve().f
    xs = np.stack([f.from_int(x) for x, _ in pts], axis=-1)
    ys = np.stack([f.from_int(y) for _, y in pts], axis=-1)
    return xs, ys


def _prep_loop(points, scalars, m):
    n = len(points)
    infinity = np.zeros(m, bool)
    pts, ks = [], []
    for i in range(m):
        if i < n and points[i] is not None:
            pts.append(points[i])
            ks.append(scalars[i] % bls.R)
        else:
            pts.append((0, 0))
            ks.append(0)
            infinity[i] = True
    px, py = _affine_loop(pts)
    return _bits_loop(ks), px, py, infinity


def _identical(a: np.ndarray, b: np.ndarray) -> bool:
    return (a.dtype == b.dtype and a.shape == b.shape
            and a.flags["C_CONTIGUOUS"] == b.flags["C_CONTIGUOUS"]
            and np.array_equal(a, b))


def _points(n):
    base = [bls.g1_mul(bls.G1_GEN, RNG.randrange(1, bls.R))
            for _ in range(min(n, 12))]
    return [base[i % len(base)] for i in range(n)]


def _scalars(n):
    edge = [0, 1, bls.R - 1, bls.R, bls.R + 5, 2 * bls.R + 1,
            (1 << 254) | 1, (1 << 255) - 1]
    return [edge[i] if i < len(edge) and i % 2 else RNG.randrange(bls.R)
            for i in range(n)]


@pytest.mark.parametrize("n", [0, 1, 7, 667, 1024])
def test_bits_are_the_loops(n):
    ks = [k % bls.R for k in _scalars(n)]
    assert _identical(dev._bits_msb_batch(ks), _bits_loop(ks))


def test_bits_of_a_scalar_past_255_bits_are_its_low_255():
    ks = [(1 << 255) + 9, (1 << 300) + (1 << 254), -1]
    assert _identical(dev._bits_msb_batch(ks), _bits_loop(ks))


@pytest.mark.parametrize("n", [0, 1, 7, 667])
def test_affine_to_device_is_the_loop(n):
    pts = _points(n) + [(0, 0), (bls.P - 1, 1)]
    for got, want in zip(dev.g1_curve().affine_to_device(pts),
                         _affine_loop(pts)):
        assert _identical(got, want)


@pytest.mark.parametrize("n,m", [(1, 1), (1, 2), (7, 8), (667, 1024),
                                 (1024, 1024)])
def test_prep_msm_is_the_loop(n, m):
    points, scalars = _points(n), _scalars(n)   # scalars >= r among them
    if n >= 7:
        points[3] = None                        # an identity in the set
    got = dev._prep_msm(points, scalars, m)
    want = _prep_loop(points, scalars, m)
    for g, w in zip(got, want):
        assert _identical(g, w)


def _lagrange_naive(ids):
    out = []
    for i in ids:
        num = den = 1
        for j in ids:
            if j != i:
                num = num * (0 - j) % bls.R
                den = den * (i - j) % bls.R
        out.append(num * pow(den, -1, bls.R) % bls.R)
    return out


@pytest.mark.parametrize("engine", ["native", "fallback"])
@pytest.mark.parametrize("k", [1, 5, 7, 667])
def test_lagrange_is_the_naive_formula(k, engine, monkeypatch):
    if engine == "fallback":
        monkeypatch.setattr(bls_native, "_lib", None)
        monkeypatch.setattr(bls_native, "_tried", True)
    elif not bls_native.available():
        pytest.skip("native bls12381 library did not build")
    ids = RNG.sample(range(1, 1001), k)         # unsorted, as callers may
    assert bls.lagrange_coeffs_at_zero(ids) == _lagrange_naive(ids)
    ids = sorted(ids)
    assert bls.lagrange_coeffs_at_zero(ids) == _lagrange_naive(ids)


def test_lagrange_takes_ids_of_any_size_and_sign():
    for ids in ([3, 2 ** 61, 17, -5], [3, 2 ** 70, 17],
                [bls.R + 2, 1, 5]):
        assert bls.lagrange_coeffs_at_zero(ids) == _lagrange_naive(ids)
    for ids in ([1, 1, 2], [0, 1], [bls.R, 2], [2, bls.R + 2]):
        with pytest.raises(ValueError):
            bls.lagrange_coeffs_at_zero(ids)
