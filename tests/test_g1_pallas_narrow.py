"""ops/g1_pallas at 128 lanes — the fewest a TPU ladder takes: one
sublane of the block's eight, the rest never read back — and the
ladder alone (Curve.scalar_mul_bits' route), against the XLA ladder and
msm_reduce bit for bit, in Pallas's interpret mode on XLA-CPU. The
flood's width and the routing are in test_g1_pallas.py."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from test_g1_pallas import (  # noqa: E402,F401 (release_programs: a fixture)
    SHORT, _lift, assert_msm_identical, case, release_programs)


@pytest.mark.parametrize("kind", ["mixed", "identity"])
def test_the_msm_kernel_is_the_ladder_then_msm_reduce_at_128_lanes(kind):
    assert_msm_identical(case(kind, 128, SHORT))


@pytest.mark.parametrize("nbits", [
    SHORT, pytest.param(255, marks=pytest.mark.slow)])
def test_the_ladder_kernel_is_scalar_mul_bits(nbits):
    """Every lane of [k_i]P_i, the padding lanes' identity among them,
    as XLA's scan leaves it."""
    import jax
    from tpubft.ops import bls12_381, g1_pallas
    cv = bls12_381.g1_curve()
    bits, px, py, infinity = case("mixed", 128, nbits)
    want = jax.jit(lambda b, *p: tuple(cv.scalar_mul_bits(
        b, _lift(cv, *p))))(bits, px, py, infinity)
    got = jax.jit(lambda b, *p: g1_pallas.scalar_mul_bits(
        cv, b, _lift(cv, *p), interpret=True))(bits, px, py, infinity)
    for w, g in zip(want, got):
        assert np.array_equal(np.asarray(w), np.asarray(g))
