"""Median over the window's `bls_msm` calls of the host work round the
kernel inside the combine's device tier: Lagrange coefficients, bit
decomposition, limb conversion, affine conversion (`prep_us`)."""
from cellbench.program_spans import call_ms


def read(ctx):
    return call_ms(ctx, "prep_us", kinds=["bls_msm"])
