"""Median over the window's `ecdsa` call rows of `prep_us`: the host's
work on a batch inside SigManager's device tier and outside the gate
(`prepare_rlc_batch`: prechecks, s^-1, bits, limbs, coefficients)."""
from cellbench.program_spans import call_ms


def read(ctx):
    return call_ms(ctx, "prep_us", kinds=["ecdsa"])
