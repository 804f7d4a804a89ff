"""Median over the window's `ed25519` calls of the host work round the
kernel inside `SigManager`'s device tier (`prep_us` of the call rows)."""
from cellbench.program_spans import call_ms


def read(ctx):
    return call_ms(ctx, "prep_us", kinds=["ed25519"])
