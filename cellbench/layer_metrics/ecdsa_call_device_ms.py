"""Median over the window's `ecdsa` call rows of `device_us`: the gate
held for one launch — transfer, the kernel, read-back — on the host's
clock."""
from cellbench.program_spans import call_ms


def read(ctx):
    return call_ms(ctx, "device_us", kinds=["ecdsa"])
