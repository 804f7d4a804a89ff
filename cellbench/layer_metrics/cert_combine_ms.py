"""Median over the window's fused combine flushes of the Lagrange
combine of every slot of the flush, host or device (the `bls_combine`
ring span round `_combine_segments`)."""
from cellbench.served_spans import span_ms


def read(ctx):
    return span_ms(ctx, "bls_combine")
