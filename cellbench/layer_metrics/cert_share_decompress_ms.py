"""Median over the window's fused combine flushes of decompressing the
flush's shares (the `bls_share_decompress` ring span, one a flush)."""
from cellbench.served_spans import span_ms


def read(ctx):
    return span_ms(ctx, "bls_share_decompress")
