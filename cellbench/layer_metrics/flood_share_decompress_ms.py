"""Median over the window's combines of `g1_decompress` summed over one
accumulator's `add` calls (the `bls_share_decompress` ring span)."""
from cellbench.program_spans import flood_span_ms


def read(ctx):
    return flood_span_ms(ctx, "bls_share_decompress")
