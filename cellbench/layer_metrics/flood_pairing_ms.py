"""Median over the window's certificates of `BlsThresholdVerifier.verify`:
decompress, hash_to_g1, pairing check (the `bls_pairing_verify` span)."""
from cellbench.program_spans import flood_span_ms


def read(ctx):
    return flood_span_ms(ctx, "bls_pairing_verify")
