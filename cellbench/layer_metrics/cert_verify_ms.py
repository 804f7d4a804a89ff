"""Median over the window's combined-certificate checks — a collector's
own after a fused combine, a backup's `CertBatchVerifier` flush — of
decompress, hash-to-G1 and the RLC pairing check (the
`bls_pairing_verify` ring span, one a call)."""
from cellbench.served_spans import span_ms


def read(ctx):
    return span_ms(ctx, "bls_pairing_verify")
