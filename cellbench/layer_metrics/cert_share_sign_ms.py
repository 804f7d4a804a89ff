"""Median over the window of one replica's threshold share signature
(the `share_sign` ring span round `sign_share`, dispatcher thread)."""
from cellbench.served_spans import span_ms


def read(ctx):
    return span_ms(ctx, "share_sign")
