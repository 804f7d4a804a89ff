"""Median over the window of a read-only request's wait for the
`skvbc_app` lock, which the execution lane holds through every write it
applies (the `ro_read_wait` ring span in `SkvbcHandler.read`,
dispatcher thread, every replica): the part of `ro_read_ms` that is the
lane's."""
from cellbench.served_spans import span_ms


def read(ctx):
    return span_ms(dict(ctx, cert_spans=ctx.get("read_spans")),
                   "ro_read_wait")
