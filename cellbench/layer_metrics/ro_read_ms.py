"""Median over the window of one replica's answer to a read-only request
(the `ro_read` ring span round `handler.read` in
`replica._post_admission`, dispatcher thread, every replica), its wait
for the application's lock included. A dispatcher's ring holds its last
seconds: the spans from where every ring that holds them is whole."""
from cellbench.served_spans import span_ms


def read(ctx):
    return span_ms(dict(ctx, cert_spans=ctx.get("read_spans")), "ro_read")
