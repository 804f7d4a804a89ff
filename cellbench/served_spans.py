"""What the readers of the served certificate path's `flight.span` ring
events share, beside `program_spans.py` (which cuts the flood's spans
by its slots): the median of the spans of one name that closed inside
the served window. The `served_bls` driver reads the rings when the
window closes (`ctx["cert_spans"]`: the spans since the window opened
and from where on they are complete), because a dispatcher's ring holds
4,096 events of every kind, one a message, and wraps many times inside
a 48 s window: a combine thread's spans cover all of it, a dispatcher's
`share_sign` its last seconds. None — never 0 — with nothing to read: a
driver that took no such reading, a program that writes no such span
(the parent of the PR that added it), no span inside the window."""
from __future__ import annotations

import statistics


def span_ms(ctx: dict, name: str):
    read = (ctx.get("cert_spans") or {}).get(name)
    if not read:
        return None
    spans, _from_ns = read
    t_close = ctx["t_close"] * 1e9
    vals = [us / 1e3 for t, _seq, us in spans if t <= t_close]
    return statistics.median(vals) if vals else None
