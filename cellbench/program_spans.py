"""What the `program_span` readers share: the slot stages the program's
flight recorder folded (`ctx["slots"]`), its device seam's per-call rows
cut by ordinal with the `calls` the driver snapshotted at both ends of
the window, and its `flight.span` ring events cut by the monotonic
times the driver's rows carry. Each gives the median over the window's
requests or calls, and None — never 0 — with nothing to read: no slot, no
call, a program that has no such stage, row or span (the parent of the
PR that added them), or a ring or row store that wrapped inside the
window."""
from __future__ import annotations

import statistics


def _median(vals):
    vals = list(vals)
    return statistics.median(vals) if vals else None


def stage_ms(ctx: dict, *stages: str, primary_only: bool = False):
    """Median over the window's REQUESTS of the sum of `stages`: each
    slot counts once for every request it ordered (`reqs`). The median
    slot of the served cell carries one request and the median request
    rides a slot of thirty, so a median over slots says what the small
    slots paid and misses where the requests were (PERF.md, PR 25). Rows
    without a request count (the parent's) count once each."""
    vals = []
    for s in ctx["slots"]:
        if primary_only and not s.get("primary"):
            continue
        if all(k in s["stages_ms"] for k in stages):
            vals += [sum(s["stages_ms"][k] for k in stages)] \
                * s.get("reqs", 1)
    return _median(vals)


def window_call_rows(ctx: dict, kinds=None):
    """The call rows of the window, every kind or `kinds`: ordinals
    above the `calls` of `before` up to those of `after`. None where the
    program keeps no rows or a wanted row is no longer kept."""
    from tpubft.utils import flight
    prof = flight.kernel_profiler()
    if not hasattr(prof, "call_rows"):
        return None
    before, after = ctx["before"]["kernels"], ctx["after"]["kernels"]
    out = []
    for kind in (after if kinds is None else kinds):
        if kind.endswith(".shard"):
            continue                 # a view of a launch, not a call
        lo = before.get(kind, (0, 0))[0]
        hi = after.get(kind, (0, 0))[0]
        rows = [r for r in prof.call_rows(kind) if lo < r["ordinal"] <= hi]
        if len(rows) != hi - lo:
            return None              # the store wrapped inside the window
        out.extend(rows)
    return out


def call_ms(ctx: dict, field: str, kinds=None):
    """Median of one interval of the window's calls, in milliseconds."""
    rows = window_call_rows(ctx, kinds)
    return _median(r[field] / 1e3 for r in rows) if rows else None


def flood_span_ms(ctx: dict, name: str):
    """Median of the ring spans `name` that closed inside the flood's
    window: from the first slot's start (`done` less its two parts) to
    the last slot's `done`; `time.monotonic` and the recorder's
    `monotonic_ns` are one clock."""
    from tpubft.utils import flight
    slots = ctx["slots"]
    if not slots or not hasattr(flight, "span_events"):
        return None
    first = slots[0]
    t0 = first["done"] - (first["verify_ms"] + first["combine_ms"]) / 1e3
    spans = flight.span_events(name, since_ns=int(t0 * 1e9))
    if spans is None:
        return None
    t1 = int(slots[-1]["done"] * 1e9)
    return _median(us / 1e3 for t, _seq, us in spans if t <= t1)
