"""What the `served_apollo` driver's counter readers share: the window's
delta of counters the driver snapshots at the window's open and close
(`ctx["apollo_before"]`, `ctx["apollo_after"]`). None — never 0 — on a
driver that snapshots none, or a program without one of the counters."""
from __future__ import annotations


def window_delta(ctx: dict, *names: str):
    """The window's delta of each of `names`, or None."""
    before, after = ctx.get("apollo_before"), ctx.get("apollo_after")
    if not before or not after or any(k not in after for k in names):
        return None
    return tuple(after[k] - before.get(k, 0) for k in names)
