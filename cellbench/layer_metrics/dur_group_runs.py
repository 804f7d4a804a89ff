"""Median over the window's requests (every replica's slots, each counted
once per request it ordered) of the run count of the durability group
that covered the request's slot (the slot row's `group_runs`,
`flight.EV_DUR_GROUP`'s arg). Slots no group covered (0) count for
nothing; None on a program whose rows lack the field (the parent of the
PR that added it) and with nothing to read."""
import statistics


def read(ctx):
    vals = []
    for s in ctx["slots"]:
        runs = s.get("group_runs")
        if runs:
            vals += [runs] * s.get("reqs", 1)
    return statistics.median(vals) if vals else None
