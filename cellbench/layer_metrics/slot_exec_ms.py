"""Median of the `exec` stage (commit quorum -> durable apply on the
execution lane) over the slots finalized in the window, all replicas:
`flight.SlotTracker`."""
import statistics


def read(ctx):
    vals = [s["stages_ms"]["exec"] for s in ctx["slots"]]
    return statistics.median(vals) if vals else None
