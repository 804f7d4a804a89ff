"""Median of the `commit` stage (prepare quorum -> commit quorum) over
the slots finalized in the window, all replicas: `flight.SlotTracker`."""
import statistics


def read(ctx):
    vals = [s["stages_ms"]["commit"] for s in ctx["slots"]]
    return statistics.median(vals) if vals else None
