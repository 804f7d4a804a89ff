"""Median over the window's slots of the benchmark's own span round
SigManager.verify_batch."""
import statistics


def read(ctx):
    vals = [s["verify_ms"] for s in ctx["slots"]]
    return statistics.median(vals) if vals else None
