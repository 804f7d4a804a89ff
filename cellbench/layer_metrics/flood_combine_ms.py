"""Median over the window's slots of the benchmark's own span round
new_accumulator -> add x shares -> combine -> verify."""
import statistics


def read(ctx):
    vals = [s["combine_ms"] for s in ctx["slots"]]
    return statistics.median(vals) if vals else None
