"""Median over the window's device calls, every kind, of the wait for the
device gate that four replicas share (`gate_wait_us` of the call rows)."""
from cellbench.program_spans import call_ms


def read(ctx):
    return call_ms(ctx, "gate_wait_us")
