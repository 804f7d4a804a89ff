"""Median over the window's requests (every replica's slots, each counted
once per request it ordered) of `reply`: durable
apply -> the dispatcher integrated the slot and its replies left."""
from cellbench.program_spans import stage_ms


def read(ctx):
    return stage_ms(ctx, "reply")
