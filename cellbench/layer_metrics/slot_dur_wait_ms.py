"""Median over the window's requests (every replica's slots, each counted
once per request it ordered) of `dur_wait`: durable
apply -> the durability group that covers the slot committed
(`flight.EV_DUR_GROUP`); a slice of `reply`."""
from cellbench.program_spans import stage_ms


def read(ctx):
    return stage_ms(ctx, "dur_wait")
