"""Median over the window's requests (every replica's slots, each counted
once per request it ordered) of `dur_queue`: the durable apply -> the io
thread took the group that covers the slot (`flight.EV_DUR_TAKE`). One
of the three parts of `dur_wait`. None, never 0, on a program that does
not split the stage (the parent of the PR that added it) and with
nothing to read."""
from cellbench.program_spans import stage_ms


def read(ctx):
    return stage_ms(ctx, "dur_queue")
