"""Median over the window's requests (every replica's slots, each counted
once per request it ordered) of `dur_fsync`: the group's write returned
-> it committed (`flight.EV_DUR_GROUP`): the fsyncs and the watermark.
One of the three parts of `dur_wait`. None, never 0, on a program that
does not split the stage (the parent of the PR that added it) and with
nothing to read."""
from cellbench.program_spans import stage_ms


def read(ctx):
    return stage_ms(ctx, "dur_fsync")
