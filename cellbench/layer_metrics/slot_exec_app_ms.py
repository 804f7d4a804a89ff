"""Median over the window's requests (every replica's slots, each counted
once per request it ordered) of `exec_app`: the slot's application calls
on the lane (the handler, the merkle walk, the block's rows staged),
summed: `flight.EV_EXEC_HANDLED`'s arg. One of the three parts of
`exec_run`. None, never 0, on a program that does not split the stage
(the parent of the PR that added it) and with nothing to read."""
from cellbench.program_spans import stage_ms


def read(ctx):
    return stage_ms(ctx, "exec_app")
