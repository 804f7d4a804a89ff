"""Median over the window's requests (every replica's slots, each counted
once per request it ordered) of `exec_wait`: commit
quorum -> the execution lane began the slot (`flight.EV_EXEC_START`)."""
from cellbench.program_spans import stage_ms


def read(ctx):
    return stage_ms(ctx, "exec_wait")
