"""Median over the window's requests (every replica's slots, each counted
once per request it ordered) of `exec_run`: the lane
began the slot (or its commit, if later) -> durable apply of its run."""
from cellbench.program_spans import stage_ms


def read(ctx):
    return stage_ms(ctx, "exec_run")
