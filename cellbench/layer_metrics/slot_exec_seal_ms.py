"""Median over the window's requests (every replica's slots, each counted
once per request it ordered) of `exec_seal`: `flight.EV_EXEC_HANDLED` ->
the durable apply: the run's `end_accumulation` into the pending store,
and the pages write when not folded. One of the three parts of
`exec_run`. None, never 0, on a program that does not split the stage
(the parent of the PR that added it) and with nothing to read."""
from cellbench.program_spans import stage_ms


def read(ctx):
    return stage_ms(ctx, "exec_seal")
