"""Median over the window's requests (every replica's slots, each counted
once per request it ordered) of `exec_reply`: the rest of the slot's
request loop, max(lane start, commit) -> `flight.EV_EXEC_HANDLED`, less
`exec_app`: reply building, reply pages, the dedup checks. One of the
three parts of `exec_run`. None, never 0, on a program that does not
split the stage (the parent of the PR that added it) and with nothing to
read."""
from cellbench.program_spans import stage_ms


def read(ctx):
    return stage_ms(ctx, "exec_reply")
