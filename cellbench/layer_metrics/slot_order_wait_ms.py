"""Median over the window's requests (the primary's slots, each counted
once per request it ordered) of `order_wait`: how long
the oldest request of the batch stood in the primary's `pending_requests`
before its PrePrepare was cut (`flight.EV_PP_CREATE`)."""
from cellbench.program_spans import stage_ms


def read(ctx):
    return stage_ms(ctx, "order_wait", primary_only=True)
