"""Median over the window's requests (every replica's slots, each counted
once per request it ordered) of `adm_wait` +
`dispatch`: admission admit -> PrePrepare handler entry -> accepted
(`flight.SlotTracker`; 0 on the primary's own proposal)."""
from cellbench.program_spans import stage_ms


def read(ctx):
    return stage_ms(ctx, "adm_wait", "dispatch")
