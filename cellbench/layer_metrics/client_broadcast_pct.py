"""Share of the write messages sent in the window that went to more
than one replica — the first retry is the broadcast: the clients'
`client_broadcasts` over `client_sends`, window delta. None on a
program whose client counts neither, or where nothing was sent."""
from cellbench.apollo_counters import window_delta


def read(ctx):
    got = window_delta(ctx, 'client_broadcasts', 'client_sends')
    if got is None or got[1] <= 0:
        return None
    return 100.0 * got[0] / got[1]
