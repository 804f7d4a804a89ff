"""Share of the ECDSA signatures verified afresh in the window (memo
hits are not verifications) that the device kernel answered: the
`signature_manager` counters `ecdsa_device_items` and
`ecdsa_host_items` of all replicas, window delta. None on a program
without the counters, or where no ECDSA signature was verified."""
from cellbench.apollo_counters import window_delta


def read(ctx):
    got = window_delta(ctx, 'ecdsa_device_items', 'ecdsa_host_items')
    if got is None or sum(got) <= 0:
        return None
    return 100.0 * got[0] / sum(got)
