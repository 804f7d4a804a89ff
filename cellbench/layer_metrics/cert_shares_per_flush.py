"""Threshold-BLS shares a batch decode carried in the window:
`bls_shares_batch_decoded` over `bls_decode_batches` of the
process-wide `threshold` component (crypto/systems.decode_shares, one
call a fused flush's job), window delta. A combine reaches the device
MSM at 128 shares a flush. None on a program without the counters or
where nothing was decoded."""
from cellbench.apollo_counters import window_delta


def read(ctx):
    got = window_delta(ctx, 'bls_shares_batch_decoded', 'bls_decode_batches')
    if got is None or got[1] <= 0:
        return None
    return got[0] / got[1]
