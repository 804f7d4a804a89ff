"""The G1 MSM's share of its roofline in the flood: the least time for
the points combined in the traced window (667 a call, not the 1,024
padded lanes) over the kernel's device time there."""
from cellbench.roofline import share


def read(ctx):
    return share(ctx, "bls_msm",
                 items_per_call=ctx["points_per_combine"])
