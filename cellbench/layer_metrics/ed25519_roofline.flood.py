"""The ed25519 kernel's share of its roofline in the flood: the least
time for the signatures the device was given in the traced window over
the kernel's device time there."""
from cellbench.roofline import share


def read(ctx):
    return share(ctx, "ed25519")
