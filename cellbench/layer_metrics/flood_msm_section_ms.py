"""Median over the window's `bls_msm` calls of the time the device gate
was held: transfer, launch, read-back, on the host's clock (`device_us`)."""
from cellbench.program_spans import call_ms


def read(ctx):
    return call_ms(ctx, "device_us", kinds=["bls_msm"])
