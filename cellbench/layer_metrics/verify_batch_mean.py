"""Mean signatures per ed25519 kernel call in the window:
`flight.kernel_profiler()` items over calls for kind `ed25519`."""


def read(ctx):
    calls0, items0 = ctx["before"]["kernels"].get("ed25519", (0, 0))
    calls1, items1 = ctx["after"]["kernels"].get("ed25519", (0, 0))
    if calls1 <= calls0:
        return None
    return (items1 - items0) / (calls1 - calls0)
