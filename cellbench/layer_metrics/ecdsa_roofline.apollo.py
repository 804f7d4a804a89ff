"""The ECDSA kernel's share of its roofline in the Apollo cell: the
least time the chip could take (cellbench/work_ecdsa.py, peaks.json)
for the signatures its traced launches carried, over those launches'
device time in the trace. The launches are the trace's (the program
named `ecdsa_rlc_kernel`); the signatures one carried are the
program's count, the window's mean. Nothing to read — no launch of the
kernel in the trace, or none counted in the window — gives None."""
from cellbench import work, work_ecdsa
from cellbench.roofline import peak_of


def read(ctx):
    seen = ctx["trace"]["kernels"].get("ecdsa")
    if not seen or seen["calls"] == 0 or seen["device_s"] <= 0:
        return None
    calls0, items0 = ctx["before"]["kernels"].get("ecdsa", (0, 0))
    calls1, items1 = ctx["after"]["kernels"].get("ecdsa", (0, 0))
    if calls1 <= calls0 or items1 <= items0:
        return None
    per_call = (items1 - items0) / (calls1 - calls0)
    least = work.least_seconds(
        work_ecdsa.ecdsa_verify(per_call * seen["calls"], seen["calls"]),
        peak_of(ctx["device_kind"]))
    return 100.0 * least["seconds"] / seen["device_s"]
