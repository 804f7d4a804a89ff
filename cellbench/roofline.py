"""A kernel's share of its roofline: the least time the chip could take
for the items the device was given (work.py, peaks.json) over the
kernel's device time in the trace. Nothing to read — no call of the
kernel in the traced window, or no device time — gives None, never 0."""
from __future__ import annotations

import json
import os

from cellbench import work

HERE = os.path.dirname(os.path.abspath(__file__))


def peak_of(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json"), encoding="utf-8") as fh:
        peaks = json.load(fh)
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it "
                       f"to cellbench/peaks.json with its source")
    return peaks[device_kind]


def share(ctx: dict, kind: str, items_per_call=None):
    with open(os.path.join(HERE, "kernels", kind + ".json"),
              encoding="utf-8") as fh:
        kernel = json.load(fh)
    seen = ctx["trace"]["kernels"].get(kind)
    if not seen or seen["calls"] == 0 or seen["device_s"] <= 0:
        return None
    # the program's counts from where the trace began to the window's end
    calls0, items0 = ctx["traced_from"]["kernels"].get(kind, (0, 0))
    calls1, items1 = ctx["after"]["kernels"].get(kind, (0, 0))
    if calls1 <= calls0:
        return None
    # the calls are the trace's; the items a call carried are the
    # program's count (or the caller's, where the program counts lanes)
    calls = seen["calls"]
    per_call = (items_per_call if items_per_call is not None
                else (items1 - items0) / (calls1 - calls0))
    if per_call <= 0:
        return None
    least = work.least_seconds(
        getattr(work, kernel["work"])(per_call * calls, calls),
        peak_of(ctx["device_kind"]))
    return 100.0 * least["seconds"] / seen["device_s"]
