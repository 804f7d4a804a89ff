"""From the profiler's trace to device numbers: busy intervals, device
time per kernel, the operations that took most time and the longest
idle gaps, each gap named by the benchmark span that covered most of
it. `read_xplane` needs nothing but JAX; `reduce` works on its plain
output, so it is tested on a small recorded trace.
"""
from __future__ import annotations

import glob
import json
import os
import re

SPAN_PREFIX = "cellbench:"
OPEN, CLOSE = SPAN_PREFIX + "trace_open", SPAN_PREFIX + "trace_close"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# the device's trace buffer is finite (some 6 million events on a v5e:
# two MSM calls); once it is full the device says so in an event of this
# name that lasts for as long as it dropped what ran
DROPPED = "Trace Buffers Dropped"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    return found[-1]


def read_xplane(path: str) -> dict:
    """{"device": {plane: {line: [[name, start_ns, dur_ns], ...]}},
    "spans": [[name, start_ns, dur_ns], ...]}: every event of the device
    planes, and of the host's only the benchmark's own spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            lines = device.setdefault(plane.name, {})
            for line in plane.lines:
                lines[line.name] = [[e.name, int(e.start_ns),
                                     int(e.duration_ns)]
                                    for e in line.events]
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, int(e.start_ns),
                                      int(e.duration_ns)])
    return {"device": device, "spans": spans}


def op_name(hlo: str) -> str:
    """`%while.8994 while`, from the instruction's text as the trace
    has it: its name and its opcode, without shapes and operands."""
    head, _, rest = hlo.partition(" = ")
    if not rest:
        return hlo[:80]
    m = re.search(r"\b([a-z][a-z0-9\-]*)\(", rest)
    return f"{head} {m.group(1)}"[:80] if m else head[:80]


def _union(intervals):
    """Merged, sorted [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _kernel_files() -> list:
    here = os.path.join(os.path.dirname(os.path.abspath(__file__)), "kernels")
    out = []
    for path in sorted(glob.glob(os.path.join(here, "*.json"))):
        with open(path, encoding="utf-8") as fh:
            out.append(json.load(fh))
    return out


def reduce(data: dict, chips: int = 1, kernels=None) -> dict:
    """The traced window is from the `trace_open` span to the
    `trace_close` span (the events' own extent where a trace has
    neither), less any stretch in which the device dropped its events.
    Busy is the union of the intervals in which an operation ran on a
    device, averaged over the `chips` busiest device planes."""
    kernels = _kernel_files() if kernels is None else kernels
    spans = data["spans"]
    marks = {n: s for n, s, _ in spans if n in (OPEN, CLOSE)}
    every = [(s, s + d) for lines in data["device"].values()
             for evs in lines.values() for _, s, d in evs]
    every += [(s, s + d) for _, s, d in spans]
    if not every:
        return {"busy_s": 0.0, "window_s": 0.0, "idle_pct": None,
                "kernels": {}, "breakdown": {"device_ops": [],
                                             "idle_gaps": []}}
    w0 = marks.get(OPEN, min(s for s, _ in every))
    w1 = marks.get(CLOSE, max(e for _, e in every))
    dropped = _union((s, s + d) for lines in data["device"].values()
                     for evs in lines.values() for n, s, d in evs
                     if n == DROPPED)
    if dropped:
        # what ran while the buffer was full left no event: the window
        # ends where the record does
        w1 = min(w1, dropped[0][0])
    planes = []
    for name, lines in data["device"].items():
        evs = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        busy = _union((max(s, w0), min(s + d, w1)) for _, s, d in evs
                      if s + d > w0 and s < w1)
        planes.append((sum(e - s for s, e in busy), name, busy, evs))
    planes.sort(reverse=True)
    used = planes[:chips]
    busy_s = (sum(p[0] for p in used) / len(used) / 1e9) if used else 0.0
    window_s = (w1 - w0) / 1e9

    per_kernel = {}
    for k in kernels:
        rx = re.compile(k["pattern"])
        calls = total = 0
        for _, name, _, _ in used:
            for ev, s, d in data["device"][name].get(k["line"], []):
                if rx.search(ev) and s + d > w0 and s < w1:
                    calls += 1
                    total += d
        per_kernel[k["kind"]] = {"calls": calls, "device_s": total / 1e9}

    ops = {}
    for _, _, _, evs in used:
        for ev, s, d in evs:
            if s + d > w0 and s < w1:
                ops[ev] = ops.get(ev, 0) + d
    device_ops = [[op_name(n), d / 1e9] for n, d in
                  sorted(ops.items(), key=lambda kv: -kv[1])[:10]]

    gaps = []
    if used:
        busy = used[0][2]
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a, b))
    gaps.sort(reverse=True)
    named = [s for s in spans if s[0] not in (OPEN, CLOSE)]
    idle_gaps = []
    for length, a, b in gaps[:10]:
        cover = {}
        for n, s, d in named:
            overlap = min(b, s + d) - max(a, s)
            if overlap > 0:
                cover[n] = cover.get(n, 0) + overlap
        name = (max(cover, key=cover.get)[len(SPAN_PREFIX):]
                if cover else "no benchmark span")
        idle_gaps.append([name, length / 1e9])
    return {"busy_s": busy_s, "window_s": window_s,
            "events_dropped": bool(dropped),
            "idle_pct": (100.0 * (1 - busy_s / window_s)
                         if window_s > 0 and used else None),
            "kernels": per_kernel,
            "breakdown": {"device_ops": device_ops, "idle_gaps": idle_gaps}}
