"""clock_check — one traced run of a benchmark cell, with the program's
spans held against the profiler's trace (ISSUE 25, tentpole 4).

Every device launch is an anchor pair: the flight call row's
`t_enter_ns` on `time.monotonic_ns`, and the `tpubft:dev:<kind>` host
span in the profiler's trace round the launch's "XLA Modules" event.
This runs `cellbench.run.run_cell(..., trace=True)` as the benchmark's
command does, keeps the trace file for a moment before the benchmark
reduces and deletes it, and reports:

  (a) which host threads wrote `tpubft:` spans, and whether any is not
      the thread that started the profiler;
  (b) how many `verify_kernel` / `msm_kernel` module events lie inside
      a `tpubft:dev:` span;
  (c) the monotonic-to-trace offset of every launch in the trace, and
      its spread;
  (d) in a served cell, how long a request took from the client's send
      to the primary's dispatcher — the part of a request that lies
      before the ordering queue, and so before every slot stage: the
      clients' `client_send` spans (utils/tracing, `time.monotonic`)
      joined by (client, request number) to the primary's `client_req`
      flight events, which are on the same clock;
  (e) in a served cell, the split of the lane's run and the durability
      group on every retained slot row: how many rows, on how many the
      three parts of `exec_run` or of `dur_wait` do not sum to it (to
      the rows' 0.001 ms rounding), the parts' medians over requests,
      and the flight events the split adds per client request.

Each thread's `tpubft:` names are counted (`span_counts_by_thread`),
beside the number of device module events in the same trace.

It also leaves the window's slot rows and the recorder's snapshot in
chiprun_out/clock_check/ for `tools/tpuprof.py`.

Usage (through the chip tool; `--rehearse` shrinks the served cell and
skips the look for a chip, for XLA-CPU, where (b) has nothing to read):

  python -m tools.clock_check --workload <cell> --seed <n> --seconds 48

The last line printed is the cell's own result line; the line before
it is the check's, which is also written to chiprun_out/clock_check/.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

PREFIX = "tpubft:"
DEV = PREFIX + "dev:"
OPENED = "cellbench:trace_open"


def read_trace(path: str) -> dict:
    """{"threads": {"plane/line#i": [[name, start_ns, dur_ns], ...]} of
    the host's `tpubft:` / `cellbench:` spans, "modules": the device
    planes' "XLA Modules" events}."""
    from jax.profiler import ProfileData
    threads, modules = {}, []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:") \
            and "CUSTOM" not in plane.name
        for i, line in enumerate(plane.lines):
            if device:
                if line.name == "XLA Modules":
                    modules += [[e.name, int(e.start_ns), int(e.duration_ns)]
                                for e in line.events]
                continue
            evs = [[e.name, int(e.start_ns), int(e.duration_ns)]
                   for e in line.events
                   if e.name.startswith((PREFIX, "cellbench:"))]
            if evs:
                # a host's lines may all be named alike ("python")
                threads[f"{plane.name}/{line.name}#{i}"] = evs
    return {"threads": threads, "modules": modules}


def pair_offsets(spans, rows, anchor_ns):
    """Offsets `trace start - t_enter_ns` of the trace's `tpubft:dev:`
    spans of one kind, each paired with the call row it belongs to. The
    trace holds part of the process's life — in the flood one launch a
    kind — so the row is found through the benchmark's own anchor: its
    `trace_open` span in the trace and the `time.monotonic()` it took
    inside that span (good to the span's few microseconds; the rows of
    a kind lie milliseconds apart)."""
    offs = []
    for _name, start, _dur in spans:
        row = min(rows, key=lambda r: abs(start - anchor_ns
                                          - r["t_enter_ns"]))
        offs.append(start - row["t_enter_ns"])
    return offs


def analyse(trace: dict, call_rows: list, patterns: dict,
            opened_mono_s: float) -> dict:
    threads = trace["threads"]
    opened = [s for evs in threads.values() for n, s, _d in evs
              if n == OPENED]
    anchor_ns = opened[0] - int(opened_mono_s * 1e9)
    opener = [t for t, evs in threads.items()
              if any(n == OPENED for n, _s, _d in evs)]
    ours = {t: sorted({n[len(PREFIX):] for n, _s, _d in evs
                       if n.startswith(PREFIX)})
            for t, evs in threads.items()}
    ours = {t: names for t, names in ours.items() if names}
    counts = {}
    for t, evs in threads.items():
        for n, _s, _d in evs:
            if n.startswith(PREFIX):
                c = counts.setdefault(t, {})
                c[n[len(PREFIX):]] = c.get(n[len(PREFIX):], 0) + 1
    dev_spans = [(n, s, d) for evs in threads.values()
                 for n, s, d in evs if n.startswith(DEV)]
    inside = {}
    for kind, pattern in patterns.items():
        rx = re.compile(pattern)
        mods = [(s, s + d) for n, s, d in trace["modules"] if rx.search(n)]
        mine = [(s, s + d) for n, s, d in dev_spans if n == DEV + kind]
        inside[kind] = {
            "module_events": len(mods), "dev_spans": len(mine),
            "inside_a_dev_span": sum(
                any(a <= m0 and m1 <= b for a, b in mine)
                for m0, m1 in mods)}
    offsets = []
    by_kind = {}
    for kind in sorted({n[len(DEV):] for n, _s, _d in dev_spans}):
        offs = pair_offsets(
            [x for x in dev_spans if x[0] == DEV + kind],
            [r for r in call_rows if r["kind"] == kind], anchor_ns)
        by_kind[kind] = len(offs)
        offsets += offs
    out = {
        "threads_with_tpubft_spans": len(ours),
        "threads_other_than_the_profiler_s": len(
            [t for t in ours if t not in opener]),
        "profiler_started_on": opener,
        "span_kinds_by_thread": ours,
        "span_counts_by_thread": counts,
        "device_module_events": len(trace["modules"]),
        "launches": inside,
        "launches_paired": by_kind,
    }
    if offsets:
        out["offset_ns"] = {
            "n": len(offsets), "median": statistics.median(offsets),
            "min": min(offsets), "max": max(offsets),
            "spread_max_minus_min": max(offsets) - min(offsets),
            "stdev": statistics.pstdev(offsets),
            "median_less_trace_open_anchor":
                statistics.median(offsets) - anchor_ns}
    return out


def request_path(snapshot: dict, slot_rows: list) -> dict:
    """(d): medians, in ms, over the finished `client_send` spans whose
    request the primary's rings still hold."""
    from tpubft.utils import flight
    from tpubft.utils.tracing import get_tracer
    primaries = {r["rid"] for r in slot_rows if r.get("primary")}
    arrived = {}
    for ring in snapshot["rings"]:
        if ring["rid"] not in primaries:
            continue
        for t_ns, code, seq, _view, arg in ring["events"]:
            if code == flight.EV_CLIENT_REQ:
                key = (arg, seq)            # (client, request number)
                arrived[key] = min(t_ns, arrived.get(key, t_ns))
    to_dispatcher, whole = [], []
    for sp in get_tracer().finished_spans():
        if sp.name != "client_send" or sp.end is None:
            continue
        t_ns = arrived.get((int(sp.tags["client"]),
                            int(sp.tags["req_seq"])))
        if t_ns is not None:
            to_dispatcher.append(t_ns / 1e6 - sp.start * 1e3)
            whole.append((sp.end - sp.start) * 1e3)
    if not whole:
        return {"requests_joined": 0}
    return {"requests_joined": len(whole), "primaries": sorted(primaries),
            "send_to_primary_dispatcher_ms_p50":
                statistics.median(to_dispatcher),
            "request_ms_p50": statistics.median(whole)}


EXEC_PARTS = ("exec_app", "exec_reply", "exec_seal")
DUR_PARTS = ("dur_queue", "dur_apply", "dur_fsync")


def split_check(slot_rows: list, n: int) -> dict:
    """(e): the rows that carry the split (a program without it: none)."""
    rows = [r for r in slot_rows if "exec_app" in r["stages_ms"]]
    if not rows:
        return {"rows": 0}

    def off(st, parts, whole):
        return abs(sum(st[k] for k in parts) - st[whole]) > 0.0015
    out = {"rows": len(rows),
           "exec_run_mismatches": sum(off(r["stages_ms"], EXEC_PARTS,
                                          "exec_run") for r in rows),
           "dur_wait_mismatches": sum(off(r["stages_ms"], DUR_PARTS,
                                          "dur_wait") for r in rows)}
    for k in ("exec_run",) + EXEC_PARTS + ("dur_wait",) + DUR_PARTS:
        vals = [r["stages_ms"][k] for r in rows
                for _ in range(r.get("reqs", 1))]
        if vals:
            out[f"{k}_ms_p50"] = statistics.median(vals)
    # one exec_handled a slot, a take and a write a group, on every
    # replica's row; a request is on n replicas' rows
    reqs = sum(r.get("reqs", 0) for r in rows) / n
    events = sum(1 + 2 / r["group_runs"] if r.get("group_runs") else 1
                 for r in rows)
    if reqs:
        out["events_added_per_request"] = events / reqs
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from cellbench import harness, run, trace as cb_trace
    from tpubft.utils import flight

    found = {}
    reduce_ = run.Tracer.reduce
    out_dir = os.path.join(_ROOT, "chiprun_out", "clock_check")
    stem = f"{args.workload}.{args.seed}"

    def reduce_and_check(self, chips):
        data = read_trace(cb_trace.find_xplane(self.dir))
        patterns = {k["kind"]: k["pattern"]
                    for k in cb_trace._kernel_files()}
        found.update(analyse(data, flight.kernel_profiler().call_rows(),
                             patterns, self.t0))
        snap = flight.snapshot()
        rows = flight.slot_tracker().recent(limit=flight.SlotTracker.KEEP)
        found["request_path"] = request_path(snap, rows)
        cluster = cell.config.get("cluster")
        if cluster:
            found["split"] = split_check(rows, cluster["n"])
            found["split"]["exec_start_run_lengths"] = sorted({
                arg for ring in snap["rings"]
                for _t, code, _seq, _view, arg in ring["events"]
                if code == flight.EV_EXEC_START})
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, stem + ".flight.json"), "w",
                  encoding="utf-8") as fh:
            json.dump(dict(snap, slot_rows=rows), fh)
        return reduce_(self, chips)

    run.Tracer.reduce = reduce_and_check
    cell = harness.Cell(args.workload)
    if args.rehearse:
        cell.traffic["classes"]["interactive"]["clients"] = 3
        cell.traffic["classes"]["bulk"]["writes_per_message"] = 32
        cell.workload.update(programs={"ed25519_batches": [32]},
                             warmup_s=1, settle_quiet_s=1)
    result = run.run_cell(cell, args.seed, args.seconds, True,
                          require_tpu=not args.rehearse)
    line = dict(found, phase="clock_check", cell=args.workload,
                seed=args.seed, device=result["device"])
    with open(os.path.join(out_dir, stem + ".json"), "w",
              encoding="utf-8") as fh:
        json.dump(line, fh)
    print(json.dumps(line), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
