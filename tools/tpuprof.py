"""tpuprof — offline flight-dump analyzer.

Merges one or more flight-recorder dump artifacts (written by
`tpubft.utils.flight.dump` — automatically on stalled/degraded health
transitions and chaos-campaign red verdicts, or on demand via
`status get flight`) into:

  * a per-slot TIMELINE: every (replica, seq) lifecycle folded from the
    raw ring events, aligned across replicas on the wall clock (each
    dump anchors its monotonic event clock with a ts_epoch/mono_ns
    pair), so "replica 2 committed 40ms after replica 0" is a table
    row, not an archaeology session;
  * a STAGE-HISTOGRAM table: adm_wait / dispatch / prepare / commit /
    exec / reply percentiles over every completed slot in the dumps,
    with the order_wait / exec_wait / exec_run / dur_wait sub-stages
    and the splits of the last two (exec_app / exec_reply / exec_seal,
    dur_queue / dur_apply / dur_fsync);
  * the KERNEL profile per dump (call counts, batch sizes, compile
    warmup vs warm time, prep / gate-wait totals, breaker states at
    call time);
  * the flight SPANS (`flight.span`: lane runs, admission drains,
    combine flushes, durability groups, the BLS host path) by name;
  * spans grouped by trace id (the cross-replica request join).

Usage:
  python tools/tpuprof.py DUMP.json [DUMP2.json ...] [--seq N]
                          [--limit 30]

Typical slow-slot investigation (docs/OPERATIONS.md has the full
recipe): grab `status get flight` from each replica (or take the
automatic dump a stalled-health transition wrote), run tpuprof over
all of them, find the slot whose total is the outlier in the timeline,
and read which stage ate the time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from tpubft.utils import flight  # noqa: E402

STAGES = flight.STAGES


def load_dump(path: str) -> Dict:
    with open(path, "r", encoding="utf-8") as fh:
        d = json.load(fh)
    d["_path"] = path
    return d


def _epoch_of(dump: Dict, t_ns: int) -> float:
    """Wall-clock time of a monotonic event timestamp, via the dump's
    anchor pair."""
    return dump["ts_epoch"] + (t_ns - dump["mono_ns"]) / 1e9


def fold_slots(dump: Dict) -> Dict[Tuple[int, int], Dict]:
    """Rebuild slot lifecycles from the dump's raw ring events through
    the live SlotTracker's own fold (`stamp` / `stamp_group` /
    `fold` are the shared stage math). Events are replayed in time
    order across rings: a durability group's take, write and commit
    mark the slots applied BEFORE them. Keyed (rid, seq)."""
    tracker = flight.SlotTracker
    events = sorted((ev[0], ring.get("rid", -1), ev)
                    for ring in dump.get("rings", [])
                    for ev in ring.get("events", []))
    slots: Dict[Tuple[int, int], Dict] = {}
    for _t, rid, (t_ns, code, seq, view, arg) in events:
        if code in tracker._GROUP_FIELD:
            tracker.stamp_group(slots.values(), rid, code, seq, arg, t_ns)
        elif code in tracker._FIELD:
            slot = slots.setdefault((rid, seq),
                                    {"rid": rid, "seq": seq, "view": view})
            tracker.stamp(slot, code, arg, t_ns)
    return slots


def _label(dump: Dict, rid: int) -> str:
    base = os.path.basename(dump["_path"])
    return f"{base}:r{rid}" if rid >= 0 else base


def timeline(dumps: List[Dict], seq_filter: Optional[int] = None,
             limit: int = 30) -> List[str]:
    """Per-slot rows merged across dumps, newest seqs last. Each row's
    t0 converts through ITS OWN dump's epoch/mono anchor (monotonic
    clocks are unrelated across processes), so cross-replica offsets
    are real wall-clock deltas."""
    rows: Dict[int, List[Tuple[str, Dict, Dict, Dict]]] = {}
    for d in dumps:
        for (rid, seq), slot in fold_slots(d).items():
            if seq_filter is not None and seq != seq_filter:
                continue
            stages = flight.SlotTracker.fold(slot)
            rows.setdefault(seq, []).append(
                (_label(d, rid), slot, stages, d))
    out = ["slot timeline (ms per stage; t0 = first event's wall clock)",
           f"{'seq':>6} {'replica':<28} {'t0':>10} "
           + " ".join(f"{s:>12}" for s in STAGES) + f" {'total':>9} path"]
    seqs = sorted(rows)
    if seq_filter is None and len(seqs) > limit:
        seqs = seqs[-limit:]
        out.insert(1, f"(showing the newest {limit} of {len(rows)} seqs; "
                      f"--limit raises)")
    base_epoch = None
    for d in dumps:
        for ring in d.get("rings", []):
            for ev in ring.get("events", []):
                e = _epoch_of(d, ev[0])
                base_epoch = e if base_epoch is None else min(base_epoch, e)
    for seq in seqs:
        for label, slot, stages, dump in sorted(
                rows[seq], key=lambda r: r[0]):
            ts = [v for k, v in slot.items()
                  if k not in ("rid", "seq", "view", "path", "reqs",
                               "order_wait_us", "app_us", "group_runs",
                               "cut")]
            t0 = ""
            if ts and base_epoch is not None:
                t0 = f"{_epoch_of(dump, min(ts)) - base_epoch:+.3f}s"
            # the overlays and sub-stages account for time inside the
            # pipeline stages: summing them would overstate the slot
            total = sum(stages[s] for s in flight.PIPELINE_STAGES)
            out.append(
                f"{seq:>6} {label:<28} {t0:>10} "
                + " ".join(f"{stages[s]:>12.3f}" for s in STAGES)
                + f" {total:>9.3f} {slot.get('path', '?')}")
    return out


def stage_table(dumps: List[Dict]) -> List[str]:
    """Percentiles per stage over every completed slot in the dumps
    (the dumps' retained `slots.recent` records plus ring folds)."""
    vals: Dict[str, List[float]] = {s: [] for s in STAGES}
    for d in dumps:
        recents = d.get("slots", {}).get("recent", [])
        seen = set()
        for rec in recents:
            seen.add((rec.get("rid"), rec.get("seq")))
            for s in STAGES:
                vals[s].append(rec["stages_ms"].get(s, 0.0))
        for (rid, seq), slot in fold_slots(d).items():
            if (rid, seq) in seen or "replied" not in slot:
                continue
            stages = flight.SlotTracker.fold(slot)
            for s in STAGES:
                vals[s].append(stages[s])
    out = ["stage histogram (ms over all completed slots)",
           f"{'stage':<12} {'count':>7} {'avg':>9} {'p50':>9} "
           f"{'p95':>9} {'max':>9}"]
    for s in STAGES:
        v = sorted(vals[s])
        n = len(v)
        if not n:
            out.append(f"{s:<12} {0:>7}")
            continue
        out.append(f"{s:<12} {n:>7} {sum(v) / n:>9.3f} {v[n // 2]:>9.3f} "
                   f"{v[min(n - 1, int(n * 0.95))]:>9.3f} {v[-1]:>9.3f}")
    return out


def kernel_table(dumps: List[Dict]) -> List[str]:
    out = ["kernel profile (ms; prep / gate wait / device are totals)",
           f"{'dump':<24} {'kind':<10} {'calls':>6} {'first(ms)':>10} "
           f"{'warm avg':>9} {'max':>9} {'batch avg':>10} {'prep':>9} "
           f"{'gate wait':>9} {'device':>9} {'breaker'}"]
    for d in dumps:
        base = os.path.basename(d["_path"])
        for kind, st in sorted(d.get("kernels", {}).items()):
            out.append(
                f"{base:<24} {kind:<10} {st['calls']:>6} "
                f"{st['first_call_ms']:>10.3f} {st['warm_avg_ms']:>9.3f} "
                f"{st['max_ms']:>9.3f} {st['batch_avg']:>10.1f} "
                f"{st.get('prep_ms', 0.0):>9.3f} "
                f"{st.get('gate_wait_ms', 0.0):>9.3f} "
                f"{st['total_ms']:>9.3f} "
                f"{st.get('breaker_states', {})}")
    return out


def span_table(dumps: List[Dict]) -> List[str]:
    """EV_SPAN rows by name (`flight.span` / `record_span`): the id in
    the event's view field resolves through the dump's `span_names`."""
    vals: Dict[str, List[float]] = {}
    for d in dumps:
        names = d.get("span_names", {})
        for ring in d.get("rings", []):
            for _t, code, _seq, view, arg in ring.get("events", []):
                if code == flight.EV_SPAN:
                    vals.setdefault(names.get(str(view), f"#{view}"),
                                    []).append(arg / 1e3)
    out = ["flight spans (ms)",
           f"{'span':<24} {'count':>7} {'avg':>9} {'p50':>9} {'max':>9}"]
    for name in sorted(vals):
        v = sorted(vals[name])
        out.append(f"{name:<24} {len(v):>7} {sum(v) / len(v):>9.3f} "
                   f"{v[len(v) // 2]:>9.3f} {v[-1]:>9.3f}")
    return out


def trace_table(dumps: List[Dict], limit: int = 20) -> List[str]:
    """Spans merged across dumps by trace id — the cross-replica
    request join (span epochs are wall-clock, directly comparable)."""
    traces: Dict[str, List[Tuple[str, Dict]]] = {}
    for d in dumps:
        base = os.path.basename(d["_path"])
        for sp in d.get("spans", []):
            traces.setdefault(sp["trace_id"], []).append((base, sp))
    out = [f"traces ({len(traces)} ids; newest {limit} shown)",
           f"{'trace':<20} {'spans':>6}  names"]
    for tid, sps in sorted(traces.items(),
                           key=lambda kv: max(s["epoch"]
                                              for _, s in kv[1]))[-limit:]:
        names = sorted({s["name"] for _, s in sps})
        out.append(f"{tid:<20} {len(sps):>6}  {','.join(names)}")
    return out


def render(paths: List[str], seq: Optional[int] = None,
           limit: int = 30) -> str:
    dumps = [load_dump(p) for p in paths]
    sections = [
        [f"tpuprof — {len(dumps)} dump(s): "
         + ", ".join(os.path.basename(p) for p in paths)],
        stage_table(dumps),
        timeline(dumps, seq_filter=seq, limit=limit),
        kernel_table(dumps),
        span_table(dumps),
        trace_table(dumps),
    ]
    return "\n\n".join("\n".join(s) for s in sections)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="merge flight-recorder dumps into slot timelines "
                    "and stage histograms")
    ap.add_argument("dumps", nargs="+", help="flight dump JSON files")
    ap.add_argument("--seq", type=int, default=None,
                    help="show only this consensus seqnum's timeline")
    ap.add_argument("--limit", type=int, default=30,
                    help="max seqs in the timeline (newest kept)")
    args = ap.parse_args(argv)
    print(render(args.dumps, seq=args.seq, limit=args.limit))
    return 0


if __name__ == "__main__":
    sys.exit(main())
