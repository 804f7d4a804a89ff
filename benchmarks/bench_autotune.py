"""Closed-loop autotuner A/B (ISSUE 14 acceptance): cold defaults vs
the controller vs the hand-benched static optimum.

Three legs over the bench_e2e profile shape (config 1, in-process
cluster, real ordered traffic):

  * ``static-cold`` — a deliberately UNBENCHED knob configuration: the
    kind of generic defaults a deployment on unknown hardware ships
    with (long flush windows sized for a device none may exist, batch
    caps sized for the wrong host, accumulation off). Autotuner off.
  * ``static-best`` — the repo's hand-benched defaults (the operating
    point earlier CPU-host rows were measured at).
    Autotuner off: this is the target the controller must reach.
  * ``autotune``   — the SAME cold knobs, autotuner on with a fast
    cadence. The controller must walk the knobs from the cold start
    toward this host's optimum from live telemetry alone.

The acceptance gate: ``autotune_over_best >= 0.9`` — from cold
defaults, the closed loop recovers at least 90% of the hand-benched
configuration's goodput. (On a noisy shared container the ratio is
REPORTED per run, measured in back-to-back pairs.)

Usage: python -m benchmarks.bench_autotune [--secs 12] [--clients 3]
           [--smoke]
Prints one JSON line per leg plus a summary line.
"""
from __future__ import annotations

import argparse
import json
from typing import Dict

from benchmarks.bench_e2e import run_config

# the "shipped for unknown hardware" cold start: every knob off the
# hand-benched point in the pessimal direction for THIS shape (long
# windows that buy latency with nothing to amortize, no coalescing)
COLD_KNOBS = {
    "verify_batch_flush_us": 2000,
    "verify_batch_size": 32,
    "combine_flush_us": 2500,
    "combine_batch_max": 4,
    "execution_max_accumulation": 1,
}

FAST_TUNER = {
    "autotune_enabled": True,
    "autotune_interval_ms": 100,
    "autotune_cooldown_ms": 250,
}


def _tuning_summary(row: Dict) -> Dict:
    """Fold the tuned leg's controller state (attached by run_config's
    profile hook while the cluster was live) into a compact shape."""
    knobs: Dict[str, Dict] = {}
    steps = flips = 0
    for state in row.pop("tuning_state", {}).values():
        if not isinstance(state, dict):
            continue
        for kname, k in state.get("knobs", {}).items():
            cur = knobs.setdefault(kname, {"values": [], "flips": 0})
            cur["values"].append(k["value"])
            cur["flips"] = max(cur["flips"], k["direction_flips"])
            flips = max(flips, k["direction_flips"])
        steps += sum(1 for d in state.get("decisions", [])
                     if d.get("source") == "policy")
    return {"knobs": knobs, "policy_steps": steps,
            "max_direction_flips": flips}


def run_ab(secs: float, clients: int, profile: bool = False) -> int:
    legs = (
        ("static-cold", {**COLD_KNOBS, "autotune_enabled": False}),
        ("static-best", {"autotune_enabled": False}),
        ("autotune", {**COLD_KNOBS, **FAST_TUNER}),
    )
    rows = {}
    for label, overrides in legs:
        from tpubft.crypto import tpu
        tpu.set_ecdsa_crossover(None)    # leg isolation: process-wide
        row = run_config(1, "cpu", secs, clients,
                         extra_overrides=overrides,
                         profile=profile or label == "autotune")
        row["leg"] = label
        if label == "autotune":
            row["tuning"] = _tuning_summary(row)
            if not profile:
                row.pop("stage_breakdown", None)
                row.pop("kernel_profile", None)
        rows[label] = row
        print(json.dumps(row), flush=True)
    best = rows["static-best"]["ops_per_sec"] or 1.0
    summary = {
        "bench": "autotune_ab", "secs": secs, "clients": clients,
        "cold_ops_per_sec": rows["static-cold"]["ops_per_sec"],
        "best_ops_per_sec": rows["static-best"]["ops_per_sec"],
        "autotune_ops_per_sec": rows["autotune"]["ops_per_sec"],
        "autotune_over_best": round(
            rows["autotune"]["ops_per_sec"] / best, 2),
        "autotune_over_cold": round(
            rows["autotune"]["ops_per_sec"]
            / (rows["static-cold"]["ops_per_sec"] or 1.0), 2),
        "gate_0p9": rows["autotune"]["ops_per_sec"] >= 0.9 * best,
    }
    print(json.dumps(summary), flush=True)
    return 0


def smoke() -> Dict:
    """Tier-1 shape (run under TPUBFT_THREADCHECK=1 by
    tests/test_bench_autotune_smoke.py): every leg orders real traffic,
    the tuned leg's controllers run at full cadence against the live
    cluster, knobs stay in bounds, and nothing oscillates. Timing
    gates stay out of tier-1 (host noise)."""
    from tpubft.utils.racecheck import get_watchdog
    out = {}
    for label, overrides in (
            ("cold", {**COLD_KNOBS, "autotune_enabled": False}),
            ("autotune", {**COLD_KNOBS, **FAST_TUNER,
                          "autotune_interval_ms": 50,
                          "autotune_cooldown_ms": 100})):
        row = run_config(1, "cpu", 2.0, 2, extra_overrides=overrides)
        out[label] = {"ok": row["ops"] > 0, "ops": row["ops"],
                      "ops_per_sec": row["ops_per_sec"]}
    out["stall_reports"] = get_watchdog().stall_reports
    return out


def main(argv=None) -> int:
    from tpubft.utils.jaxcache import setup_cache
    setup_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--secs", type=float, default=12.0,
                    help="measurement window per leg")
    ap.add_argument("--clients", type=int, default=3)
    ap.add_argument("--profile", action="store_true",
                    help="attach stage breakdown + kernel profile per leg")
    ap.add_argument("--smoke", action="store_true",
                    help="tier-1 shape: short legs, liveness gates only")
    args = ap.parse_args(argv)
    if args.smoke:
        print(json.dumps(smoke()), flush=True)
        return 0
    return run_ab(args.secs, args.clients, profile=args.profile)


if __name__ == "__main__":
    raise SystemExit(main())
