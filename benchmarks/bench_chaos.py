"""Chaos-campaign runner: seeded fault schedules over the live stack.

Runs the scenario matrix from tpubft/testing/campaign.py and prints ONE
JSON line (the repo's bench convention):

  {"metric": "chaos-scenarios-passed", "value": K, "unit": "scenarios",
   "seed": S, "event_log_digest": "...", ...}

plus writes the full campaign artifact (seed, event log + digest,
per-scenario verdicts, recovery-time stats) to CHAOS_r0N.json at the
repo root (next free round number) or to --out.

Determinism contract: the event-log digest is a pure function of
(seed, matrix) — `--replay-check` runs the campaign twice and fails
loudly if the digests differ, which is the property that makes a red
seed attachable to a bug report.

Usage:
  python -m benchmarks.bench_chaos [--seed N] [--smoke | --full]
      [--scenario NAME ...] [--out PATH] [--replay-check] [--keep-tmp]

--smoke runs the in-process matrix only (seconds; wired into tier-1 via
tests/test_chaos_campaign.py); the default/--full matrix adds the
real-subprocess scenarios (BftTestNetwork: SIGSTOP partitions, SIGKILL
crashes, env-triggered crashpoints).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _next_artifact_path() -> str:
    n = 1
    while os.path.exists(os.path.join(_REPO_ROOT, "CHAOS_r%02d.json" % n)):
        n += 1
    return os.path.join(_REPO_ROOT, "CHAOS_r%02d.json" % n)


def run_campaign(seed: int, specs, keep_tmp: bool = False) -> dict:
    from tpubft.testing.campaign import ChaosCampaign
    return ChaosCampaign(seed=seed, specs=specs, keep_tmp=keep_tmp).run()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="seeded chaos campaign")
    p.add_argument("--seed", type=int, default=None,
                   help="campaign seed (default: campaign.DEFAULT_SEED)")
    depth = p.add_mutually_exclusive_group()
    depth.add_argument("--smoke", action="store_true",
                       help="in-process matrix only (tier-1 shape)")
    depth.add_argument("--full", action="store_true",
                       help="the full matrix (the default)")
    p.add_argument("--scenario", action="append", default=[],
                   help="run only the named scenario(s); repeatable")
    p.add_argument("--out", default=None,
                   help="artifact path (default: CHAOS_r0N.json, next N)")
    p.add_argument("--no-artifact", action="store_true",
                   help="print the JSON line only")
    p.add_argument("--replay-check", action="store_true",
                   help="run twice, fail unless event-log digests match")
    p.add_argument("--keep-tmp", action="store_true")
    p.add_argument("--list", action="store_true",
                   help="list scenario names and exit")
    args = p.parse_args(argv)

    # the CPU jax backend, chosen before anything imports the ops plane:
    # chaos campaigns measure recovery, never kernels
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from tpubft.utils.jaxcache import setup_cache
    setup_cache()

    from tpubft.testing import campaign as cmp
    seed = args.seed if args.seed is not None else cmp.DEFAULT_SEED
    if args.list:
        for s in cmp.full_matrix():
            print(f"{s.name:40s} {s.kind:8s} budget={s.time_budget_s:.0f}s"
                  f" tags={','.join(s.tags)}")
        return 0
    if args.scenario:
        by_name = cmp.matrix_by_name()
        missing = [n for n in args.scenario if n not in by_name]
        if missing:
            print(f"unknown scenario(s): {missing}; have "
                  f"{sorted(by_name)}", file=sys.stderr)
            return 2
        specs = [by_name[n] for n in args.scenario]
    elif args.smoke:
        specs = cmp.smoke_matrix()
    else:
        specs = cmp.full_matrix()

    artifact = run_campaign(seed, specs, keep_tmp=args.keep_tmp)
    if args.replay_check:
        second = run_campaign(seed, specs, keep_tmp=args.keep_tmp)
        match = (artifact["event_log_digest"]
                 == second["event_log_digest"])
        # verdicts live OUTSIDE the digest, so a scenario that fails
        # only on the replay pass (a nondeterministic recovery bug
        # under the identical schedule — the thing this mode exists to
        # surface) must fail the run in its own right
        second_failed = [s["name"] for s in second["scenarios"]
                         if not s["ok"]]
        artifact["replay_check"] = {
            "match": match,
            "second_digest": second["event_log_digest"],
            "second_failed": second_failed}
        if not match:
            print("REPLAY DETERMINISM BROKEN: digests differ "
                  f"({artifact['event_log_digest']} vs "
                  f"{second['event_log_digest']})", file=sys.stderr)
        if second_failed:
            print(f"replay pass went red: {second_failed} failed under "
                  f"the identical schedule", file=sys.stderr)

    out_path = None
    if not args.no_artifact:
        out_path = args.out or _next_artifact_path()
        with open(out_path, "w") as fh:
            json.dump(artifact, fh, indent=1)
        artifact_note = {"artifact": out_path}
    else:
        artifact_note = {}

    record = {
        "metric": "chaos-scenarios-passed (of %d)"
                  % len(artifact["scenarios"]),
        "value": artifact["passed"],
        "unit": "scenarios",
        "seed": artifact["seed"],
        "event_log_digest": artifact["event_log_digest"],
        "failed": [s["name"] for s in artifact["scenarios"]
                   if not s["ok"]],
        **artifact_note,
    }
    if artifact.get("degraded"):
        record["degraded"] = True
        record["probe_error"] = artifact["probe_error"]
    if args.replay_check:
        record["replay_match"] = artifact["replay_check"]["match"]
        if artifact["replay_check"]["second_failed"]:
            record["replay_failed"] = \
                artifact["replay_check"]["second_failed"]
    print(json.dumps(record))
    ok = (artifact["failed"] == 0
          and (not args.replay_check
               or (artifact["replay_check"]["match"]
                   and not artifact["replay_check"]["second_failed"])))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
