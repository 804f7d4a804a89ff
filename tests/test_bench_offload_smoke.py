"""Tier-1 wiring for benchmarks/bench_offload.py (--smoke shape): the
offload tier's bench must produce well-formed rows whose leased and
local verdicts are byte-identical, whose kill drill holds liveness
without quarantining the crashed (merely sick) helper, and whose lying
drill catches the Byzantine helper on its first lying lease. Timing
ASSERTIONS stay out of tier-1 (host noise)."""
import json

from benchmarks.bench_offload import main


def test_bench_offload_smoke_cli(capsys):
    assert main(["--smoke"]) == 0
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert len(lines) == 4
    by_bench = {ln["bench"]: ln for ln in lines}
    assert set(by_bench) == {"offload_ab", "offload_soundness",
                             "offload_helper_kill",
                             "offload_lying_helper"}
    ab = by_bench["offload_ab"]
    assert ab["verdicts_match"]
    assert ab["leases_verified"] > 0 and ab["leases_rejected"] == 0
    assert ab["soundness_us_per_lease"] > 0
    kill = by_bench["offload_helper_kill"]
    assert kill["liveness_held"] and kill["verdicts_match"]
    assert kill["quarantined"] == []        # crash = sick, never evicted
    lie = by_bench["offload_lying_helper"]
    assert lie["caught_on_first_lie"] and lie["verdicts_match"]
    assert lie["quarantined"] == ["bench-liar"]
    # the device-on-XLA-CPU convention: rows are plumbing validation
    for row in lines:
        if row.get("platform") == "cpu":
            assert row["degraded"] and "probe_error" in row
