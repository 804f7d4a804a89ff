"""Tier-1 wiring for benchmarks/bench_combine.py (--smoke shape): the
fused combine plane's microbench must produce well-formed rows whose
fused and per-slot verdicts are identical (byte-level combined
signatures included), and the crossover row must carry both schemes'
costs plus the certificate-size tradeoff. Timing ASSERTIONS stay out of
tier-1 (host noise)."""
import json

from benchmarks.bench_combine import crossover_row, main, sweep_row


def test_sweep_row_shape_and_verdict_equivalence():
    row = sweep_row("threshold-bls", 4, 3, 4, "cpu", 0.05)
    assert row["verdicts_match"], row
    assert row["fused_combines_per_sec"] > 0
    assert row["per_slot_combines_per_sec"] > 0
    assert row["in_flight_slots"] == 4 and row["k"] == 3
    ms = sweep_row("multisig-ed25519", 4, 3, 2, "cpu", 0.05)
    assert ms["verdicts_match"], ms


def test_crossover_row_carries_both_schemes():
    row = crossover_row(4, 3, 4, "cpu", 0.05)
    assert row["winner"] in ("multisig-ed25519", "threshold-bls")
    assert row["multisig_us_per_combine"] > 0
    assert row["bls_us_per_combine"] > 0
    # the size tradeoff the adaptive scheme trades away at small n
    assert row["bls_cert_bytes"] == 48
    assert row["multisig_cert_bytes"] == 2 + 66 * 3


def test_bench_combine_smoke_cli(capsys):
    assert main(["--smoke"]) == 0
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert len(lines) == 3
    benches = {ln["bench"] for ln in lines}
    assert benches == {"combine_sweep", "scheme_crossover"}
    assert all(ln.get("verdicts_match", True) for ln in lines)
