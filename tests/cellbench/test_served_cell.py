"""The `served` driver through a whole run at a tiny size on XLA-CPU:
the run's object as the contract has it, and `correct` coming out false
under the control and under each fault the cell can have. The look for
a chip is the one thing skipped (`require_tpu=False`); main() keeps it.
"""
import pytest

from cellbench import control, harness, run

SECONDS = 4
SOUND_SECONDS = 8      # room for a whole round when six workers share the CPU


def tiny_cell():
    cell = harness.Cell("skvbc_n4.mixed_c64_bulk1")
    cell.traffic["classes"]["interactive"]["clients"] = 3
    cell.traffic["classes"]["bulk"]["writes_per_message"] = 32
    # the first run of this process lowers and compiles the kernel ahead
    # of time, as every run on the chip does; the later ones find it in
    # the process and skip ten seconds of tracing each
    cell.workload["programs"] = ({} if _warmed
                                 else {"ed25519_batches": [32]})
    _warmed.append(True)
    cell.workload["warmup_s"] = 1
    cell.workload["settle_quiet_s"] = 1
    return cell


_warmed = []


@pytest.mark.parametrize("trace", [0, 1])
def test_a_sound_run_is_correct_and_complete(one_chip_plane, trace):
    cell = tiny_cell()
    r = run.run_cell(cell, 2_900_000_021 + trace, SOUND_SECONDS, bool(trace),
                     require_tpu=False)
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "compared"
    assert r["correct"] is True, r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 0
    want = cell.per_layer() if trace else cell.end_to_end()
    got = r["metrics"]
    if trace:
        # no device plane on XLA-CPU: the trace's metrics stay out of
        # the line instead of reading 0
        assert not [n for n in got if "roofline" in n or "idle" in n]
        assert {"verify_device_share", "verify_batch_mean", "slot_exec_ms",
                "slot_commit_ms", "reqs_per_slot",
                "window_writes_per_s"} <= set(got)
        assert {"busy_s", "window_s"} <= set(r["device"])
        assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(got) == {m["name"] for m in want}
        assert all(v["value"] > 0 for v in got.values())
    units = {m["name"]: m["unit"] for m in want}
    assert all(v["unit"] == units[n] for n, v in got.items())
    assert all(v["limit"] == 0 for v in r["compared"].values())


@pytest.mark.parametrize("plant,must_fail", [
    ("control.replica_skips_writes", "ledgers_divergent"),
    ("fault.state_unchanged", "acked_writes_without_block"),
    ("fault.half_batch", "reads_wrong"),
    ("fault.answer_altered", "reads_wrong"),
])
def test_a_broken_timed_path_is_not_correct(one_chip_plane, plant,
                                            must_fail):
    with control.planted("served", plant):
        r = run.run_cell(tiny_cell(), 2_900_000_031, SECONDS, False,
                         require_tpu=False)
    assert r["correct"] is False
    assert r["compared"][must_fail]["value"] > 0, r["compared"]
