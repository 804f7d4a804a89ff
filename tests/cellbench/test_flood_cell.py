"""The `flood` driver through a whole run at a tiny size on XLA-CPU, and
`correct` coming out false under the control and each fault the cell
can have (it keeps no state and crosses no chips, so: half of the batch
left out, and an answer altered). The profiler on XLA-CPU records every
thunk of the MSM, which takes a traced slot past any test's patience:
the traced path is rehearsed in test_served_cell.py, and the flood's
readers in test_layer_metrics.py."""
import pytest

from cellbench import control, harness, run

SECONDS = 3


def tiny_cell():
    cell = harness.Cell("flood_n1000.slots")
    cell.traffic.update(principals=40, messages_per_slot=40, signers=8,
                        threshold=5, shares_per_slot=5, digests=2,
                        forged=2, truncated=1, duplicates=1)
    cell.config["cluster"] = {"n": 40, "f": 13, "c": 0}
    # ahead-of-time lowering in the first run of the process only (see
    # test_served_cell.py)
    cell.workload.update(programs=({} if _warmed else
                                   {"ed25519_batches": [40],
                                    "msm_points": [5]}),
                         warmup_slots=1, check_slots=4, trace_window_s=1)
    _warmed.append(True)
    return cell


_warmed = []


@pytest.fixture
def device_msm(one_chip_plane, monkeypatch):
    """k=5 is under the MSM crossover (128): force the device combine,
    which at the cell's k=667 is the default."""
    monkeypatch.setenv("TPUBFT_MSM_CROSSOVER_K", "1")


def test_a_sound_run_reports_its_end_to_end_metrics(device_msm):
    r = run.run_cell(tiny_cell(), 2_900_000_042, SECONDS, False,
                     require_tpu=False)
    assert r["correct"] is True, r["compared"]
    assert set(r["metrics"]) == {"flood_sigs_per_s", "setup_s"}
    assert r["metrics"]["flood_sigs_per_s"]["value"] > 0
    assert r["attempted"] >= 45 and r["failed"] == 0
    assert list(r)[-1] == "compared"
    assert all(v["limit"] == 0 for v in r["compared"].values())


@pytest.mark.parametrize("plant,must_fail", [
    ("control.share_dropped", "certificate_mismatches"),
    ("fault.half_batch", "verdict_mismatches"),
    ("fault.answer_altered", "verdict_mismatches"),
])
def test_a_broken_timed_path_is_not_correct(device_msm, plant, must_fail):
    with control.planted("flood", plant):
        r = run.run_cell(tiny_cell(), 2_900_000_043, SECONDS, False,
                         require_tpu=False)
    assert r["correct"] is False
    assert r["compared"][must_fail]["value"] > 0, r["compared"]
