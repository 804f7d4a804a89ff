"""Each per-layer reader on a context worked by hand: what it reads,
and that it returns nothing — not 0 — where there is nothing to read."""
import pytest

from cellbench import harness


def read(metric, ctx):
    return harness.load_by_name("layer_metrics", metric).read(ctx)


class _Cell:
    config = {"cluster": {"n": 4}}


def served_ctx():
    before = {"sigs_device_dispatched": 100, "batched_verifies": 100,
              "scalar_fallbacks": 50, "slots_finalized": 40,
              "kernels": {"ed25519": (2, 128)}}
    after = {"sigs_device_dispatched": 740, "batched_verifies": 740,
             "scalar_fallbacks": 410, "slots_finalized": 160,
             "kernels": {"ed25519": (12, 768)}}
    slots = [{"stages_ms": {"commit": c, "exec": e}}
             for c, e in ((10, 1000), (30, 3000), (20, 2000))]
    return dict(before=before, after=after, traced_from=before, slots=slots,
                writes_acked=600,
                window_s=48.0, cell=_Cell(), device_kind="TPU v5 lite",
                trace={"idle_pct": 99.5, "kernels": {
                    "ed25519": {"calls": 10, "device_s": 0.022}}})


def flood_ctx():
    return dict(
        before={"kernels": {"ed25519": (2, 2000), "bls_msm": (2, 2048)}},
        traced_from={"kernels": {"ed25519": (68, 68000),
                                 "bls_msm": (68, 69632)}},
        after={"kernels": {"ed25519": (70, 70000), "bls_msm": (70, 71680)}},
        slots=[{"verify_ms": v, "combine_ms": c}
               for v, c in ((70, 500), (72, 580), (90, 560))],
        points_per_combine=667, device_kind="TPU v5 lite",
        trace={"idle_pct": 67.0, "kernels": {
            "ed25519": {"calls": 2, "device_s": 0.0044},
            "bls_msm": {"calls": 2, "device_s": 0.425}}})


@pytest.mark.parametrize("metric,want", [
    ("verify_device_share", 100 * 640 / (640 + 360)),
    ("verify_batch_mean", 64.0),
    ("slot_commit_ms", 20),
    ("slot_exec_ms", 2000),
    ("reqs_per_slot", 600 / 30),
    ("window_writes_per_s", 12.5),
    ("device_idle_pct.skvbc", 99.5),
    ("ed25519_roofline.skvbc",
     100 * (640 * 4271 * 2048 / 393e12) / 0.022),
])
def test_served_readers(metric, want):
    assert read(metric, served_ctx()) == pytest.approx(want)


@pytest.mark.parametrize("metric,want", [
    ("flood_verify_ms", 72),
    ("flood_combine_ms", 560),
    ("device_idle_pct.flood", 67.0),
    ("ed25519_roofline.flood",
     100 * (2000 * 4271 * 2048 / 393e12) / 0.0044),
    # 667 points a call, whatever lanes the program padded them to
    ("msm_roofline.flood",
     100 * ((2 * 667 * 3203.5 + 2 * 573) * 4608 / 393e12) / 0.425),
])
def test_flood_readers(metric, want):
    got = read(metric, flood_ctx())
    assert got == pytest.approx(want)
    if "roofline" in metric:
        assert 0 < got < 100


@pytest.mark.parametrize("metric", [m["name"] for m in
                                    harness.load_manifest()["per_layer"]])
def test_nothing_to_read_returns_nothing(metric):
    counters = {"sigs_device_dispatched": 5, "batched_verifies": 5,
                "scalar_fallbacks": 5, "slots_finalized": 5, "kernels": {}}
    empty = dict(before=counters, after=counters, traced_from=counters, slots=[],
                 writes_acked=0, window_s=48.0, cell=_Cell(), device_kind="TPU v5 lite",
                 points_per_combine=667,
                 trace={"idle_pct": None, "kernels": {}})
    assert read(metric, empty) is None
