"""work.py against counts worked by hand, and the rooflines' guard."""
import pytest

from cellbench import roofline, work


def test_ed25519_counts():
    # 270 + 253*8 + 190*9 + 267 field multiplications, 1024 byte
    # products each, two operations a product
    assert work.ED25519_FIELD_MULTS == 4271
    w = work.ed25519_verify(1000)
    assert w["ops"] == 1000 * 4271 * 1024 * 2 == 8_747_008_000
    assert w["bytes"] == 1000 * 129


def test_msm_counts():
    assert work.MSM_FIELD_MULTS_PER_POINT == 3203.5
    w = work.bls12_381_g1_msm(667, calls=1)
    assert w["ops"] == (667 * 3203.5 + 573) * 2304 * 2
    assert w["bytes"] == 667 * 128 + 96


def test_least_seconds_names_its_bound():
    peak = roofline.peak_of("TPU v5 lite")
    assert peak["int8_ops_per_s"] == 393e12
    assert peak["hbm_bytes_per_s"] == 819e9
    t = work.least_seconds(work.ed25519_verify(1000), peak)
    assert t["bound"] == "compute"
    assert t["seconds"] == pytest.approx(8_747_008_000 / 393e12)
    t = work.least_seconds({"ops": 1, "bytes": 819e9}, peak)
    assert t["bound"] == "memory" and t["seconds"] == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peak_of("cpu")


def _ctx(calls_seen, device_s):
    return {"trace": {"kernels": {"ed25519": {"calls": calls_seen,
                                              "device_s": device_s}}},
            "traced_from": {"kernels": {"ed25519": (2, 100)}},
            "after": {"kernels": {"ed25519": (6, 2100)}},
            "device_kind": "TPU v5 lite"}


def test_roofline_share_reads_trace_time_and_program_items():
    # 4 calls of 500 items in 0.01 s of device time
    got = roofline.share(_ctx(4, 0.01), "ed25519")
    want = 100 * (2000 * 4271 * 2048 / 393e12) / 0.01
    assert got == pytest.approx(want)
    assert 0 < got < 100


def test_roofline_with_nothing_to_read_is_none_not_zero():
    assert roofline.share(_ctx(0, 0.0), "ed25519") is None
    ctx = _ctx(4, 0.01)
    ctx["after"] = ctx["traced_from"]
    assert roofline.share(ctx, "ed25519") is None
