"""The reduction from a trace to device numbers, on a small trace
recorded on the chip (data/trace_small.json) and on hand-made ones."""
import json
import os

import pytest

from cellbench import trace

HERE = os.path.dirname(os.path.abspath(__file__))
KERNELS = [{"kind": "ed25519", "line": "XLA Modules",
            "pattern": "verify_kernel"}]


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "trace_small.json")) as fh:
        return json.load(fh)


def test_recorded_trace_reduces_to_its_own_sums(recorded):
    out = trace.reduce(recorded)
    plane = recorded["device"]["/device:TPU:0"]
    modules = plane["XLA Modules"]
    assert out["kernels"]["ed25519"]["calls"] == len(modules) == 3
    assert out["kernels"]["ed25519"]["device_s"] == pytest.approx(
        sum(d for _, _, d in modules) / 1e9)
    assert out["kernels"]["bls_msm"] == {"calls": 0, "device_s": 0.0}
    # ops do not overlap on one core: busy is their sum, and inside the
    # modules' time
    ops = sum(d for _, _, d in plane["XLA Ops"]) / 1e9
    assert out["busy_s"] == pytest.approx(ops, rel=1e-6)
    assert out["busy_s"] <= out["kernels"]["ed25519"]["device_s"]
    marks = {n: s for n, s, _ in recorded["spans"]}
    assert out["window_s"] == pytest.approx(
        (marks["cellbench:trace_close"] - marks["cellbench:trace_open"])
        / 1e9)
    assert out["idle_pct"] == pytest.approx(
        100 * (1 - out["busy_s"] / out["window_s"]))
    top = out["breakdown"]["device_ops"][0]
    assert top[0] == "%verify_kernel.1 custom-call" and top[1] > 0.006
    assert len(out["breakdown"]["idle_gaps"]) <= 10
    assert out["breakdown"]["idle_gaps"][0][1] >= 1.0   # before the first


def _hand(ops, spans=(), extra=None):
    lines = {"XLA Ops": ops, "XLA Modules": []}
    lines.update(extra or {})
    return {"device": {"/device:TPU:0": lines}, "spans": list(spans)}


def test_busy_is_a_union_and_gaps_are_named_by_the_covering_span():
    data = _hand(
        [["%a = f32[] add(x)", 100, 50], ["%b = f32[] add(x)", 120, 80],
         ["%c = f32[] mul(x)", 500, 100]],
        [["cellbench:trace_open", 0, 1], ["cellbench:trace_close", 1000, 1],
         ["cellbench:verify_batch", 0, 90],
         ["cellbench:accumulate_combine_verify", 180, 400]])
    out = trace.reduce(data, kernels=[])
    assert out["busy_s"] == pytest.approx(200e-9)      # [100,200]+[500,600]
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["idle_pct"] == pytest.approx(80.0)
    gaps = out["breakdown"]["idle_gaps"]
    assert gaps[0] == ["no benchmark span", pytest.approx(400e-9)]
    assert gaps[1] == ["accumulate_combine_verify", pytest.approx(300e-9)]
    assert gaps[2] == ["verify_batch", pytest.approx(100e-9)]
    assert out["breakdown"]["device_ops"][:2] == [
        ["%c mul", pytest.approx(100e-9)], ["%b add", pytest.approx(80e-9)]]


def test_a_full_trace_buffer_ends_the_window_where_the_record_ends():
    data = _hand(
        [["%a = f32[] add(x)", 100, 100]],
        [["cellbench:trace_open", 0, 1], ["cellbench:trace_close", 1000, 1]],
        extra={"XLA TraceMe": [["Trace Buffers Dropped", 400, 600]]})
    out = trace.reduce(data, kernels=[])
    assert out["events_dropped"] is True
    assert out["window_s"] == pytest.approx(400e-9)
    assert out["idle_pct"] == pytest.approx(75.0)


def test_no_device_plane_gives_nothing_to_read():
    out = trace.reduce({"device": {}, "spans": [
        ["cellbench:trace_open", 0, 1], ["cellbench:trace_close", 10, 1]]})
    assert out["busy_s"] == 0.0 and out["idle_pct"] is None
    assert trace.reduce({"device": {}, "spans": []})["window_s"] == 0.0


def test_kernels_are_matched_by_the_patterns_written_down():
    names = {k["kind"]: k for k in trace._kernel_files()}
    assert set(names) >= {"ed25519", "bls_msm"}
    data = _hand([], extra={"XLA Modules": [
        ["jit_verify_kernel(3872916200508191001)", 10, 5],
        ["jit_msm_kernel(9204335798406615472)", 20, 7]]})
    out = trace.reduce(data)
    assert out["kernels"]["ed25519"] == {"calls": 1, "device_s": 5e-9}
    assert out["kernels"]["bls_msm"] == {"calls": 1, "device_s": 7e-9}
