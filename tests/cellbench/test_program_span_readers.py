"""Each `program_span` reader on a context worked by hand, and nothing
— not 0 — where there is nothing to read: no slot or call in the
window, a program without the stage, row or span (the parent commit),
or a store that wrapped inside the window. The slot readers read
`ctx["slots"]`; the call-row and ring readers read the live recorder,
so the tests write into it what a run would."""
import time

import pytest

from cellbench import harness
from tpubft.utils import flight


def read(metric, ctx):
    return harness.load_by_name("layer_metrics", metric).read(ctx)


def served_slots():
    """Two big slots and three of one request, as the cell forms them,
    on a primary and a backup: 66 requests on the primary's rows."""
    def row(primary, reqs, order, adm, disp, wait, run, dur, reply):
        return {"primary": primary, "reqs": reqs, "stages_ms": dict(
            order_wait=order, adm_wait=adm, dispatch=disp, exec_wait=wait,
            exec_run=run, exec=wait + run, dur_wait=dur, reply=reply,
            commit=20)}
    return [row(True, 32, 3400, 0, 0, 100, 4200, 90, 104),
            row(True, 1, 5, 0, 0, 3900, 160, 8, 11),
            row(True, 31, 3100, 0, 0, 130, 4000, 80, 99),
            row(True, 1, 2, 0, 0, 3700, 170, 9, 12),
            row(True, 1, 9, 0, 0, 3600, 150, 7, 10),
            row(False, 32, 0, 4, 6, 900, 4300, 170, 190),
            row(False, 1, 0, 1, 1, 4100, 180, 20, 25)]


@pytest.mark.parametrize("metric,want", [
    # the 33rd and 34th of the primary's 66 requests, by their wait:
    # 3 in the small slots, then 31 at 3,100, then 32 at 3,400
    ("slot_order_wait_ms", 3100),
    ("slot_admit_ms", 0),               # 66 requests at 0, 32 at 10, 1 at 2
    ("slot_exec_wait_ms", 130),         # 99 requests: the 50th
    ("slot_exec_run_ms", 4200),
    ("slot_dur_wait_ms", 90),
    ("slot_reply_ms", 104),
])
def test_slot_stage_readers_weight_each_slot_by_its_requests(metric, want):
    slots = served_slots()
    assert read(metric, {"slots": slots}) == want
    # the median over slots would read the one-request slots instead
    if metric == "slot_order_wait_ms":
        import statistics
        assert statistics.median(
            s["stages_ms"]["order_wait"] for s in slots if s["primary"]) == 9
    # an empty (wedge-fill) slot carries no request and counts for nothing
    slots.append({"primary": True, "reqs": 0, "stages_ms": dict(
        slots[0]["stages_ms"], order_wait=9e9, exec_wait=9e9, exec_run=9e9,
        dur_wait=9e9, reply=9e9, adm_wait=9e9)})
    assert read(metric, {"slots": slots}) == want


@pytest.mark.parametrize("metric", [
    "slot_order_wait_ms", "slot_exec_wait_ms", "slot_exec_run_ms",
    "slot_dur_wait_ms"])
def test_slot_readers_find_nothing_in_the_parent_s_rows(metric):
    """PR 24's program folds six stages and marks no primary."""
    parent = [{"stages_ms": {"adm_wait": 1, "dispatch": 2, "prepare": 3,
                             "commit": 4, "exec": 5, "reply": 6,
                             "spec_overlap": 0, "cert_lag": 0}}]
    assert read(metric, {"slots": parent}) is None
    assert read("slot_admit_ms", {"slots": parent}) == 3
    assert read("slot_reply_ms", {"slots": parent}) == 6


def book(kind, prep_us, wait_us, device_us, batch=1):
    return flight.kernel_profiler().record(
        kind, batch, int(device_us * 1e3), "closed",
        gate_wait_ns=int(wait_us * 1e3), prep_ns=int(prep_us * 1e3))


def counts():
    return {"kernels": harness.kernel_profile()}


@pytest.fixture
def clean_recorder():
    flight.reset()
    yield
    flight.reset()


def test_call_row_readers_cut_the_window_by_ordinal(clean_recorder):
    book("ed25519", 9e6, 9e6, 9e6)          # set-up: before the window
    book("bls_msm", 9e6, 9e6, 9e6)
    before = counts()
    for prep, wait, dev in ((52_000, 10, 2_300), (58_000, 30, 2_200),
                            (61_000, 20, 2_400)):
        book("ed25519", prep, wait, dev, batch=1000)
    for prep, wait, dev in ((290_000, 5, 255_000), (310_000, 7, 251_000)):
        book("bls_msm", prep, wait, dev, batch=1024)
    after = counts()
    book("ed25519", 9e6, 9e6, 9e6)          # the drain: after it
    ctx = dict(before=before, after=after)
    assert read("flood_verify_prep_ms", ctx) == pytest.approx(58.0)
    assert read("flood_combine_prep_ms", ctx) == pytest.approx(300.0)
    assert read("flood_msm_section_ms", ctx) == pytest.approx(253.0)
    # every kind's calls: waits 10, 30, 20, 5, 7 us
    assert read("device_gate_wait_ms.skvbc", ctx) == pytest.approx(0.010)
    # a window with no call of the kind
    assert read("flood_combine_prep_ms", dict(before=after, after=after)) \
        is None


def test_call_row_readers_refuse_a_store_that_wrapped(clean_recorder):
    before = counts()
    for _ in range(flight.KernelProfiler.CALL_ROWS + 1):
        book("ed25519", 10, 10, 10)
    ctx = dict(before=before, after=counts())
    assert read("flood_verify_prep_ms", ctx) is None
    assert read("device_gate_wait_ms.skvbc", ctx) is None


def flood_window(spans_by_slot):
    """Slots as the flood driver rows them, each writing its ring
    spans before its `done`; one span before the window and one after."""
    flight.record_span("bls_share_decompress", 9_000_000)
    flight.record_span("bls_pairing_verify", 9_000_000)
    time.sleep(0.002)
    slots = []
    for decompress_us, pairing_us in spans_by_slot:
        t0 = time.monotonic()
        flight.record_span("bls_share_decompress", decompress_us)
        flight.record_span("bls_pairing_verify", pairing_us)
        time.sleep(0.001)
        t2 = time.monotonic()
        slots.append(dict(done=t2, verify_ms=(t2 - t0) * 400,
                          combine_ms=(t2 - t0) * 600))
    time.sleep(0.002)
    flight.record_span("bls_pairing_verify", 9_000_000)
    return slots


def test_ring_span_readers_cut_the_window_by_the_slots_clock(
        clean_recorder):
    slots = flood_window([(120_000, 21_000), (131_000, 25_000),
                          (125_000, 19_000)])
    assert read("flood_share_decompress_ms", {"slots": slots}) \
        == pytest.approx(125.0)
    assert read("flood_pairing_ms", {"slots": slots}) \
        == pytest.approx(21.0)
    # a ring that wrapped inside the window says nothing
    for i in range(flight.RING_SIZE):
        flight.record(flight.EV_ADM_INGEST, arg=i)
    flight.record_span("bls_pairing_verify", 1)
    assert read("flood_pairing_ms", {"slots": slots}) is None


def test_ring_span_readers_find_nothing_without_spans(clean_recorder):
    t = time.monotonic()
    slots = [dict(done=t, verify_ms=60.0, combine_ms=570.0)]
    assert read("flood_share_decompress_ms", {"slots": slots}) is None
    assert read("flood_pairing_ms", {"slots": []}) is None


def test_readers_find_nothing_in_a_program_without_the_seams(
        clean_recorder, monkeypatch):
    """The benchmark's files are laid over the parent's checkout too:
    its recorder has no call rows and no `span_events`."""
    book("ed25519", 10, 10, 10)
    ctx = dict(before={"kernels": {}}, after=counts(),
               slots=[dict(done=time.monotonic(), verify_ms=1.0,
                           combine_ms=1.0)])
    monkeypatch.delattr(flight.KernelProfiler, "call_rows")
    monkeypatch.delattr(flight, "span_events")
    for metric in ("flood_verify_prep_ms", "flood_combine_prep_ms",
                   "flood_msm_section_ms", "device_gate_wait_ms.skvbc",
                   "flood_share_decompress_ms", "flood_pairing_ms"):
        assert read(metric, ctx) is None
