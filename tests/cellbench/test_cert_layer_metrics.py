"""The readers this cell's certificate path brought — the commit path's
share and the four `cert_*_ms` spans — on a context worked by hand: a
number on a program that writes the spans and the `path` field, and
nothing — not 0 — on rows and rings without them (the parent commit).
The `served_bls` driver reads the recorder's rings as the window
closes, so the tests write into them what a run would and take the
driver's own reading."""
import time

import pytest

from cellbench import harness, served_spans
from tpubft.utils import flight

SPANS = {"cert_share_sign_ms": "share_sign",
         "cert_share_decompress_ms": "bls_share_decompress",
         "cert_combine_ms": "bls_combine",
         "cert_verify_ms": "bls_pairing_verify"}


def read(metric, ctx):
    return harness.load_by_name("layer_metrics", metric).read(ctx)


def test_fast_path_share_counts_rows_that_name_a_path():
    rows = [{"path": "fast"}] * 3 + [{"path": "slow"}] + [{"path": "?"}]
    assert read("slot_fast_path_pct", {"slots": rows}) == 75.0
    assert read("slot_fast_path_pct", {"slots": [{"path": "slow"}]}) == 0.0
    # a program whose rows carry no path, and no row at all
    assert read("slot_fast_path_pct", {"slots": [{"reqs": 3}]}) is None
    assert read("slot_fast_path_pct", {"slots": []}) is None


@pytest.fixture
def recorder():
    flight.reset()
    yield
    flight.reset()


def at_close(t_open, t_close):
    """The context as the `served_bls` driver leaves it: the rings read
    when the window closed."""
    from cellbench.drivers import served_bls
    d = served_bls.Driver.__new__(served_bls.Driver)
    d.t_open, d.t_close = t_open, t_close
    d._read_cert_spans()
    return {"cert_spans": d.cert_spans, "t_close": t_close}


@pytest.mark.parametrize("metric", sorted(SPANS))
def test_span_readers_take_the_median_inside_the_window(recorder, metric):
    name = SPANS[metric]
    flight.record_span(name, 9000, 1)          # before the window
    time.sleep(0.002)
    t0 = time.monotonic()
    for us in (1000, 5000, 3000):
        flight.record_span(name, us, 2)
    t1 = time.monotonic()
    ctx = at_close(t0, t1)
    assert read(metric, ctx) == 3.0
    # what the drain and the check write afterwards is not the window's
    flight.record_span(name, 7000, 3)
    assert read(metric, ctx) == 3.0
    # a window that holds none
    assert read(metric, at_close(time.monotonic(), time.monotonic())) is None


@pytest.mark.parametrize("metric", sorted(SPANS))
def test_span_readers_say_nothing_on_a_program_without_the_span(
        recorder, metric, monkeypatch):
    t = time.monotonic()
    flight.record_span("some_other_span", 10)
    assert read(metric, at_close(t, time.monotonic())) is None
    # a driver that took no reading (`served`), and the parent commit:
    # spans of these names may exist, the tail reader does not
    flight.record_span(SPANS[metric], 2000, 1)
    assert read(metric, {"window": [], "slots": []}) is None
    monkeypatch.delattr(flight, "span_events_tail")
    assert read(metric, at_close(t, time.monotonic())) is None


def test_a_wrapped_ring_is_read_from_where_it_is_whole(recorder,
                                                       monkeypatch):
    """A dispatcher's ring wraps inside the window: the reader takes the
    tail every ring still holds, where `span_events` says None."""
    monkeypatch.setattr(flight, "RING_SIZE", 64)
    flight.reset()
    t0 = time.monotonic()
    for i in range(200):
        flight.record(flight.EV_DISPATCH, seq=i)
        if i % 10 == 0:
            flight.record_span("share_sign", 1000 * (i // 10), i)
    t1 = time.monotonic()
    assert flight.span_events("share_sign", since_ns=int(t0 * 1e9)) is None
    spans, from_ns = flight.span_events_tail("share_sign",
                                             since_ns=int(t0 * 1e9))
    assert from_ns > t0 * 1e9
    assert [s for _t, s, _us in spans] == [150, 160, 170, 180, 190]
    assert served_spans.span_ms(at_close(t0, t1), "share_sign") == 17.0
