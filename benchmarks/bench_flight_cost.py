"""What the always-on telemetry costs per call on this host: one
`flight.record`, one `flight.span`, one `flight.annotate`, one empty
`device_tier` + `device_section`, each with the recorder on and off,
with `jax` imported (the `TraceAnnotation` half is live, no profile
being taken); and what the split of the lane's run and the durability
group adds: a slot's `exec_handled` event, a group event scanning 32
live slots of its replica, and the two `perf_counter_ns` reads round a
request's application call. Host arithmetic only — nothing runs on a
device.

Usage: python -m benchmarks.bench_flight_cost [--n 200000]
Prints one JSON line: nanoseconds per call, best of 5 rounds of `n`.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def per_call_ns(fn, n: int, rounds: int = 5) -> float:
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter_ns()
        for _ in range(n):
            fn()
        best = min(best, (time.perf_counter_ns() - t0) / n)
    return round(best, 1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=200_000)
    args = ap.parse_args(argv)
    import jax  # noqa: F401 — makes flight.annotation() live
    from tpubft.ops.dispatch import device_section, device_tier
    from tpubft.utils import flight

    def record():
        flight.record(flight.EV_DISPATCH, 1, 0, 7)

    def slot_event():
        # a folded event: ring write + the tracker's lock
        flight.record(flight.EV_EXEC_START, 1, 0, 1)

    def span():
        with flight.span("cost"):
            pass

    def annotate():
        with flight.annotate("cost"):
            pass

    def handled():
        flight.record(flight.EV_EXEC_HANDLED, 1, 0, 5)

    def group_event():
        # watermark 0: scans the replica's live slots, stamps none
        flight.record(flight.EV_DUR_WRITTEN, 0, 0, 1)

    def clock_pair():
        t0 = time.perf_counter_ns()
        return time.perf_counter_ns() - t0

    def section():
        with device_section("cost", 1):
            pass

    def tier_section():
        with device_tier("cost"):
            with device_section("cost", 1):
                pass

    def empty():
        pass

    out = {"n": args.n,
           "loop_ns": per_call_ns(empty, args.n),
           "perf_counter_pair_ns": per_call_ns(clock_pair, args.n)}
    for on in (True, False):
        flight._set_enabled(on)
        flight.reset()
        tag = "on" if on else "off"
        out[f"record_{tag}_ns"] = per_call_ns(record, args.n)
        out[f"record_folded_{tag}_ns"] = per_call_ns(slot_event, args.n)
        out[f"span_{tag}_ns"] = per_call_ns(span, args.n)
        out[f"annotate_{tag}_ns"] = per_call_ns(annotate, args.n)
        out[f"exec_handled_{tag}_ns"] = per_call_ns(handled, args.n)
        flight.set_thread_rid(0)
        for seq in range(1, 33):
            flight.record(flight.EV_EXEC_APPLY, seq, 0, 1)
        out[f"group_event_32_live_{tag}_ns"] = per_call_ns(group_event,
                                                           args.n)
        out[f"device_section_{tag}_ns"] = per_call_ns(section, args.n // 4)
        out[f"tier_and_section_{tag}_ns"] = per_call_ns(tier_section,
                                                        args.n // 4)
    flight._set_enabled(True)
    flight.reset()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
