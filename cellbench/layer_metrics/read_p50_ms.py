"""Median read-only read of the window, from the client's side: send →
f+1 matching replies, over every read completed inside the window (the
`served_ycsb` driver's own clock, as the write latencies are). None
where the driver timed no read."""
import statistics


def read(ctx):
    lat = ctx.get("read_latencies_ms")
    return statistics.median(lat) if lat else None
