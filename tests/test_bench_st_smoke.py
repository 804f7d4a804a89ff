"""Tier-1 wiring for benchmarks/bench_st.py (--smoke shape): the
pipelined multi-source state transfer must beat stop-and-wait under
injected per-message latency even on a loaded CI host. This asserts a
conservative floor so the tier-1 gate doesn't flake on host noise."""
from benchmarks.bench_st import compare


def test_bench_st_smoke():
    # one retry on the timing floor only: the CI container's shared disk
    # has nonstationary latency (probed fsync drifting 2→21 ms within a
    # session) that can depress a single sample of either side of the
    # ratio; a genuine pipelining regression fails both attempts
    for attempt in (0, 1):
        out = compare(n_blocks=64, range_blocks=8, window=4, n_sources=4,
                      latency_s=0.005)
        assert out["baseline"]["ok"], out
        assert out["pipelined"]["ok"], out
        # clean run: nobody stalled, nobody was punished
        assert out["pipelined"]["source_failovers"] == 0, out
        # measured 3.3x on the build host; 1.5x is the flake floor
        if out["speedup"] >= 1.5:
            return
    assert out["speedup"] >= 1.5, out
