"""Node reads the sparse merkle walk issued to its engine, per key
updated: `smt_engine_reads` over `smt_keys_updated`, the process-wide
`kvbc` counters of `tpubft/kvbc/sparse_merkle.py` (every replica's
ledger in the process), as they stand when the window has closed and
what was in flight has drained. A ratio since process start, warm-up
included: the benchmark's driver takes no snapshot of them at the
window's open. A walk that reads every sibling makes 256 a key."""


def read(ctx):
    if ctx["writes_acked"] <= 0:
        return None
    try:
        from tpubft.kvbc.sparse_merkle import METRICS
    except ImportError:        # a program that counts no such thing
        return None
    totals = METRICS.snapshot()["counters"]
    keys = totals.get("smt_keys_updated", 0)
    if keys <= 0 or "smt_engine_reads" not in totals:
        return None
    return totals["smt_engine_reads"] / keys
