"""Share of the changed merkle leaves whose walk the native call made:
100 x `smt_keys_native` over `smt_keys_updated`, the process-wide `kvbc`
counters of `tpubft/kvbc/sparse_merkle.py` (every replica's ledger in
the process), as they stand when the window has closed and what was in
flight has drained. Since process start, warm-up included, like
`smt_engine_reads_per_key`. A block of fewer than 192 changed leaves
takes the native walk, so a cell of single-key blocks reads 100."""


def read(ctx):
    if ctx["writes_acked"] <= 0:
        return None
    try:
        from tpubft.kvbc.sparse_merkle import METRICS
    except ImportError:        # a program that counts no such thing
        return None
    totals = METRICS.snapshot()["counters"]
    keys = totals.get("smt_keys_updated", 0)
    if keys <= 0 or "smt_keys_native" not in totals:
        return None
    return 100.0 * totals["smt_keys_native"] / keys
