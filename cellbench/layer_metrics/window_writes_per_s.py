"""Writes acknowledged by a reply quorum in the window, over the window.
In this closed loop it is the writes in flight over the mean latency,
counted in bursts: the clients finish in step, some thirty writes at a
time, so a 48 s window reads it in steps of 5 % (PERF.md §2). That is
why it stands here and the latencies are the end-to-end metrics."""


def read(ctx):
    if ctx["writes_acked"] <= 0:
        return None
    return ctx["writes_acked"] / ctx["window_s"]
