"""Requests ordered per slot: the writes acknowledged in the window
over the slots one replica finalized in it (`finalized_total`, all
replicas, over n)."""


def read(ctx):
    n = ctx["cell"].config["cluster"]["n"]
    slots = (ctx["after"]["slots_finalized"]
             - ctx["before"]["slots_finalized"]) / n
    if slots <= 0 or ctx["writes_acked"] <= 0:
        return None
    return ctx["writes_acked"] / slots
