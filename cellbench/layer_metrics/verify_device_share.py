"""Share of the signatures verified in the window (memo hits are not
verifications) that rode the cross-principal device batch: the
`signature_manager` counters of all replicas, window delta."""


def read(ctx):
    d = {k: ctx["after"][k] - ctx["before"][k]
         for k in ("sigs_device_dispatched", "batched_verifies",
                   "scalar_fallbacks")}
    verified = d["batched_verifies"] + d["scalar_fallbacks"]
    if verified <= 0:
        return None
    return 100.0 * d["sigs_device_dispatched"] / verified
