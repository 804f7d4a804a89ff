"""Share of the window's slots, all replicas' rows, that committed on
the fast path: the `path` field (`fast` / `slow`) of the
`flight.SlotTracker` rows. Nothing where no row names a path."""


def read(ctx):
    paths = [s.get("path") for s in ctx["slots"]]
    known = paths.count("fast") + paths.count("slow")
    return 100.0 * paths.count("fast") / known if known else None
