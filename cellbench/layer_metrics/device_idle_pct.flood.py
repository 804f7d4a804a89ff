"""1 - the union of device-operation intervals over the traced window,
flood."""


def read(ctx):
    return ctx["trace"]["idle_pct"]
