"""1 - the union of device-operation intervals over the traced window,
served cell."""


def read(ctx):
    return ctx["trace"]["idle_pct"]
