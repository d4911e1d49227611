"""Model step: device busy time in the traced window (the union of op
intervals, averaged over the chips) per finished request."""


def read(ctx):
    if not ctx["done"]:
        return None
    return ctx["trace"]["busy_s"] * 1e3 / len(ctx["done"])
