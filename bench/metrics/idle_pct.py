"""Device: share of the traced window in which no operation ran (1 - busy
over window, averaged over the chips)."""


def read(ctx):
    tr = ctx["trace"]
    if tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
