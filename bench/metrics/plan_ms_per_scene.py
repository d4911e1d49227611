"""Planner: host plan work (``WaveStats.plan_ms``, summed over requests and
planner threads) per finished request."""


def read(ctx):
    if not ctx["done"]:
        return None
    return sum(w.plan_ms for w in ctx["window"].waves) / len(ctx["done"])
