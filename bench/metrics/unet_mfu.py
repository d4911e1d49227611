"""Model step: the useful FLOPs of every finished request's forward pass
(``bench/work.py``: 2 x active pairs x C_in x C_out for every conv, and
the classifier) over the traced window x chips x peak FLOP/s."""


def read(ctx):
    if not ctx["work"]["flops"]:
        return None
    t = ctx["trace"]["window_s"] * ctx["chips"] * ctx["peaks"]["flops_per_s"]
    return 100.0 * ctx["work"]["flops"] / t
