"""Kernel: summed device time of the fused SSpNNA kernel's events per
finished scene."""


def read(ctx):
    if not ctx["done"]:
        return None
    t = ctx["trace"]["kernel_s"].get("sspnna", 0.0)
    return t * 1e3 / len(ctx["done"]) if t > 0 else None
