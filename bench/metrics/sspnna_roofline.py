"""Kernel: the least time the chip could take for the kernel's calls (sum
over calls of max(useful FLOPs / peak, compulsory bytes / HBM bandwidth),
``bench/work.py``) over their summed device time."""


def read(ctx):
    t = ctx["trace"]["kernel_s"].get("sspnna", 0.0)
    if t <= 0:
        return None
    return 100.0 * ctx["work"]["kernel_least_s"] / t
