"""Serving: share of the window's wave slots that carried a request
(``WaveStats.fill_frac``). A wave dispatched before the batch is full is
padded, and the device computes the padding slot's scene for no one."""


def read(ctx):
    waves = ctx["window"].waves
    if not waves:
        return None
    return 100.0 * sum(w.fill_frac for w in waves) / len(waves)
