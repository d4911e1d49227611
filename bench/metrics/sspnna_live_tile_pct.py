"""Kernel: share of the fused kernel's tiles that hold a pair, over the
window's waves (``WaveStats.notes["sspnna_live_tiles"]`` over
``["sspnna_tiles"]``, the requests' own plans and not the padding slots).
The pinned grid has a fixed number of tiles; the kernel skips the dead
ones, but still steps through them. A program without the notes reads
nothing."""


def read(ctx):
    waves = [w for w in ctx["window"].waves if "sspnna_tiles" in w.notes]
    tiles = sum(w.notes["sspnna_tiles"] for w in waves)
    if tiles <= 0:
        return None
    return 100.0 * sum(w.notes["sspnna_live_tiles"] for w in waves) / tiles
