"""The work a forward pass needs, whatever implements it.

Counted from the scene's own geometry (the architecture's ``scene_convs``,
``bench/plug.py``: the active (output, input) pairs of every conv), not
from what an implementation moves: no one-hot gather, lane padding or dead
tiles.

* FLOPs of a conv: 2 x active pairs x C_in x C_out.
* Compulsory bytes of a conv: ``VALUE_BYTES`` x (active input rows x C_in
  + K x C_in x C_out + active output rows x C_out), values read and written
  once at the precision the configurations state (bfloat16).

``kernel_sites`` names the (site, level) pairs whose convs the program
sends to the fused kernel; only those count towards the kernel's roofline,
while every conv counts towards the whole step's.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
#: bytes of one value: bfloat16, the precision that the check holds and the
#: configurations state, so a program may move no fewer
VALUE_BYTES = 2


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device {device_kind!r} in {PEAKS.name}")
    return table[device_kind]


def conv(pairs: int, n_in: int, n_out: int, c_in: int, c_out: int,
         k: int) -> tuple[int, int]:
    """(FLOPs, compulsory bytes) of one sparse conv."""
    return (2 * pairs * c_in * c_out,
            VALUE_BYTES * (n_in * c_in + k * c_in * c_out + n_out * c_out))


def scene_work(coords, cfg: dict, model, kernel_sites) -> dict:
    """Useful FLOPs of the whole pass, and the kernel calls' (FLOPs,
    bytes), for the architecture module ``model``."""
    convs = model.scene_convs(coords, cfg)
    sites = set(kernel_sites)
    kernel = [(f, b) for site, li, f, b in convs if (site, li) in sites]
    return {"flops": sum(f for _, _, f, _ in convs), "kernel": kernel}


#: at most this many finished requests are counted, evenly spread over the
#: window, and the sums scaled to all of them (a stream's frames differ by
#: a few hundred voxels; rooms windows hold fewer than this)
MAX_COUNTED = 64


def window_work(traffic, done, cfg: dict, model, kernel_sites,
                pk: dict) -> dict:
    """Over the finished requests: ``flops`` of the whole passes, and
    ``kernel_least_s``, the sum over kernel calls of max(FLOPs / peak,
    bytes / bandwidth)."""
    if not done:
        return {"flops": 0, "kernel_least_s": 0.0}
    step = max(1, -(-len(done) // MAX_COUNTED))
    counted = done[::step]
    flops, least = 0, 0.0
    for rec in counted:
        coords, _, mask = traffic.scene_of(rec)
        w = scene_work(coords[mask], cfg, model, kernel_sites)
        flops += w["flops"]
        least += sum(max(f / pk["flops_per_s"], b / pk["hbm_bytes_per_s"])
                     for f, b in w["kernel"])
    scale = len(done) / len(counted)
    return {"flops": flops * scale, "kernel_least_s": least * scale}
