"""The work a U-Net forward pass needs, whatever implements it.

Counted from the scene's own geometry (``reference.geometry``: the active
(output, input) pairs of every conv), not from what an implementation moves:
no one-hot gather, lane padding or dead tiles.

* FLOPs of a conv: 2 x active pairs x C_in x C_out.
* Compulsory bytes of a conv: ``VALUE_BYTES`` x (active input rows x C_in
  + K x C_in x C_out + active output rows x C_out), values read and written
  once at the precision the configurations state (bfloat16).

``kernel_levels`` names the levels whose submanifold convs (and, at level 0,
the stem) the program sends to the fused kernel; only those count towards
the kernel's roofline, while every conv and the classifier count towards
the whole step's.
"""
from __future__ import annotations

import json
from pathlib import Path

import reference

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


def scene_convs(pc: dict, widths, reps: int, in_ch: int,
                n_classes: int) -> list[tuple[str, int, int, int]]:
    """Every conv of one forward pass as (site, level, FLOPs, bytes); the
    classifier is site ``head``."""
    n = pc["n"]
    out = [("stem", 0) + conv(pc["sub"][0], n[0], n[0], in_ch, widths[0],
                              27)]
    for li, c in enumerate(widths):
        for _ in range(reps):
            out.append(("sub", li) + conv(pc["sub"][li], n[li], n[li], c, c,
                                          27))
        if li + 1 < len(widths):
            c2 = widths[li + 1]
            out.append(("down", li) + conv(pc["down"][li], n[li], n[li + 1],
                                           c, c2, 8))
            out.append(("up", li) + conv(pc["up"][li], n[li + 1], n[li],
                                         c2, c, 8))
            for r in range(reps):
                cin = 2 * c if r == 0 else c
                out.append(("sub", li) + conv(pc["sub"][li], n[li], n[li],
                                              cin, c, 27))
    out.append(("head", 0, 2 * n[0] * widths[0] * n_classes,
                VALUE_BYTES * (n[0] * widths[0] + widths[0] * n_classes
                               + n[0] * n_classes)))
    return out


def scene_work(coords, cfg: dict, kernel_levels) -> dict:
    """Useful FLOPs of the whole pass, and the kernel calls' FLOPs and least
    time on the given peaks (summed per call, not per total)."""
    geo = reference.geometry(coords, len(cfg["n_planes"]), cfg["full_scale"])
    convs = scene_convs(reference.pair_counts(geo), cfg["n_planes"],
                        cfg["block_reps"], cfg["input_features"],
                        cfg["nClasses"])
    kl = set(kernel_levels)
    kernel = [(f, b) for site, li, f, b in convs
              if site in ("stem", "sub") and li in kl]
    return {"flops": sum(f for _, _, f, _ in convs), "kernel": kernel}


#: at most this many finished requests are counted, evenly spread over the
#: window, and the sums scaled to all of them (a stream's frames differ by
#: a few hundred voxels; rooms windows hold fewer than this)
MAX_COUNTED = 64


def window_work(traffic, done, cfg: dict, kernel_levels, pk: dict) -> dict:
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
        w = scene_work(coords[mask], cfg, kernel_levels)
        flops += w["flops"]
        least += sum(max(f / pk["flops_per_s"], b / pk["hbm_bytes_per_s"])
                     for f, b in w["kernel"])
    scale = len(done) / len(counted)
    return {"flops": flops * scale, "kernel_least_s": least * scale}
