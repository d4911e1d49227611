"""Plain reference of the SCN U-Net (Graham et al., CVPR 2018;
SparseConvNet ``examples/ScanNet/unet.py``), written from the published
description and independent of the program under test: its weights, its
forward pass and its convs' work. The program's side is
``scn_unet_sut.py``.

Semantics, for a config of ``n_planes`` w_0..w_{L-1} and ``block_reps``
blocks:

* level 0 holds the input's active voxels; level l+1 holds
  ``unique(coords_l // 2)``;
* a block is a submanifold 3x3x3 conv (output set = input set, only active
  neighbours contribute), batch norm over the level's active voxels
  (biased variance, eps 1e-5) and ReLU;
* the stem is a submanifold conv from the input features to w_0;
* the encoder runs ``reps`` blocks at each level, then a 2x2x2 stride-2
  conv down;
* the decoder, from level L-2 up, runs a 2x2x2 stride-2 transposed conv,
  concatenates [skip, upsampled] and runs ``reps`` blocks, the first from
  2 w_l to w_l;
* a linear classifier maps w_0 to the class logits.

Every conv has a bias. Weights are a flat dict of float32 arrays:
``stem.{w,b}``, ``l<i>.enc<r>.{w,b,scale,offset}``, ``l<i>.{down,up}.{w,b}``,
``l<i>.dec<r>.{w,b,scale,offset}`` and ``head.{w,b}``; conv weights are
``(K, C_in, C_out)`` in the plane order of ``bench/reference.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import reference as ref
import work


def weight_shapes(cfg: dict) -> dict:
    """name -> shape of every weight."""
    widths, reps = cfg["n_planes"], cfg["block_reps"]
    s = {"stem.w": (27, cfg["input_features"], widths[0]),
         "stem.b": (widths[0],)}
    n = len(widths)
    for li, c in enumerate(widths):
        for r in range(reps):
            s[f"l{li}.enc{r}.w"] = (27, c, c)
            for k in ("b", "scale", "offset"):
                s[f"l{li}.enc{r}.{k}"] = (c,)
        if li + 1 < n:
            s[f"l{li}.down.w"] = (8, c, widths[li + 1])
            s[f"l{li}.down.b"] = (widths[li + 1],)
            s[f"l{li}.up.w"] = (8, widths[li + 1], c)
            s[f"l{li}.up.b"] = (c,)
            for r in range(reps):
                s[f"l{li}.dec{r}.w"] = (27, 2 * c if r == 0 else c, c)
                for k in ("b", "scale", "offset"):
                    s[f"l{li}.dec{r}.{k}"] = (c,)
    s["head.w"] = (widths[0], cfg["nClasses"])
    s["head.b"] = (cfg["nClasses"],)
    return s


def geometry(coords: np.ndarray, n_levels: int, resolution: int) -> dict:
    """Per-level active coordinates and neighbour tables of one scene.

    ``coords`` are level 0's active voxels in the caller's row order; the
    tables index rows of the same level (sub), the finer level (down) or
    the coarser one (up)."""
    lv = [np.asarray(coords, np.int64)]
    for li in range(1, n_levels):
        lv.append(ref.coarsen(lv[-1], 2, max(resolution >> li, 1)))
    idx = [ref.Index(c, max(resolution >> li, 1)) for li, c in enumerate(lv)]
    sub, down, up_src, up_plane = [], [], [], []
    for li, c in enumerate(lv):
        sub.append(ref.table(idx[li], c, 3))
        if li + 1 < n_levels:
            down.append(ref.table(idx[li], lv[li + 1], 2, stride=2,
                                  centered=False))
            src, plane = ref.up_table(idx[li + 1], c, 2)
            up_src.append(src)
            up_plane.append(plane)
    return {"coords": lv, "sub": sub, "down": down, "up_src": up_src,
            "up_plane": up_plane}


def pair_counts(geo: dict) -> dict:
    """Active (output, input) pairs of every conv of the U-Net: ``sub[l]``,
    ``down[l]`` (level l -> l+1) and ``up[l]`` (l+1 -> l)."""
    return {"n": [len(c) for c in geo["coords"]],
            "sub": [int((t >= 0).sum()) for t in geo["sub"]],
            "down": [int((t >= 0).sum()) for t in geo["down"]],
            "up": [len(p) for p in geo["up_plane"]]}


def padded_tables(geo: dict, capacity: int) -> dict:
    """The neighbour tables padded to ``capacity`` rows at every level, so
    that one compiled reference serves every scene of a config."""
    pad = ref.pad_rows
    return {
        "mask": [pad(np.ones(len(c), bool), capacity, False)
                 for c in geo["coords"]],
        "sub": [pad(t, capacity, -1) for t in geo["sub"]],
        "down": [pad(t, capacity, -1) for t in geo["down"]],
        "up_src": [pad(t, capacity, -1) for t in geo["up_src"]],
        "up_plane": [pad(t, capacity, 0) for t in geo["up_plane"]],
    }


def forward(weights: dict, feats, tables: dict, *, n_levels: int, reps: int,
            operand_dtype=None, store_dtype=None):
    """Level-0 logits (capacity, n_classes) of one scene."""
    w, f, od, sd = weights, feats, operand_dtype, store_dtype
    keep = ref.keep
    masks = tables["mask"]
    x = keep(ref.conv(f, tables["sub"][0], w["stem.w"], w["stem.b"],
                      masks[0], od), sd)
    skips = []
    for li in range(n_levels):
        for r in range(reps):
            p = f"l{li}.enc{r}."
            x = keep(ref.conv(x, tables["sub"][li], w[p + "w"], w[p + "b"],
                              masks[li], od), sd)
            x = keep(ref.bn_relu(x, masks[li], w[p + "scale"],
                                 w[p + "offset"]), sd)
        if li + 1 < n_levels:
            skips.append(x)
            x = keep(ref.conv(x, tables["down"][li], w[f"l{li}.down.w"],
                              w[f"l{li}.down.b"], masks[li + 1], od), sd)
    for li in range(n_levels - 2, -1, -1):
        up = keep(ref.up(x, tables["up_src"][li], tables["up_plane"][li],
                         w[f"l{li}.up.w"], w[f"l{li}.up.b"], masks[li], od),
                  sd)
        x = jnp.concatenate([skips[li], up], -1)
        for r in range(reps):
            p = f"l{li}.dec{r}."
            x = keep(ref.conv(x, tables["sub"][li], w[p + "w"], w[p + "b"],
                              masks[li], od), sd)
            x = keep(ref.bn_relu(x, masks[li], w[p + "scale"],
                                 w[p + "offset"]), sd)
    out = (ref.round_to(x, od) @ ref.round_to(w["head.w"], od)
           + w["head.b"])
    return jnp.where(masks[0][:, None], out, 0)


@functools.partial(jax.jit, static_argnames=(
    "n_levels", "reps", "operand_dtype", "store_dtype"))
def _forward_jit(weights, feats, tables, *, n_levels, reps, operand_dtype,
                 store_dtype):
    return forward(weights, feats, tables, n_levels=n_levels, reps=reps,
                   operand_dtype=operand_dtype, store_dtype=store_dtype)


def logits(weights: dict, coords: np.ndarray, feats: np.ndarray, cfg: dict,
           *, operand_dtype=None, store_dtype=None) -> np.ndarray:
    """Reference logits of one scene's active voxels, in their row order:
    ``coords``/``feats`` hold just the active rows. Every product is exact
    float32 (``highest`` precision), of operands first rounded to
    ``operand_dtype`` where one is given, and every activation a layer
    hands on is rounded to ``store_dtype`` where one is given (controls)."""
    n_levels = len(cfg["n_planes"])
    geo = geometry(coords, n_levels, cfg["full_scale"])
    tables = padded_tables(geo, cfg["capacity"])
    f = ref.pad_rows(np.asarray(feats, np.float32), cfg["capacity"], 0.0)
    with jax.default_matmul_precision("highest"):
        out = _forward_jit(weights, f, tables, n_levels=n_levels,
                           reps=cfg["block_reps"],
                           operand_dtype=operand_dtype,
                           store_dtype=store_dtype)
    return np.asarray(out)[:len(coords)]


def convs(pc: dict, widths, reps: int, in_ch: int,
          n_classes: int) -> list[tuple[str, int, int, int]]:
    """Every conv of one forward pass, from its pair counts, as (site,
    level, FLOPs, bytes); the classifier is site ``head``, a 1x1 conv."""
    n = pc["n"]
    out = [("stem", 0) + work.conv(pc["sub"][0], n[0], n[0], in_ch,
                                   widths[0], 27)]
    for li, c in enumerate(widths):
        for _ in range(reps):
            out.append(("sub", li) + work.conv(pc["sub"][li], n[li], n[li],
                                               c, c, 27))
        if li + 1 < len(widths):
            c2 = widths[li + 1]
            out.append(("down", li) + work.conv(pc["down"][li], n[li],
                                                n[li + 1], c, c2, 8))
            out.append(("up", li) + work.conv(pc["up"][li], n[li + 1], n[li],
                                              c2, c, 8))
            for r in range(reps):
                cin = 2 * c if r == 0 else c
                out.append(("sub", li) + work.conv(pc["sub"][li], n[li],
                                                   n[li], cin, c, 27))
    out.append(("head", 0) + work.conv(n[0], n[0], n[0], widths[0],
                                       n_classes, 1))
    return out


def scene_convs(coords: np.ndarray, cfg: dict) -> list:
    """Every conv of one scene's forward pass (``convs``)."""
    geo = geometry(coords, len(cfg["n_planes"]), cfg["full_scale"])
    return convs(pair_counts(geo), cfg["n_planes"], cfg["block_reps"],
                 cfg["input_features"], cfg["nClasses"])
