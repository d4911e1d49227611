"""Plain reference of MinkUNet (NVIDIA MinkowskiEngine
``examples/minkunet.py``; Choy, Gwak, Savarese, CVPR 2019), written from the
published network and independent of the program under test: its weights,
its forward pass and its convs' work. The program's side is
``minkunet_sut.py``.

Semantics, for a config of ``init_dim``, ``planes`` p_0..p_{2h-1},
``layers`` n_0..n_{2h-1} and ``stem_kernel`` s:

* level 0 holds the input's active voxels; level l+1 holds
  ``unique(coords_l // 2)``; there are h + 1 levels;
* batch norm normalises over the level's active voxels (biased variance,
  eps 1e-5); no conv has a bias but the classifier;
* the stem is an s^3 conv centred on each active voxel (output set = input
  set), from the input features to ``init_dim``, then BN and ReLU;
* a ``BasicBlock`` from c to w is conv3^3-BN-ReLU-conv3^3-BN plus the
  shortcut, then ReLU; the shortcut is the input, or where c != w a 1x1
  conv and BN;
* going down from level l, a 2x2x2 stride-2 conv keeps the width, then BN
  and ReLU; level l >= 1 then runs n_{l-1} blocks of width p_{l-1};
* going up to level l < h, a 2x2x2 stride-2 transposed conv onto level l's
  own voxels, to p_{2h-1-l} channels, then BN and ReLU, then
  ``cat(up, skip)`` (the skip is level l's encoder output: the stem's at
  level 0) and n_{2h-1-l} blocks of width p_{2h-1-l};
* a linear classifier (``final``, 1x1 with a bias) maps p_{2h-1} to the
  class logits.

Weights are a flat dict of float32 arrays: ``stem.{w,scale,offset}``,
``l<i>.{enc,dec}<b>.{conv1,conv2,proj}.w`` and
``l<i>.{enc,dec}<b>.{bn1,bn2,proj_bn}.{scale,offset}`` (``proj`` where the
width changes), ``l<i>.{down,up}.w``, ``l<i>.{down_bn,up_bn}.{scale,offset}``
and ``head.{w,b}``; conv weights are ``(K, C_in, C_out)`` in the plane
order of ``bench/reference.py``, a 1x1 conv's K is 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import reference as ref
import work


def stages(cfg: dict) -> dict:
    """The network's widths: ``enc[l]``, ``dec[l]`` as (width, blocks) and
    ``skip[l]``, level l's encoder output width."""
    planes, layers, h = cfg["planes"], cfg["layers"], len(cfg["planes"]) // 2
    enc = [(cfg["init_dim"], 0)] + [(planes[i], layers[i]) for i in range(h)]
    dec = [(planes[2 * h - 1 - li], layers[2 * h - 1 - li])
           for li in range(h)]
    return {"enc": enc, "dec": dec, "skip": [w for w, _ in enc], "h": h}


def _block_shapes(s: dict, p: str, c: int, w: int) -> None:
    s[p + "conv1.w"] = (27, c, w)
    s[p + "conv2.w"] = (27, w, w)
    norms = ["bn1", "bn2"]
    if c != w:
        s[p + "proj.w"] = (1, c, w)
        norms.append("proj_bn")
    for n in norms:
        s[p + n + ".scale"] = (w,)
        s[p + n + ".offset"] = (w,)


def weight_shapes(cfg: dict) -> dict:
    """name -> shape of every weight."""
    st = stages(cfg)
    h, skip = st["h"], st["skip"]
    init = cfg["init_dim"]
    s = {"stem.w": (cfg["stem_kernel"] ** 3, cfg["input_features"], init),
         "stem.scale": (init,), "stem.offset": (init,)}
    for li in range(h + 1):
        width, blocks = st["enc"][li]
        c = skip[li - 1] if li else init
        for b in range(blocks):
            _block_shapes(s, f"l{li}.enc{b}.", c if b == 0 else width, width)
        if li < h:
            dec_w, dec_blocks = st["dec"][li]
            below = st["dec"][li + 1][0] if li + 1 < h else skip[h]
            s[f"l{li}.down.w"] = (8, width, width)
            s[f"l{li}.up.w"] = (8, below, dec_w)
            for n, w in (("down_bn", width), ("up_bn", dec_w)):
                s[f"l{li}.{n}.scale"] = (w,)
                s[f"l{li}.{n}.offset"] = (w,)
            for b in range(dec_blocks):
                _block_shapes(s, f"l{li}.dec{b}.",
                              dec_w + width if b == 0 else dec_w, dec_w)
    s["head.w"] = (st["dec"][0][0], cfg["nClasses"])
    s["head.b"] = (cfg["nClasses"],)
    return s


def geometry(coords: np.ndarray, n_levels: int, resolution: int,
             stem_kernel: int) -> dict:
    """Per-level active coordinates and neighbour tables of one scene: the
    stem's s^3 table at level 0, each level's 3^3 and 1x1 tables, the
    down tables (level l -> l+1) and the up tables (l+1 -> l)."""
    lv = [np.asarray(coords, np.int64)]
    for li in range(1, n_levels):
        lv.append(ref.coarsen(lv[-1], 2, max(resolution >> li, 1)))
    idx = [ref.Index(c, max(resolution >> li, 1)) for li, c in enumerate(lv)]
    geo = {"coords": lv, "stem": ref.table(idx[0], lv[0], stem_kernel),
           "sub": [], "one": [], "down": [], "up_src": [], "up_plane": []}
    for li, c in enumerate(lv):
        geo["sub"].append(ref.table(idx[li], c, 3))
        geo["one"].append(ref.table(idx[li], c, 1))
        if li + 1 < n_levels:
            geo["down"].append(ref.table(idx[li], lv[li + 1], 2, stride=2,
                                         centered=False))
            src, plane = ref.up_table(idx[li + 1], c, 2)
            geo["up_src"].append(src)
            geo["up_plane"].append(plane)
    return geo


def padded_tables(geo: dict, capacity: int) -> dict:
    """The neighbour tables padded to ``capacity`` rows at every level, so
    that one compiled reference serves every scene of a config."""
    pad = ref.pad_rows
    out = {"mask": [pad(np.ones(len(c), bool), capacity, False)
                    for c in geo["coords"]],
           "stem": pad(geo["stem"], capacity, -1),
           "up_plane": [pad(t, capacity, 0) for t in geo["up_plane"]]}
    for k in ("sub", "one", "down", "up_src"):
        out[k] = [pad(t, capacity, -1) for t in geo[k]]
    return out


def _block(x, w, p, sub, one, mask, od, sd):
    """One ``BasicBlock`` on a level's active rows."""
    keep = ref.keep
    y = keep(ref.conv(x, sub, w[p + "conv1.w"], 0.0, mask, od), sd)
    y = keep(ref.bn_relu(y, mask, w[p + "bn1.scale"], w[p + "bn1.offset"]),
             sd)
    y = keep(ref.conv(y, sub, w[p + "conv2.w"], 0.0, mask, od), sd)
    y = ref.batch_norm(y, mask, w[p + "bn2.scale"], w[p + "bn2.offset"])
    if p + "proj.w" in w:
        x = keep(ref.conv(x, one, w[p + "proj.w"], 0.0, mask, od), sd)
        x = ref.batch_norm(x, mask, w[p + "proj_bn.scale"],
                           w[p + "proj_bn.offset"])
    return keep(jax.nn.relu(y + x) * mask[:, None], sd)


def forward(weights: dict, feats, tables: dict, *, levels: tuple,
            operand_dtype=None, store_dtype=None):
    """Level-0 logits (capacity, n_classes) of one scene. ``levels`` holds
    (encoder blocks, decoder blocks) of each level."""
    w, od, sd = weights, operand_dtype, store_dtype
    keep, masks = ref.keep, tables["mask"]
    h = len(levels) - 1
    x = keep(ref.conv(feats, tables["stem"], w["stem.w"], 0.0, masks[0], od),
             sd)
    x = keep(ref.bn_relu(x, masks[0], w["stem.scale"], w["stem.offset"]), sd)
    skips = []
    for li, (enc, _) in enumerate(levels):
        for b in range(enc):
            x = _block(x, w, f"l{li}.enc{b}.", tables["sub"][li],
                       tables["one"][li], masks[li], od, sd)
        if li < h:
            skips.append(x)
            x = keep(ref.conv(x, tables["down"][li], w[f"l{li}.down.w"], 0.0,
                              masks[li + 1], od), sd)
            x = keep(ref.bn_relu(x, masks[li + 1], w[f"l{li}.down_bn.scale"],
                                 w[f"l{li}.down_bn.offset"]), sd)
    for li in range(h - 1, -1, -1):
        up = keep(ref.up(x, tables["up_src"][li], tables["up_plane"][li],
                         w[f"l{li}.up.w"], 0.0, masks[li], od), sd)
        up = keep(ref.bn_relu(up, masks[li], w[f"l{li}.up_bn.scale"],
                              w[f"l{li}.up_bn.offset"]), sd)
        x = jnp.concatenate([up, skips[li]], -1)
        for b in range(levels[li][1]):
            x = _block(x, w, f"l{li}.dec{b}.", tables["sub"][li],
                       tables["one"][li], masks[li], od, sd)
    out = (ref.round_to(x, od) @ ref.round_to(w["head.w"], od)
           + w["head.b"])
    return jnp.where(masks[0][:, None], out, 0)


@functools.partial(jax.jit, static_argnames=(
    "levels", "operand_dtype", "store_dtype"))
def _forward_jit(weights, feats, tables, *, levels, operand_dtype,
                 store_dtype):
    return forward(weights, feats, tables, levels=levels,
                   operand_dtype=operand_dtype, store_dtype=store_dtype)


def _levels(cfg: dict) -> tuple:
    st = stages(cfg)
    return tuple((st["enc"][li][1], st["dec"][li][1] if li < st["h"] else 0)
                 for li in range(st["h"] + 1))


def logits(weights: dict, coords: np.ndarray, feats: np.ndarray, cfg: dict,
           *, operand_dtype=None, store_dtype=None) -> np.ndarray:
    """Reference logits of one scene's active voxels, in their row order:
    ``coords``/``feats`` hold just the active rows. Every product is exact
    float32 (``highest`` precision), of operands first rounded to
    ``operand_dtype`` where one is given, and every activation a layer
    hands on is rounded to ``store_dtype`` where one is given (controls)."""
    levels = _levels(cfg)
    geo = geometry(coords, len(levels), cfg["full_scale"], cfg["stem_kernel"])
    tables = padded_tables(geo, cfg["capacity"])
    f = ref.pad_rows(np.asarray(feats, np.float32), cfg["capacity"], 0.0)
    with jax.default_matmul_precision("highest"):
        out = _forward_jit(weights, f, tables, levels=levels,
                           operand_dtype=operand_dtype,
                           store_dtype=store_dtype)
    return np.asarray(out)[:len(coords)]


def scene_convs(coords: np.ndarray, cfg: dict) -> list:
    """Every conv of one scene's forward pass as (site, level, FLOPs,
    bytes) (``work.conv``): the stem, each block's 3^3 convs (site
    ``sub``) and 1x1 projections (``proj``), the downs, the ups and the
    classifier (``head``)."""
    st = stages(cfg)
    h, skip = st["h"], st["skip"]
    geo = geometry(coords, h + 1, cfg["full_scale"], cfg["stem_kernel"])
    n = [len(c) for c in geo["coords"]]
    pairs = [int((t >= 0).sum()) for t in geo["sub"]]
    init = cfg["init_dim"]
    out = [("stem", 0) + work.conv(int((geo["stem"] >= 0).sum()), n[0], n[0],
                                   cfg["input_features"], init,
                                   cfg["stem_kernel"] ** 3)]

    def blocks(li, count, c, width):
        for b in range(count):
            cin = c if b == 0 else width
            out.append(("sub", li) + work.conv(pairs[li], n[li], n[li], cin,
                                               width, 27))
            out.append(("sub", li) + work.conv(pairs[li], n[li], n[li],
                                               width, width, 27))
            if cin != width:
                out.append(("proj", li) + work.conv(n[li], n[li], n[li], cin,
                                                    width, 1))

    for li in range(h + 1):
        width, count = st["enc"][li]
        blocks(li, count, skip[li - 1] if li else init, width)
        if li < h:
            down = int((geo["down"][li] >= 0).sum())
            out.append(("down", li) + work.conv(down, n[li], n[li + 1], width,
                                                width, 8))
    for li in range(h - 1, -1, -1):
        dec_w, count = st["dec"][li]
        below = st["dec"][li + 1][0] if li + 1 < h else skip[h]
        out.append(("up", li) + work.conv(len(geo["up_plane"][li]), n[li + 1],
                                          n[li], below, dec_w, 8))
        blocks(li, count, dec_w + skip[li], dec_w)
    out.append(("head", 0) + work.conv(n[0], n[0], n[0], st["dec"][0][0],
                                       cfg["nClasses"], 1))
    return out
