"""Plain reference of the SCN U-Net forward pass (Graham et al., CVPR 2018;
SparseConvNet ``examples/ScanNet/unet.py``), written from the published
description and independent of the program under test.

Semantics, for a config of ``widths`` w_0..w_{L-1} and ``reps`` blocks:

* level 0 holds the input's active voxels; level l+1 holds
  ``unique(coords_l // 2)``;
* a block is a submanifold 3x3x3 conv (output set = input set, only active
  neighbours contribute), batch norm over the level's active voxels
  (biased variance, eps 1e-5) and ReLU;
* the stem is a submanifold conv from the input features to w_0;
* the encoder runs ``reps`` blocks at each level, then a 2x2x2 stride-2
  conv down (output voxel o reads input ``2 o + d``, d in {0,1}^3);
* the decoder, from level L-2 up, runs a 2x2x2 stride-2 transposed conv
  (fine voxel o reads coarse voxel ``o // 2`` through the plane of
  ``o mod 2``), concatenates [skip, upsampled] and runs ``reps`` blocks, the
  first from 2 w_l to w_l;
* a linear classifier maps w_0 to the class logits.

Kernel planes are in lexicographic offset order (x outer, z inner); the 3^3
offsets run -1..1, the 2^3 ones 0..1. Every conv has a bias. Weights are
a flat dict made by ``bench/weights.py``.

The neighbour tables are built on the host with sorted keys, the maths in
``jax.numpy`` float32 at ``highest`` matmul precision, in blocks of rows so
that it fits beside nothing else. A control rounds every matmul operand to
``operand_dtype`` (float8 e4m3: the step below the bfloat16 that the
configuration states) and, with ``store_dtype``, every activation that a
layer hands on (bfloat16 storage: a reading of what the check can hold);
sums stay float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: rows per gather-contract block
BLOCK = 8192


def offsets(size: int, centered: bool) -> np.ndarray:
    lo = -(size // 2) if centered else 0
    r = np.arange(lo, lo + size)
    return np.stack(np.meshgrid(r, r, r, indexing="ij"), -1).reshape(-1, 3)


def _keys(c: np.ndarray, res: int) -> np.ndarray:
    c = c.astype(np.int64)
    return (c[:, 0] * res + c[:, 1]) * res + c[:, 2]


class _Index:
    """Sorted linear keys of one level's voxels, for neighbour lookups."""

    def __init__(self, coords: np.ndarray, res: int):
        self.res = res
        keys = _keys(coords, res)
        self.order = np.argsort(keys, kind="stable")
        self.sorted = keys[self.order]

    def find(self, probe: np.ndarray) -> np.ndarray:
        """Row of each probed coordinate (..., 3), -1 where it is absent or
        out of the grid."""
        ok = np.all((probe >= 0) & (probe < self.res), -1)
        k = _keys(probe.reshape(-1, 3), self.res).reshape(ok.shape)
        pos = np.minimum(np.searchsorted(self.sorted, k), len(self.sorted) - 1)
        hit = ok & (self.sorted[pos] == k)
        return np.where(hit, self.order[pos], -1).astype(np.int32)


def geometry(coords: np.ndarray, n_levels: int, resolution: int) -> dict:
    """Per-level active coordinates and neighbour tables of one scene.

    ``coords`` are level 0's active voxels in the caller's row order; the
    tables index rows of the same level (sub), the finer level (down) or
    the coarser one (up)."""
    lv = [np.asarray(coords, np.int64)]
    for li in range(1, n_levels):
        res = max(resolution >> li, 1)
        k = np.unique(_keys(lv[-1] // 2, res))
        lv.append(np.stack([k // (res * res), (k // res) % res, k % res], 1))
    o3, o2 = offsets(3, True), offsets(2, False)
    idx = [_Index(c, max(resolution >> li, 1)) for li, c in enumerate(lv)]
    sub, down, up_src, up_plane = [], [], [], []
    for li, c in enumerate(lv):
        sub.append(idx[li].find(c[:, None, :] + o3[None]))
        if li + 1 < n_levels:
            down.append(idx[li].find(2 * lv[li + 1][:, None, :] + o2[None]))
            up_src.append(idx[li + 1].find(c // 2))
            m = c % 2
            up_plane.append((m[:, 0] * 4 + m[:, 1] * 2 + m[:, 2])
                            .astype(np.int32))
    return {"coords": lv, "sub": sub, "down": down, "up_src": up_src,
            "up_plane": up_plane}


def pair_counts(geo: dict) -> dict:
    """Active (output, input) pairs of every conv of the U-Net: ``sub[l]``,
    ``down[l]`` (level l -> l+1) and ``up[l]`` (l+1 -> l)."""
    return {"n": [len(c) for c in geo["coords"]],
            "sub": [int((t >= 0).sum()) for t in geo["sub"]],
            "down": [int((t >= 0).sum()) for t in geo["down"]],
            "up": [len(p) for p in geo["up_plane"]]}


def _pad_rows(a: np.ndarray, n: int, fill) -> np.ndarray:
    out = np.full((n,) + a.shape[1:], fill, a.dtype)
    out[:len(a)] = a
    return out


def padded_tables(geo: dict, capacity: int) -> dict:
    """The neighbour tables padded to ``capacity`` rows at every level, so
    that one compiled reference serves every scene of a config."""
    return {
        "mask": [_pad_rows(np.ones(len(c), bool), capacity, False)
                 for c in geo["coords"]],
        "sub": [_pad_rows(t, capacity, -1) for t in geo["sub"]],
        "down": [_pad_rows(t, capacity, -1) for t in geo["down"]],
        "up_src": [_pad_rows(t, capacity, -1) for t in geo["up_src"]],
        "up_plane": [_pad_rows(t, capacity, 0) for t in geo["up_plane"]],
    }


def _block(v: int) -> int:
    return BLOCK if v % BLOCK == 0 else v


def _round(x, operand_dtype):
    """A matmul operand as the products see it."""
    if operand_dtype is None:
        return x
    return x.astype(operand_dtype).astype(x.dtype)


def _conv(x, nbr, w, b, mask, od):
    """out[o] = sum_k x[nbr[o, k]] @ w[k] + b on active rows, zero elsewhere;
    in row blocks."""
    v = nbr.shape[0]
    blk = _block(v)
    k, c, n = w.shape
    w = _round(w, od)

    def one(idx):
        g = jnp.where((idx >= 0)[..., None], x[jnp.maximum(idx, 0)], 0)
        return jnp.einsum("okc,kcn->on", _round(g, od), w)

    out = jax.lax.map(one, nbr.reshape(v // blk, blk, k)).reshape(v, n)
    return jnp.where(mask[:, None], out + b, 0)


def _up(x, src, plane, w, b, mask, od):
    """Transposed conv: fine row o reads coarse row src[o] through plane
    plane[o]."""
    v = src.shape[0]
    blk = _block(v)
    w = _round(w, od)

    def one(args):
        s, p = args
        g = x[jnp.maximum(s, 0)]
        every = jnp.einsum("oc,kcn->okn", _round(g, od), w)
        return jnp.take_along_axis(every, p[:, None, None], 1)[:, 0]

    out = jax.lax.map(one, (src.reshape(v // blk, blk),
                            plane.reshape(v // blk, blk)))
    out = out.reshape(v, w.shape[-1])
    return jnp.where(mask[:, None], out + b, 0)


def _keep(x, store_dtype):
    """An activation as a layer stores it."""
    return _round(x, store_dtype)


def _bn_relu(x, mask, scale, offset):
    m = mask[:, None].astype(x.dtype)
    n = jnp.maximum(jnp.sum(m), 1)
    mean = jnp.sum(x * m, 0) / n
    var = jnp.sum(jnp.square(x - mean) * m, 0) / n
    y = (x - mean) * jax.lax.rsqrt(var + 1e-5) * scale + offset
    return jax.nn.relu(y) * m


def forward(weights: dict, feats, tables: dict, *, n_levels: int, reps: int,
            operand_dtype=None, store_dtype=None):
    """Level-0 logits (capacity, n_classes) of one scene."""
    w, f, od, sd = weights, feats, operand_dtype, store_dtype
    masks = tables["mask"]
    x = _keep(_conv(f, tables["sub"][0], w["stem.w"], w["stem.b"], masks[0],
                    od), sd)
    skips = []
    for li in range(n_levels):
        for r in range(reps):
            p = f"l{li}.enc{r}."
            x = _keep(_conv(x, tables["sub"][li], w[p + "w"], w[p + "b"],
                            masks[li], od), sd)
            x = _keep(_bn_relu(x, masks[li], w[p + "scale"],
                               w[p + "offset"]), sd)
        if li + 1 < n_levels:
            skips.append(x)
            x = _keep(_conv(x, tables["down"][li], w[f"l{li}.down.w"],
                            w[f"l{li}.down.b"], masks[li + 1], od), sd)
    for li in range(n_levels - 2, -1, -1):
        up = _keep(_up(x, tables["up_src"][li], tables["up_plane"][li],
                       w[f"l{li}.up.w"], w[f"l{li}.up.b"], masks[li], od), sd)
        x = jnp.concatenate([skips[li], up], -1)
        for r in range(reps):
            p = f"l{li}.dec{r}."
            x = _keep(_conv(x, tables["sub"][li], w[p + "w"], w[p + "b"],
                            masks[li], od), sd)
            x = _keep(_bn_relu(x, masks[li], w[p + "scale"],
                               w[p + "offset"]), sd)
    out = _round(x, od) @ _round(w["head.w"], od) + w["head.b"]
    return jnp.where(masks[0][:, None], out, 0)


@functools.partial(jax.jit, static_argnames=(
    "n_levels", "reps", "operand_dtype", "store_dtype"))
def _forward_jit(weights, feats, tables, *, n_levels, reps, operand_dtype,
                 store_dtype):
    return forward(weights, feats, tables, n_levels=n_levels, reps=reps,
                   operand_dtype=operand_dtype, store_dtype=store_dtype)


def logits(weights: dict, coords: np.ndarray, feats: np.ndarray, *,
           widths, reps: int, resolution: int, capacity: int,
           operand_dtype=None, store_dtype=None) -> np.ndarray:
    """Reference logits of one scene's active voxels, in their row order:
    ``coords``/``feats`` hold just the active rows. Every product is exact
    float32 (``highest`` precision), of operands first rounded to
    ``operand_dtype`` where one is given, and every activation a layer
    hands on is rounded to ``store_dtype`` where one is given (controls)."""
    geo = geometry(coords, len(widths), resolution)
    tables = padded_tables(geo, capacity)
    f = _pad_rows(np.asarray(feats, np.float32), capacity, 0.0)
    with jax.default_matmul_precision("highest"):
        out = _forward_jit(weights, f, tables, n_levels=len(widths),
                           reps=reps, operand_dtype=operand_dtype,
                           store_dtype=store_dtype)
    return np.asarray(out)[:len(coords)]
