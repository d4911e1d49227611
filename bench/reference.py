"""Building blocks of the plain references (``bench/archs/<arch>.py``),
independent of the program under test.

Neighbour tables are built on the host with sorted keys, for any cubic
kernel and stride: output voxel o of a conv with kernel size k and stride s
reads input voxel ``s o + d`` through the plane of offset d. Offsets are in
lexicographic order (x outer, z inner); a centred kernel's run
``-(k // 2) .. k - 1 - k // 2``, an uncentred one's ``0 .. k - 1``. A
transposed conv of stride s reads, for fine voxel o, coarse voxel ``o // s``
through the plane of ``o mod s``. A table's ``-1`` is an absent neighbour.

The maths is ``jax.numpy`` float32, run by the architecture at ``highest``
matmul precision, in blocks of rows so that it fits beside nothing else. A
control rounds every matmul operand to ``operand_dtype`` and, with
``store_dtype``, every activation that a layer hands on (``keep``); sums
stay float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: rows per gather-contract block
BLOCK = 8192


def offsets(size: int, centered: bool) -> np.ndarray:
    lo = -(size // 2) if centered else 0
    r = np.arange(lo, lo + size)
    return np.stack(np.meshgrid(r, r, r, indexing="ij"), -1).reshape(-1, 3)


def keys(c: np.ndarray, res: int) -> np.ndarray:
    c = c.astype(np.int64)
    return (c[:, 0] * res + c[:, 1]) * res + c[:, 2]


class Index:
    """Sorted linear keys of one level's voxels, for neighbour lookups."""

    def __init__(self, coords: np.ndarray, res: int):
        self.res = res
        k = keys(coords, res)
        self.order = np.argsort(k, kind="stable")
        self.sorted = k[self.order]

    def find(self, probe: np.ndarray) -> np.ndarray:
        """Row of each probed coordinate (..., 3), -1 where it is absent or
        out of the grid."""
        ok = np.all((probe >= 0) & (probe < self.res), -1)
        k = keys(probe.reshape(-1, 3), self.res).reshape(ok.shape)
        pos = np.minimum(np.searchsorted(self.sorted, k), len(self.sorted) - 1)
        hit = ok & (self.sorted[pos] == k)
        return np.where(hit, self.order[pos], -1).astype(np.int32)


def coarsen(coords: np.ndarray, stride: int, res: int) -> np.ndarray:
    """The active voxels of the level below: ``unique(coords // stride)``,
    sorted, on a grid of ``res``."""
    k = np.unique(keys(coords // stride, res))
    return np.stack([k // (res * res), (k // res) % res, k % res], 1)


def table(index: Index, out_coords: np.ndarray, size: int, stride: int = 1,
          centered: bool = True) -> np.ndarray:
    """(outputs, size^3) rows of ``index`` that each output voxel reads."""
    return index.find(stride * out_coords[:, None, :]
                      + offsets(size, centered)[None])


def up_table(coarse: Index, fine_coords: np.ndarray,
             stride: int) -> tuple[np.ndarray, np.ndarray]:
    """A transposed conv's (source row in ``coarse``, plane) of each fine
    voxel."""
    m = fine_coords % stride
    plane = (m[:, 0] * stride + m[:, 1]) * stride + m[:, 2]
    return coarse.find(fine_coords // stride), plane.astype(np.int32)


def pad_rows(a: np.ndarray, n: int, fill) -> np.ndarray:
    out = np.full((n,) + a.shape[1:], fill, a.dtype)
    out[:len(a)] = a
    return out


def _block(v: int) -> int:
    return BLOCK if v % BLOCK == 0 else v


def round_to(x, operand_dtype):
    """A matmul operand as the products see it."""
    if operand_dtype is None:
        return x
    return x.astype(operand_dtype).astype(x.dtype)


def keep(x, store_dtype):
    """An activation as a layer stores it."""
    return round_to(x, store_dtype)


def conv(x, nbr, w, b, mask, od):
    """out[o] = sum_k x[nbr[o, k]] @ w[k] + b on active rows, zero elsewhere;
    in row blocks."""
    v = nbr.shape[0]
    blk = _block(v)
    k, c, n = w.shape
    w = round_to(w, od)

    def one(idx):
        g = jnp.where((idx >= 0)[..., None], x[jnp.maximum(idx, 0)], 0)
        return jnp.einsum("okc,kcn->on", round_to(g, od), w)

    out = jax.lax.map(one, nbr.reshape(v // blk, blk, k)).reshape(v, n)
    return jnp.where(mask[:, None], out + b, 0)


def up(x, src, plane, w, b, mask, od):
    """Transposed conv: fine row o reads coarse row src[o] through plane
    plane[o]."""
    v = src.shape[0]
    blk = _block(v)
    w = round_to(w, od)

    def one(args):
        s, p = args
        g = x[jnp.maximum(s, 0)]
        every = jnp.einsum("oc,kcn->okn", round_to(g, od), w)
        return jnp.take_along_axis(every, p[:, None, None], 1)[:, 0]

    out = jax.lax.map(one, (src.reshape(v // blk, blk),
                            plane.reshape(v // blk, blk)))
    out = out.reshape(v, w.shape[-1])
    return jnp.where(mask[:, None], out + b, 0)


def _normalize(x, mask, scale, offset):
    """Batch norm over the active rows (biased variance, eps 1e-5)."""
    m = mask[:, None].astype(x.dtype)
    n = jnp.maximum(jnp.sum(m), 1)
    mean = jnp.sum(x * m, 0) / n
    var = jnp.sum(jnp.square(x - mean) * m, 0) / n
    return (x - mean) * jax.lax.rsqrt(var + 1e-5) * scale + offset, m


def batch_norm(x, mask, scale, offset):
    """Batch norm, zero on inactive rows."""
    y, m = _normalize(x, mask, scale, offset)
    return y * m


def bn_relu(x, mask, scale, offset):
    """Batch norm and ReLU, zero on inactive rows."""
    y, m = _normalize(x, mask, scale, offset)
    return jax.nn.relu(y) * m
