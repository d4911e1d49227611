"""COIR: Compressed Output-response / Input-receptive Field metadata (§IV-A).

Two flavors, exactly as in the paper:

* **CIRF** (out-major): one entry per unique *output* voxel — the indices of
  every active *input* voxel in its receptive field, plus a K-bit weight mask
  whose set bits name the kernel offset (weight plane) of each partner.
* **CORF** (in-major): one entry per unique *input* voxel — the indices of
  every *output* voxel in its response field, plus the weight mask.

The paper stores variable-length index lists; for fixed-shape jit we store a
dense ``(V, K)`` index block with -1 holes and keep the bitmask as the header
word (the WAVES front-end consumes exactly this header). A kernel of more
than 32 planes (a 5^3 stem has 125) takes ``ceil(K / 32)`` header words per
row: plane k is bit ``k % 32`` of word ``k // 32``. Logical
(variable-length) metadata sizes for bandwidth accounting are computed from
the bitmask popcounts, so compression numbers match the paper's definition,
not the padded layout.

For a submanifold conv the two flavors are transposes of one another; for
resolution-changing convs they differ and SPADE picks the cheaper one.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hashgrid import SortedGrid, query_neighbors


class COIR(NamedTuple):
    """COIR metadata block (either flavor; flavor tracked by the caller).

    indices: (V, K) int32 — partner voxel index per weight plane, -1 absent.
    bitmask: (V,) uint32  — bit k set iff indices[:, k] >= 0; for K > 32,
             (V, ceil(K / 32)) uint32, plane k at bit k % 32 of word k // 32.
    mask:    (V,)  bool   — active rows of the major point set.
    """

    indices: jax.Array
    bitmask: jax.Array
    mask: jax.Array

    @property
    def n_weight_planes(self) -> int:
        return self.indices.shape[1]

    def valid(self) -> jax.Array:
        return self.indices >= 0

    def popcount(self) -> jax.Array:
        """Active partners per entry (receptive/response field size)."""
        return jnp.sum((self.indices >= 0).astype(jnp.int32), axis=1)

    def arf(self) -> jax.Array:
        """Average Receptive (or Response) Field over active entries —
        the paper's ARF, a.k.a. sparsity attribute SA_MO."""
        pc = self.popcount() * self.mask.astype(jnp.int32)
        n = jnp.maximum(jnp.sum(self.mask.astype(jnp.int32)), 1)
        return jnp.sum(pc) / n

    def n_pairs(self) -> jax.Array:
        return jnp.sum(self.popcount() * self.mask.astype(jnp.int32))


def _pack_bitmask(indices: jax.Array) -> jax.Array:
    v, k = indices.shape
    words = -(-k // 32)
    valid = jnp.pad(indices >= 0, ((0, 0), (0, 32 * words - k)))
    bits = (valid.reshape(v, words, 32).astype(jnp.uint32)
            << jnp.arange(32, dtype=jnp.uint32))
    packed = jnp.sum(bits, axis=2, dtype=jnp.uint32)
    return packed[:, 0] if words == 1 else packed


@functools.partial(jax.jit, static_argnames=("resolution", "stride"))
def build_cirf(
    out_coords: jax.Array,
    out_mask: jax.Array,
    in_coords: jax.Array,
    in_mask: jax.Array,
    offsets: jax.Array,
    resolution: int,
    stride: int = 1,
) -> COIR:
    """CIRF: out-major receptive-field metadata.

    ``indices[o, k]`` is the input voxel at ``out_coords[o]*stride + offsets[k]``.
    """
    idx = query_neighbors(
        out_coords, out_mask, in_coords, in_mask, offsets, resolution, stride
    )
    return COIR(idx, _pack_bitmask(idx), out_mask)


@functools.partial(jax.jit, static_argnames=("resolution", "stride"))
def build_corf(
    out_coords: jax.Array,
    out_mask: jax.Array,
    in_coords: jax.Array,
    in_mask: jax.Array,
    offsets: jax.Array,
    resolution: int,
    stride: int = 1,
) -> COIR:
    """CORF: in-major response-field metadata.

    Output o is in the response field of input i at plane k iff
    ``o*stride + offsets[k] == i``, i.e. ``o == (i - offsets[k]) / stride``
    where the division is exact and in-bounds.
    """
    out_res = max(resolution // stride, 1) if stride > 1 else resolution
    grid = SortedGrid(out_coords, out_mask, out_res)
    diff = in_coords[:, None, :] - offsets[None, :, :]  # (Vi, K, 3)
    exact = jnp.all(diff % stride == 0, axis=-1)
    probe = diff // stride
    valid = in_mask[:, None] & exact
    idx = grid.lookup(probe, valid)
    return COIR(idx, _pack_bitmask(idx), in_mask)


def transpose_flavor(
    coir: COIR, minor_capacity: int
) -> COIR:
    """Convert CIRF<->CORF by inverting the (major, minor, plane) relation.

    Each (major m, plane k) -> minor i pair becomes (i, k) -> m. Weight-plane
    slot is preserved, so at most one partner per (minor, plane) exists for
    convolution metadata and the scatter is collision-free.
    """
    v, k = coir.indices.shape
    minor = coir.indices  # (V, K)
    major = jnp.broadcast_to(jnp.arange(v, dtype=jnp.int32)[:, None], (v, k))
    plane = jnp.broadcast_to(jnp.arange(k, dtype=jnp.int32)[None, :], (v, k))
    ok = minor >= 0
    out = jnp.full((minor_capacity, k), -1, jnp.int32)
    flat_rows = jnp.where(ok, minor, minor_capacity)  # drop invalid
    out = out.at[flat_rows.reshape(-1), plane.reshape(-1)].set(
        jnp.where(ok, major, -1).reshape(-1), mode="drop"
    )
    row_mask = jnp.any(out >= 0, axis=1)
    return COIR(out, _pack_bitmask(out), row_mask)


# ---------------------------------------------------------------------------
# Metadata size accounting (paper §IV-A compression claim; benchmarks use it)
# ---------------------------------------------------------------------------

def coir_size_words(coir: COIR) -> jax.Array:
    """Logical COIR size in 32-bit words: per active entry, 1 header word
    (bitmask) + 1 self index + one word per active partner."""
    act = coir.mask.astype(jnp.int32)
    return jnp.sum((2 + coir.popcount()) * act)


def rulebook_size_words(coir: COIR) -> jax.Array:
    """Size of the baseline per-weight-plane rulebook (SCN reference impl):
    every valid (in, out) pair appears as 2 words in some weight plane list."""
    return 2 * coir.n_pairs()


def kernel_offsets_np(kernel_size: int, centered: bool | None = None) -> np.ndarray:
    from repro.core.hashgrid import kernel_offsets

    return kernel_offsets(kernel_size, centered)
