"""AdMAC: adjacency-map / neighbourhood-search accelerator, TPU adaptation.

The paper's AdMAC (Section IV-E) streams voxels through a two-level banked
spatial hash so 26 neighbours resolve in one SRAM cycle. TPUs have no banked
random-access scratchpad, so the TPU-idiomatic equivalent is *sorted linear
keys + vectorized binary search*: every (voxel, kernel-offset) pair issues one
``searchsorted`` probe, fully batched on the VPU. Complexity O(V*K*log V) with
perfect vectorization — this is the role the 8-banked {y,z}-interleaved hash
plays on the ASIC.

All functions are jit-compatible with static capacities.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.sparse.tensor import PAD_COORD, linear_key


def kernel_offsets(kernel_size: int, centered: bool | None = None) -> np.ndarray:
    """Lexicographic (K^3, 3) integer offsets for a cubic kernel.

    Odd kernels default to centered offsets (submanifold convs); even kernels
    to [0, K) offsets (strided down/up-sampling convs), matching SCN.
    """
    if centered is None:
        centered = kernel_size % 2 == 1
    lo = -(kernel_size // 2) if centered else 0
    rng = np.arange(lo, lo + kernel_size)
    grid = np.stack(np.meshgrid(rng, rng, rng, indexing="ij"), axis=-1)
    return grid.reshape(-1, 3).astype(np.int32)


class SortedGrid:
    """Sorted-key index over an active-voxel set (the adjacency 'hash')."""

    def __init__(self, coords: jax.Array, mask: jax.Array, resolution: int):
        self.coords = coords
        self.mask = mask
        self.resolution = resolution
        keys = linear_key(coords, resolution, mask)
        order = jnp.argsort(keys)
        self.sorted_keys = keys[order]
        self.sorted_idx = order.astype(jnp.int32)

    def lookup(self, query_coords: jax.Array, query_valid: jax.Array) -> jax.Array:
        """Indices into the voxel list for each query coord; -1 if absent."""
        r = self.resolution
        in_bounds = jnp.all((query_coords >= 0) & (query_coords < r), axis=-1)
        valid = query_valid & in_bounds
        qkey = linear_key(query_coords, r, valid)
        pos = jnp.searchsorted(self.sorted_keys, qkey)
        pos = jnp.clip(pos, 0, self.sorted_keys.shape[0] - 1)
        found = valid & (self.sorted_keys[pos] == qkey)
        return jnp.where(found, self.sorted_idx[pos], -1)


@functools.partial(jax.jit, static_argnames=("resolution", "stride"))
def query_neighbors(
    out_coords: jax.Array,
    out_mask: jax.Array,
    in_coords: jax.Array,
    in_mask: jax.Array,
    offsets: jax.Array,
    resolution: int,
    stride: int = 1,
) -> jax.Array:
    """For each output voxel, index of the input voxel at each kernel offset.

    input coordinate probed for output o and offset d is ``o * stride + d``
    (in input-space units). Returns (V_out, K) int32 with -1 where the input
    voxel is inactive / out of bounds / the output row is padding.
    """
    grid = SortedGrid(in_coords, in_mask, resolution)
    probe = out_coords[:, None, :] * stride + offsets[None, :, :]  # (Vo, K, 3)
    valid = out_mask[:, None] & jnp.ones(offsets.shape[0], bool)[None, :]
    return grid.lookup(probe, valid)


@functools.partial(jax.jit, static_argnames=("resolution",))
def build_neighbor_table(
    coords: jax.Array, mask: jax.Array, offsets: jax.Array, resolution: int
) -> jax.Array:
    """Adjacency map of an active set against itself (submanifold case)."""
    return query_neighbors(coords, mask, coords, mask, offsets, resolution, stride=1)


@functools.partial(jax.jit, static_argnames=("factor", "capacity_out", "resolution"))
def downsample_coords(
    coords: jax.Array,
    mask: jax.Array,
    resolution: int,
    factor: int = 2,
    capacity_out: int | None = None,
):
    """Output active set of a strided conv: unique(floor(coords / factor)).

    Returns (out_coords (Vo,3) int32, out_mask (Vo,)) with Vo = capacity_out
    (defaults to the input capacity). Output rows are sorted by linear key,
    giving a deterministic canonical order.
    """
    cap_out = capacity_out or coords.shape[0]
    down = jnp.where(mask[:, None], coords // factor, PAD_COORD)
    keys = linear_key(down, max(resolution // factor, 1), mask)
    sorted_keys = jnp.sort(keys)
    is_first = jnp.concatenate(
        [jnp.array([True]), sorted_keys[1:] != sorted_keys[:-1]]
    ) & (sorted_keys < jnp.int32(max(resolution // factor, 1)) ** 3)
    # Compact first-occurrences into the output prefix.
    dest = jnp.cumsum(is_first.astype(jnp.int32)) - 1
    out_keys = jnp.full((cap_out,), jnp.int32(2**31 - 1))
    out_keys = out_keys.at[jnp.where(is_first, dest, cap_out)].set(
        sorted_keys, mode="drop"
    )
    n_out = jnp.sum(is_first.astype(jnp.int32))
    out_mask = jnp.arange(cap_out) < n_out
    r_out = max(resolution // factor, 1)
    out_coords = jnp.stack(
        [
            out_keys // (r_out * r_out),
            (out_keys // r_out) % r_out,
            out_keys % r_out,
        ],
        axis=-1,
    ).astype(jnp.int32)
    out_coords = jnp.where(out_mask[:, None], out_coords, PAD_COORD)
    return out_coords, out_mask


class UpdatableSortedGrid:
    """Updatable sorted-key index: the streaming seam of the AdMAC search.

    ``SortedGrid`` and the host planner's builders (``host_meta``) re-sort
    the key set per scene — fine for i.i.d. uploads, wasteful for a 10–20 Hz LiDAR stream
    where frame t+1 keeps most of frame t's voxels. This numpy structure
    keeps only the *active* keys sorted (paired with their row ids) and
    supports the three stream mutations without a full re-sort:

    * ``shift(key_offset)`` — uniform ego motion. Linear keys are linear in
      the coordinate, so a constant coordinate shift is a constant key
      offset and preserves sorted order entirely (O(n) add).
    * ``delete(keys)`` — batched removal by sorted key (O(n) compress).
    * ``insert(keys, rows)`` — batched insertion of sorted new keys at
      their ``searchsorted`` positions (O(n + m log n) merge, no re-sort).

    ``lookup`` returns bit-identical results to ``SortedGrid.lookup`` on
    the same active set: active keys are unique, and the sentinel rows the
    capacity-shaped grid carries can never match a valid query, so
    dropping them changes nothing.
    """

    def __init__(self, resolution: int, keys: np.ndarray | None = None,
                 rows: np.ndarray | None = None):
        self.resolution = resolution
        self.keys = (np.empty((0,), np.int32) if keys is None
                     else np.asarray(keys, np.int32))
        self.rows = (np.empty((0,), np.int32) if rows is None
                     else np.asarray(rows, np.int32))
        if self.keys.shape != self.rows.shape:
            raise ValueError(
                f"keys {self.keys.shape} / rows {self.rows.shape} mismatch")

    @classmethod
    def from_coords(cls, coords: np.ndarray, mask: np.ndarray,
                    resolution: int) -> "UpdatableSortedGrid":
        from repro.core.host_meta import linear_key_np

        mask = np.asarray(mask)
        rows = np.flatnonzero(mask).astype(np.int32)
        keys = linear_key_np(np.asarray(coords)[rows], resolution)
        order = np.argsort(keys, kind="stable")
        return cls(resolution, keys[order], rows[order])

    def __len__(self) -> int:
        return int(self.keys.shape[0])

    def shift(self, key_offset: int) -> None:
        """Apply a uniform key offset (ego motion after removals: every
        remaining coordinate stays in bounds, so no per-component borrow
        can break the linear-key arithmetic)."""
        if key_offset:
            self.keys = self.keys + np.int32(key_offset)

    def delete(self, keys: np.ndarray) -> None:
        """Remove ``keys`` (sorted or not; must all be present)."""
        keys = np.asarray(keys, np.int32)
        if not keys.size:
            return
        pos = np.searchsorted(self.keys, keys)
        if (pos >= len(self.keys)).any() or (self.keys[np.minimum(
                pos, len(self.keys) - 1)] != keys).any():
            raise KeyError("delete of keys not present in the grid")
        keep = np.ones(len(self.keys), bool)
        keep[pos] = False
        self.keys = self.keys[keep]
        self.rows = self.rows[keep]

    def insert(self, keys: np.ndarray, rows: np.ndarray) -> None:
        """Insert new (key, row) pairs (keys must be sorted + absent)."""
        keys = np.asarray(keys, np.int32)
        rows = np.asarray(rows, np.int32)
        if not keys.size:
            return
        pos = np.searchsorted(self.keys, keys)
        self.keys = np.insert(self.keys, pos, keys)
        self.rows = np.insert(self.rows, pos, rows)

    def lookup(self, query_coords: np.ndarray,
               query_valid: np.ndarray) -> np.ndarray:
        """Row ids for query coords; -1 if absent (``SortedGrid`` twin)."""
        from repro.core.host_meta import linear_key_np

        r = self.resolution
        q = np.asarray(query_coords)
        in_bounds = np.all((q >= 0) & (q < r), axis=-1)
        valid = np.asarray(query_valid) & in_bounds
        qkey = linear_key_np(q, r, valid)
        if not len(self.keys):
            return np.full(qkey.shape, -1, np.int32)
        pos = np.searchsorted(self.keys, qkey)
        pos = np.minimum(pos, len(self.keys) - 1)
        found = valid & (self.keys[pos] == qkey)
        return np.where(found, self.rows[pos], -1).astype(np.int32)


def upsample_coords(coords: jax.Array, mask: jax.Array):
    """Output set of a transposed (deconv) layer restoring a finer level.

    SCN U-Nets restore the *saved* finer-level active set rather than
    expanding; callers pass the skip connection's coords, so this is just a
    passthrough that documents the contract.
    """
    return coords, mask
