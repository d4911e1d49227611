"""SOAR: Surface-Orientation-Aware Reordering of pointclouds (§IV-B).

Host-side (numpy) offline pass, exactly the paper's algorithm:

1. Build the adjacency map (from ``repro.core.hashgrid`` neighbour tables)
   as CSR neighbour lists of the active rows, so the pass costs time in
   proportion to active voxels and edges, not to the table's capacity.
2. Pick the unselected voxel with the minimum number of neighbours as the
   root (a surface corner).
3. Grow an m-ary tree in breadth-first order: pop voxels from the Neighbour
   Queue; skip already-selected ones; otherwise append to the chunk, mark
   selected, and push all its neighbours.
4. When the chunk reaches the size bound, emit it; the next root is the
   minimum-degree voxel in the Neighbour Queue, which is then flushed.

Hierarchical SOAR (§V-B): chunks are reinterpreted as points (adjacent iff
any member voxels are adjacent) and SOAR recurses with the outer level's
size bound, innermost to outermost.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass

import numpy as np


@dataclass
class SoarResult:
    order: np.ndarray        # (n_active,) permutation: new position -> old index
    chunk_starts: np.ndarray  # (n_chunks + 1,) boundaries into `order`

    @property
    def n_chunks(self) -> int:
        return len(self.chunk_starts) - 1


def soar_order(
    neighbor_table: np.ndarray,
    active_mask: np.ndarray,
    max_chunk_voxels: int,
) -> SoarResult:
    """Chunked breadth-first reordering of the active voxels.

    The BFS runs over positions among the active rows (``act`` maps them
    back to table rows). A voxel's degree counts its valid non-self
    entries, inactive neighbours included; its CSR list drops those
    neighbours, which the BFS would never enqueue.
    """
    table = np.asarray(neighbor_table)
    act = np.flatnonzero(np.asarray(active_mask, bool))
    n = len(act)
    local = np.full(table.shape[0], -1, np.int64)  # table row -> position
    local[act] = np.arange(n)
    rows = table[act]
    valid = (rows >= 0) & (rows != act[:, None])
    degree = valid.sum(axis=1)
    nb = local[np.where(valid, rows, act[:, None])]
    keep = valid & (nb >= 0)
    flat = nb[keep].tolist()
    starts = np.concatenate([[0], np.cumsum(keep.sum(axis=1))]).tolist()
    deg = degree.tolist()
    # min-degree order of the active voxels, used for root selection
    root_order = np.argsort(degree, kind="stable").tolist()
    root_ptr = 0
    selected = bytearray(n)

    order: list[int] = []
    chunk_starts = [0]
    queue: deque[int] = deque()
    chunk_count = 0

    def next_root() -> int:
        nonlocal root_ptr
        # prefer min-degree voxel from the Neighbour Queue (paper), else the
        # globally min-degree unselected voxel
        cands = [q for q in queue if not selected[q]]
        if cands:
            return min(cands, key=deg.__getitem__)
        while root_ptr < n:
            r = root_order[root_ptr]
            root_ptr += 1
            if not selected[r]:
                return r
        return -1

    while len(order) < n:
        root = next_root()
        if root < 0:
            break
        queue.clear()
        queue.append(root)
        while queue and chunk_count < max_chunk_voxels:
            u = queue.popleft()
            if selected[u]:
                continue
            selected[u] = 1
            order.append(u)
            chunk_count += 1
            for w in flat[starts[u]:starts[u + 1]]:
                if not selected[w]:
                    queue.append(w)
        if chunk_count >= max_chunk_voxels or not queue:
            if chunk_count:
                chunk_starts.append(len(order))
                chunk_count = 0
            # queue is flushed after root selection of next chunk (paper);
            # we keep it until next_root() has inspected it, then clear there
    if chunk_starts[-1] != len(order):
        chunk_starts.append(len(order))
    rows_in_order = act[np.array(order, np.int64)].astype(np.int64, copy=False)
    return SoarResult(rows_in_order, np.array(chunk_starts, np.int64))


def soar_hierarchical(
    neighbor_table: np.ndarray,
    active_mask: np.ndarray,
    chunk_sizes: list[int],
) -> SoarResult:
    """Multi-level SOAR: innermost chunk size first (§V-B).

    Returns the flattened voxel order with chunk boundaries of the
    *innermost* level; outer levels permute whole inner chunks.
    """
    assert chunk_sizes, "need at least one level"
    inner = soar_order(neighbor_table, active_mask, chunk_sizes[0])
    if len(chunk_sizes) == 1:
        return inner
    # Build chunk-level adjacency: chunks adjacent iff any voxel pair is.
    n_chunks = inner.n_chunks
    chunk_of = np.full(neighbor_table.shape[0], -1, np.int64)
    for c in range(n_chunks):
        seg = inner.order[inner.chunk_starts[c]:inner.chunk_starts[c + 1]]
        chunk_of[seg] = c
    adj = [set() for _ in range(n_chunks)]
    for i in np.flatnonzero(np.asarray(active_mask)):
        ci = chunk_of[i]
        if ci < 0:
            continue
        for w in neighbor_table[i]:
            if w >= 0 and chunk_of[w] >= 0 and chunk_of[w] != ci:
                adj[ci].add(int(chunk_of[w]))
    kmax = max((len(a) for a in adj), default=1) or 1
    chunk_nbr = np.full((n_chunks, kmax), -1, np.int64)
    for c, a in enumerate(adj):
        lst = sorted(a)
        chunk_nbr[c, : len(lst)] = lst
    outer_budget = max(chunk_sizes[1] // max(chunk_sizes[0], 1), 1)
    outer = soar_hierarchical(
        chunk_nbr, np.ones(n_chunks, bool), [outer_budget] + [
            s // max(chunk_sizes[0], 1) for s in chunk_sizes[2:]
        ],
    )
    # Flatten: permute inner chunks by the outer order.
    order = np.concatenate(
        [
            inner.order[inner.chunk_starts[c]:inner.chunk_starts[c + 1]]
            for c in outer.order
        ]
    )
    sizes = np.diff(inner.chunk_starts)[outer.order]
    chunk_starts = np.concatenate([[0], np.cumsum(sizes)])
    return SoarResult(order, chunk_starts)


def raster_order(coords: np.ndarray, active_mask: np.ndarray,
                 axes=(0, 1, 2)) -> np.ndarray:
    """Raster-scan baseline orderings (Fig 23): lexicographic sort along the
    given axis priority."""
    act = np.flatnonzero(np.asarray(active_mask))
    keycols = [coords[act, a] for a in reversed(axes)]
    return act[np.lexsort(keycols)]


def tiled_unique_input_accesses(
    order: np.ndarray, cirf_indices: np.ndarray, tile_out: int
) -> int:
    """Data-access cost model used for Fig 23: process outputs in `order` in
    tiles of `tile_out`; each tile fetches its unique input partners once.
    Returns total input-row fetches across tiles."""
    total = 0
    for s in range(0, len(order), tile_out):
        rows = cirf_indices[order[s:s + tile_out]]
        ids = rows[rows >= 0]
        total += len(np.unique(ids))
    return total
