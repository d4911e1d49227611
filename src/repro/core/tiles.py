"""Tiled COIR metadata (dM) for SSpNNA execution (§V-C processing flow).

OTF-SPADE re-groups the adjacency/COIR entries into per-tile metadata blocks
sized by the SPADE plan: each tile owns a run of dO consecutive SOAR-ordered
outputs, the tile's unique input rows (its L1 working set), and *tile-local*
partner indices. Tiles whose unique-input count overshoots the RST
allocation are split in two (next power of two), exactly the paper's
overshoot rule; the budgeted planner the serving engine uses instead closes
a tile before the row that would overflow it. A *single row* whose working
set overshoots ``delta_i`` is split across plane groups (unbudgeted mode)
or is a hard planning error (budgeted mode) — pairs are never silently
dropped; ``TilePlan`` carries the accounting (``n_row_splits`` /
``dropped_pairs``) so callers can assert the no-drop invariant.

Both planners work a window of ``dO`` rows at a time in numpy, never row
by row: one sort of a run of windows' packed (window, partner, slot) pairs
gives every window's working set at once, so the cost follows the active
rows' pairs, and the same sorted pairs fill the tile tables.

Host-side numpy; the result is a stack of fixed-shape arrays consumed by
the Pallas kernel (``repro.kernels.sspnna``) and, via ``dma_tile_tables``,
by the fused kernel's scalar-prefetched DMA engines (§V-A-3): the ordered
datatype gets one block entry per tile, the unordered datatype one
per-voxel entry — exactly the two tables the fused kernel walks.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import NamedTuple

import numpy as np

from repro.analysis import spans


@dataclass
class TilePlan:
    out_rows: np.ndarray    # (T, dO) int32 global output row per tile slot, -1 pad
    in_rows: np.ndarray     # (T, dI) int32 global input rows (tile working set), -1 pad
    local_idx: np.ndarray   # (T, dO, K) int32 index into the tile's in_rows, -1 hole
    pair_counts: np.ndarray  # (T,) int32 valid pairs per tile (ops-per-tile / dC / dN)
    n_row_splits: int = 0   # tiles created by splitting one row across planes
    dropped_pairs: int = 0  # invariant: always 0 (kept so callers can assert it)

    @property
    def n_tiles(self) -> int:
        return self.out_rows.shape[0]

    @property
    def delta_o(self) -> int:
        return self.out_rows.shape[1]

    @property
    def delta_i(self) -> int:
        return self.in_rows.shape[1]


class DmaTileTables(NamedTuple):
    """``TilePlan`` re-emitted in the layout the fused kernel's DMA engines
    walk (scalar-prefetch arguments, §V-A-3):

    * ``in_rows``: (T, dI) int32, pad slots clamped to row 0 — every entry is
      a safe HBM source; validity lives in ``local_idx`` (no hole ever
      references a pad slot, so the clamped rows are gathered-and-ignored).
    * ``out_rows``: (T, dO) int32, pad slots redirected to the trash row
      ``n_out`` — the kernel scatters every slot unconditionally into an
      ``(n_out + 1)``-row buffer and the caller slices the trash row off.
    * ``pair_counts``: (T,) int32, the dead-tile predicate (0 ⇒ the kernel
      skips the tile's DMAs and MACs entirely).
    """

    in_rows: np.ndarray
    out_rows: np.ndarray
    pair_counts: np.ndarray


def dma_tile_tables(plan: TilePlan, n_out: int) -> DmaTileTables:
    """Emit ``plan``'s tables in DMA-table layout for an ``n_out``-row scene."""
    in_rows = np.maximum(plan.in_rows, 0).astype(np.int32)
    out_rows = np.where(plan.out_rows < 0, n_out, plan.out_rows).astype(np.int32)
    return DmaTileTables(in_rows, out_rows,
                         plan.pair_counts.astype(np.int32))


def max_tiles(n_rows: int, delta_o: int, delta_i: int, kernel_volume: int) -> int:
    """Upper bound on the tile count of the budgeted (``n_tiles``) planner.

    A tile closes either full-by-rows (at most ceil(n/dO) such tiles) or
    full-by-inputs, holding more than ``delta_i - K`` unique inputs; since
    per-tile unique inputs sum to at most ``n_rows * K`` pairs, the second
    kind is bounded too. Used to pin static shapes for the serving engine.
    """
    n = max(n_rows, 1)
    by_rows = math.ceil(n / delta_o)
    by_inputs = math.ceil(n * kernel_volume / max(delta_i - kernel_volume + 1, 1))
    return by_rows + by_inputs + 1


def _split_row_by_planes(part: np.ndarray, delta_i: int) -> list[np.ndarray]:
    """Partition one row's K planes into groups whose unique partner sets fit
    ``delta_i``. Each plane contributes at most one partner, so the greedy
    walk needs at most ceil(n_unique / delta_i) groups and drops nothing."""
    k = part.shape[0]
    groups: list[list[int]] = []
    cur: list[int] = []
    cur_uniq: set[int] = set()
    for p in range(k):
        partner = int(part[p])
        new = {partner} if partner >= 0 else set()
        if cur and len(cur_uniq | new) > delta_i:
            groups.append(cur)
            cur, cur_uniq = [], set()
        cur.append(p)
        cur_uniq |= new
    if cur:
        groups.append(cur)
    return [np.asarray(g, np.int64) for g in groups]


def _window_pairs(part: np.ndarray, start: int, stop: int,
                  width: int) -> tuple[np.ndarray, ...]:
    """The pairs of rows ``[start, stop)`` of ``part`` (COIR rows in walk
    order), cut into windows of ``width`` rows from ``start``, from one sort
    of packed ``(window, partner, slot)`` keys; ``slot`` is the row in the
    window × K + the plane. Returns ``window``, ``partner``, ``slot`` and
    ``head`` (the pair is its partner's first in the window: a window's
    heads, in order, are its sorted working set)."""
    k = part.shape[1]
    flat = part[start:stop].ravel()
    pos = np.flatnonzero(flat >= 0)
    val_bits = int(flat.max(initial=1)).bit_length()
    slot_bits = (width * k - 1).bit_length()
    assert ((stop - start) // width).bit_length() + val_bits + slot_bits < 64
    key = flat[pos].astype(np.int64) << slot_bits
    key += (pos // (width * k)) << (val_bits + slot_bits)
    key += pos % (width * k)
    key.sort()
    slot = key & ((1 << slot_bits) - 1)
    key >>= slot_bits
    head = np.ones(len(key), bool)
    head[1:] = key[1:] != key[:-1]
    return key >> val_bits, key & ((1 << val_bits) - 1), slot, head


def build_tile_plan(
    cirf_indices: np.ndarray,
    order: np.ndarray,
    delta_o: int,
    delta_i: int,
    n_tiles: int | None = None,
) -> TilePlan:
    """Regroup out-major COIR into fixed-shape tile metadata.

    cirf_indices: (V, K) global partner indices (-1 holes).
    order: SOAR (or raster) ordering of active output rows.
    n_tiles: when given, use the budgeted greedy planner — every tile fits
        ``delta_i`` by construction (close a tile before a row would
        overflow it) — and pad the tile stack to exactly ``n_tiles`` so the
        output shapes are scene-independent (serving-engine mode). Raises
        ``ValueError`` if the scene needs more tiles than that, or if a
        single row's working set cannot fit ``delta_i`` (pairs are never
        silently dropped).

    In unbudgeted mode a single row whose unique partners overshoot
    ``delta_i`` (only possible when ``delta_i < K``) is split across plane
    groups into several tiles that share the output row; such plans require
    an accumulating scatter (``TilePlan.n_row_splits > 0`` flags them, and
    the fused kernel's overwrite-DMA path refuses them).

    The budgeted walk assumes that every tile from where it stands fills
    by rows: it sorts the pairs of the next ``delta_o``-row windows, and
    each window up to the first whose working set overflows ``delta_i`` is
    a tile. That window closes at the last row whose running count of
    first-seen partners fits, and the walk goes on after it, sorting half
    as many windows (twice as many after a pass with no overflow). The
    unbudgeted mode sorts its fixed windows once and halves only those that
    overflow. A tile's sorted pairs fill its row: the first pair of each
    partner gives ``in_rows``, and a pair's ``local_idx`` is that first
    pair's rank. Adds ``rows``, ``tiles`` and ``input_closed`` (the tiles
    that ``delta_i`` closed short of ``delta_o`` rows) to the innermost
    span's counters.
    """
    cirf_indices = np.asarray(cirf_indices)
    k = cirf_indices.shape[1]
    order = np.asarray(order, np.int64)
    if n_tiles is not None and delta_i < k:
        raise ValueError(f"delta_i {delta_i} < kernel volume {k}")
    part = cirf_indices[order]          # (n, K): the COIR rows in walk order
    n = len(order)

    starts: list[int] = []    # each tile's first position in ``order``
    lengths: list[int] = []   # and its rows
    chunks: list[tuple] = []  # (offset, window, partner, slot): the sorted
    #                           pairs of tiles offset + window
    n_row_splits = input_closed = 0

    def take(start, stop, win, partner, slot, w0, w1):
        """Windows ``[w0, w1)`` of ``win`` (cut from ``start``) as tiles."""
        lo, hi = np.searchsorted(win, (w0, w1))
        chunks.append((len(starts) - w0, win[lo:hi], partner[lo:hi],
                       slot[lo:hi]))
        firsts = range(start + w0 * delta_o,
                       min(stop, start + w1 * delta_o), delta_o)
        starts.extend(firsts)
        lengths.extend(min(delta_o, stop - a) for a in firsts)

    if n_tiles is not None:
        s, reach = 0, -(-n // delta_o)   # reach: the windows a pass sorts
        while s < n:
            stop = min(n, s + reach * delta_o)
            win, partner, slot, head = _window_pairs(part, s, stop, delta_o)
            n_win = -(-(stop - s) // delta_o)
            fits = np.bincount(win[head], minlength=n_win) <= delta_i
            ok = n_win if fits.all() else int(np.argmin(fits))
            take(s, stop, win, partner, slot, 0, ok)
            if ok == n_win:
                s, reach = stop, 2 * reach
                continue
            # window ``ok`` overflows: its tile closes before the first row
            # whose first-seen partners take the working set past delta_i
            b = s + ok * delta_o
            lo, hi = np.searchsorted(win, (ok, ok + 1))
            rows = slot[lo:hi] // k
            new = np.bincount(rows[head[lo:hi]], minlength=delta_o)
            fit = int(np.count_nonzero(np.cumsum(new) <= delta_i))
            if fit == 0:  # can't happen while delta_i >= K; be loud
                raise ValueError(
                    f"row {int(order[b])} working set {int(new[0])} > "
                    f"delta_i {delta_i} in budgeted mode (would drop pairs)")
            keep = lo + np.flatnonzero(rows < fit)
            chunks.append((len(starts) - ok, win[keep], partner[keep],
                           slot[keep]))
            starts.append(b)
            lengths.append(fit)
            input_closed += 1
            s, reach = b + fit, max(reach // 2, 2 * ok, 1)
        if len(starts) > n_tiles:
            raise ValueError(
                f"scene needs {len(starts)} tiles > budget {n_tiles} "
                f"(delta_o={delta_o}, delta_i={delta_i})")
    else:
        def emit(a: int, b: int):
            """Split rows ``[a, b)`` until the working set fits delta_i."""
            nonlocal n_row_splits, input_closed
            win, partner, slot, head = _window_pairs(part, a, b, b - a)
            if np.count_nonzero(head) <= delta_i:
                chunks.append((len(starts), win, partner, slot))
                starts.append(a)
                lengths.append(b - a)
            elif b - a > 1:
                input_closed += 1
                emit(a, a + (b - a) // 2)
                emit(a + (b - a) // 2, b)
            else:  # single-row overshoot: split across plane groups
                groups = _split_row_by_planes(part[a], delta_i)
                n_row_splits += len(groups) - 1
                input_closed += len(groups) - 1
                for g in groups:
                    keep = np.isin(slot, g)
                    chunks.append((len(starts), win[keep], partner[keep],
                                   slot[keep]))
                    starts.append(a)
                    lengths.append(1)

        win, partner, slot, head = _window_pairs(part, 0, n, delta_o)
        n_win = -(-n // delta_o)
        over = np.bincount(win[head], minlength=n_win) > delta_i
        w0 = 0
        for w in [*np.flatnonzero(over).tolist(), n_win]:
            take(0, n, win, partner, slot, w0, w)
            if w < n_win:
                emit(w * delta_o, min(n, (w + 1) * delta_o))
            w0 = w + 1

    t = n_tiles if n_tiles is not None else len(starts)
    out_rows = np.full((t, delta_o), -1, np.int32)
    in_rows = np.full((t, delta_i), -1, np.int32)
    local_idx = np.full((t, delta_o, k), -1, np.int32)
    pair_counts = np.zeros((t,), np.int32)
    if starts:
        lens = np.asarray(lengths)
        tile_of = np.repeat(np.arange(len(lens)), lens)
        at = np.arange(len(tile_of)) - np.repeat(np.cumsum(lens) - lens, lens)
        out_rows[tile_of, at] = order[np.asarray(starts)[tile_of] + at]
        tile = np.concatenate([t0 + w for t0, w, _, _ in chunks])
        partner = np.concatenate([c[2] for c in chunks])
        slot = np.concatenate([c[3] for c in chunks])
        # a tile's pairs are one run sorted by partner: its heads (a
        # partner's first pair) are its working set, in order, and a pair's
        # local index is its head's rank in the tile
        first = np.ones(len(tile), bool)
        first[1:] = tile[1:] != tile[:-1]
        head = first.copy()
        head[1:] |= partner[1:] != partner[:-1]
        h = np.flatnonzero(head)
        rank = np.arange(len(h))
        rank -= np.maximum.accumulate(np.where(first[h], rank, 0))
        assert rank.max(initial=-1) < delta_i, \
            "planner invariant: working set fits"
        in_rows[tile[h], rank] = partner[h]
        local_idx.reshape(t, -1)[tile, slot] = rank[np.cumsum(head) - 1]
        pair_counts[: len(lens)] = np.bincount(tile, minlength=len(lens))
    spans.count(rows=n, tiles=len(starts), input_closed=input_closed)
    return TilePlan(out_rows, in_rows, local_idx, pair_counts,
                    n_row_splits=n_row_splits, dropped_pairs=0)


def plan_dma_tables(plan: TilePlan) -> dict:
    """DMA descriptor accounting (§V-A-3): ordered datatype -> one block
    entry per tile; unordered datatype -> one entry per voxel. Returns entry
    counts + transferred elements for the energy/bandwidth model."""
    t = plan.n_tiles
    in_valid = (plan.in_rows >= 0).sum()
    out_valid = (plan.out_rows >= 0).sum()
    return {
        "block_entries": t,            # ordered side: 1 per tile
        "voxel_entries": int(in_valid),  # unordered side: per voxel
        "in_rows_transferred": int(in_valid),
        "out_rows_transferred": int(out_valid),
    }


def modeled_hbm_bytes(plan: TilePlan, c_in: int, n_out: int,
                      itemsize: int = 4) -> dict:
    """Modeled HBM feature traffic of the three execution paths for one conv
    with ``c_in`` input and ``n_out`` output channels (§V-A).

    The fused kernel streams every DMA-table slot of every *alive* tile —
    pad slots are clamped entries and transfer too, so the model charges
    the padded ``dI`` / ``dO`` widths, exactly what ``_fused_kernel``'s DMA
    loops issue; dead tiles are skipped. The pre-gathered paths transfer
    the valid entries through the gather/scatter *and* round-trip the full
    ``(T, dI, C)`` working-set copy and ``(T, dO, N)`` tile-output stack
    through HBM (padded, dead tiles included — XLA can't skip them).
    Metadata (int32 tables) is counted once for every path.
    """
    d = plan_dma_tables(plan)
    t, d_o, d_i = plan.n_tiles, plan.delta_o, plan.delta_i
    k = plan.local_idx.shape[2]
    meta = (t * d_i + t * d_o + t * d_o * k + t) * 4  # int32 tables
    valid_read = d["in_rows_transferred"] * c_in * itemsize
    valid_write = d["out_rows_transferred"] * n_out * itemsize
    alive = int((plan.pair_counts > 0).sum())
    gathered = t * d_i * c_in * itemsize       # full (T, dI, C) copy
    tile_out = t * d_o * n_out * itemsize      # full (T, dO, N) stack
    # gather write + kernel read of the copy, tile-out write + scatter read
    roundtrip = meta + valid_read + valid_write + 2 * gathered + 2 * tile_out
    return {
        "alive_tiles": alive,
        "fused": meta + alive * (d_i * c_in + d_o * n_out) * itemsize,
        "pregathered": roundtrip,
        "reference_gather": roundtrip,
    }
