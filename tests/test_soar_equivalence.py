"""SOAR over active rows only is bitwise the per-row algorithm it replaced.

``soar_order_oracle`` below is the earlier implementation of
``repro.core.soar.soar_order``, kept verbatim: it builds a neighbour list
for every table row and walks the BFS with numpy scalar lookups, so its
cost follows the table's capacity. The served path now runs the CSR form;
these tests hold it to the same ``order`` and ``chunk_starts`` on random
tables, on every level of a capacity-padded pyramid, through hierarchical
SOAR, and through the plan's tile tables."""
from collections import deque

import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core import soar
from repro.core.hashgrid import kernel_offsets
from repro.core.host_meta import build_cirf_np
from repro.data.scenes import N_CLASSES, make_scene
from repro.engine import plan as plan_mod
from repro.engine.plan import level_geometry
from repro.models.scn import UNetConfig
from repro.sparse.tensor import SparseVoxelTensor


def _neighbor_lists(neighbor_table: np.ndarray) -> list[np.ndarray]:
    """Per-voxel neighbour index lists from a (V, K) table (-1 holes),
    excluding self-edges."""
    v = neighbor_table.shape[0]
    lists = []
    for i in range(v):
        nb = neighbor_table[i]
        nb = nb[(nb >= 0) & (nb != i)]
        lists.append(nb)
    return lists


def soar_order_oracle(
    neighbor_table: np.ndarray,
    active_mask: np.ndarray,
    max_chunk_voxels: int,
) -> soar.SoarResult:
    """Chunked breadth-first reordering of the active voxels."""
    v = neighbor_table.shape[0]
    nbrs = _neighbor_lists(neighbor_table)
    degree = np.array([len(n) for n in nbrs])
    active = np.asarray(active_mask, bool).copy()
    selected = np.zeros(v, bool)
    # min-degree order among active voxels, used for root selection
    root_order = np.argsort(degree + np.where(active, 0, 1 << 30), kind="stable")
    root_ptr = 0

    order: list[int] = []
    chunk_starts = [0]
    queue: deque[int] = deque()
    n_active = int(active.sum())
    chunk_count = 0

    def next_root() -> int:
        nonlocal root_ptr
        # prefer min-degree voxel from the Neighbour Queue (paper), else the
        # globally min-degree unselected voxel
        if queue:
            cands = [q for q in queue if active[q] and not selected[q]]
            if cands:
                return min(cands, key=lambda q: degree[q])
        while root_ptr < v:
            r = root_order[root_ptr]
            root_ptr += 1
            if active[r] and not selected[r]:
                return int(r)
        return -1

    while len(order) < n_active:
        root = next_root()
        if root < 0:
            break
        queue.clear()
        queue.append(root)
        while queue and chunk_count < max_chunk_voxels:
            u = queue.popleft()
            if selected[u] or not active[u]:
                continue
            selected[u] = True
            order.append(u)
            chunk_count += 1
            for w in nbrs[u]:
                if active[w] and not selected[w]:
                    queue.append(int(w))
        if chunk_count >= max_chunk_voxels or not queue:
            if chunk_count:
                chunk_starts.append(len(order))
                chunk_count = 0
            # queue is flushed after root selection of next chunk (paper);
            # we keep it until next_root() has inspected it, then clear there
    if chunk_starts[-1] != len(order):
        chunk_starts.append(len(order))
    return soar.SoarResult(np.array(order, np.int64),
                           np.array(chunk_starts, np.int64))


def _assert_same(got: soar.SoarResult, want: soar.SoarResult):
    assert got.order.dtype == want.order.dtype == np.int64
    assert got.chunk_starts.dtype == want.chunk_starts.dtype == np.int64
    np.testing.assert_array_equal(got.order, want.order)
    np.testing.assert_array_equal(got.chunk_starts, want.chunk_starts)


def _random_table(seed: int):
    """A (V, K) table with -1 holes, self-edges, one-way edges, inactive
    rows and edges into inactive rows; and a chunk bound in 1..64."""
    rng = np.random.default_rng(seed)
    v = int(rng.integers(1, 400))
    k = int(rng.integers(1, 28))
    table = rng.integers(0, v, size=(v, k))
    table[rng.random((v, k)) < rng.uniform(0.0, 0.8)] = -1
    selfs = rng.random((v, k)) < 0.05
    table[selfs] = np.broadcast_to(np.arange(v)[:, None], (v, k))[selfs]
    mask = rng.random(v) < rng.uniform(0.2, 1.0)
    chunk = (1, 64)[seed] if seed < 2 else int(rng.integers(1, 65))
    return table.astype(np.int32), mask, chunk


@pytest.mark.parametrize("seed", range(24))
def test_soar_order_matches_oracle_on_random_tables(seed):
    table, mask, chunk = _random_table(seed)
    _assert_same(soar.soar_order(table, mask, chunk),
                 soar_order_oracle(table, mask, chunk))


N_LEVELS = 7


@pytest.fixture(scope="module")
def padded_pyramid():
    """Every level's submanifold CIRF of a room at capacity 16384, about
    four times its active voxels: the deepest levels hold a few dozen."""
    res, cap = 64, 16384
    coords, feats, _, mask = make_scene(3, resolution=res, capacity=cap)
    t = SparseVoxelTensor(jnp.asarray(coords), jnp.asarray(feats),
                          jnp.asarray(mask))
    cfg = UNetConfig(widths=(8,) * N_LEVELS, reps=1, resolution=res,
                     capacity=cap, n_classes=N_CLASSES)
    offs3 = kernel_offsets(3)
    return [(np.asarray(build_cirf_np(c, m, c, m, offs3, r).indices), m)
            for c, m, r in level_geometry(t, cfg)]


@pytest.mark.parametrize("level", range(N_LEVELS))
def test_soar_order_matches_oracle_on_padded_pyramid(padded_pyramid, level):
    idx, mask = padded_pyramid[level]
    n_active = int(mask.sum())
    assert 0 < n_active < len(mask) // 2
    if level == N_LEVELS - 1:
        assert n_active < 100
    for chunk in (64, 512):
        _assert_same(soar.soar_order(idx, mask, chunk),
                     soar_order_oracle(idx, mask, chunk))


@pytest.mark.parametrize("chunk_sizes", [[64, 512], [16, 128, 1024]])
def test_soar_hierarchical_matches_oracle(shell, monkeypatch, chunk_sizes):
    t, nbr, _ = shell
    mask = np.asarray(t.mask)
    got = soar.soar_hierarchical(nbr, mask, chunk_sizes)
    monkeypatch.setattr(soar, "soar_order", soar_order_oracle)
    want = soar.soar_hierarchical(nbr, mask, chunk_sizes)
    np.testing.assert_array_equal(got.order, want.order)
    np.testing.assert_array_equal(got.chunk_starts, want.chunk_starts)


def _room(seed, res=16, cap=1024):
    coords, feats, _, mask = make_scene(seed, resolution=res, capacity=cap)
    return SparseVoxelTensor(jnp.asarray(coords), jnp.asarray(feats),
                             jnp.asarray(mask))


def _tile_tables(plan):
    out = []
    for lvl in plan.levels:
        tiles = lvl.sub.tiles
        out.append((lvl.sub.dispatch, None if tiles is None else
                    tuple(np.asarray(a) for a in (tiles.out_rows, tiles.in_rows,
                                                  tiles.local_idx,
                                                  tiles.pair_counts))))
    return out


@pytest.mark.parametrize("pinned", [True, False], ids=["pinned", "adaptive"])
def test_plan_tiles_match_oracle_ordering(monkeypatch, pinned):
    """The same spec, tile tables and live-tile counts whether the ordering
    comes from ``soar_order`` or the oracle: the kernel's inputs are
    unchanged."""
    cfg = UNetConfig(widths=(8, 16), reps=1, resolution=16, capacity=1024,
                     n_classes=N_CLASSES)
    budget = 16 * 1024

    def build():
        spec = (engine.build_plan_spec([_room(100), _room(101)], cfg,
                                       mem_budget=budget) if pinned else None)
        plan = engine.build_scene_plan_host(_room(200), cfg, spec=spec,
                                            mem_budget=budget)
        return spec, plan

    spec, plan = build()
    monkeypatch.setattr(plan_mod, "soar_order", soar_order_oracle)
    spec_o, plan_o = build()
    assert spec == spec_o
    assert any(lvl.sub.tiles is not None for lvl in plan.levels)
    assert ([s.get("n_live_tiles") for s in plan.stats]
            == [s.get("n_live_tiles") for s in plan_o.stats])
    for (d, tabs), (d_o, tabs_o) in zip(_tile_tables(plan), _tile_tables(plan_o)):
        assert d == d_o
        assert (tabs is None) == (tabs_o is None)
        if tabs is not None:
            for a, b in zip(tabs, tabs_o):
                np.testing.assert_array_equal(a, b)
