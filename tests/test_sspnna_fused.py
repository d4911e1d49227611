"""Fused gather-GEMM-scatter SSpNNA kernel: bitwise oracle equivalence,
DMA-table layout, dead-tile skip, HLO traffic elimination, plan-key bump."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import example, given, settings, strategies as st
except ImportError:  # image without hypothesis: deterministic local shim
    from _hypothesis_mini import example, given, settings, strategies as st

from conftest import make_shell_scene
from repro import engine
from repro.core.tiles import (
    build_tile_plan,
    dma_tile_tables,
    modeled_hbm_bytes,
    plan_dma_tables,
)
from repro.kernels.sspnna.ops import run_sspnna_conv
from repro.kernels.sspnna.ref import sspnna_tile_ref
from repro.kernels.sspnna.sspnna import sspnna_fused, sspnna_tiles
from repro.sparse.tensor import from_dense

K = 27


def _random_problem(rng, *, v=96, c=8, n=16, t=5, d_i=32, d_o=8,
                    hole_p=0.3, dead_p=0.3):
    """Random fused-kernel inputs honoring the planner contract: local_idx
    only references slots holding valid in_rows; alive tiles own disjoint
    output rows; dead tiles are all-pad."""
    feats = rng.normal(size=(v, c)).astype(np.float32)
    weights = (rng.normal(size=(K, c, n)) * 0.1).astype(np.float32)
    in_rows = np.full((t, d_i), -1, np.int32)
    out_rows = np.full((t, d_o), -1, np.int32)
    local_idx = np.full((t, d_o, K), -1, np.int32)
    out_pool = rng.permutation(v)
    taken = 0
    for ti in range(t):
        if rng.random() < dead_p:
            continue  # dead tile: all pads, pair_count 0
        n_valid = int(rng.integers(1, d_i + 1))
        in_rows[ti, :n_valid] = rng.choice(v, size=n_valid, replace=False)
        n_rows = int(rng.integers(1, d_o + 1))
        out_rows[ti, :n_rows] = out_pool[taken:taken + n_rows]
        taken += n_rows
        li = rng.integers(0, n_valid, (n_rows, K)).astype(np.int32)
        holes = rng.random((n_rows, K)) < hole_p
        local_idx[ti, :n_rows] = np.where(holes, -1, li)
    pair_counts = (local_idx >= 0).sum(axis=(1, 2)).astype(np.int32)
    return feats, weights, in_rows, out_rows, local_idx, pair_counts


def _oracle_conv(feats, weights, in_rows, out_rows, local_idx, pair_counts):
    """Compose the pinned tile oracle with a host-side gather/scatter."""
    tf = feats[np.maximum(in_rows, 0)]
    tile_out = np.asarray(sspnna_tile_ref(
        jnp.asarray(tf), jnp.asarray(local_idx), jnp.asarray(weights)))
    out = np.zeros((feats.shape[0], weights.shape[2]), np.float32)
    for ti in range(in_rows.shape[0]):
        if pair_counts[ti] == 0:
            continue  # dead tiles contribute nothing (rows stay zero)
        for o, row in enumerate(out_rows[ti]):
            if row >= 0:
                out[row] = tile_out[ti, o]
    return out


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 6), st.integers(4, 24), st.integers(1, 8),
       st.floats(0.0, 1.0), st.floats(0.0, 0.8))
# one output row per tile: a (1, C) @ (C, N) product that XLA may lower as a
# vector reduction -- the oracle must reduce in the kernel's order anyway
@example(t=1, d_i=4, d_o=1, hole_p=0.0, dead_p=0.0)
def test_fused_bitwise_matches_oracle_over_random_plans(
        t, d_i, d_o, hole_p, dead_p):
    """Property: fused kernel == oracle bitwise over random tile plans with
    holes, empty/padded tiles, and dead tiles."""
    rng = np.random.default_rng(t * 1000 + d_i * 10 + d_o)
    feats, weights, in_rows, out_rows, local_idx, counts = _random_problem(
        rng, t=t, d_i=d_i, d_o=d_o, hole_p=hole_p, dead_p=dead_p)
    got = sspnna_fused(
        jnp.asarray(feats), jnp.asarray(weights), jnp.asarray(out_rows),
        jnp.asarray(in_rows), jnp.asarray(local_idx), jnp.asarray(counts),
        n_out=feats.shape[0], interpret=True)
    want = _oracle_conv(feats, weights, in_rows, out_rows, local_idx, counts)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("block_n,c,n", [
    (None, 8, 16),    # one lane-padded N block
    (8, 8, 16),       # a sub-lane block_n rounds up to one lane tile
    (128, 20, 256),   # two N blocks revisit one working set
    (None, 20, 200),  # ragged C and N both lane-padded
])
def test_fused_blocking_modes(rng, block_n, c, n):
    """N-blocking and lane padding never touch the per-plane contraction:
    every mode is bitwise the oracle."""
    feats, weights, in_rows, out_rows, local_idx, counts = _random_problem(
        rng, c=c, n=n, t=6, d_i=48, d_o=16, hole_p=0.4, dead_p=0.25)
    got = np.asarray(sspnna_fused(
        jnp.asarray(feats), jnp.asarray(weights), jnp.asarray(out_rows),
        jnp.asarray(in_rows), jnp.asarray(local_idx), jnp.asarray(counts),
        n_out=feats.shape[0], block_n=block_n, interpret=True))
    want = _oracle_conv(feats, weights, in_rows, out_rows, local_idx, counts)
    np.testing.assert_array_equal(got, want)


def test_pregathered_kernel_bitwise_matches_oracle(rng):
    """The tile-stack kernel shares _tile_compute: bitwise too."""
    t, d_i, d_o, c, n = 4, 32, 8, 8, 16
    feats = jnp.asarray(rng.normal(size=(t, d_i, c)), jnp.float32)
    idx = jnp.asarray(rng.integers(-1, d_i, (t, d_o, K)).astype(np.int32))
    w = jnp.asarray(rng.normal(size=(K, c, n)) * 0.1, jnp.float32)
    got = sspnna_tiles(feats, idx, w, interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(sspnna_tile_ref(feats, idx, w)))


def test_fused_under_vmap_matches_stacked(rng):
    """The serving engine vmaps apply_unet over scenes; the fused kernel must
    batch correctly (each scene sees its own plan tables)."""
    probs = [_random_problem(np.random.default_rng(s), t=4, d_i=24, d_o=8)
             for s in (1, 2)]
    stack = [jnp.asarray(np.stack([p[i] for p in probs])) for i in range(6)]
    # _random_problem yields (feats, w, in_rows, out_rows, idx, counts);
    # sspnna_fused takes out_rows before in_rows
    got = jax.vmap(
        lambda f, w, irow, orow, li, pc: sspnna_fused(
            f, w, orow, irow, li, pc, n_out=probs[0][0].shape[0],
            interpret=True)
    )(*stack)
    for b, p in enumerate(probs):
        want = _oracle_conv(*p)
        np.testing.assert_array_equal(np.asarray(got[b]), want)


def test_fused_all_dead_tiles_yield_zeros(rng):
    feats, weights, in_rows, out_rows, local_idx, _ = _random_problem(
        rng, t=3, d_i=16, d_o=4, dead_p=0.0)
    counts = jnp.zeros((3,), jnp.int32)  # force every tile dead
    got = np.asarray(sspnna_fused(
        jnp.asarray(feats), jnp.asarray(weights), jnp.asarray(out_rows),
        jnp.asarray(in_rows), jnp.asarray(local_idx), counts,
        n_out=feats.shape[0], interpret=True))
    np.testing.assert_array_equal(got, np.zeros_like(got))


def test_fused_full_conv_path_on_real_scene(rng):
    """End-to-end on a real shell scene + budgeted (padded) tile plan: fused
    == pre-gathered kernel == oracle path, all through run_sspnna_conv."""
    from repro.core import soar
    from repro.core.hashgrid import build_neighbor_table, kernel_offsets
    from repro.core.sparse_conv import submanifold_coir

    dense = make_shell_scene(rng, 18, 8)
    t = from_dense(dense)
    coir = submanifold_coir(t, 18, 3)
    nbr = np.asarray(build_neighbor_table(
        t.coords, t.mask, jnp.asarray(kernel_offsets(3)), 18))
    order = soar.soar_order(nbr, np.asarray(t.mask), 64).order
    realized = build_tile_plan(np.asarray(coir.indices), order, 32, 128)
    tp = build_tile_plan(np.asarray(coir.indices), order, 32, 128,
                         n_tiles=2 * realized.n_tiles + 2)  # dead-tile pad
    assert int((tp.pair_counts == 0).sum()) > 0
    dma = dma_tile_tables(tp, t.capacity)
    w = jnp.asarray(rng.normal(size=(K, 8, 16)) * 0.1, jnp.float32)

    def path(**kw):
        return np.asarray(run_sspnna_conv(
            t.feats, w, jnp.asarray(dma.out_rows), jnp.asarray(dma.in_rows),
            jnp.asarray(tp.local_idx), n_out=t.capacity, **kw))

    fused = path(pair_counts=jnp.asarray(dma.pair_counts), use_kernel=True)
    gathered = path(use_kernel=True, fused=False)
    oracle = path(use_kernel=False, fused=False)
    np.testing.assert_array_equal(fused, gathered)
    np.testing.assert_array_equal(fused, oracle)


def test_fused_hlo_eliminates_gather_and_scatter(rng):
    """Acceptance: the fused jitted graph holds no XLA gather, no scatter,
    and no (T, dI, C) working-set intermediate; the pre-gathered graph (the
    positive control) holds the gather and the intermediate."""
    v, c, n, t, d_i, d_o = 256, 8, 16, 6, 48, 16
    feats, weights, in_rows, out_rows, local_idx, counts = _random_problem(
        rng, v=v, c=c, n=n, t=t, d_i=d_i, d_o=d_o)
    args = (jnp.asarray(feats), jnp.asarray(weights))
    orow, irow = jnp.asarray(out_rows), jnp.asarray(in_rows)
    li, pc = jnp.asarray(local_idx), jnp.asarray(counts)

    def fused(f, w):
        return run_sspnna_conv(f, w, orow, irow, li, n_out=v,
                               pair_counts=pc, use_kernel=True)

    def pregathered(f, w):
        return run_sspnna_conv(f, w, orow, irow, li, n_out=v,
                               use_kernel=True, fused=False)

    inter = re.compile(rf"f32\[{t},{d_i},{c}\]")
    fused_hlo = jax.jit(fused).lower(*args).compile().as_text()
    assert not re.search(r"\bgather\(", fused_hlo)
    assert not re.search(r"\bscatter\(", fused_hlo)
    assert not inter.search(fused_hlo)
    pre_hlo = jax.jit(pregathered).lower(*args).compile().as_text()
    assert re.search(r"\bgather\(", pre_hlo)
    assert inter.search(pre_hlo)


# ---------------------------------------------------------------------------
# tile planner: DMA tables, overshoot handling, no silent pair drops
# ---------------------------------------------------------------------------

def test_dma_tile_tables_layout():
    cirf = np.array([[0, 1, -1], [1, 2, -1], [2, -1, -1]], np.int32)
    tp = build_tile_plan(cirf, np.arange(3), delta_o=2, delta_i=3)
    dma = dma_tile_tables(tp, n_out=3)
    assert dma.in_rows.min() >= 0
    assert set(np.unique(dma.out_rows[tp.out_rows < 0])) <= {3}
    assert dma.out_rows[tp.out_rows >= 0].min() >= 0
    assert dma.pair_counts.dtype == np.int32
    np.testing.assert_array_equal(dma.pair_counts, tp.pair_counts)


def test_single_row_overshoot_splits_unbudgeted_no_drops():
    """One row with 6 distinct partners, delta_i=2: the old planner silently
    truncated to 2 pairs; now it plane-splits with zero drops."""
    k = 6
    cirf = np.array([[10, 11, 12, 13, 14, 15]], np.int32)
    tp = build_tile_plan(cirf, np.array([0]), delta_o=4, delta_i=2)
    assert tp.n_row_splits == 2  # 6 partners / 2-slot working sets -> 3 tiles
    assert tp.dropped_pairs == 0
    assert int(tp.pair_counts.sum()) == k  # every pair survives
    # all split tiles target the same output row -> fused path must refuse
    rows = tp.out_rows[tp.out_rows >= 0]
    assert (rows == 0).all() and len(rows) == 3

    # numerics through the accumulating pre-gathered path == dense reference
    rng = np.random.default_rng(0)
    feats = jnp.asarray(rng.normal(size=(16, 4)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, 4, 8)) * 0.1, jnp.float32)
    got = np.asarray(run_sspnna_conv(
        feats, w, jnp.asarray(tp.out_rows), jnp.asarray(tp.in_rows),
        jnp.asarray(tp.local_idx), n_out=16, use_kernel=False, fused=False))
    want = np.zeros((16, 8), np.float32)
    want[0] = sum(np.asarray(feats)[cirf[0, p]] @ np.asarray(w)[p]
                  for p in range(k))
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)


def test_single_row_overshoot_raises_budgeted():
    cirf = np.array([[10, 11, 12, 13]], np.int32)
    with pytest.raises(ValueError, match="delta_i"):
        build_tile_plan(cirf, np.array([0]), delta_o=2, delta_i=2, n_tiles=4)


def test_conv_plan_for_layer_rejects_plane_splits():
    from repro.core.coir import COIR

    cirf = np.array([[1, 2, 3, 4], [2, 3, 4, 5]], np.int32)
    coir = COIR(jnp.asarray(cirf), jnp.zeros((2,), jnp.uint32),
                jnp.ones((8,), bool))
    with pytest.raises(ValueError, match="plane-split"):
        engine.conv_plan_for_layer(coir, np.arange(2), 2, 2)


def test_modeled_hbm_bytes_orders_paths():
    cirf = np.tile(np.arange(9, dtype=np.int32), (12, 3))[:, :27]
    tp = build_tile_plan(cirf, np.arange(12), delta_o=4, delta_i=32)
    d = plan_dma_tables(tp)
    m = modeled_hbm_bytes(tp, 16, 16)
    assert d["voxel_entries"] > 0 and d["block_entries"] == tp.n_tiles
    assert m["fused"] < m["pregathered"]  # the whole point of the PR


# ---------------------------------------------------------------------------
# plan-cache key versioning + block_n pinning
# ---------------------------------------------------------------------------

def _tiny_scene(seed=0):
    from repro.data.scenes import make_scene
    from repro.sparse.tensor import SparseVoxelTensor

    coords, feats, _, mask = make_scene(seed, resolution=16, capacity=512)
    return SparseVoxelTensor(jnp.asarray(coords), jnp.asarray(feats),
                             jnp.asarray(mask))


def test_plan_cache_key_changes_across_table_layout_versions(monkeypatch):
    """Regression: a table-layout version bump must invalidate cached plans
    (same scene + config => different key)."""
    from repro.engine import plan as plan_mod
    from repro.models.scn import UNetConfig

    cfg = UNetConfig(widths=(8,), reps=1, resolution=16, capacity=512,
                     n_classes=4)
    t = _tiny_scene()
    cache = engine.PlanCache()
    k1 = cache.key_for(t, cfg, plan_tiles=False)
    assert k1 == cache.key_for(t, cfg, plan_tiles=False)  # stable in-version
    monkeypatch.setattr(plan_mod, "_PLAN_VERSION", plan_mod._PLAN_VERSION + 1)
    k2 = cache.key_for(t, cfg, plan_tiles=False)
    assert k1 != k2
    # and the current version is the v2 DMA-table layout
    assert plan_mod._PLAN_VERSION - 1 >= 2


def test_tile_arrays_carry_pair_counts_in_plans():
    from repro.models.scn import UNetConfig

    cfg = UNetConfig(widths=(8, 16), reps=1, resolution=24, capacity=2048,
                     n_classes=4)
    from repro.data.scenes import make_scene
    from repro.sparse.tensor import SparseVoxelTensor
    coords, feats, _, mask = make_scene(0, resolution=24, capacity=2048)
    t = SparseVoxelTensor(jnp.asarray(coords), jnp.asarray(feats),
                          jnp.asarray(mask))
    plan = engine.build_scene_plan(t, cfg, mem_budget=16 * 1024)
    tiled = [lvl.sub for lvl in plan.levels if lvl.sub.tiles is not None]
    assert tiled, "expected at least one tiled level"
    for cp in tiled:
        tiles = cp.tiles
        assert tiles.pair_counts.shape == (tiles.out_rows.shape[0],)
        n_out = cp.coir.mask.shape[0]
        assert int(jnp.min(tiles.in_rows)) >= 0          # DMA layout
        assert int(jnp.max(tiles.out_rows)) <= n_out     # trash row bound
