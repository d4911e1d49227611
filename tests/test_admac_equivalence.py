"""AdMAC tables searched over active rows are bitwise the capacity sweep
they replaced.

``query_neighbors_np``, ``build_cirf_np``, ``build_corf_np`` and
``transposed_coir_np`` below are the earlier implementations of the
``repro.core.host_meta`` builders, kept verbatim with the grid and the
bitmask packing they used: they key every capacity row, sentinels
included, and probe every row with every plane, so their cost follows the
table's capacity. The planner now searches the sorted keys of the active
rows only, fills reciprocal planes by scatter and builds the stride-2 pair
from one parent search. These tests hold it to the same ``indices``,
``bitmask`` and ``mask`` on random layouts with holes, on the grid's faces,
on degenerate masks, on duplicate coordinates, on centred and non-centred
stencils, on every level of a capacity-padded pyramid, and through whole
plans; and check the ``rows`` and ``probes`` counters of ``plan.cirf``."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.analysis import spans
from repro.core import host_meta
from repro.core.coir import COIR
from repro.core.hashgrid import kernel_offsets
from repro.core.host_meta import linear_key_np
from repro.data.scenes import N_CLASSES, make_scene
from repro.engine import plan as plan_mod
from repro.engine.plan import level_geometry
from repro.models.minkunet import MinkUNetConfig
from repro.models.scn import UNetConfig
from repro.sparse.tensor import PAD_COORD, SparseVoxelTensor


# -- the oracle: the capacity-swept builders, verbatim ------------------------


class SortedGridNp:
    """Numpy twin of ``hashgrid.SortedGrid`` (sorted keys + binary search)."""

    def __init__(self, coords: np.ndarray, mask: np.ndarray, resolution: int):
        self.resolution = resolution
        keys = linear_key_np(coords, resolution, mask)
        order = np.argsort(keys, kind="stable")
        self.sorted_keys = keys[order]
        self.sorted_idx = order.astype(np.int32)

    def lookup(self, query_coords: np.ndarray,
               query_valid: np.ndarray) -> np.ndarray:
        r = self.resolution
        q = np.asarray(query_coords)
        in_bounds = np.all((q >= 0) & (q < r), axis=-1)
        valid = np.asarray(query_valid) & in_bounds
        qkey = linear_key_np(q, r, valid)
        pos = np.searchsorted(self.sorted_keys, qkey)
        pos = np.clip(pos, 0, self.sorted_keys.shape[0] - 1)
        found = valid & (self.sorted_keys[pos] == qkey)
        return np.where(found, self.sorted_idx[pos], -1).astype(np.int32)


def query_neighbors_np(
    out_coords: np.ndarray,
    out_mask: np.ndarray,
    in_coords: np.ndarray,
    in_mask: np.ndarray,
    offsets: np.ndarray,
    resolution: int,
    stride: int = 1,
) -> np.ndarray:
    """Numpy twin of ``hashgrid.query_neighbors``."""
    grid = SortedGridNp(in_coords, in_mask, resolution)
    out_coords = np.asarray(out_coords)
    offsets = np.asarray(offsets)
    probe = out_coords[:, None, :] * stride + offsets[None, :, :]
    valid = np.broadcast_to(np.asarray(out_mask)[:, None],
                            (out_coords.shape[0], offsets.shape[0]))
    return grid.lookup(probe, valid)


def _pack_bitmask_np(indices: np.ndarray) -> np.ndarray:
    """Numpy twin of ``coir._pack_bitmask``: one uint32 word per row, or
    ``ceil(K / 32)`` words per row for K > 32 (plane k at bit k % 32 of
    word k // 32)."""
    v, k = indices.shape
    words = -(-k // 32)
    valid = np.zeros((v, 32 * words), bool)
    valid[:, :k] = indices >= 0
    bits = (valid.reshape(v, words, 32).astype(np.uint32)
            << np.arange(32, dtype=np.uint32))
    packed = bits.sum(axis=2, dtype=np.uint32)
    return packed[:, 0] if words == 1 else packed


def build_cirf_np(
    out_coords: np.ndarray,
    out_mask: np.ndarray,
    in_coords: np.ndarray,
    in_mask: np.ndarray,
    offsets: np.ndarray,
    resolution: int,
    stride: int = 1,
) -> COIR:
    """Numpy twin of ``coir.build_cirf`` (COIR with numpy leaves)."""
    idx = query_neighbors_np(out_coords, out_mask, in_coords, in_mask,
                             offsets, resolution, stride)
    return COIR(idx, _pack_bitmask_np(idx), np.asarray(out_mask))


def build_corf_np(
    out_coords: np.ndarray,
    out_mask: np.ndarray,
    in_coords: np.ndarray,
    in_mask: np.ndarray,
    offsets: np.ndarray,
    resolution: int,
    stride: int = 1,
) -> COIR:
    """Numpy twin of ``coir.build_corf``."""
    out_res = max(resolution // stride, 1) if stride > 1 else resolution
    grid = SortedGridNp(out_coords, out_mask, out_res)
    in_coords = np.asarray(in_coords)
    offsets = np.asarray(offsets)
    diff = in_coords[:, None, :] - offsets[None, :, :]
    exact = np.all(diff % stride == 0, axis=-1)
    probe = diff // stride
    valid = np.asarray(in_mask)[:, None] & exact
    idx = grid.lookup(probe, valid)
    return COIR(idx, _pack_bitmask_np(idx), np.asarray(in_mask))


def transposed_coir_np(
    coarse_coords: np.ndarray,
    coarse_mask: np.ndarray,
    fine_coords: np.ndarray,
    fine_mask: np.ndarray,
    fine_resolution: int,
    kernel_size: int = 2,
    stride: int = 2,
) -> COIR:
    """Numpy twin of ``sparse_conv.transposed_coir``."""
    offs = kernel_offsets(kernel_size, centered=False)
    return build_corf_np(coarse_coords, coarse_mask, fine_coords, fine_mask,
                         offs, fine_resolution, stride)


# -- helpers -----------------------------------------------------------------

OFFS2 = kernel_offsets(2, centered=False)
STENCILS = {
    "3cube": kernel_offsets(3),
    "5cube": kernel_offsets(5),
    # a 3-wide stencil that is not centred: no plane is another's mirror
    "3cube_corner": kernel_offsets(3, centered=False),
}


def _assert_same(got: COIR, want: COIR):
    for name in ("indices", "bitmask", "mask"):
        g, w = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_array_equal(g, w, err_msg=name)


def _check_level(coords, mask, res, stencil):
    """The submanifold table of one layout against the oracle."""
    offs = STENCILS[stencil]
    _assert_same(host_meta.build_cirf_np(coords, mask, coords, mask, offs, res),
                 build_cirf_np(coords, mask, coords, mask, offs, res))


def _check_pair(cc, cm, fc, fm, res):
    """The stride-2 down/up pair, together and one by one."""
    want_d = build_cirf_np(cc, cm, fc, fm, OFFS2, res, stride=2)
    want_u = transposed_coir_np(cc, cm, fc, fm, res, 2, 2)
    down, up = host_meta.down_up_coirs_np(cc, cm, fc, fm, res)
    _assert_same(down, want_d)
    _assert_same(up, want_u)
    _assert_same(host_meta.build_cirf_np(cc, cm, fc, fm, OFFS2, res,
                                         stride=2), want_d)
    _assert_same(host_meta.transposed_coir_np(cc, cm, fc, fm, res, 2, 2),
                 want_u)


def _layout(coords: np.ndarray, capacity: int, rng) -> tuple:
    """``coords`` at random rows of a capacity-sized layout: holes between
    active rows, neither a prefix nor in key order."""
    rows = rng.choice(capacity, size=len(coords), replace=False)
    out = np.full((capacity, 3), PAD_COORD, np.int32)
    out[rows] = coords
    mask = np.zeros(capacity, bool)
    mask[rows] = True
    return out, mask


def _random_voxels(rng, res: int, n: int) -> np.ndarray:
    keys = rng.choice(res ** 3, size=n, replace=False)
    return np.stack([keys // (res * res), (keys // res) % res, keys % res],
                    axis=-1).astype(np.int32)


def _coarsen(coords, mask, res, rng):
    """The canonical coarse level of a layout, scattered to random rows."""
    cc, cm = host_meta.downsample_coords_np(coords, mask, res, 2)
    return _layout(cc[cm], len(cm), rng)


# -- tables ------------------------------------------------------------------


@pytest.mark.parametrize("stencil", sorted(STENCILS))
@pytest.mark.parametrize("seed", range(6))
def test_random_layouts_with_holes(seed, stencil):
    rng = np.random.default_rng(seed)
    res = int(rng.choice([6, 8, 11, 16]))
    n = int(rng.integers(1, min(res ** 3, 300)))
    coords, mask = _layout(_random_voxels(rng, res, n), 512, rng)
    _check_level(coords, mask, res, stencil)


@pytest.mark.parametrize("seed", range(6))
def test_random_pairs_with_holes(seed):
    rng = np.random.default_rng(100 + seed)
    res = int(rng.choice([4, 8, 16]))
    n = int(rng.integers(1, min(res ** 3, 300)))
    fc, fm = _layout(_random_voxels(rng, res, n), 512, rng)
    cc, cm = _coarsen(fc, fm, res, rng)
    _check_pair(cc, cm, fc, fm, res)


@pytest.mark.parametrize("stencil", sorted(STENCILS))
def test_voxels_on_all_six_faces(stencil):
    """Every voxel of the grid's surface and a few inside: a probe that
    leaves the grid through one face must not alias a key on the
    opposite one."""
    res = 7
    g = np.stack(np.meshgrid(*[np.arange(res)] * 3, indexing="ij"),
                 axis=-1).reshape(-1, 3)
    face = np.any((g == 0) | (g == res - 1), axis=1)
    rng = np.random.default_rng(7)
    inner = g[~face][rng.random((~face).sum()) < 0.3]
    coords, mask = _layout(np.concatenate([g[face], inner]).astype(np.int32),
                           512, rng)
    _check_level(coords, mask, res, stencil)
    fc, fm = _layout(g[face].astype(np.int32), 512, rng)
    cc, cm = _coarsen(fc, fm, 8, rng)   # the pair needs an even grid
    _check_pair(cc, cm, fc, fm, 8)


@pytest.mark.parametrize("stencil", sorted(STENCILS))
@pytest.mark.parametrize("case", ["empty", "single", "full"])
def test_empty_single_and_full_masks(case, stencil):
    rng = np.random.default_rng(11)
    res, cap = 8, 512
    n = {"empty": 0, "single": 1, "full": cap}[case]
    coords, mask = _layout(_random_voxels(rng, res, n), cap, rng)
    assert mask.sum() == n
    _check_level(coords, mask, res, stencil)
    cc, cm = _coarsen(coords, mask, res, rng)
    _check_pair(cc, cm, coords, mask, res)


@pytest.mark.parametrize("stencil", sorted(STENCILS))
def test_duplicate_coordinates(stencil):
    """Two active rows at one voxel: the lower row answers every probe, so
    the reciprocal fill must stand down; the pair falls back as well."""
    rng = np.random.default_rng(5)
    res = 8
    vox = _random_voxels(rng, res, 120)
    dup = vox[rng.choice(len(vox), 30, replace=False)]
    coords, mask = _layout(np.concatenate([vox, dup]), 512, rng)
    _check_level(coords, mask, res, stencil)
    cc, cm = _coarsen(coords, mask, res, rng)
    _check_pair(cc, cm, coords, mask, res)
    # duplicates on the coarse side only
    cc2, cm2 = _layout(np.concatenate([cc[cm], cc[cm][:5]]), 512, rng)
    _check_pair(cc2, cm2, coords, mask, res)


def test_other_layouts_and_out_of_range_rows():
    """An output layout other than the input's, an odd grid for the pair,
    and active rows outside the grid: the general search, bitwise."""
    rng = np.random.default_rng(9)
    res = 9
    a, am = _layout(_random_voxels(rng, res, 150), 256, rng)
    b, bm = _layout(_random_voxels(rng, res, 90), 256, rng)
    offs = kernel_offsets(3)
    _assert_same(host_meta.build_cirf_np(b, bm, a, am, offs, res),
                 build_cirf_np(b, bm, a, am, offs, res))
    _assert_same(host_meta.build_corf_np(b, bm, a, am, offs, res),
                 build_corf_np(b, bm, a, am, offs, res))
    cc, cm = _coarsen(a, am, res, rng)
    _check_pair(cc, cm, a, am, res)
    # active rows past the grid's edge on one axis
    bad = a.copy()
    rows = np.flatnonzero(am)[:10]
    bad[rows, 1] = res + rng.integers(0, 3, 10)
    _check_level(bad, am, res, "3cube")
    _check_pair(cc, cm, bad, am, res - 1)


@pytest.mark.parametrize("kernel_size", [2, 4])
def test_stride2_tables_with_multiword_bitmasks(kernel_size):
    """The ``[0,2)^3`` pair and a 4^3 stride-2 stencil, whose 64 planes
    take two bitmask words."""
    rng = np.random.default_rng(21)
    res = 16
    fc, fm = _layout(_random_voxels(rng, res, 400), 1024, rng)
    cc, cm = _coarsen(fc, fm, res, rng)
    offs = kernel_offsets(kernel_size, centered=False)
    got = host_meta.build_cirf_np(cc, cm, fc, fm, offs, res, stride=2)
    want = build_cirf_np(cc, cm, fc, fm, offs, res, stride=2)
    _assert_same(got, want)
    assert np.asarray(got.bitmask).ndim == (2 if kernel_size == 4 else 1)
    _assert_same(host_meta.transposed_coir_np(cc, cm, fc, fm, res,
                                              kernel_size, 2),
                 transposed_coir_np(cc, cm, fc, fm, res, kernel_size, 2))


N_LEVELS = 7


@pytest.fixture(scope="module")
def padded_pyramid():
    """The geometry of a room at capacity 16384, about four times its
    active voxels: the deepest levels hold a few dozen."""
    res, cap = 64, 16384
    coords, feats, _, mask = make_scene(3, resolution=res, capacity=cap)
    t = SparseVoxelTensor(jnp.asarray(coords), jnp.asarray(feats),
                          jnp.asarray(mask))
    cfg = UNetConfig(widths=(8,) * N_LEVELS, reps=1, resolution=res,
                     capacity=cap, n_classes=N_CLASSES)
    return level_geometry(t, cfg)


@pytest.mark.parametrize("level", range(N_LEVELS))
def test_every_level_of_a_padded_pyramid(padded_pyramid, level):
    coords, mask, res = padded_pyramid[level]
    assert 0 < mask.sum() < len(mask) // 2
    _check_level(coords, mask, res, "3cube")
    if level == 0:
        _check_level(coords, mask, res, "5cube")
    if level < N_LEVELS - 1:
        cc, cm, _ = padded_pyramid[level + 1]
        _check_pair(cc, cm, coords, mask, res)


# -- whole plans -------------------------------------------------------------


def _room(seed, res=16, cap=1024):
    coords, feats, _, mask = make_scene(seed, resolution=res, capacity=cap)
    return SparseVoxelTensor(jnp.asarray(coords), jnp.asarray(feats),
                             jnp.asarray(mask))


def _oracle_down_up(cc, cm, fc, fm, res):
    return (build_cirf_np(cc, cm, fc, fm, OFFS2, res, stride=2),
            transposed_coir_np(cc, cm, fc, fm, res, 2, 2))


CONFIGS = {
    "scn": UNetConfig(widths=(8, 16, 24), reps=1, resolution=16,
                      capacity=1024, n_classes=N_CLASSES),
    "minkunet": MinkUNetConfig(in_channels=4, init_dim=8, planes=(16, 24),
                               layers=(2, 2), resolution=16, capacity=1024),
}


@pytest.mark.parametrize("pinned", [True, False], ids=["pinned", "adaptive"])
@pytest.mark.parametrize("arch", sorted(CONFIGS))
def test_plans_match_oracle_tables(monkeypatch, arch, pinned):
    """The same spec and plan leaves whether the tables come from the
    planner's builders or the oracle's."""
    cfg = CONFIGS[arch]
    budget = 16 * 1024

    def build():
        spec = (engine.build_plan_spec([_room(100), _room(101)], cfg,
                                       mem_budget=budget) if pinned else None)
        plan = engine.build_scene_plan_host(_room(200), cfg, spec=spec,
                                            mem_budget=budget)
        return spec, plan

    spec, plan = build()
    monkeypatch.setattr(plan_mod, "build_cirf_np", build_cirf_np)
    monkeypatch.setattr(plan_mod, "down_up_coirs_np", _oracle_down_up)
    spec_o, plan_o = build()
    assert spec == spec_o
    assert plan.stats == plan_o.stats
    leaves, tree = jax.tree.flatten(plan.levels)
    leaves_o, tree_o = jax.tree.flatten(plan_o.levels)
    assert tree == tree_o and len(leaves) == len(leaves_o)
    for a, b in zip(leaves, leaves_o):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


# -- counters ----------------------------------------------------------------


@pytest.mark.parametrize("arch", sorted(CONFIGS))
def test_plan_cirf_span_counts_rows_and_probes(monkeypatch, arch):
    """``plan.cirf`` carries the active rows searched and the key lookups
    made: on a prefix room, centre and half of the 27 planes per level row
    (reciprocal halving), one parent lookup per fine row for the pair, and
    centre and half of the 125 stem planes per row."""
    cfg = CONFIGS[arch]
    t = _room(300)
    geometry = level_geometry(t, cfg)
    n = [int(m.sum()) for _, m, _ in geometry]
    m0 = np.asarray(t.mask)
    assert m0[:n[0]].all() and not m0[n[0]:].any()   # a prefix room
    seen = []

    @contextlib.contextmanager
    def recording(name, **meta):
        with spans.span(name, **meta) as sp:
            yield sp
        seen.append(sp)

    monkeypatch.setattr(plan_mod, "span", recording)
    engine.build_scene_plan_host(t, cfg, mem_budget=16 * 1024)
    cirf = [sp for sp in seen if sp.name == "plan.cirf"]
    last = len(n) - 1
    want = [{"rows": n[li] + (n[li] if li < last else 0),
             "probes": 14 * n[li] + (n[li] if li < last else 0)}
            for li in range(len(n))]
    if plan_mod.stem_site(cfg) is not None:
        want.append({"rows": n[0], "probes": 63 * n[0]})
    assert [sp.counts for sp in cirf] == want


def test_count_adds_to_the_innermost_span():
    spans.count(rows=5)   # outside any span: nothing to add to
    with spans.span("outer") as outer:
        with spans.span("inner") as inner:
            spans.count(rows=2, probes=7)
            spans.count(rows=3)
        spans.count(probes=1)
    assert inner.counts == {"rows": 5, "probes": 7}
    assert outer.counts == {"probes": 1}
    assert inner.meta == {} and outer.meta == {}
