"""Tile tables built a window of rows at a time are bitwise the row walk
they replaced.

``build_tile_plan_np`` below is the earlier implementation of
``repro.core.tiles.build_tile_plan``, kept verbatim with its plane splitter:
the budgeted mode walks every row with set unions and closes a tile before
a row that would overflow ``delta_i`` or the tile's ``delta_o`` rows, the
unbudgeted mode halves fixed windows, and both fill each tile in a Python
loop. ``build_tile_plan`` now counts whole windows' working sets from one
sort of their pairs and fills every tile from that sort. These tests hold
it to the same ``out_rows``, ``in_rows``, ``local_idx``, ``pair_counts``,
``n_row_splits`` and ``dropped_pairs``, and to the same errors: on every
level of seeded rooms, a 5^3 stem table, tiles that close by inputs, random
tables with repeated partners, edge cases, and every level of the
benchmark cells' own pin rooms under their pinned specs; and check the
``rows``, ``tiles`` and ``input_closed`` counters of ``plan.tiles``."""
import contextlib
import functools
import importlib
import json
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.analysis import spans
from repro.core.hashgrid import kernel_offsets
from repro.core.host_meta import build_cirf_np
from repro.core.soar import soar_order
from repro.core.tiles import TilePlan, build_tile_plan, max_tiles
from repro.data.scenes import make_scene
from repro.engine import plan as plan_mod
from repro.engine.plan import level_geometry
from repro.models.minkunet import MinkUNetConfig
from repro.models.scn import UNetConfig
from repro.sparse.tensor import SparseVoxelTensor

ROOT = Path(__file__).resolve().parents[1]


# -- the oracle: the row walk and per-tile fill, verbatim ---------------------


def _split_row_by_planes(part: np.ndarray, delta_i: int) -> list[np.ndarray]:
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


def build_tile_plan_np(
    cirf_indices: np.ndarray,
    order: np.ndarray,
    delta_o: int,
    delta_i: int,
    n_tiles: int | None = None,
) -> TilePlan:
    cirf_indices = np.asarray(cirf_indices)
    k = cirf_indices.shape[1]

    tiles: list[tuple[np.ndarray, np.ndarray | None]] = []
    n_row_splits = 0

    if n_tiles is not None:
        if delta_i < k:
            raise ValueError(f"delta_i {delta_i} < kernel volume {k}")
        cur: list[int] = []
        cur_uniq: set[int] = set()
        for r in np.asarray(order, np.int64):
            part = cirf_indices[r]
            new = set(part[part >= 0].tolist())
            if len(new) > delta_i:  # can't happen while delta_i >= K; be loud
                raise ValueError(
                    f"row {int(r)} working set {len(new)} > delta_i {delta_i} "
                    "in budgeted mode (would drop pairs)")
            if cur and (len(cur) == delta_o or len(cur_uniq | new) > delta_i):
                tiles.append((np.asarray(cur, np.int64), None))
                cur, cur_uniq = [], set()
            cur.append(int(r))
            cur_uniq |= new
        if cur:
            tiles.append((np.asarray(cur, np.int64), None))
        if len(tiles) > n_tiles:
            raise ValueError(
                f"scene needs {len(tiles)} tiles > budget {n_tiles} "
                f"(delta_o={delta_o}, delta_i={delta_i})")
    else:
        def emit(rows: np.ndarray):
            part = cirf_indices[rows]
            uniq = np.unique(part[part >= 0])
            if len(uniq) > delta_i:
                if len(rows) > 1:
                    mid = len(rows) // 2
                    emit(rows[:mid])
                    emit(rows[mid:])
                else:
                    nonlocal n_row_splits
                    groups = _split_row_by_planes(part[0], delta_i)
                    n_row_splits += len(groups) - 1
                    for g in groups:
                        tiles.append((rows, g))
            else:
                tiles.append((rows, None))

        for s in range(0, len(order), delta_o):
            emit(np.asarray(order[s:s + delta_o], np.int64))

    t = n_tiles if n_tiles is not None else len(tiles)
    out_rows = np.full((t, delta_o), -1, np.int32)
    in_rows = np.full((t, delta_i), -1, np.int32)
    local_idx = np.full((t, delta_o, k), -1, np.int32)
    pair_counts = np.zeros((t,), np.int32)
    for ti, (rows, planes) in enumerate(tiles):
        out_rows[ti, : len(rows)] = rows
        part = cirf_indices[rows].copy()  # (r, K)
        if planes is not None:  # plane-split tile: hole the other planes
            keep = np.zeros((k,), bool)
            keep[planes] = True
            part[:, ~keep] = -1
        valid = part >= 0
        uniq = np.unique(part[valid])
        assert len(uniq) <= delta_i, "planner invariant: working set fits"
        in_rows[ti, : len(uniq)] = uniq
        loc = np.searchsorted(uniq, part)
        loc = np.clip(loc, 0, max(len(uniq) - 1, 0))
        hit = valid & (uniq[loc] == part) if len(uniq) else np.zeros_like(valid)
        local_idx[ti, : len(rows)] = np.where(hit, loc, -1)
        pair_counts[ti] = int(hit.sum())
    return TilePlan(out_rows, in_rows, local_idx, pair_counts,
                    n_row_splits=n_row_splits, dropped_pairs=0)


# -- helpers -----------------------------------------------------------------

FIELDS = ("out_rows", "in_rows", "local_idx", "pair_counts")
MODES = ("budgeted", "unbudgeted")


def _check(cirf, order, delta_o, delta_i, n_tiles=None) -> TilePlan | None:
    """``build_tile_plan`` against the oracle: the same plan, or the same
    error."""
    try:
        want = build_tile_plan_np(cirf, order, delta_o, delta_i, n_tiles)
    except ValueError as e:
        with pytest.raises(ValueError) as got:
            build_tile_plan(cirf, order, delta_o, delta_i, n_tiles)
        assert str(got.value) == str(e)
        return None
    got = build_tile_plan(cirf, order, delta_o, delta_i, n_tiles)
    for name in FIELDS:
        g, w = getattr(got, name), getattr(want, name)
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_array_equal(g, w, err_msg=name)
    assert got.n_row_splits == want.n_row_splits
    assert got.dropped_pairs == want.dropped_pairs == 0
    return got


def _budget(order, delta_o, delta_i, k) -> int:
    """``max_tiles``, the bound the serving engine pins, held to two more
    tiles than rows (no tile is empty) so that tight budgets stay small."""
    return min(max_tiles(len(order), delta_o, delta_i, k), len(order) + 2)


def _check_mode(cirf, order, delta_o, delta_i, mode) -> TilePlan | None:
    """Unbudgeted, or budgeted with padding."""
    n_tiles = None
    if mode == "budgeted":
        n_tiles = _budget(order, delta_o, delta_i, np.asarray(cirf).shape[1])
    return _check(cirf, order, delta_o, delta_i, n_tiles)


def _counting(fn, *args, **kw) -> dict:
    with spans.span("probe") as sp:
        fn(*args, **kw)
    return sp.counts


def _input_closed(plan: TilePlan) -> int:
    """Tiles the oracle closed short of ``delta_o`` rows, but the last."""
    rows = (plan.out_rows >= 0).sum(axis=1)
    live = rows[rows > 0]
    return int((live[:-1] < plan.delta_o).sum())


N_LEVELS = 5


@functools.lru_cache(maxsize=None)
def _room_levels(seed: int, res: int = 32, cap: int = 4096) -> list:
    """(3^3 table, SOAR order) of every level of a seeded room."""
    coords, feats, _, mask = make_scene(seed, resolution=res, capacity=cap)
    t = SparseVoxelTensor(jnp.asarray(coords), jnp.asarray(feats),
                          jnp.asarray(mask))
    cfg = UNetConfig(widths=(8,) * N_LEVELS, reps=1, resolution=res,
                     capacity=cap)
    out = []
    for c, m, r in level_geometry(t, cfg):
        cirf = np.asarray(build_cirf_np(c, m, c, m, kernel_offsets(3),
                                        r).indices)
        out.append((cirf, soar_order(cirf, m, 64).order))
    return out


# -- tables ------------------------------------------------------------------


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("level", range(N_LEVELS))
@pytest.mark.parametrize("seed", range(3))
def test_every_level_of_seeded_rooms(seed, level, mode):
    cirf, order = _room_levels(seed)[level]
    assert len(order) > 0
    for delta_o, delta_i in ((8, 40), (16, 64), (32, 100), (64, 160)):
        _check_mode(cirf, order, delta_o, delta_i, mode)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("delta_o,delta_i", [(8, 125), (16, 200), (32, 400)])
def test_5cube_kernel(delta_o, delta_i, mode):
    """A 5^3 stem table (K 125) of a room's finest level."""
    coords, feats, _, mask = make_scene(4, resolution=32, capacity=4096)
    cirf = np.asarray(build_cirf_np(coords, mask, coords, mask,
                                    kernel_offsets(5), 32).indices)
    assert cirf.shape[1] == 125
    order = soar_order(np.asarray(build_cirf_np(
        coords, mask, coords, mask, kernel_offsets(3), 32).indices),
        mask, 64).order
    _check_mode(cirf, order, delta_o, delta_i, mode)


@pytest.mark.parametrize("delta_o,delta_i",
                         [(32, 27), (32, 30), (32, 45), (64, 60), (4, 27)])
def test_tiles_close_by_inputs(delta_o, delta_i):
    """Budgets under a full tile's working set, down to ``delta_i == K``:
    most tiles close by inputs, and the counter says how many."""
    cirf, order = _room_levels(0)[0]
    n_tiles = _budget(order, delta_o, delta_i, 27)
    got = _check(cirf, order, delta_o, delta_i, n_tiles)
    want = build_tile_plan_np(cirf, order, delta_o, delta_i, n_tiles)
    closed = _input_closed(want)
    assert closed > 0
    counts = _counting(build_tile_plan, cirf, order, delta_o, delta_i,
                       n_tiles)
    assert counts == {"rows": len(order),
                      "tiles": int(np.count_nonzero(got.pair_counts)),
                      "input_closed": closed}


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("seed", range(6))
def test_random_tables_with_repeated_partners(seed, mode):
    """Tables no conv gives: few partners, repeated within and across rows,
    holes, and an order that skips rows and is not sorted."""
    rng = np.random.default_rng(seed)
    v, k = 300, int(rng.choice([8, 27]))
    cirf = rng.integers(-1, int(rng.choice([20, 60, 300])), (v, k),
                        dtype=np.int32)
    cirf[rng.random((v, k)) < 0.4] = -1
    order = rng.permutation(v)[: int(rng.integers(1, v))]
    for delta_o, delta_i in ((4, k), (8, 2 * k), (16, 3 * k)):
        _check_mode(cirf, order, delta_o, delta_i, mode)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("case", ["single_row", "empty_order", "no_pairs"])
def test_single_row_and_empty_orders(case, mode):
    cirf, order = _room_levels(1)[0]
    order = {"single_row": order[:1], "empty_order": order[:0],
             "no_pairs": order[:3]}[case]
    if case == "no_pairs":
        cirf = np.full_like(cirf, -1)
    got = _check_mode(cirf, order, 16, 64, mode)
    assert int(got.pair_counts.sum()) == int((cirf[order] >= 0).sum())


@pytest.mark.parametrize("short", [0, 1])
@pytest.mark.parametrize("delta_o,delta_i", [(16, 64), (32, 45)])
def test_tile_budget_exact_and_one_short(delta_o, delta_i, short):
    """A budget of exactly the tiles a room needs holds no padding; one
    less raises the same ``ValueError`` as the oracle."""
    cirf, order = _room_levels(2)[1]
    padded = build_tile_plan_np(cirf, order, delta_o, delta_i,
                                _budget(order, delta_o, delta_i, 27))
    need = int(np.count_nonzero((padded.out_rows >= 0).any(axis=1)))
    got = _check(cirf, order, delta_o, delta_i, need - short)
    assert (got is None) == bool(short)
    if got is not None:
        assert (got.out_rows >= 0).any(axis=1).all()


@pytest.mark.parametrize("delta_i", [5, 26])
def test_budget_under_the_kernel_volume_raises(delta_i):
    cirf, order = _room_levels(0)[2]
    assert _check(cirf, order, 16, delta_i, 1000) is None


@pytest.mark.parametrize("delta_o,delta_i", [(8, 4), (16, 10), (4, 20)])
def test_unbudgeted_plane_splits(delta_o, delta_i):
    """``delta_i`` under a row's partners: windows halve down to single
    rows, whose planes split into groups that share the output row."""
    cirf, order = _room_levels(0)[0]
    got = _check(cirf, order, delta_o, delta_i)
    assert got.n_row_splits > 0
    assert int(got.pair_counts.sum()) == int((cirf[order] >= 0).sum())
    assert _counting(build_tile_plan, cirf, order, delta_o,
                     delta_i)["input_closed"] > 0


# -- the benchmark cells' pin rooms ------------------------------------------


@functools.lru_cache(maxsize=None)
def _cells() -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return {w["name"]: w for w in bench["workloads"]}


@pytest.fixture(scope="module")
def harness():
    """The benchmark's room generator and program side, as its cells pin
    their plan specs."""
    with pytest.MonkeyPatch.context() as mp:
        mp.syspath_prepend(str(ROOT / "bench"))
        yield importlib.import_module("drive"), importlib.import_module("sut")
    for name in ("drive", "sut", "scenes", "plug"):
        sys.modules.pop(name, None)


@pytest.mark.parametrize("cell", sorted(_cells()))
def test_pin_rooms_of_the_cells(harness, monkeypatch, cell):
    """Every tiled level of each cell's pin room, under the spec the cell
    pins, in both modes."""
    drive, sut = harness
    w = _cells()[cell]
    cfg = json.loads((ROOT / "bench" / "configs"
                      / f"{w['config']}.json").read_text())
    mix = json.loads((ROOT / "bench" / "traffic"
                      / f"{w['traffic']}.json").read_text())
    prog = sut.program(cfg, ROOT)
    mcfg = prog.model_config(cfg)
    rooms = drive.pin_rooms(mix, cfg)
    spec = prog.pin_spec(mcfg, rooms)
    calls = []

    def recording(cirf, order, delta_o, delta_i, n_tiles=None):
        calls.append((np.asarray(cirf), order, delta_o, delta_i, n_tiles))
        return build_tile_plan(cirf, order, delta_o, delta_i, n_tiles)

    monkeypatch.setattr(plan_mod, "build_tile_plan", recording)
    plan = engine.build_scene_plan_host(rooms[0], mcfg, spec=spec)
    tiled = [d for d in spec.levels if d.backend == engine.SSPNNA]
    assert len(calls) == len(tiled) > 0
    for cirf, order, delta_o, delta_i, n_tiles in calls:
        assert n_tiles
        _check(cirf, order, delta_o, delta_i, n_tiles)
        _check(cirf, order, delta_o, delta_i)
    assert not any(s.get("tile_overflow") for s in plan.stats)


# -- counters ----------------------------------------------------------------


CONFIGS = {
    "scn": UNetConfig(widths=(8, 16, 24), reps=1, resolution=16,
                      capacity=1024),
    "minkunet": MinkUNetConfig(in_channels=4, init_dim=8, planes=(16, 24),
                               layers=(2, 2), resolution=16, capacity=1024),
}


@pytest.mark.parametrize("arch", sorted(CONFIGS))
def test_plan_tiles_span_counts_rows_tiles_and_input_closed(monkeypatch,
                                                            arch):
    """Each budgeted build under ``plan.tiles`` carries the level's active
    rows, its live tiles and the tiles that closed by inputs."""
    cfg = CONFIGS[arch]

    def room(seed):
        coords, feats, _, mask = make_scene(seed, resolution=16,
                                            capacity=1024)
        return SparseVoxelTensor(jnp.asarray(coords), jnp.asarray(feats),
                                 jnp.asarray(mask))

    spec = engine.build_plan_spec([room(100), room(101)], cfg,
                                  mem_budget=16 * 1024)
    seen, built = [], []

    @contextlib.contextmanager
    def recording(name, **meta):
        with spans.span(name, **meta) as sp:
            yield sp
        seen.append(sp)

    def oracle_too(cirf, order, delta_o, delta_i, n_tiles=None):
        built.append(build_tile_plan_np(cirf, order, delta_o, delta_i,
                                        n_tiles))
        return build_tile_plan(cirf, order, delta_o, delta_i, n_tiles)

    monkeypatch.setattr(plan_mod, "span", recording)
    monkeypatch.setattr(plan_mod, "build_tile_plan", oracle_too)
    plan = engine.build_scene_plan_host(room(200), cfg, spec=spec)
    tiles = [sp.counts for sp in seen if sp.name == "plan.tiles"]
    levels = [s for s in plan.stats if "n_live_tiles" in s]
    assert len(tiles) == len(levels) == len(built) > 0
    for counts, info, want in zip(tiles, levels, built):
        assert counts == {"rows": info["n_active"],
                          "tiles": info["n_live_tiles"],
                          "input_closed": _input_closed(want)}
