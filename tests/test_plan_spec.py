"""The one dispatch decision, site by site, for the three served U-Nets at
their published widths: SPADE's analytic choice, capped by the REPRO-H003
VMEM gate, pinned by ``build_plan_spec`` and kept by every plan built from
the spec. Resolution and capacity are cut to (64, 4096); the widths, depths
and kernel volumes are the configurations' own."""
import functools

import jax.numpy as jnp
import pytest

from repro import engine
from repro.core.tiles import max_tiles
from repro.data.scenes import make_scene
from repro.engine.plan import (
    REFERENCE_DISPATCH,
    Dispatch,
    _fits_vmem,
    stem_site,
)
from repro.models.minkunet import MinkUNetConfig
from repro.models.scn import UNetConfig
from repro.sparse.tensor import SparseVoxelTensor

RES, CAP = 64, 4096
BUDGET = 64 * 1024  # the benchmark's pinned tile budget

CONFIGS = {
    # SparseConvNet examples/ScanNet/unet.py: m=16 reps 1, m=32 reps 2
    "m16": UNetConfig(widths=tuple(16 * i for i in range(1, 8)), reps=1,
                      resolution=RES, capacity=CAP),
    "m32": UNetConfig(widths=tuple(32 * i for i in range(1, 8)), reps=2,
                      resolution=RES, capacity=CAP),
    # MinkowskiEngine examples/minkunet.py: MinkUNet34C, 5^3 stem
    "mink34c": MinkUNetConfig(in_channels=4, resolution=RES, capacity=CAP),
}
SITES = [(name, li) for name, cfg in CONFIGS.items()
         for li in range(len(cfg.widths))] + [("mink34c", "stem")]


def _room(seed: int) -> SparseVoxelTensor:
    coords, feats, _, mask = make_scene(seed, RES, CAP)
    return SparseVoxelTensor(jnp.asarray(coords), jnp.asarray(feats),
                             jnp.asarray(mask))


@functools.lru_cache(maxsize=None)
def _specs(name: str):
    """Two specs pinned from the same two rooms, and a plan of a third room
    under the first."""
    cfg = CONFIGS[name]
    rooms = [_room(0), _room(1)]
    a = engine.build_plan_spec(rooms, cfg, mem_budget=BUDGET)
    b = engine.build_plan_spec(rooms, cfg, mem_budget=BUDGET)
    plan = engine.build_scene_plan_host(_room(2), cfg, spec=a)
    return a, b, plan


@pytest.mark.parametrize("name,site", SITES,
                         ids=[f"{n}-{s if s == 'stem' else f'L{s}'}"
                              for n, s in SITES])
def test_site_dispatch(name, site):
    cfg = CONFIGS[name]
    spec, again, plan = _specs(name)
    if site == "stem":
        size, c_in, c_out = stem_site(cfg)
        d, d2 = spec.stem, again.stem
        info = next(s for s in plan.stats if s.get("site") == "stem")
    else:
        size, c_in, c_out = 3, cfg.widths[site], cfg.widths[site]
        d, d2 = spec.levels[site], again.levels[site]
        info = plan.stats[site]
    k = size ** 3

    # (a) the decision is a function of the rooms alone
    assert d == d2
    # only the 5^3 stem's weight slab is beyond the kernel's VMEM here
    assert (d.backend == engine.REFERENCE) == (site == "stem")
    if d.backend == engine.SSPNNA:
        # (b) the kernel's site fits VMEM, with a bounded tile budget
        assert _fits_vmem(d, c_in, c_out, k)
        assert 0 < d.n_tiles <= max_tiles(cfg.capacity, d.delta_o,
                                          d.delta_i, k)
    else:
        # (c) a refused site has no tiles and keeps SPADE's walk
        assert d == Dispatch(engine.REFERENCE, d.flavor, d.walk)
        assert d.walk in ("OS", "WS")

    # (d) a plan under the spec runs the spec's decision, or the
    # reference after a recorded overflow of the tile budget
    if info.get("tile_overflow"):
        assert d.backend == engine.SSPNNA
        assert info["dispatch"] == REFERENCE_DISPATCH
        assert "n_tiles" not in info
    else:
        assert info["dispatch"] == d
        if d.backend == engine.SSPNNA:
            # the counts WaveStats.notes turns into sspnna_live_tile_pct
            assert info["n_tiles"] == d.n_tiles
            assert 0 < info["n_live_tiles"] <= info["n_tiles"]
        else:
            assert "n_tiles" not in info
    assert info["n_active"] > 0
