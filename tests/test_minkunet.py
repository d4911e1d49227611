"""MinkUNet through the shared U-Net walk: served logits against the
benchmark's plain reference, the 5^3 stem's COIR, the stem's dispatch rule,
and SCN's served logits held to a digest."""
import contextlib
import hashlib
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.analysis import plan_check
from repro.analysis.spans import span
from repro.core.coir import COIR
from repro.core.hashgrid import kernel_offsets
from repro.core.host_meta import build_cirf_np
from repro.data.scenes import make_scene
from repro.engine import shard
from repro.models.minkunet import MinkUNetConfig, init_minkunet
from repro.models.scn import UNetConfig, init_unet
from repro.serving.scene_engine import SceneEngine, SceneRequest
from repro.sparse.tensor import SparseVoxelTensor

BENCH = Path(__file__).resolve().parents[1] / "bench"
RES, CAP = 24, 2048
# a small L1 budget, so that SPADE tiles the levels for the kernel
BUDGET = 16 * 1024
# widths 8..24 on 2 levels: the level-1 stage widens 8 -> 16 and the
# decoder reads 24 + 8 -> 24, both through 1x1 projections; a 5^3 stem
BENCH_CFG = {"name": "tiny_mink", "arch": "minkunet", "init_dim": 8,
             "planes": [16, 24], "layers": [2, 2], "stem_kernel": 5,
             "nClasses": 5, "input_features": 4, "full_scale": RES,
             "capacity": CAP, "batch": 2}
# served against the reference: float32 sums in another order (per plane
# in the kernel, all planes at once in the reference), through per-scene
# batch norms that divide by small deviations, move the logits by ~1e-6
# of their largest value on the CPU; a wrong term moves them by 1e-2 and
# more
GAP = 1e-4


@pytest.fixture(scope="module")
def arch():
    """The benchmark's two MinkUNet modules, loaded by path (the reference
    imports its building blocks from ``bench/``)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.syspath_prepend(str(BENCH))
        mods = []
        for name in ("minkunet", "minkunet_sut"):
            spec = importlib.util.spec_from_file_location(
                f"bench_arch_{name}", BENCH / "archs" / f"{name}.py")
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            mods.append(mod)
        mods.append(sys.modules["reference"])
    return mods


def _weights(shapes: dict, seed: int) -> dict:
    """Seeded float32 weights, norms and biases away from identity."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, shape in sorted(shapes.items()):
        x = rng.standard_normal(shape).astype(np.float32)
        if name.endswith(".w"):
            fan_in = shape[0] * shape[1] if len(shape) == 3 else shape[0]
            out[name] = jnp.asarray(x / np.sqrt(fan_in))
        elif name.endswith(".scale"):
            out[name] = jnp.asarray(1.0 + 0.1 * x)
        else:
            out[name] = jnp.asarray(0.1 * x)
    return out


def _scene(seed: int) -> SparseVoxelTensor:
    coords, feats, _, mask = make_scene(seed, resolution=RES, capacity=CAP)
    return SparseVoxelTensor(coords, feats, mask)


def _gap(got, want) -> float:
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["reference", "interpret_kernel"])
def test_served_logits_match_the_reference(arch, use_kernel):
    model, sut, _ = arch
    w = _weights(model.weight_shapes(BENCH_CFG), 3)
    mcfg = sut.model_config(BENCH_CFG)
    scenes = [_scene(s) for s in (1, 2, 3)]
    if use_kernel:
        spec = engine.build_plan_spec(scenes[:1], mcfg, mem_budget=BUDGET)
        assert {d.backend for d in spec.levels} == {engine.SSPNNA}
        eng = SceneEngine(mcfg, sut.params(w, BENCH_CFG), batch=2,
                          spec=spec, interpret=True)
    else:
        eng = SceneEngine(mcfg, sut.params(w, BENCH_CFG), batch=2,
                          backend="reference")
    with contextlib.closing(eng):
        reqs = [SceneRequest(i, t) for i, t in enumerate(scenes)]
        for h in eng.submit(reqs):
            h.result(timeout=600)
    for r, t in zip(reqs, scenes):
        m = np.asarray(t.mask)
        want = model.logits(w, np.asarray(t.coords)[m],
                            np.asarray(t.feats)[m], BENCH_CFG)
        assert _gap(np.asarray(r.logits)[m], want) < GAP


def test_a_5cube_coir_is_the_reference_table(arch):
    ref = arch[2]
    t = _scene(4)
    coords, mask = np.asarray(t.coords), np.asarray(t.mask)
    coir = build_cirf_np(coords, mask, coords, mask, kernel_offsets(5), RES)
    assert coir.indices.shape == (CAP, 125)
    assert coir.bitmask.shape == (CAP, 4) and coir.bitmask.dtype == np.uint32
    n = int(mask.sum())
    assert mask[:n].all()
    table = ref.table(ref.Index(coords[:n], RES), coords[:n], 5)
    np.testing.assert_array_equal(coir.indices[:n], table)
    assert (coir.indices[n:] == -1).all()
    # the bitmask agrees with the holes, word by word (P001)
    assert plan_check.check_coir(coir, CAP, "stem") == []
    words = coir.bitmask.copy()
    row = int(np.flatnonzero(coir.indices[:, 100] >= 0)[0])
    words[row, 3] ^= np.uint32(1 << (100 - 96))
    bad = plan_check.check_coir(COIR(coir.indices, words, coir.mask), CAP,
                                "stem")
    assert [f.rule for f in bad] == ["REPRO-P001"]


def test_the_stem_has_its_own_site_and_span():
    cfg = MinkUNetConfig(in_channels=4, init_dim=8, planes=(16, 24),
                         layers=(2, 2), resolution=RES, capacity=CAP)
    t = _scene(5)
    with span("plan.request") as sp:
        plan = engine.build_scene_plan_host(t, cfg, mem_budget=BUDGET)
    assert {"plan.stem", "plan.level", "plan.cirf"} <= set(sp.phase_ms)
    stem = plan.levels[0].stem
    assert stem is not None and stem.coir.indices.shape[1] == 125
    assert plan.stats[-1]["site"] == "stem"
    assert plan_check.check_scene_plan(plan) == []
    # SCN's 3^3 stem reads level 0's submanifold plan: no stem site
    scn = UNetConfig(widths=(8, 16), reps=1, resolution=RES, capacity=CAP)
    scn_plan = engine.build_scene_plan_host(t, scn, mem_budget=BUDGET)
    assert all(lvl.stem is None for lvl in scn_plan.levels)
    with pytest.raises(ValueError, match="stem"):
        engine.StreamPlanState(cfg)


@pytest.mark.parametrize("budget", [BUDGET, 64 * 1024])
def test_the_stem_goes_to_the_kernel_only_within_h003(arch, budget):
    """The spec pins the stem to the fused kernel only where the kernel's
    lane-padded VMEM passes REPRO-H003; otherwise to the reference backend
    on SPADE's walk."""
    from repro.core import spade
    from repro.engine.plan import _explore, _fits_vmem, _order_rows

    cfg = MinkUNetConfig(in_channels=4, init_dim=8, planes=(16, 24),
                         layers=(2, 2), resolution=RES, capacity=CAP)
    t = _scene(6)
    spec = engine.build_plan_spec([t], cfg, mem_budget=budget)
    coords, mask = np.asarray(t.coords), np.asarray(t.mask)
    sub = build_cirf_np(coords, mask, coords, mask, kernel_offsets(3), RES)
    stem = build_cirf_np(coords, mask, coords, mask, kernel_offsets(5), RES)
    attrs = spade.extract_attributes(
        stem.indices, mask, _order_rows(sub, coords, mask, "soar", 512))
    d, _ = _explore("stem", attrs, CAP, 125, 4, 8, budget)
    assert d.backend == engine.SSPNNA
    sites = arch[1].kernel_sites(spec)
    if _fits_vmem(d, 4, 8, 125):
        assert spec.stem.backend == engine.SSPNNA and ("stem", 0) in sites
    else:
        assert (spec.stem.backend, spec.stem.walk) == (engine.REFERENCE,
                                                       d.walk)
        assert ("stem", 0) not in sites


def test_h003_refuses_the_2cm_stem_tiles():
    """The stem's tiles SPADE pins for a 128k-voxel room at 2 cm (d_O 128,
    d_I 867): a 125 x 128 x 128 float32 weight slab, double-buffered, and
    the working set pass 16 MiB; at K = 27 the same tiles fit."""
    from repro.engine.plan import _fits_vmem

    d = engine.Dispatch(engine.SSPNNA, "CIRF", "WS", 128, 867)
    assert not _fits_vmem(d, 4, 32, 125)
    assert _fits_vmem(d, 4, 32, 27)


def test_sharded_scenes_refuse_another_unet():
    cfg = MinkUNetConfig(in_channels=4, init_dim=8, planes=(16, 24),
                         layers=(2, 2), resolution=RES, capacity=CAP)
    with pytest.raises(ValueError, match="SCN U-Net walk only"):
        shard._scn_walk_only(init_minkunet(jax.random.PRNGKey(0), cfg))
    scn = UNetConfig(widths=(8, 16), reps=1, resolution=RES, capacity=CAP)
    shard._scn_walk_only(init_unet(jax.random.PRNGKey(0), scn))


#: sha256 of SCN's served logits (float32 bytes) for the seeds below, as
#: the program computed them before MinkUNet shared its walk
SCN_DIGESTS = {
    "served":
        "f840a8f4a6a98d67149cc0d6e97fe1ec9e6f88a60f45fb139c63ce4bb40a7c58",
    "reference":
        "8db15637b0cd7775738aa131cdcfd1845b6d69d30139de4f8d6ae08f12dcc7e7",
}


@pytest.mark.parametrize("path", sorted(SCN_DIGESTS))
def test_scn_logits_keep_their_digest(path):
    cfg = UNetConfig(widths=(8, 16, 24), reps=2, resolution=RES,
                     capacity=CAP, n_classes=5)
    params = init_unet(jax.random.PRNGKey(11), cfg)
    t = _scene(7)
    if path == "served":
        spec = engine.build_plan_spec([t], cfg, mem_budget=BUDGET)
        eng = SceneEngine(cfg, params, batch=2, spec=spec, interpret=True)
    else:
        eng = SceneEngine(cfg, params, batch=2, backend="reference")
    with contextlib.closing(eng):
        (h,) = eng.submit([SceneRequest(0, t)])
        h.result(timeout=600)
    got = hashlib.sha256(
        np.ascontiguousarray(h.request.logits, np.float32)).hexdigest()
    assert got == SCN_DIGESTS[path]

