"""repro.engine: plan building, backend dispatch, shims, batched serving."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.data.scenes import N_CLASSES, make_scene
from repro.models import scn
from repro.models.scn import UNetConfig, init_unet
from repro.serving.scene_engine import SceneEngine, SceneRequest
from repro.sparse.tensor import SparseVoxelTensor

RES, CAP = 24, 2048
# small L1 budget so SPADE picks an actual tiling (sspnna) on these scenes
BUDGET = 16 * 1024


def _scene(seed):
    coords, feats, labels, mask = make_scene(seed, resolution=RES, capacity=CAP)
    return SparseVoxelTensor(jnp.asarray(coords), jnp.asarray(feats),
                             jnp.asarray(mask))


@pytest.fixture(scope="module")
def setup():
    cfg = UNetConfig(widths=(8, 16), reps=1, resolution=RES, capacity=CAP,
                     n_classes=N_CLASSES)
    params = init_unet(jax.random.PRNGKey(0), cfg)
    t = _scene(0)
    plan = engine.build_scene_plan(t, cfg, mem_budget=BUDGET)
    return cfg, params, t, plan


def test_backends_agree_on_unet(setup):
    cfg, params, t, plan = setup
    # the plan must actually exercise the tiled path for this to mean much
    assert any(lvl.sub.tiles is not None for lvl in plan.levels)
    ref = engine.apply_unet(params, t.feats, plan, backend="reference")
    ssp = engine.apply_unet(params, t.feats, plan, backend="sspnna",
                            use_kernel=True)
    m = np.asarray(t.mask)
    np.testing.assert_allclose(np.asarray(ref)[m], np.asarray(ssp)[m],
                               rtol=1e-4, atol=1e-4)


def test_auto_follows_spade_plan(setup):
    cfg, params, t, plan = setup
    for lvl in plan.levels:
        assert engine.resolve_backend(lvl.sub, "auto") == lvl.sub.dispatch.backend
        # resolution-changing convs stay on the coarse reference dispatch
        for cp in (lvl.down, lvl.up):
            if cp is not None:
                assert engine.resolve_backend(cp, "auto") == engine.REFERENCE
    auto = engine.apply_unet(params, t.feats, plan, backend="auto",
                             use_kernel=False)
    ref = engine.apply_unet(params, t.feats, plan, backend="reference")
    m = np.asarray(t.mask)
    np.testing.assert_allclose(np.asarray(auto)[m], np.asarray(ref)[m],
                               rtol=1e-4, atol=1e-4)


def test_single_conv_pallas_backend_agrees(setup):
    cfg, params, t, plan = setup
    lvl0 = plan.levels[0]
    assert lvl0.sub.dispatch.backend == engine.SSPNNA
    ref = engine.sparse_conv(t.feats, params["stem"], lvl0.sub,
                             backend="reference")
    ssp = engine.sparse_conv(t.feats, params["stem"], lvl0.sub,
                             backend="sspnna", use_kernel=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ssp),
                               rtol=1e-4, atol=1e-4)
    with pytest.raises(ValueError):
        engine.sparse_conv(t.feats, params["stem"], lvl0.sub, backend="bogus")


def test_plan_cache_hits_by_scene_content(setup):
    cfg, params, t, plan = setup
    cache = engine.PlanCache(capacity=4)
    p1 = cache.get_or_build(t, cfg, plan_tiles=False)
    p2 = cache.get_or_build(_scene(0), cfg, plan_tiles=False)  # same content
    assert p1 is p2 and cache.hits == 1 and cache.misses == 1
    cache.get_or_build(_scene(1), cfg, plan_tiles=False)
    assert cache.misses == 2


def test_deprecated_shims_numerically_identical(setup):
    cfg, params, t, plan = setup
    with pytest.warns(DeprecationWarning):
        meta = scn.build_unet_metadata(t, cfg)
    with pytest.warns(DeprecationWarning):
        old = scn.apply_unet(params, t.feats, meta)
    new = engine.apply_unet(
        params, t.feats, engine.build_scene_plan(t, cfg, plan_tiles=False),
        backend="reference")
    np.testing.assert_array_equal(np.asarray(old), np.asarray(new))

    from repro.core.sparse_conv import reference_conv_cirf, sparse_conv_cirf
    with pytest.warns(DeprecationWarning):
        old_conv = sparse_conv_cirf(t.feats, plan.levels[0].sub.coir,
                                    params["stem"])
    np.testing.assert_array_equal(
        np.asarray(old_conv),
        np.asarray(reference_conv_cirf(t.feats, plan.levels[0].sub.coir,
                                       params["stem"])))

    from repro.core.tiles import build_tile_plan
    from repro.kernels.sspnna.ops import sspnna_conv_from_plan
    lvl0 = plan.levels[0]
    tp = build_tile_plan(np.asarray(lvl0.sub.coir.indices),
                         np.flatnonzero(np.asarray(t.mask)), 64, 256)
    with pytest.warns(DeprecationWarning):
        old_tiled = sspnna_conv_from_plan(
            t.feats, params["stem"].weight, tp, n_out=CAP, use_kernel=False)
    ref = np.asarray(reference_conv_cirf(t.feats, lvl0.sub.coir,
                                         params["stem"]))
    got = np.asarray(old_tiled) + np.asarray(params["stem"].bias)
    m = np.asarray(t.mask)
    np.testing.assert_allclose(got[m], ref[m], rtol=1e-4, atol=1e-4)


def test_scene_engine_serves_batches_with_one_compilation():
    cfg = UNetConfig(widths=(8, 16), reps=1, resolution=RES, capacity=CAP,
                     n_classes=N_CLASSES)
    params = init_unet(jax.random.PRNGKey(0), cfg)
    spec = engine.build_plan_spec([_scene(100), _scene(101)], cfg,
                                  mem_budget=BUDGET)
    assert any(d.backend == engine.SSPNNA for d in spec.levels)
    eng = SceneEngine(cfg, params, batch=4, spec=spec)
    scenes = [_scene(200 + i) for i in range(6)]
    handles = eng.submit([SceneRequest(i, s)
                          for i, s in enumerate(scenes[:4])])
    eng.serve()
    handles += eng.submit([SceneRequest(4 + i, s)
                           for i, s in enumerate(scenes[4:])])
    eng.serve()  # short wave: exercises padding
    assert eng.n_compilations == 1
    assert len(handles) == 6 and all(h.done() for h in handles)
    for h in handles:
        r = h.result()
        assert r.logits.shape == (CAP, N_CLASSES)
        assert not np.any(np.isnan(r.logits))
    # batched result == single-scene engine apply off the cached plan
    r0 = handles[0].result()
    plan0 = eng.cache.get_or_build(r0.scene, cfg, spec=spec)
    single = engine.apply_unet(params, r0.scene.feats, plan0)
    np.testing.assert_allclose(r0.logits, np.asarray(single),
                               rtol=1e-5, atol=1e-5)
    # resubmitting a known scene hits the plan cache and the jit cache
    eng.submit(SceneRequest(99, scenes[0])).result()
    assert eng.cache.hits >= 1 and eng.n_compilations == 1


def test_host_meta_numpy_mirrors_match_jax_builders(setup):
    """The host plan pass must be bit-identical to the jitted AdMAC ops."""
    from repro.core import host_meta
    from repro.core.coir import build_cirf
    from repro.core.hashgrid import downsample_coords, kernel_offsets
    from repro.core.sparse_conv import transposed_coir

    cfg, params, t, plan = setup
    coords, mask = np.asarray(t.coords), np.asarray(t.mask)
    offs3 = kernel_offsets(3)
    got = host_meta.build_cirf_np(coords, mask, coords, mask, offs3, RES)
    want = build_cirf(t.coords, t.mask, t.coords, t.mask,
                      jnp.asarray(offs3), RES)
    np.testing.assert_array_equal(got.indices, np.asarray(want.indices))
    np.testing.assert_array_equal(got.bitmask, np.asarray(want.bitmask))

    dn_c, dn_m = host_meta.downsample_coords_np(coords, mask, RES, 2)
    jn_c, jn_m = downsample_coords(t.coords, t.mask, RES, 2)
    np.testing.assert_array_equal(dn_c, np.asarray(jn_c))
    np.testing.assert_array_equal(dn_m, np.asarray(jn_m))

    offs2 = kernel_offsets(2, centered=False)
    got2 = host_meta.build_cirf_np(dn_c, dn_m, coords, mask, offs2, RES,
                                   stride=2)
    want2 = build_cirf(jn_c, jn_m, t.coords, t.mask, jnp.asarray(offs2),
                       RES, stride=2)
    np.testing.assert_array_equal(got2.indices, np.asarray(want2.indices))

    got3 = host_meta.transposed_coir_np(dn_c, dn_m, coords, mask, RES, 2, 2)
    coarse = SparseVoxelTensor(jn_c, jnp.zeros((jn_c.shape[0], 1)), jn_m)
    want3 = transposed_coir(coarse, t.coords, t.mask, RES, 2, 2)
    np.testing.assert_array_equal(got3.indices, np.asarray(want3.indices))
    np.testing.assert_array_equal(got3.bitmask, np.asarray(want3.bitmask))


# ---------------------------------------------------------------------------
# Backend registry + ExecutionContext (the PR-5 API seam)
# ---------------------------------------------------------------------------

class _DoubledBackend(engine.Backend):
    """Toy backend: reference numerics times two (distinguishable)."""

    name = "doubled"

    def run(self, x, params, plan, *, ctx, **kw):
        from repro.core.sparse_conv import reference_conv_cirf
        return 2.0 * reference_conv_cirf(x, plan.coir, params)


def test_new_backend_registers_without_touching_the_dispatcher(setup):
    """The acceptance seam: a backend defined here — no engine.api edits —
    is routable by explicit name AND via a plan's Dispatch decision."""
    cfg, params, t, plan = setup
    ctx = engine.ExecutionContext()  # scoped registry view
    ctx.registry.register("doubled", _DoubledBackend())
    lvl0 = plan.levels[0].sub
    ref = engine.sparse_conv(t.feats, params["stem"], lvl0,
                             backend="reference")
    got = engine.sparse_conv(t.feats, params["stem"], lvl0,
                             backend="doubled", ctx=ctx)
    np.testing.assert_array_equal(np.asarray(got), 2.0 * np.asarray(ref))
    # SPADE/Dispatch emit a *name*; the registry resolves it under "auto"
    named = engine.ConvPlan(lvl0.coir, None,
                            engine.Dispatch(backend="doubled"))
    got_auto = engine.sparse_conv(t.feats, params["stem"], named,
                                  backend="auto", ctx=ctx)
    np.testing.assert_array_equal(np.asarray(got_auto), 2.0 * np.asarray(ref))
    assert engine.resolve_backend(named, "auto", ctx=ctx) == "doubled"
    # the scoped registration never leaked into the process default
    assert "doubled" not in engine.default_registry()
    with pytest.raises(ValueError):
        engine.sparse_conv(t.feats, params["stem"], lvl0, backend="doubled")
    # global registration path (+ cleanup) works too
    engine.register_backend("doubled", _DoubledBackend())
    try:
        assert "doubled" in engine.available_backends()
        assert "doubled" in engine.BACKENDS  # legacy alias stays live
    finally:
        engine.default_registry().unregister("doubled")
    assert "doubled" not in engine.available_backends()


def test_backend_fallback_chain_and_errors(setup):
    cfg, params, t, plan = setup
    bare = engine.reference_plan(plan.levels[0].sub.coir)
    # sspnna without tile metadata degrades along its declared fallback
    assert engine.resolve_backend(bare, "sspnna") == "reference"
    reg = engine.default_registry().view()
    with pytest.raises(ValueError, match="not one of"):
        reg.resolve(bare, "bogus")
    with pytest.raises(ValueError):
        reg.register("auto", _DoubledBackend())  # reserved name
    with pytest.raises(ValueError):
        reg.register("reference", _DoubledBackend())  # no silent shadowing


def test_use_context_scopes_ambient_resolution(setup):
    cfg, params, t, plan = setup
    ctx = engine.ExecutionContext()
    ctx.registry.register("doubled", _DoubledBackend())
    lvl0 = plan.levels[0].sub
    ref = engine.sparse_conv(t.feats, params["stem"], lvl0,
                             backend="reference")
    with engine.use_context(ctx):
        assert engine.current_context() is ctx
        got = engine.sparse_conv(t.feats, params["stem"], lvl0,
                                 backend="doubled")  # no ctx= needed
    np.testing.assert_array_equal(np.asarray(got), 2.0 * np.asarray(ref))
    assert engine.current_context() is engine.default_context()
    with pytest.raises(ValueError):
        engine.sparse_conv(t.feats, params["stem"], lvl0, backend="doubled")


def test_scene_engine_accepts_shared_context(setup):
    """Two engines on one context share its plan cache."""
    cfg, params, t, plan = setup
    ctx = engine.ExecutionContext()
    e1 = SceneEngine(cfg, params, batch=2, ctx=ctx)
    e2 = SceneEngine(cfg, params, batch=2, ctx=ctx)
    assert e1.cache is ctx.plan_cache and e2.cache is ctx.plan_cache
    e1.submit([SceneRequest(0, t)])
    e1.serve()
    e2.submit([SceneRequest(1, t)])
    e2.serve()
    assert ctx.plan_cache.hits >= 1  # e2 hit e1's plan
    e1.close(), e2.close()
