"""Async wave pipeline: sync/async equivalence, thread-safe plan cache,
poisoned-wave recovery, shared scheduler plumbing."""
import threading
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.data.scenes import N_CLASSES, make_scene
from repro.engine.plan import PlanCache
from repro.models.scn import UNetConfig, init_unet
from repro.serving.scene_engine import SceneEngine, SceneRequest
from repro.serving.scheduler import WaveScheduler
from repro.sparse.tensor import SparseVoxelTensor

RES, CAP = 16, 1024


def _scene(seed, cap=CAP):
    coords, feats, _, mask = make_scene(seed, resolution=RES, capacity=cap)
    return SparseVoxelTensor(jnp.asarray(coords), jnp.asarray(feats),
                             jnp.asarray(mask))


@pytest.fixture(scope="module")
def setup():
    cfg = UNetConfig(widths=(8, 16), reps=1, resolution=RES, capacity=CAP,
                     n_classes=N_CLASSES)
    params = init_unet(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _serve(eng, scenes):
    handles = eng.submit([SceneRequest(i, s) for i, s in enumerate(scenes)])
    eng.serve()
    return {h.request.rid: h.result() for h in handles}


def test_async_matches_sync_bitwise(setup):
    cfg, params = setup
    scenes = [_scene(200 + i) for i in range(5)]  # batch 2 -> short last wave
    by_sync = _serve(SceneEngine(cfg, params, batch=2, sync=True), scenes)
    by_async = _serve(SceneEngine(cfg, params, batch=2, sync=False, depth=2,
                                  planner_threads=2), scenes)
    assert by_sync.keys() == by_async.keys()
    for rid in by_sync:
        np.testing.assert_array_equal(by_sync[rid].logits,
                                      by_async[rid].logits)
        assert by_async[rid].done


def test_async_matches_sync_with_pinned_spec(setup):
    cfg, params = setup
    spec = engine.build_plan_spec([_scene(100), _scene(101)], cfg,
                                  mem_budget=16 * 1024)
    assert any(d.backend == engine.SSPNNA for d in spec.levels)
    scenes = [_scene(300 + i) for i in range(4)]
    by_sync = _serve(SceneEngine(cfg, params, batch=2, spec=spec,
                                 use_kernel=False, sync=True), scenes)
    eng = SceneEngine(cfg, params, batch=2, spec=spec, use_kernel=False,
                      sync=False)
    by_async = _serve(eng, scenes)
    for rid in by_sync:
        np.testing.assert_array_equal(by_sync[rid].logits,
                                      by_async[rid].logits)
    assert eng.n_compilations == 1  # pinned spec: one signature, async too


def test_async_wave_stats_and_timings(setup):
    cfg, params = setup
    eng = SceneEngine(cfg, params, batch=2, sync=False)
    _serve(eng, [_scene(400 + i) for i in range(4)])
    assert len(eng.wave_stats) == 2
    for st in eng.wave_stats:
        assert st.plan_ms > 0 and st.inflight_ms > 0
        assert 0.0 <= st.overlap_frac <= 1.0
        assert not st.sync
    tm = eng.timings()
    assert tm["waves"] == 2
    assert set(tm) >= {"plan_ms", "plan_wait_ms", "inflight_ms", "drain_ms",
                       "overlap_frac"}
    # sync mode reports zero overlap by construction
    es = SceneEngine(cfg, params, batch=2, sync=True)
    _serve(es, [_scene(500 + i) for i in range(2)])
    assert es.timings()["overlap_frac"] == 0.0


def test_plan_cache_concurrent_same_scene_builds_once(setup):
    cfg, _ = setup
    cache = PlanCache(capacity=8)
    t = _scene(600)
    n = 8
    results: list = [None] * n
    barrier = threading.Barrier(n)

    def worker(i):
        barrier.wait()
        results[i] = cache.get_or_build(t, cfg, plan_tiles=False)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert cache.misses == 1 and cache.hits == n - 1
    assert len(cache) == 1
    assert all(r is results[0] for r in results)  # one shared plan object


def test_plan_cache_concurrent_distinct_scenes(setup):
    cfg, _ = setup
    cache = PlanCache(capacity=8)
    scenes = [_scene(700 + i) for i in range(4)]
    out: dict = {}
    barrier = threading.Barrier(len(scenes))

    def worker(i):
        barrier.wait()
        out[i] = cache.get_or_build(scenes[i], cfg, plan_tiles=False)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(scenes))]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert cache.misses == len(scenes) and len(cache) == len(scenes)
    # host/device split: device=False returns numpy-leaf plans, device=True
    # the memoized uploaded twin
    host = cache.get_or_build(scenes[0], cfg, device=False, plan_tiles=False)
    assert isinstance(host.levels[0].sub.coir.indices, np.ndarray)
    dev = cache.get_or_build(scenes[0], cfg, device=True, plan_tiles=False)
    assert dev is cache.get_or_build(scenes[0], cfg, device=True,
                                     plan_tiles=False)
    np.testing.assert_array_equal(np.asarray(dev.levels[0].sub.coir.indices),
                                  host.levels[0].sub.coir.indices)


def test_plan_cache_failed_build_releases_key(setup):
    cfg, _ = setup
    cache = PlanCache(capacity=4)
    bad = _scene(800)
    bad_cfg = UNetConfig(widths=(8, 16, 32), reps=1, resolution=RES,
                         capacity=CAP, n_classes=N_CLASSES)
    spec = engine.build_plan_spec([_scene(801)], cfg, mem_budget=16 * 1024)
    with pytest.raises(ValueError):  # spec levels != cfg levels
        cache.get_or_build(bad, bad_cfg, spec=spec)
    # the key is released: a second attempt raises again (no deadlock) and
    # the cache still works for good builds
    with pytest.raises(ValueError):
        cache.get_or_build(bad, bad_cfg, spec=spec)
    assert cache.get_or_build(bad, cfg, plan_tiles=False) is not None


@pytest.mark.parametrize("sync", [True, False])
def test_poisoned_wave_requeues_without_losing_requests(setup, sync):
    cfg, params = setup
    eng = SceneEngine(cfg, params, batch=2, sync=sync, depth=2,
                      planner_threads=2)
    reqs = [SceneRequest(i, _scene(900 + i)) for i in range(6)]
    # rid 2 has a different capacity: its plan/feats can't stack with the
    # wave -> dispatch blows up after wave 0 is already in flight
    reqs[2] = SceneRequest(2, _scene(902, cap=CAP // 2))
    eng.submit(reqs)
    with pytest.raises(Exception):
        eng.serve()
    done = {r.rid for r in reqs if r.status == "completed"}
    queued = [r.rid for r in eng.queue]
    # nothing dropped, nothing duplicated, poisoned wave back at the front
    assert sorted(done) + queued == list(range(6))
    assert 2 in queued
    # drop the poison and the remaining requests serve to completion
    good = [r for r in eng.queue if r.rid != 2]
    eng.queue.clear()
    eng.submit(good)
    eng.serve()
    survivors = [r for r in reqs if r.rid != 2]
    assert {r.rid for r in survivors if r.status == "completed"} == \
        {0, 1, 3, 4, 5}
    for r in survivors:
        assert r.logits is not None and not np.any(np.isnan(r.logits))


def test_scheduler_validates_knobs():
    stages = dict(plan=lambda r: r, dispatch=lambda rs, ps, st: ps,
                  drain=lambda rs, h: None)
    with pytest.raises(ValueError):
        WaveScheduler(batch=0, **stages)
    with pytest.raises(ValueError):
        WaveScheduler(batch=1, depth=0, **stages)
    with pytest.raises(ValueError):
        WaveScheduler(batch=1, planner_threads=0, **stages)
    sched = WaveScheduler(batch=2, **stages)
    assert isinstance(sched.queue, deque)
    assert sched.run() == []  # empty queue is a no-op in both modes
    assert sched.run(sync=False) == []


def test_close_idempotent_and_drains_inflight_plans(setup):
    """close() racing an async run waits for the run — draining its
    planner-thread futures — instead of cancelling them; repeated closes
    are no-ops and the engine stays usable afterwards."""
    cfg, params = setup
    eng = SceneEngine(cfg, params, batch=2, sync=False, depth=2,
                      planner_threads=2)
    scenes = [_scene(1100 + i) for i in range(6)]
    handles = eng.submit([SceneRequest(i, s) for i, s in enumerate(scenes)])
    t = threading.Thread(target=eng.serve)
    t.start()
    eng.close()  # may land mid-run: must block until the run drains
    t.join()
    for h in handles:
        assert h.done() and h.result().logits is not None
    eng.close()  # idempotent
    eng.close()
    # a later serve lazily recreates the planner pool
    h2 = eng.submit(SceneRequest(99, _scene(1199)))
    eng.serve()
    assert h2.result().logits is not None
    eng.close()


def test_lm_engine_async_matches_sync(rng):
    from repro.configs import get_config
    from repro.models.transformer import init_lm
    from repro.serving.engine import Engine, Request

    cfg = get_config("stablelm-1.6b").reduced()
    params = init_lm(jax.random.PRNGKey(0), cfg)
    prompts = [rng.integers(0, cfg.vocab_size, 10).astype(np.int32)
               for _ in range(5)]

    def serve(sync, eos=None):
        eng = Engine(cfg, params, batch=2, prompt_len=16, max_new=4, eos=eos,
                     sync=sync)
        handles = eng.submit([Request(i, p) for i, p in enumerate(prompts)])
        eng.serve()
        return {h.request.rid: h.result().out for h in handles}

    outs_sync, outs_async = serve(True), serve(False)
    assert outs_sync == outs_async
    assert all(len(o) == 4 for o in outs_sync.values())
    # EOS truncation happens at drain time -> still mode-independent
    eos = outs_sync[0][0]
    assert serve(True, eos=eos) == serve(False, eos=eos)


def test_async_survives_plan_cache_eviction(setup):
    """LRU pressure between plan and dispatch must not rebuild or corrupt:
    dispatch adopts the plan-stage payload instead of re-building."""
    cfg, params = setup
    scenes = [_scene(1000 + i) for i in range(6)]
    by_sync = _serve(SceneEngine(cfg, params, batch=2, sync=True), scenes)
    eng = SceneEngine(cfg, params, batch=2, sync=False, depth=2,
                      planner_threads=2, plan_cache_size=1)
    by_async = _serve(eng, scenes)
    for rid in by_sync:
        np.testing.assert_array_equal(by_sync[rid].logits,
                                      by_async[rid].logits)
    # one counted miss per distinct scene at the plan stage; the dispatch
    # adoption path never counts and never rebuilds
    assert eng.cache.misses == len(scenes) and eng.cache.hits == 0
