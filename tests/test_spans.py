"""Spans on the profiler's clock: the serving and plan layers' stages as
trace events, and the ``WaveStats`` counters read from the same spans.

A real ``jax.profiler`` trace (host tracer level 1, as ``bench/run.py
--trace 1`` records) is taken around a tiny asynchronous ``SceneEngine``
run, a stream and an adaptive plan build, then read back with
``ProfileData``."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import engine
from repro.analysis.spans import span
from repro.data.scenes import N_CLASSES, make_lidar_sweep, make_scene
from repro.engine.plan import build_scene_plan_host
from repro.models.scn import UNetConfig, init_unet
from repro.serving.scene_engine import SceneEngine, SceneRequest
from repro.sparse.tensor import SparseVoxelTensor

RES, CAP = 16, 1024
SERVE = ("serve.admit", "serve.plan_wait", "serve.dispatch", "serve.upload",
         "serve.drain")
PLAN = ("plan.request", "plan.key", "plan.geometry", "plan.level",
        "plan.cirf", "plan.order", "plan.spade", "plan.tiles")
STREAM = ("plan.frame", "plan.patch", "plan.rebuild")
MAX_SPANS_PER_REQUEST = 80


def _scene(seed):
    coords, feats, _, mask = make_scene(seed, resolution=RES, capacity=CAP)
    return SparseVoxelTensor(jnp.asarray(coords), jnp.asarray(feats),
                             jnp.asarray(mask))


def _events(tdir):
    """-> [(name, line, start_ns, end_ns, stats)] of the host plane's
    program spans; ``line`` tells the threads apart."""
    path = sorted(glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(("serve.", "plan.")):
                    out.append((e.name, li, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One trace around an async wave run (pinned spec), a stream of four
    frames and an adaptive host plan build."""
    cfg = UNetConfig(widths=(8, 16), reps=1, resolution=RES, capacity=CAP,
                     n_classes=N_CLASSES)
    params = init_unet(jax.random.PRNGKey(0), cfg)
    spec = engine.build_plan_spec([_scene(100), _scene(101)], cfg,
                                  mem_budget=16 * 1024)
    assert any(d.backend == engine.SSPNNA for d in spec.levels)
    eng = SceneEngine(cfg, params, batch=2, spec=spec, use_kernel=False,
                      sync=False)
    frames, shifts = make_lidar_sweep(7, 4, resolution=RES, capacity=CAP,
                                      step=4, churn=0.05)
    sweep = [SparseVoxelTensor(jnp.asarray(c), jnp.asarray(f),
                               jnp.asarray(m)) for c, f, _, m in frames]
    scenes = [_scene(700 + i) for i in range(5)]  # batch 2: a padded wave
    # compile outside the trace; the traced run's first request then
    # finds its plan in the cache
    eng.submit([SceneRequest(0, scenes[0])])
    eng.serve()
    tdir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    n0 = len(eng.wave_stats)
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        handles = eng.submit([SceneRequest(10 + i, s)
                              for i, s in enumerate(scenes)])
        eng.serve()
        waves = list(eng.wave_stats[n0:])
        stream = eng.serve_stream(sweep, shifts)
        build_scene_plan_host(scenes[0], cfg, mem_budget=16 * 1024)
    finally:
        jax.profiler.stop_trace()
    for h in handles:
        assert h.result().done
    eng.close()
    return {"events": _events(tdir), "waves": waves, "stream": stream,
            "rids": [10 + i for i in range(len(scenes))]}


def _within(inner, outer):
    return (inner[1] == outer[1] and outer[2] <= inner[2]
            and inner[3] <= outer[3])


def _wall_ms(events) -> float:
    """The events' ``wall_ms`` stats, after checking that each event's
    duration on the profiler's clock brackets it."""
    for e in events:
        assert (e[3] - e[2]) * 1e-6 >= e[4]["wall_ms"] * (1 - 1e-3) - 1e-3
    return sum(e[4]["wall_ms"] for e in events)


def test_every_span_is_recorded_with_its_ids(traced):
    ev = traced["events"]
    names = {e[0] for e in ev}
    assert set(SERVE) | set(PLAN) | set(STREAM) <= names
    requests = [e for e in ev if e[0] == "plan.request"]
    assert {e[4]["rid"] for e in requests} >= set(traced["rids"])
    for name, _, _, _, meta in ev:
        if name.startswith("serve."):
            assert "wave" in meta, name
        assert {"wall_ms", "cpu_ms"} <= set(meta), name
        if name == "plan.request":
            assert {"rid", "wave"} <= set(meta)
    # a phase inside a request carries its request's ids
    for e in ev:
        outer = [r for r in requests if _within(e, r)]
        if e[0].startswith("plan.") and outer:
            assert e[4]["rid"] == outer[0][4]["rid"], e[0]
            assert e[4]["wave"] == outer[0][4]["wave"], e[0]


@pytest.mark.parametrize("inner,outer", [
    ("plan.level", "plan.request"), ("plan.cirf", "plan.level"),
    ("plan.order", "plan.level"), ("plan.tiles", "plan.level"),
    ("serve.upload", "serve.dispatch"), ("plan.patch", "plan.frame")])
def test_spans_nest(traced, inner, outer):
    ev = traced["events"]
    outers = [e for e in ev if e[0] == outer]
    inners = [e for e in ev if e[0] == inner]
    if inner.startswith("plan.") and outer != "plan.frame":
        # the rooms' requests: the adaptive build ran outside any, and a
        # stream frame assembles its levels without a plan.level span
        inners = [e for e in inners if e[4].get("rid") in traced["rids"]]
    assert inners
    for e in inners:
        assert any(_within(e, o) for o in outers), (inner, e)


def test_a_request_emits_few_spans(traced):
    ev = traced["events"]
    for rid in traced["rids"]:
        mine = [e for e in ev if e[4].get("rid") == rid]
        wave = next(e[4]["wave"] for e in mine if e[0] == "plan.request")
        shared = [e for e in ev if e[0].startswith("serve.")
                  and e[4].get("wave") == wave]
        assert 0 < len(mine) + len(shared) <= MAX_SPANS_PER_REQUEST


def test_wave_stats_are_the_spans(traced):
    """``plan_ms``, ``plan_cpu_ms``, the phases and ``dispatch_ms`` are
    the sums of the spans' own measurements, which the trace's events
    carry on the profiler's clock."""
    ev, waves = traced["events"], traced["waves"]
    requests = [e for e in ev if e[0] == "plan.request"
                and e[4]["rid"] in traced["rids"]]
    assert len(requests) == len(traced["rids"])
    assert sum(w.plan_ms for w in waves) == pytest.approx(
        _wall_ms(requests), rel=1e-9)
    assert sum(w.plan_cpu_ms for w in waves) == pytest.approx(
        sum(e[4]["cpu_ms"] for e in requests), rel=1e-9)
    order = [e for e in ev if e[0] == "plan.order"
             and any(_within(e, r) for r in requests)]
    assert sum(w.plan_phase_ms["plan.order"] for w in waves) \
        == pytest.approx(_wall_ms(order), rel=1e-9)
    for w in waves:
        assert 0 < w.plan_phase_ms["plan.level"] <= w.plan_ms
        assert w.plan_cpu_ms > 0
        assert w.inflight_ms >= w.dispatch_ms > 0
        assert w.notes["upload_ms"] <= w.dispatch_ms
    dispatch = [e for e in ev if e[0] == "serve.dispatch"
                and e[4]["wave"] in {w.wave for w in waves}]
    assert sum(w.dispatch_ms for w in waves) == pytest.approx(
        _wall_ms(dispatch), rel=1e-9)


def test_a_cached_plan_adds_no_phases(traced):
    ev = traced["events"]
    req = next(e for e in ev if e[0] == "plan.request"
               and e[4]["rid"] == traced["rids"][0])
    inside = {e[0] for e in ev if _within(e, req) and e is not req}
    assert inside == {"plan.key"}


def test_dispatch_counts_the_waves_tiles(traced):
    for w in traced["waves"]:
        assert 0 < w.notes["sspnna_live_tiles"] <= w.notes["sspnna_tiles"]


def test_stream_frames_are_timed_by_their_spans(traced):
    ev = traced["events"]
    frames = sorted((e for e in ev if e[0] == "plan.frame"),
                    key=lambda e: e[4]["frame"])
    reqs = traced["stream"]
    assert [e[4]["frame"] for e in frames] == [r.frame_no for r in reqs]
    for e, r in zip(frames, reqs):
        assert r.plan_info["plan_ms"] == pytest.approx(_wall_ms([e]),
                                                       rel=1e-9)
        kind = [k for k in ev if k[0] in ("plan.patch", "plan.rebuild")
                and _within(k, e)]
        assert len(kind) == 1
        assert kind[0][4]["mode"] == r.plan_info["mode"]
        assert kind[0][0] == ("plan.rebuild" if r.plan_info["mode"]
                              == "rebuilt" else "plan.patch")


def test_level_info_counts_live_tiles():
    cfg = UNetConfig(widths=(8, 16), reps=1, resolution=RES, capacity=CAP,
                     n_classes=N_CLASSES)
    t = _scene(800)
    plan = build_scene_plan_host(t, cfg, mem_budget=16 * 1024)
    tiled = [(lv, info) for lv, info in zip(plan.levels, plan.stats)
             if lv.sub.tiles is not None]
    assert tiled
    for lv, info in tiled:
        counts = np.asarray(lv.sub.tiles.pair_counts)
        assert info["n_tiles"] == counts.shape[0]
        assert info["n_live_tiles"] == int((counts > 0).sum()) > 0


def test_span_inherits_ids_and_sums_phases():
    with span("outer", rid=3, wave=1) as outer:
        with span("mid", level=0) as mid:
            with span("leaf") as leaf:
                pass
            with span("leaf"):
                pass
        with span("mid", wave=9) as other:
            pass
    assert mid.meta == {"level": 0, "rid": 3, "wave": 1}
    assert leaf.meta == {"rid": 3, "wave": 1}
    assert other.meta["wave"] == 9  # given beats inherited
    assert set(outer.phase_ms) == {"mid", "leaf"}
    assert set(mid.phase_ms) == {"leaf"}
    assert outer.phase_ms["mid"] == pytest.approx(mid.wall_ms
                                                  + other.wall_ms)
    assert outer.wall_ms >= outer.phase_ms["mid"] >= 0.0
    assert outer.cpu_ms >= 0.0


def test_span_leaves_out_none_and_unwinds_on_error():
    with pytest.raises(ValueError):
        with span("a", rid=None, wave=2):
            raise ValueError("x")
    with span("b") as b:  # a fresh stack: nothing inherited from "a"
        b.note(n=1)
    assert b.meta == {}
    assert span("c", rid=None).meta == {}
