"""Sync vs async scene serving: wave-pipeline throughput comparison.

Measures the ROADMAP "Async scene serving" item: ``SceneEngine`` with
``sync=False`` overlaps host-side plan building (AdMAC + SOAR + SPADE, the
paper's offline pass) with device execution of the previous wave. Three
arrival scenarios, each served by a sync and an async engine over the same
scenes:

* **cold/burst** — fresh scenes, all submitted up front: every wave pays a
  full plan build and the pipeline has maximal cross-wave overlap to mine.
* **cold/paced** — fresh scenes arriving in two-wave groups with a
  ``run()`` per group: overlap is limited to what each group exposes.
* **warm** — the cold/burst scenes resubmitted: plan-cache hits, the two
  modes should converge (there is no plan work left to hide).

Per-request logits are asserted bitwise identical between the modes before
any row is emitted. Rows report wall-clock per request; ``derived`` carries
the overlap stats and the async-vs-sync speedup.

Standalone CLI (what the CI smoke job runs):

    python -m benchmarks.bench_serving --quick --json BENCH_serving.json
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, standalone_bench_main
from repro import engine
from repro.data.scenes import N_CLASSES, make_scene
from repro.models.scn import UNetConfig, init_unet
from repro.serving.scene_engine import SceneEngine, SceneRequest
from repro.serving.scheduler import overlap_fraction
from repro.sparse.tensor import SparseVoxelTensor


def _load(seed, res, cap):
    coords, feats, _, mask = make_scene(seed, res, cap)
    return SparseVoxelTensor(jnp.asarray(coords), jnp.asarray(feats),
                             jnp.asarray(mask))


def _make_engine(cfg, params, batch, spec, sync):
    # planner_threads=1: on small hosts a single planner hides behind device
    # execution without GIL-fighting a second builder; depth=2 = double
    # buffering (wave k+1 plans while k executes and k-1 drains).
    # Levels the spec sends to "sspnna" run the fused Pallas kernel:
    # compiled on a TPU, the Pallas interpreter on any other backend.
    return SceneEngine(cfg, params, batch=batch, spec=spec, sync=sync,
                       depth=2, planner_threads=1)


def _serve(eng, scenes, base_rid, group=None):
    """Serve ``scenes``; ``group=None`` is one burst, else paced groups.

    Returns (wall_s, {rid: logits}, stats) with ``stats`` restricted to the
    waves of *this* serve (not warmup or earlier scenarios).
    """
    reqs = [SceneRequest(base_rid + i, s) for i, s in enumerate(scenes)]
    n0 = len(eng.wave_stats)
    t0 = time.perf_counter()
    if group is None:
        eng.submit(reqs)
        eng.serve()
    else:
        for i in range(0, len(reqs), group):
            eng.submit(reqs[i:i + group])
            eng.serve()
    wall = time.perf_counter() - t0
    return wall, {r.rid: r.logits for r in reqs}, eng.wave_stats[n0:]


def _assert_bitwise(name, a, b):
    assert a.keys() == b.keys(), name
    for rid in a:
        np.testing.assert_array_equal(a[rid], b[rid], err_msg=f"{name}/{rid}")


def _emit_pair(name, n_reqs, sync_wall, async_wall, async_stats):
    plan = sum(s.plan_ms for s in async_stats)
    span = sum(s.plan_span_ms for s in async_stats)
    wait = sum(s.plan_wait_ms for s in async_stats)
    dev = sum(s.device_ms for s in async_stats)
    overlap = overlap_fraction(span, wait)
    emit(f"serving/{name}_sync", sync_wall / n_reqs * 1e6,
         f"wall={sync_wall:.3f}s n={n_reqs}")
    emit(f"serving/{name}_async", async_wall / n_reqs * 1e6,
         f"wall={async_wall:.3f}s n={n_reqs} overlap_frac={overlap:.2f} "
         f"plan_ms={plan:.0f} device_ms={dev:.0f} "
         f"speedup={sync_wall / max(async_wall, 1e-9):.2f}x")


def run(quick: bool = False):
    # scene size is NOT reduced in quick mode: tiny scenes make the numpy
    # plan pass GIL-dominated and the comparison noise-bound; quick trims
    # request counts/reps instead
    res, cap, widths, batch = 24, 2048, (16, 32), 2
    n_reqs, reps = (6, 2) if quick else (8, 3)
    cfg = UNetConfig(widths=widths, reps=1, resolution=res, capacity=cap,
                     n_classes=N_CLASSES)
    params = init_unet(jax.random.PRNGKey(0), cfg)
    # pinned offline-SPADE spec: plan builds include SOAR + tile tables,
    # i.e. real host work for the pipeline to hide
    spec = engine.build_plan_spec([_load(900, res, cap), _load(901, res, cap)],
                                  cfg, mem_budget=16 * 1024)

    engines = {mode: _make_engine(cfg, params, batch, spec, mode == "sync")
               for mode in ("sync", "async")}
    # jit warmup on a throwaway wave so compile time doesn't skew either mode
    for eng in engines.values():
        _serve(eng, [_load(800 + i, res, cap) for i in range(batch)], 9000)

    # cold/burst: fresh scenes submitted at once, best-of-`reps` with a new
    # scene set per rep so the plan cache stays cold
    best = {"sync": float("inf"), "async": float("inf")}
    best_stats = []
    cold0 = None
    for rep in range(reps):
        cold = [_load(10_000 * rep + 100 + i, res, cap) for i in range(n_reqs)]
        cold0 = cold0 or cold
        sync_wall, sync_out, _ = _serve(engines["sync"], cold, rep * 1000)
        async_wall, async_out, a_st = _serve(engines["async"], cold,
                                             rep * 1000)
        _assert_bitwise(f"cold_burst/rep{rep}", sync_out, async_out)
        if async_wall < best["async"]:
            best["async"], best_stats = async_wall, a_st
        best["sync"] = min(best["sync"], sync_wall)
    _emit_pair("cold_burst", n_reqs, best["sync"], best["async"], best_stats)

    # warm: the first cold set again, plans cached in both engines
    sync_wall, sync_out, _ = _serve(engines["sync"], cold0, 90_000)
    async_wall, async_out, async_stats = _serve(engines["async"], cold0,
                                                90_000)
    _assert_bitwise("warm", sync_out, async_out)
    _emit_pair("warm", n_reqs, sync_wall, async_wall, async_stats)

    # cold/paced: fresh scenes in two-wave groups, run() per group
    paced = [_load(500_000 + i, res, cap) for i in range(n_reqs)]
    sync_wall, sync_out, _ = _serve(engines["sync"], paced, 0, group=2 * batch)
    async_wall, async_out, async_stats = _serve(
        engines["async"], paced, 0, group=2 * batch)
    _assert_bitwise("cold_paced", sync_out, async_out)
    _emit_pair("cold_paced", n_reqs, sync_wall, async_wall, async_stats)

    emit("serving/bitwise_match", 0.0,
         "sync and async logits identical across all scenarios")


def main(argv=None) -> None:
    standalone_bench_main(run, "bench_serving",
                          "small scenes/counts (the CI smoke job)",
                          description=__doc__, argv=argv)


if __name__ == "__main__":
    main()
