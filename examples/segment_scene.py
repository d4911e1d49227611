"""Batched 3D-segmentation serving through ``repro.engine``.

The paper's end-to-end inference flow as a serving loop: representative
scenes pin the SPADE dataflow decisions once (offline-SPADE, §V-C), then
``serving.scene_engine.SceneEngine`` serves waves of pointcloud requests —
per scene one cached AdMAC/SOAR plan build, one shared jit compilation for
every wave. By default the engine runs its async pipeline (plan builds for
wave k+1 overlap device execution of wave k) and prints the per-stage
timings; ``--sync`` falls back to the blocking wave loop for comparison.

``--shards N`` serves each scene mesh-sharded instead: the capacity axis
splits over an N-way mesh axis, per-shard plans (local COIR + halo send
tables) build on the planner threads, and every conv exchanges only its
halo rows (run under ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
to get a real multi-device mesh on CPU; with fewer than N devices it fails).

Run:  PYTHONPATH=src python examples/segment_scene.py [--requests 8] [--sync]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import engine
from repro.data.scenes import N_CLASSES, make_scene
from repro.dist.compat import make_mesh
from repro.launch.compile_cache import use_compile_cache
from repro.models.scn import UNetConfig, init_unet
from repro.serving.scene_engine import SceneEngine, SceneRequest
from repro.sparse.tensor import SparseVoxelTensor


def load_scene(seed, res, cap):
    coords, feats, labels, mask = make_scene(seed, res, cap)
    return SparseVoxelTensor(jnp.asarray(coords), jnp.asarray(feats),
                             jnp.asarray(mask))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--res", type=int, default=32)
    ap.add_argument("--cap", type=int, default=4096)
    ap.add_argument("--sync", action="store_true",
                    help="serve with the blocking wave loop instead of the "
                         "async plan/dispatch/drain pipeline")
    ap.add_argument("--planner-threads", type=int, default=1)
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--shards", type=int, default=0,
                    help="serve mesh-sharded scenes over this many shards "
                         "(0 = unsharded batched serving)")
    args = ap.parse_args()
    use_compile_cache()

    cfg = UNetConfig(widths=(16, 32, 48), reps=1, resolution=args.res,
                     capacity=args.cap, n_classes=N_CLASSES)
    params = init_unet(jax.random.PRNGKey(0), cfg)

    t0 = time.time()
    reps = [load_scene(123 + i, args.res, args.cap) for i in range(2)]
    if args.shards:
        # pin the halo budget from representative scenes (one jit signature)
        layout = engine.pin_halo(
            reps, cfg, engine.ShardLayout(n_shards=args.shards))
        if len(jax.devices()) < args.shards:
            raise SystemExit(
                f"--shards {args.shards} needs {args.shards} devices, "
                f"found {len(jax.devices())}")
        mesh = make_mesh((args.shards,), ("shard",),
                         devices=jax.devices()[:args.shards])
        ctx = engine.ExecutionContext(mesh=mesh)
        print(f"sharded layout: {layout} on a {args.shards}-device mesh; "
              f"halo budget pinned in {time.time() - t0:.1f}s")
        eng = SceneEngine(cfg, params, batch=args.batch, ctx=ctx,
                          layout=layout, sync=args.sync, depth=args.depth,
                          planner_threads=args.planner_threads)
    else:
        # offline-SPADE: pin the per-level dataflow from representative
        # scenes
        spec = engine.build_plan_spec(reps, cfg, mem_budget=64 * 1024)
        for li, d in enumerate(spec.levels):
            print(f"spec level{li}: {d.backend} walk={d.walk} "
                  f"dO={d.delta_o} dI={d.delta_i} tiles={d.n_tiles}")
        print(f"plan spec pinned in {time.time() - t0:.1f}s")
        eng = SceneEngine(cfg, params, batch=args.batch, spec=spec,
                          sync=args.sync, depth=args.depth,
                          planner_threads=args.planner_threads)
    t_serve = time.time()
    reqs = [SceneRequest(rid, load_scene(1000 + rid, args.res, args.cap))
            for rid in range(args.requests)]
    handles = eng.submit(reqs)
    eng.serve()
    for h in handles:
        r = h.result()
        n = int(np.asarray(r.scene.mask).sum())
        hist = np.bincount(r.pred[np.asarray(r.scene.mask)],
                           minlength=N_CLASSES)
        print(f"req {r.rid}: {n} voxels, classes={hist.tolist()}")
    tm = eng.timings()
    mode = "sync" if args.sync else "async"
    print(f"{mode} serve of {args.requests} reqs in "
          f"{time.time() - t_serve:.1f}s over {tm['waves']} waves "
          f"(compilations={eng.n_compilations}, "
          f"plan cache {eng.cache.hits} hits / {eng.cache.misses} misses)")
    print(f"pipeline: plan={tm['plan_ms']:.0f}ms "
          f"(waited {tm['plan_wait_ms']:.0f}ms) "
          f"inflight={tm['inflight_ms']:.0f}ms drain={tm['drain_ms']:.0f}ms "
          f"overlap_frac={tm['overlap_frac']:.2f}")
    if args.shards:
        halo = sum(st.notes.get("halo_rows", 0) for st in eng.wave_stats)
        print(f"sharded: {args.shards}-way, "
              f"{halo} halo rows exchanged across all waves")


if __name__ == "__main__":
    main()
