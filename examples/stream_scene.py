"""Serve a synthetic LiDAR sweep through the streaming scene engine.

Opens a stream on a ``SceneEngine``, feeds it an ego-motion sweep from
``make_lidar_sweep``, and prints per-frame plan-reuse stats: after the
first frame's full build, each frame's host plan is *patched* from the
previous one (delta-based incremental planning), falling back to a full
rebuild only under heavy churn.

Run:  PYTHONPATH=src python examples/stream_scene.py [--frames 8]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.data.scenes import N_CLASSES, make_lidar_sweep
from repro.launch.compile_cache import use_compile_cache
from repro.models.scn import UNetConfig, init_unet
from repro.serving.scene_engine import SceneEngine
from repro.sparse.tensor import SparseVoxelTensor


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=8)
    ap.add_argument("--resolution", type=int, default=48)
    ap.add_argument("--capacity", type=int, default=4096)
    ap.add_argument("--step", type=int, default=4,
                    help="ego translation (voxels) per frame along x")
    ap.add_argument("--churn", type=float, default=0.05,
                    help="fraction of voxels appearing/disappearing per frame")
    ap.add_argument("--sync", action="store_true",
                    help="blocking waves instead of the async pipeline")
    args = ap.parse_args()
    use_compile_cache()

    cfg = UNetConfig(widths=(16, 32, 32), reps=1, resolution=args.resolution,
                     capacity=args.capacity, n_classes=N_CLASSES)
    params = init_unet(jax.random.PRNGKey(0), cfg)
    eng = SceneEngine(cfg, params, batch=2, sync=args.sync,
                      depth=2, planner_threads=1)

    frames, shifts = make_lidar_sweep(
        0, args.frames, resolution=args.resolution, capacity=args.capacity,
        step=args.step, churn=args.churn)
    scenes = [SparseVoxelTensor(jnp.asarray(c), jnp.asarray(f),
                                jnp.asarray(m)) for c, f, _, m in frames]

    stream = eng.open_stream(stream_id="lidar0")
    t0 = time.time()
    reqs = eng.serve_stream(scenes, shifts, stream=stream)
    wall = time.time() - t0

    print("frame  mode     overlap  plan_ms  active")
    for r in reqs:
        info = r.plan_info
        n_act = int(jnp.sum(r.scene.mask))
        print(f"{r.frame_no:>5}  {info['mode']:<8} {info['overlap']:>6.3f}"
              f"  {info['plan_ms']:>7.2f}  {n_act:>6}")
    agg = stream.stats()
    print(f"\n{agg['frames']} frames in {wall:.2f}s | "
          f"patched={agg['patched']} rebuilt={agg['rebuilt']} "
          f"reused={agg['reused']} | mean overlap {agg['mean_overlap']:.3f} "
          f"| mean host plan {agg['mean_plan_ms']:.2f} ms")
    notes = [w.notes for w in eng.wave_stats if w.notes]
    if notes:
        print(f"last wave notes: {notes[-1]}")


if __name__ == "__main__":
    main()
