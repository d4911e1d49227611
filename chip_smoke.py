#!/usr/bin/env python3
"""Prove that scene serving runs on a TPU, end to end, through its entry points.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: one scene sharded over a mesh

One chip. The SCN ScanNet segmentation U-Net at its published widths
(Graham et al. 2018; SparseConvNet ``examples/ScanNet/unet.py``: m=16, seven
levels 16..112, one block per level, 20 classes, a 256^3 grid at 5 cm) with
random weights from ``--seed`` serves six generated indoor scenes of 40k+
active voxels through ``SceneEngine`` in async mode, with a ``PlanSpec``
pinned from two representative scenes. The script checks that every request
completed, every circuit breaker is closed, the wave program compiled once
and holds the fused SSpNNA Mosaic kernel (``tpu_custom_call``), and each
request's logits are within a stated tolerance of a float32 reference. The
input keeps the repo's 4 features (normal + height), not ScanNet's 3 colours.

Four chips. The same model serves scenes whose capacity axis is split over
a 4-device mesh (``engine.pin_halo`` layout, ``ExecutionContext(mesh=...)``).
The script checks that outputs live on 4 devices and the program holds the
``all-to-all`` halo exchange, and compares each scene with the same
``ShardedScenePlan`` run serially on one device. No one-chip phase runs.

Any failed check raises: the exit code is nonzero and no result line is
printed. On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The script fails when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import engine  # noqa: E402
from repro.data.scenes import make_scene  # noqa: E402
from repro.dist.compat import make_mesh  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models.scn import UNetConfig, init_unet  # noqa: E402
from repro.serving.api import COMPLETED  # noqa: E402
from repro.serving.scene_engine import SceneEngine, SceneRequest  # noqa: E402
from repro.sparse.tensor import SparseVoxelTensor  # noqa: E402

#: SCN ScanNet U-Net, published widths (m=16, 7 levels, reps=1)
PUBLISHED = UNetConfig(name="scn_scannet_m16", in_channels=4, n_classes=20,
                       widths=(16, 32, 48, 64, 80, 96, 112), reps=1,
                       resolution=256, capacity=65536)
POINTS_PER_UNIT = 1.2e6   # surface samples per unit: ~50k voxels per room
MIN_VOXELS = 40_000       # an indoor room at 5 cm: 10^4 .. 10^5 voxels
N_REQUESTS = 6
BATCH = 2
#: what a compiled Mosaic kernel looks like in the optimized HLO
KERNEL_MARK = "tpu_custom_call"
#: the served f32 matmuls run at the TPU's default precision: bf16 operands
#: with f32 accumulation, a relative rounding of 2^-9 per operand that
#: compounds over the U-Net's 26 convs and batch norms to about 1e-2 of the
#: logit scale. A wrong gather, scatter or halo row errs by O(1).
REL_TOL = 5e-2
TOL_REASON = ("served matmuls run at TPU default precision (bf16 operands, "
              "f32 accumulation: 2^-9 relative per operand, compounding over "
              "26 convs + BN to ~1e-2 of the logit scale); a wrong gather, "
              "scatter or halo row errs by O(1)")


class SmokeFailure(AssertionError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Sums XLA backend-compile seconds reported through jax.monitoring."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def load_scene(seed: int, cfg: UNetConfig, points_per_unit: float,
               min_voxels: int) -> SparseVoxelTensor:
    coords, feats, _, mask = make_scene(seed, cfg.resolution, cfg.capacity,
                                        points_per_unit=points_per_unit)
    n = int(mask.sum())
    check(min_voxels <= n < cfg.capacity,
          f"scene {seed}: {n} active voxels, want [{min_voxels}, "
          f"{cfg.capacity})")
    return SparseVoxelTensor(jnp.asarray(coords), jnp.asarray(feats),
                             jnp.asarray(mask))


def compare(got: np.ndarray, want: np.ndarray, what: str) -> str:
    """Max abs error of ``got`` against ``want``, as a printable summary;
    fails past the tolerance."""
    check(got.shape == want.shape, f"{what}: shape {got.shape} != "
                                   f"{want.shape}")
    check(bool(np.isfinite(got).all()), f"{what}: non-finite output")
    err = float(np.abs(got - want).max())
    scale = float(np.abs(want).max())
    check(err <= REL_TOL * scale,
          f"{what}: max abs err {err:.3e} > {REL_TOL} x {scale:.3e}")
    return (f"max abs err {err:.3e} = {err / scale:.3e} of the reference's "
            f"max |value| {scale:.3e}")


_reference = jax.jit(lambda p, f, plan: engine.apply_unet(
    p, f, plan, backend="reference"))


def reference_logits(params, scene, cfg) -> np.ndarray:
    """The ``"reference"`` backend at highest matmul precision, on an
    all-reference plan (no SOAR, SPADE or tiles)."""
    plan = engine.build_scene_plan(scene, cfg, plan_tiles=False)
    with jax.default_matmul_precision("highest"):
        return np.asarray(_reference(params, scene.feats, plan))


def check_served(eng, handles) -> list:
    reqs = [h.result() for h in handles]
    check(all(r.status == COMPLETED for r in reqs), "a request did not "
                                                   "complete")
    slo = eng.slo_stats()
    check(slo["n_failed"] == 0 and slo["n_shed"] == 0,
          f"failed/shed requests: {slo['n_failed']}/{slo['n_shed']}")
    breakers = eng.health()["breakers"]
    check(all(b["state"] == "closed" for b in breakers.values()),
          f"open circuit breakers: {breakers}")
    check(eng.n_compilations == 1,
          f"wave program compiled {eng.n_compilations} times, want 1")
    return reqs


def kernel_conv_check(params, scene, cfg) -> None:
    """Level 0's submanifold conv through ``backend="sspnna"`` at a tiled
    plan, against the reference conv; for specs that route no level to
    the kernel."""
    coir = engine.build_scene_plan(scene, cfg,
                                   plan_tiles=False).levels[0].sub.coir
    order = np.flatnonzero(np.asarray(scene.mask))
    tiled = engine.conv_plan_for_layer(coir, order, 32, 256)
    fn = jax.jit(lambda x, p, cp: engine.sparse_conv(x, p, cp,
                                                     backend="sspnna"))
    hlo = fn.lower(scene.feats, params["stem"], tiled).compile().as_text()
    check(KERNEL_MARK in hlo, "level-0 sspnna conv holds no kernel")
    with jax.default_matmul_precision("highest"):
        want = np.asarray(engine.sparse_conv(
            scene.feats, params["stem"], engine.reference_plan(coir),
            backend="reference"))
    err = compare(np.asarray(fn(scene.feats, params["stem"], tiled)), want,
                  "level-0 sspnna conv")
    log(f"level-0 sspnna conv: {err}")


def smoke_one_chip(cfg, params, reps, scenes) -> None:
    """Pin a spec, serve ``scenes`` through the kernel, check every result."""
    t0 = time.perf_counter()
    spec = engine.build_plan_spec(reps, cfg, mem_budget=64 * 1024)
    log(f"plan spec pinned in {time.perf_counter() - t0:.3f} s")
    for li, d in enumerate(spec.levels):
        log(f"level {li} (C={cfg.widths[li]}): {d.backend} walk={d.walk} "
            f"dO={d.delta_o} dI={d.delta_i} tiles={d.n_tiles}")
    if all(d.backend != engine.SSPNNA for d in spec.levels):
        log("the spec sends no level to sspnna: checking level 0 directly")
        kernel_conv_check(params, scenes[0], cfg)

    clock = CompileClock()
    eng = SceneEngine(cfg, params, batch=BATCH, spec=spec, sync=False)
    t0 = time.perf_counter()
    handles = eng.submit([SceneRequest(i, s) for i, s in enumerate(scenes)])
    eng.serve()
    reqs = check_served(eng, handles)
    wall = time.perf_counter() - t0
    eng.close()
    log(f"served {len(reqs)} requests in {eng.timings()['waves']} waves: "
        f"wall {wall:.3f} s including {clock.seconds:.3f} s of XLA compile")

    # the wave program, from the jit cache: every sspnna level is a kernel
    plan = engine.build_scene_plan(scenes[0], cfg, spec=spec)
    hlo = eng._apply.lower(params, [scenes[0].feats] * BATCH,
                           [plan] * BATCH).compile().as_text()
    n_kernels = hlo.count(KERNEL_MARK)
    check(n_kernels > 0, f"the wave program holds no {KERNEL_MARK}")
    log(f"wave program: {n_kernels} {KERNEL_MARK} kernels")

    log(f"tolerance: max abs err <= {REL_TOL} x max|reference logit|; "
        f"{TOL_REASON}")
    for r in reqs:
        err = compare(np.asarray(r.logits),
                      reference_logits(params, r.scene, cfg),
                      f"request {r.rid}")
        log(f"request {r.rid}: {int(np.asarray(r.scene.mask).sum())} voxels, "
            f"vs f32 reference: {err}")
    # the rounding the tolerance allows for, without the kernel: the same
    # reference at the default matmul precision
    scene = reqs[0].scene
    plan = engine.build_scene_plan(scene, cfg, plan_tiles=False)
    rounded = np.asarray(_reference(params, scene.feats, plan))
    log("reference at default vs highest precision, request 0: "
        + compare(rounded, reference_logits(params, scene, cfg),
                  "default-precision reference"))


def smoke_four_chips(cfg, params, reps, scenes, devices) -> None:
    """Serve ``scenes`` sharded over a 4-device mesh; compare each with the
    same plan run serially on one device."""
    n_shards = 4
    check(len(devices) >= n_shards,
          f"--chips 4 needs 4 devices, found {len(devices)}")
    mesh = make_mesh((n_shards,), ("shard",), devices=devices[:n_shards])
    t0 = time.perf_counter()
    layout = engine.pin_halo(reps, cfg, engine.ShardLayout(n_shards=n_shards))
    log(f"halo budget pinned in {time.perf_counter() - t0:.3f} s: {layout}")

    clock = CompileClock()
    ctx = engine.ExecutionContext(mesh=mesh)
    eng = SceneEngine(cfg, params, batch=BATCH, ctx=ctx, layout=layout,
                      sync=False)
    t0 = time.perf_counter()
    handles = eng.submit([SceneRequest(i, s) for i, s in enumerate(scenes)])
    eng.serve()
    reqs = check_served(eng, handles)
    wall = time.perf_counter() - t0
    eng.close()
    log(f"served {len(reqs)} sharded requests: wall {wall:.3f} s including "
        f"{clock.seconds:.3f} s of XLA compile")

    plan = engine.build_sharded_scene_plan(scenes[0], cfg, layout=layout)
    out = eng._apply(params, scenes[0].feats, plan)
    check(len(out.sharding.device_set) == n_shards,
          f"logits live on {len(out.sharding.device_set)} devices")
    hlo = eng._apply.lower(params, scenes[0].feats, plan).compile().as_text()
    check("all-to-all" in hlo, "the sharded program holds no all-to-all")
    log(f"sharded logits on {n_shards} devices; program holds "
        f"{hlo.count('all-to-all(')} all-to-all halo exchanges")

    serial = jax.jit(lambda p, f, pl: engine.apply_unet(p, f, pl))
    log(f"tolerance: max abs err <= {REL_TOL} x max|serial logit|; "
        f"{TOL_REASON}")
    for r in reqs:
        splan = engine.build_sharded_scene_plan(r.scene, cfg, layout=layout)
        want = np.asarray(serial(params, r.scene.feats, splan))
        got = np.asarray(r.logits)
        err = compare(got, want, f"sharded request {r.rid}")
        log(f"sharded request {r.rid} vs one-device serial: {err} "
            f"(bitwise: {bool(np.array_equal(got, want))})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-scene path on a 4-chip mesh")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights and the generated scenes")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    check(dev.platform == "tpu", f"no TPU: JAX found {dev.platform}")
    log(f"compile cache: {use_compile_cache()}")

    cfg = PUBLISHED
    params = init_unet(jax.random.PRNGKey(args.seed), cfg)
    t0 = time.perf_counter()
    reps = [load_scene(args.seed + i, cfg, POINTS_PER_UNIT, MIN_VOXELS)
            for i in range(2)]
    scenes = [load_scene(args.seed + 100 + i, cfg, POINTS_PER_UNIT,
                         MIN_VOXELS)
              for i in range(N_REQUESTS if args.chips == 1 else 3)]
    log(f"generated {len(reps) + len(scenes)} scenes in "
        f"{time.perf_counter() - t0:.3f} s")

    if args.chips == 1:
        smoke_one_chip(cfg, params, reps, scenes)
    else:
        smoke_four_chips(cfg, params, reps, scenes, devices)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
