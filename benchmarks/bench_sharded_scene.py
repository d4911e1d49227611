"""Sharded-scene serving: wall-clock + halo traffic vs the unsharded path.

Three arms over the same scene and parameters:

* ``ref_unsharded`` — the engine's reference einsum U-Net on one device;
* ``serial_SN``     — the deterministic sharded program on one device
  (``vmap(axis_name=...)``), the bitwise oracle for the mesh arm;
* ``mesh_SN``       — the same program ``shard_map``-ed over an N-way mesh
  axis with real halo-exchange/all-gather collectives. It needs >= N
  devices and fails without them; on a CPU host run under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the CI smoke job
  sets exactly that).

The interesting number on CPU hosts is not wall-clock (virtual devices
share the same cores; the sharded arms also pay the deterministic
plane-accumulated contraction) but the *wire traffic model* in the derived
column: ``halo_kb`` is what the plan's send tables actually exchange per
forward (plus the chunked BN partial gathers), ``dense_kb`` what a naive
replicated all-gather of every conv input would move. The mesh arm is
asserted bitwise equal to the serial arm.
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks import common
from repro import engine
from repro.dist.compat import make_mesh
from repro.models.scn import UNetConfig, init_unet


def _conv_input_widths(cfg) -> list[dict]:
    """Per level: channel width of each use of the three conv sites."""
    w, reps, n = cfg.widths, cfg.reps, len(cfg.widths)
    out = []
    for li in range(n):
        sub = [w[li]] * reps                       # encoder blocks
        if li == 0:
            sub = [cfg.in_channels] + sub          # stem shares level-0 sub
        down = up = []
        if li < n - 1:
            down = [w[li]]
            up = [w[li + 1]]
            sub = sub + [2 * w[li]] + [w[li]] * (reps - 1)  # decoder blocks
        out.append({"sub": sub, "down": down, "up": up})
    return out


def _traffic_model(plan: engine.ShardedScenePlan, cfg, capacity: int):
    """(halo_bytes, bn_bytes, dense_bytes) one sharded forward moves,
    summed across shards. ``halo_bytes`` counts the *padded* all_to_all
    payload (S x S pair slots x the per-pair budget H) — what actually
    crosses the wire — not just the real halo rows."""
    widths = _conv_input_widths(cfg)
    halo = dense = bn = 0
    S = plan.layout.n_shards
    chunk = plan.layout.bn_chunk
    for lvl_stats, use in zip(plan.stats, widths):
        for site, budget in lvl_stats["halo_budget"].items():
            for c in use[site]:
                halo += S * S * budget * c * 4
                dense += (S - 1) * (capacity // S) * S * c * 4
    # chunked BN partial gathers: 2 per conv block (mean+count, then var)
    for li in range(len(cfg.widths)):
        per_gather = (capacity // chunk) * (cfg.widths[li] + 1) * 4
        bn += 2 * per_gather * cfg.reps  # enc blocks at this level
        if li < len(cfg.widths) - 1:
            bn += 2 * per_gather * cfg.reps  # dec blocks
    return halo, bn, dense


def run(quick: bool = False):
    res, cap = (24, 2048) if quick else (32, 8192)
    n_shards = 4
    cfg = UNetConfig(widths=(16, 32), reps=1, resolution=res, capacity=cap,
                     n_classes=5)
    params = init_unet(jax.random.PRNGKey(0), cfg)
    t, _ = common.build_scene(0, res, cap)

    plan_ref = engine.build_scene_plan(t, cfg, plan_tiles=False)
    layout = engine.ShardLayout(n_shards=n_shards)
    splan = engine.build_sharded_scene_plan(t, cfg, layout=layout)
    halo_b, bn_b, dense_b = _traffic_model(splan, cfg, cap)
    traffic = (f"halo_kb={halo_b / 1024:.0f} bn_kb={bn_b / 1024:.0f} "
               f"dense_kb={dense_b / 1024:.0f} "
               f"saved={1 - (halo_b + bn_b) / max(dense_b, 1):.0%} "
               f"halo_rows={splan.halo_rows()}")

    ref_fn = jax.jit(lambda p, f: engine.apply_unet(
        p, f, plan_ref, backend="reference"))
    us = common.time_fn(ref_fn, params, t.feats, iters=3, reps=2)
    common.emit("sharded_scene/ref_unsharded", us,
                f"V={cap} res={res}")

    serial_fn = jax.jit(lambda p, f: engine.apply_unet(p, f, splan))
    us = common.time_fn(serial_fn, params, t.feats, iters=3, reps=2)
    common.emit(f"sharded_scene/serial_S{n_shards}", us, traffic)

    if len(jax.devices()) < n_shards:
        raise RuntimeError(
            f"mesh_S{n_shards} needs {n_shards} devices, found "
            f"{len(jax.devices())} (on CPU set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=4)")
    mesh = make_mesh((n_shards,), ("shard",), devices=jax.devices()[:n_shards])
    ctx = engine.ExecutionContext(mesh=mesh)
    mesh_fn = jax.jit(lambda p, f: engine.apply_unet(p, f, splan, ctx=ctx))
    us = common.time_fn(mesh_fn, params, t.feats, iters=3, reps=2)
    # the mesh execution must be bitwise the serial oracle
    same = np.array_equal(np.asarray(mesh_fn(params, t.feats)),
                          np.asarray(serial_fn(params, t.feats)))
    assert same, "mesh sharded forward diverged from the serial oracle"
    common.emit(f"sharded_scene/mesh_S{n_shards}", us,
                f"bitwise_vs_serial=ok {traffic}")


def main(argv=None) -> None:
    common.standalone_bench_main(
        run, "bench_sharded_scene",
        quick_help="small scene (the CI smoke job)",
        description=__doc__, argv=argv)


if __name__ == "__main__":
    main()
