"""Dispatch benchmarks: Table III savings + measured-vs-modeled dispatch.

Two arms:

* **Table III** (analytical): micro-op + data-access savings from coarse
  (M-V) dispatch. Per selected layer shape: uOps at scalar-MAC granularity
  (prior sparse accelerators) vs M-V granularity (SSpNNA) vs
  one-fused-einsum-per-tile (this repo's MXU mapping); data accesses
  with/without per-pair refetch.

* **Measured** (wall-clock): per scene shape, build the analytical SPADE
  dispatch under a deliberately small L1 budget (the regime where the model
  picks the tiled SSpNNA path even on hosts where the XLA gather-einsum
  wins), measure every registered backend on the realized plan via
  ``engine.autotune.measure_backends``, record the numbers into a
  ``CostTable`` (optionally seeded from earlier ``BENCH_*.json`` artifacts
  via ``--seed-from``), and compare the tuned choice against the analytical
  one. The tuned dispatcher picks the measured argmin, so it can never be
  measured slower than the analytical choice — asserted per case — and the
  ``dispatch/tuned_vs_analytical_geomean`` row quantifies the win.

Standalone CLI (what the CI smoke job runs):

    python -m benchmarks.bench_dispatch --quick \
        --seed-from BENCH_sspnna.json --json BENCH_dispatch.json
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import build_scene, emit, scene_metadata, standalone_bench_main

# (name, dC, dN) tile channel sizes echoing Table III's layers
LAYERS = [("L2-like", 16, 32), ("L12-like", 16, 32), ("L35-like", 8, 16)]

# measured-arm scene shapes: (name, resolution, capacity, channels)
SWEEP = [("r16_c8", 16, 512, 8), ("r24_c16", 24, 1024, 16),
         ("r32_c16", 32, 2048, 16)]

# small SPADE L1 budget: forces an actual tiling, i.e. the regime where the
# analytical model dispatches to sspnna (which the measured arm contests)
MEASURE_BUDGET = 16 * 1024


def _table_iii():
    t, _ = build_scene(0, 48, 16384)
    coir, nbr, order = scene_metadata(t, 48)
    idx = np.asarray(coir.indices)
    mask = np.asarray(t.mask)
    pairs = int((idx[mask] >= 0).sum())
    for name, dc, dn in LAYERS:
        total_macs = pairs * dc * dn
        uops_scalar = total_macs
        uops_mv = pairs                      # one M-V op per valid pair
        uops_saving = uops_scalar / uops_mv
        # data accesses: scalar dispatch refetches the input vector per MAC
        da_scalar = pairs * (dc + dn + dc * dn / min(dc, dn))
        da_mv = pairs * dc + pairs * dn      # vector in, vector out per pair
        emit(f"tableIII/{name}/uops_saving", 0.0,
             f"{uops_saving:.0f}x ({uops_scalar:.2e}->{uops_mv:.2e})")
        emit(f"tableIII/{name}/da_saving", 0.0,
             f"{da_scalar / da_mv:.2f}x")


def _measured_case(table, name, res, cap, c, k):
    """Measure all backends on one scene shape; returns the
    analytical-over-tuned wall-clock ratio (>= 1 by construction)."""
    import jax.numpy as jnp

    from repro.core import spade
    from repro.core.sparse_conv import SparseConvParams
    from repro.engine.autotune import measure_backends, signature
    from repro.engine.plan import (
        _layer_spec,
        conv_plan_for_layer,
        dispatch_from_dataflow,
    )

    t, _ = build_scene(seed=0, resolution=res, capacity=cap)
    coir, _, order = scene_metadata(t, res)
    mask = np.asarray(t.mask)
    n_active = int(mask.sum())
    density = n_active / res**3

    # analytical dispatch, exactly as _assemble_site derives it
    attrs = spade.extract_attributes(
        np.asarray(coir.indices), mask, order.order)
    k = int(np.asarray(coir.indices).shape[1])
    layer = _layer_spec(name, n_active, k, c, c)
    df = spade.explore(layer, {"CIRF": attrs, "CORF": attrs}, MEASURE_BUDGET)
    analytical = dispatch_from_dataflow(df, attrs, n_active, k)
    d_o = analytical.delta_o or 32
    d_i = analytical.delta_i or 123

    # one realized tiled plan; the reference backend ignores the tiles and
    # runs the XLA gather-einsum on the same COIR, so every backend sees
    # the identical conv
    plan = conv_plan_for_layer(coir, order.order, d_o, d_i)
    rng = np.random.default_rng(0)
    feats = jnp.asarray(rng.normal(size=(cap, c)), jnp.float32)
    params = SparseConvParams(
        jnp.asarray(rng.normal(size=(27, c, c)) * 0.1, jnp.float32),
        jnp.zeros((c,), jnp.float32))

    times = measure_backends(plan, feats, params, k=k)
    for bname, m in sorted(times.items()):
        sig = signature(n_active, n_active, c, c, density=density,
                        backend=bname)
        table.record(sig, m.median_us, spread_us=m.spread_us, k=m.k,
                     delta_o=d_o, delta_i=d_i)
        emit(f"dispatch/{name}_{bname}", m.median_us,
             f"sig={sig.encode()} delta_o={d_o} delta_i={d_i} "
             f"spread_us={m.spread_us:.1f}")

    tuned = table.adjust_dispatch(
        analytical, n_in=n_active, n_out=n_active, c_in=c, c_out=c,
        density=density)
    t_analytical = times[analytical.backend].median_us
    t_tuned = times[tuned.backend].median_us
    # the tuned winner is the measured argmin: never slower than analytical
    assert t_tuned <= t_analytical, (
        f"{name}: tuned {tuned.backend} ({t_tuned:.1f}us) measured slower "
        f"than analytical {analytical.backend} ({t_analytical:.1f}us)")
    ratio = t_analytical / max(t_tuned, 1e-9)
    emit(f"dispatch/{name}_choice", 0.0,
         f"analytical={analytical.backend} tuned={tuned.backend} "
         f"tuned_vs_analytical={ratio:.2f}x n_active={n_active} "
         f"density={density:.4f}")
    return ratio


def _measured_arm(quick: bool, seed_from):
    from repro.engine.autotune import CostTable, seed_cost_table

    table = CostTable()
    if seed_from:
        n = seed_cost_table(table, list(seed_from))
        emit("dispatch/seeded", 0.0,
             f"entries={n} from {len(list(seed_from))} artifact(s)")
    cases = SWEEP[:1] if quick else SWEEP
    k = 2 if quick else 3
    ratios = [_measured_case(table, name, res, cap, c, k)
              for name, res, cap, c in cases]
    geomean = float(np.exp(np.mean(np.log(ratios))))
    emit("dispatch/tuned_vs_analytical_geomean", 0.0,
         f"{geomean:.2f}x across {len(ratios)} scene shapes "
         f"(tuned dispatch picks the measured winner)")


def run(quick: bool = False, seed_from=()):
    _table_iii()
    _measured_arm(quick, seed_from)


def main(argv=None) -> None:
    standalone_bench_main(
        run, "bench_dispatch", "single small scene (the CI smoke job)",
        description=__doc__, argv=argv,
        configure=lambda ap: ap.add_argument(
            "--seed-from", nargs="*", default=[], metavar="JSON",
            help="seed the cost table from bench-rows/v1 artifacts "
                 "(e.g. BENCH_sspnna.json from a prior CI run)"),
        run_kw=lambda args: {"seed_from": args.seed_from})


if __name__ == "__main__":
    main()
