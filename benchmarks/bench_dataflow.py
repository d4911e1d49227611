"""Fig 22 + Fig 24: feature ablation (SPADE/CAROM/SOAR/offline) + measured
CPU speedup of SPADE-tiled execution.

Fig 22 analogue: data accesses (model, Eqn 5) of
  baseline IS dataflow  vs  +SPADE  vs  +CAROM  vs  +SOAR ordering.
Fig 24 analogue: wall-clock of the reference XLA sparse conv vs the
SPADE-tiled gather-GEMM path on this host CPU.
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import build_scene, emit, scene_metadata, time_fn
from repro import engine
from repro.core import carom, soar, spade
from repro.core.sparse_conv import init_sparse_conv


def run():
    t, _ = build_scene(4, 48, 16384)
    coir, nbr, order = scene_metadata(t, 48)
    idx = np.asarray(coir.indices)
    mask = np.asarray(t.mask)
    v = int(mask.sum())
    layer = spade.LayerSpec("ablate", v, v, 27, 32, 32, 2)

    attrs_soar = spade.extract_attributes(idx, mask, order.order)
    rast = soar.raster_order(np.asarray(t.coords), mask)
    attrs_rast = spade.extract_attributes(idx, mask, rast)

    # baseline: input-stationary, fixed tile, raster order (paper's ref pt)
    da_base, _ = spade.data_accesses(layer, attrs_rast, 256, 32, 32, "IS", "CIRF")
    # + SPADE (optimal tile/walk/flavor)
    best = spade.explore(layer, {"CIRF": attrs_rast, "CORF": attrs_rast},
                         64 * 1024)
    # + SOAR ordering (better attributes)
    best_soar = spade.explore(layer, {"CIRF": attrs_soar, "CORF": attrs_soar},
                              64 * 1024)
    # + CAROM (2-level, balance on-chip vs DRAM)
    levels = [carom.MemLevel("L2", 2 << 20, 16, 1024),
              carom.MemLevel("L1", 64 << 10, 64, 1024)]
    plans = carom.carom_search(layer, {"CIRF": attrs_soar, "CORF": attrs_soar},
                               levels)
    emit("fig22/baseline_IS_da", 0.0, f"{da_base:.3e} elems")
    emit("fig22/spade_da", 0.0,
         f"{da_base / best.da_elems:.2f}x fewer ({best.walk}/{best.flavor}"
         f"/dO={best.delta_major})")
    emit("fig22/spade+soar_da", 0.0, f"{da_base / best_soar.da_elems:.2f}x fewer")
    if plans:
        emit("fig22/carom_outer_da", 0.0,
             f"{plans[0].da_elems:.3e} elems @L2 "
             f"(inner {plans[-1].da_elems:.3e} @L1)")

    # offline-SPADE (MSA table) vs input-specific (JSA) — §V-C
    msa = spade.meta_attributes([attrs_soar])
    table = spade.build_offline_table([layer], msa, 64 * 1024)
    plan_off = spade.otf_lookup(table, layer, float(attrs_soar.arf_avg[0]))
    emit("fig22/offline_vs_jsa", 0.0,
         f"{plan_off.da_elems / best_soar.da_elems:.3f}x DA of input-specific")

    # Fig 24 analogue: measured wall time of both engine backends on the
    # same SPADE-planned conv (one ConvPlan, two `backend=` forcings)
    params = init_sparse_conv(jax.random.PRNGKey(0), 27, 4, 32)
    conv_plan = engine.conv_plan_for_layer(
        coir, order.order, best_soar.delta_major,
        int(best_soar.delta_major
            * attrs_soar.at(best_soar.delta_major, "sa_minor_alloc_rst")) + 27,
        walk=best_soar.walk)
    ref_fn = jax.jit(lambda f: engine.sparse_conv(
        f, params, conv_plan, backend="reference"))
    us_ref = time_fn(ref_fn, t.feats)
    tiled_fn = jax.jit(lambda f: engine.sparse_conv(
        f, params, conv_plan, backend="sspnna"))
    us_tiled = time_fn(tiled_fn, t.feats)
    emit("fig24/ref_conv", us_ref, "engine backend=reference (XLA einsum)")
    emit("fig24/spade_tiled_conv", us_tiled,
         f"{us_ref / us_tiled:.2f}x vs ref ({jax.default_backend()} wall; "
         f"tiles={conv_plan.dispatch.n_tiles})")
