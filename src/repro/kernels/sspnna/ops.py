"""jit'd public wrapper: sparse conv through the SSpNNA kernel + tile plan.

Implements the full §V-A execution flow on one chip. The default (fused)
path hands the *global* feature array to the Pallas kernel, whose
scalar-prefetched DMA tables stream each tile's working set HBM→VMEM and
write tile outputs straight to their global rows — the jitted graph holds
no ``(T, dI, C)`` gathered intermediate and no post-kernel scatter
(``tests/test_sspnna_fused.py`` pins this via HLO inspection).

The legacy pre-gathered path (``fused=False`` / ``use_kernel=False``)
materializes the working-set copy with XLA dynamic-gather, runs the
tile-stack kernel or the jnp oracle, and scatters tile outputs back with an
accumulating ``.at[].add`` — the accumulate (not overwrite) is what makes
plane-split tiles (``TilePlan.n_row_splits > 0``) correct, and is a bitwise
no-op for ordinary disjoint-row plans.

``run_sspnna_conv`` is the execution primitive the engine dispatcher
(``repro.engine.sparse_conv``) drives; ``sspnna_conv`` and
``sspnna_conv_from_plan`` are the old direct entry points, kept as
deprecation shims.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp

from repro.core.tiles import TilePlan
from repro.kernels.runtime import resolve_interpret
from repro.kernels.sspnna.ref import sspnna_tile_ref
from repro.kernels.sspnna.sspnna import sspnna_fused, sspnna_tiles


def run_sspnna_conv(
    feats: jax.Array,         # (V_in, C) global input features
    weights: jax.Array,       # (K, C, N)
    out_rows: jax.Array,      # (T, dO) from TilePlan / dma_tile_tables
    in_rows: jax.Array,       # (T, dI)
    local_idx: jax.Array,     # (T, dO, K)
    *,
    n_out: int,
    pair_counts: jax.Array | None = None,  # (T,) enables the fused path
    use_kernel: bool = True,
    fused: bool | None = None,
    interpret: bool | None = None,
    block_n: int | None = None,
) -> jax.Array:
    """Tiled sparse convolution -> (n_out, N) features (no bias/mask).

    ``fused=None`` resolves to the fused gather-GEMM-scatter kernel whenever
    the kernel path is on and ``pair_counts`` is available (the engine
    always threads it from the plan's ``TileArrays``); passing
    ``fused=True`` without counts derives them from ``local_idx`` on
    device. Plans whose tiles share output rows (``n_row_splits > 0``)
    must pass ``fused=False`` — the fused output DMA overwrites, the
    pre-gathered scatter accumulates.

    ``interpret`` resolves *before* the jit boundary (see
    ``kernels.runtime.resolve_interpret``) so direct calls honor late
    backend/env changes by retracing. Callers that wrap this in their own
    long-lived jit (e.g. the serving engines) capture the mode at their
    first trace — pass ``interpret=`` explicitly there instead."""
    if fused is None:
        fused = use_kernel and pair_counts is not None
    if fused and not use_kernel:
        raise ValueError("fused=True requires use_kernel=True "
                         "(the fused path is the Pallas kernel)")
    return _run_sspnna_conv(
        feats, weights, out_rows, in_rows, local_idx, pair_counts,
        n_out=n_out, use_kernel=use_kernel, fused=fused,
        interpret=resolve_interpret(interpret), block_n=block_n)


@functools.partial(
    jax.jit, static_argnames=("n_out", "use_kernel", "fused", "interpret",
                              "block_n"))
def _run_sspnna_conv(
    feats: jax.Array,
    weights: jax.Array,
    out_rows: jax.Array,
    in_rows: jax.Array,
    local_idx: jax.Array,
    pair_counts: jax.Array | None,
    *,
    n_out: int,
    use_kernel: bool,
    fused: bool,
    interpret: bool,
    block_n: int | None,
) -> jax.Array:
    n = weights.shape[2]
    if fused:
        counts = (pair_counts if pair_counts is not None
                  else (local_idx >= 0).sum(axis=(1, 2)).astype(jnp.int32))
        return sspnna_fused(
            feats, weights, out_rows, in_rows, local_idx, counts,
            n_out=n_out, block_n=block_n, interpret=interpret)
    in_ok = in_rows >= 0
    tile_feats = jnp.take(feats, jnp.maximum(in_rows, 0), axis=0)
    tile_feats = jnp.where(in_ok[..., None], tile_feats, 0)
    if use_kernel:
        tile_out = sspnna_tiles(
            tile_feats, local_idx, weights, block_n=block_n,
            interpret=interpret)
    else:
        tile_out = sspnna_tile_ref(tile_feats, local_idx, weights)
    rows = jnp.where(out_rows >= 0, out_rows, n_out)
    out = jnp.zeros((n_out + 1, n), tile_out.dtype)
    # accumulate (not overwrite): plane-split tiles may share an output row;
    # for disjoint-row plans adding into zeros is the same result
    out = out.at[rows.reshape(-1)].add(tile_out.reshape(-1, n), mode="drop")
    return out[:n_out]


def sspnna_conv(
    feats: jax.Array,
    weights: jax.Array,
    out_rows: jax.Array,
    in_rows: jax.Array,
    local_idx: jax.Array,
    *,
    n_out: int,
    use_kernel: bool = True,
    interpret: bool | None = None,
    block_n: int | None = None,
) -> jax.Array:
    """Deprecated: call ``repro.engine.sparse_conv(backend='sspnna')``."""
    warnings.warn(
        "sspnna_conv is deprecated; route through repro.engine.sparse_conv "
        "with a tiled ConvPlan instead", DeprecationWarning, stacklevel=2)
    return run_sspnna_conv(
        feats, weights, out_rows, in_rows, local_idx, n_out=n_out,
        use_kernel=use_kernel, interpret=interpret, block_n=block_n)


def sspnna_conv_from_plan(
    feats: jax.Array,
    weights: jax.Array,
    plan: TilePlan,
    *,
    n_out: int,
    use_kernel: bool = True,
    interpret: bool | None = None,
    block_n: int | None = None,
) -> jax.Array:
    """Deprecated: call ``repro.engine.sparse_conv(backend='sspnna')``."""
    warnings.warn(
        "sspnna_conv_from_plan is deprecated; route through "
        "repro.engine.sparse_conv with a tiled ConvPlan instead",
        DeprecationWarning, stacklevel=2)
    return run_sspnna_conv(
        feats,
        weights,
        jnp.asarray(plan.out_rows),
        jnp.asarray(plan.in_rows),
        jnp.asarray(plan.local_idx),
        n_out=n_out,
        # shared-row (plane-split) plans need the accumulating scatter
        pair_counts=(jnp.asarray(plan.pair_counts)
                     if use_kernel and plan.n_row_splits == 0 else None),
        use_kernel=use_kernel,
        interpret=interpret,
        block_n=block_n,
    )
