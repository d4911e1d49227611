"""SSpNNA kernels: fused gather-GEMM-scatter over weight planes (Pallas, TPU).

TPU adaptation of the SSpNNA core (§IV-D, §V-A):

* **DMA front-end** (§V-A-3): the fused kernel takes the *global* ``(V, C)``
  feature array plus ``in_rows``/``out_rows`` DMA tables, one tile's rows
  per grid step in SMEM, and streams each tile's working set HBM→VMEM with
  per-voxel async copies — the unordered-datatype DMA engine.
  Tile *t+1*'s gather is issued before tile *t*'s MACs run (manual double
  buffering over a 2-slot VMEM working-set scratch, so the grid is declared
  sequential), and tile outputs are DMA'd straight to their global rows
  (ordered-datatype engine) — no ``(T, dI, C)`` HBM intermediate, no
  post-kernel scatter.
* **WAVES front-end** (weight-plane active-voxel scheduling): the tile's
  COIR block ``local_idx`` names, per output slot and weight plane, the
  partner row in the tile-local working set. Per plane, the kernel turns its
  column into a ``(dO, dI)`` partial-permutation one-hot matrix on the VPU
  (compare-against-iota) — the pair-selection logic WAVES' smart-lookup
  performs, 4 voxels/cycle, on the ASIC.
* **SyMAC back-end** (systolic + multicast MACs): per plane, the gather
  (``onehot @ feats``) and the contraction (``(dO, C) @ (C, N)``) run on the
  MXU with f32 accumulation in plane order — the MXU's operand broadcast
  plays SyMAC's IFM multicast. ``sspnna_tile_ref`` pins the same order, so
  the kernels are bitwise identical to it off-TPU.

Why one-hot instead of a dynamic VMEM gather: TPU VMEM has no random
scatter/gather port; a partial-permutation matmul maps irregular access onto
the systolic array at full utilization, which *is* the paper's core move —
turn sparse bookkeeping into dense compute at M-V (here tile-level)
granularity.

**Lane groups.** A TPU DMA of one feature row is legal only for a row of
exactly one 128-lane tile, so the fused kernel sees features as lane groups:
``(V, C)`` is zero-padded to ``Cp = G*LANES`` channels and laid out as
``(G, V, LANES)``, each voxel row moving as ``G`` single-tile DMAs, and the
output comes back the same way. Zero channels add exact zeros to every dot.
The cost is HBM bytes: one padded (and, for ``G > 1``, transposed) copy of
the input and the output per call, ``V*(Cp + Np)`` elements, and per-row
DMAs of ``Cp`` lanes (at C=16 that is 8x the useful bytes).

Dead tiles (``pair_counts == 0`` — the budgeted serving planner pads the
tile stack heavily) skip their DMAs and MACs entirely via ``pl.when``; their
output rows stay on the zero-initialized trash-row buffer.

Per-cell VMEM (SPADE's dT budget, Eqn 1): ``2*dI*Cp`` (double-buffered
working set) + ``dO*K`` (COIR block) + ``2*K*Cp*dN`` (pipelined weight
slab) + ``dO*dN`` (output staging) plus the transient ``dO*dI`` one-hot.

``sspnna_tiles`` keeps the pre-gathered ``(T, dI, C)`` stack API (used by
the benchmark baseline and direct tests); it shares ``_tile_compute`` with
the fused kernel, so both are bitwise identical to the oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

#: TPU vreg lane width: channel axes are padded to a multiple of this
LANES = 128


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _lane_block(n_p: int, block_n: int | None) -> int:
    """The N-block in whole lane tiles: ``block_n`` rounded up to a
    multiple of ``LANES``, or all of ``n_p`` when that does not divide it."""
    bn = min(_round_up(block_n or n_p, LANES), n_p)
    return bn if n_p % bn == 0 else n_p


def _pad_channels(feats, weights):
    """Zero-pad the channel axes to whole lane tiles: feats ``(..., C)``,
    weights ``(K, C, N)``."""
    c, n = weights.shape[1], weights.shape[2]
    c_p, n_p = _round_up(c, LANES), _round_up(n, LANES)
    pad = [(0, 0)] * (feats.ndim - 1) + [(0, c_p - c)]
    return (jnp.pad(feats, pad),
            jnp.pad(weights, ((0, 0), (0, c_p - c), (0, n_p - n))))


def _tile_compute(groups, idx, w):
    """One tile's MACs: ``groups`` the working set's ``G`` lane groups, each
    (dI, LANES); idx (dO, K) -1 holes; w (K, G*LANES, dN) -> f32 (dO, dN).

    Plane by plane, a ``(dO, dI)`` partial-permutation matmul gathers the
    plane's partners one lane group at a time, and each ``(dO, LANES) @
    (LANES, dN)`` matmul adds into an f32 sum, planes outer and groups
    inner — the contraction order ``sspnna_tile_ref`` pins. The gather runs
    at ``HIGHEST`` precision so it stays an exact row copy on the MXU. ``w``
    may be a ref: only the current plane is loaded.
    """
    d_i = groups[0].shape[0]
    d_o, k = idx.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (d_o, d_i), 1)
    acc = jnp.zeros((d_o, w.shape[2]), jnp.float32)
    for p in range(k):
        onehot = (idx[:, p:p + 1] == iota).astype(groups[0].dtype)  # VPU
        for g, feats in enumerate(groups):
            gathered = jnp.dot(onehot, feats,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
            acc = acc + jnp.dot(gathered.astype(feats.dtype),
                                w[p, g * LANES:(g + 1) * LANES],
                                preferred_element_type=jnp.float32)
    return acc


_SEQUENTIAL = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary"))


# ---------------------------------------------------------------------------
# Pre-gathered tile-stack kernel (baseline; direct (T, dI, C) API)
# ---------------------------------------------------------------------------

def _pregathered_kernel(feats_ref, idx_ref, w_ref, out_ref):
    groups = [feats_ref[0, :, g * LANES:(g + 1) * LANES]
              for g in range(feats_ref.shape[2] // LANES)]
    out_ref[0] = _tile_compute(groups, idx_ref[0], w_ref).astype(out_ref.dtype)


def sspnna_tiles(
    feats: jax.Array,      # (T, dI, C)
    local_idx: jax.Array,  # (T, dO, K)
    weights: jax.Array,    # (K, C, N)
    *,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Run the SSpNNA kernel over a pre-gathered stack of tiles -> (T, dO, N).

    ``interpret`` resolves *before* the jit boundary so the cache is keyed
    on the concrete mode."""
    return _sspnna_tiles(feats, local_idx, weights, block_n=block_n,
                         interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def _sspnna_tiles(
    feats: jax.Array,
    local_idx: jax.Array,
    weights: jax.Array,
    *,
    block_n: int | None,
    interpret: bool,
) -> jax.Array:
    t, d_i, _ = feats.shape
    _, d_o, k = local_idx.shape
    n = weights.shape[2]
    feats, weights = _pad_channels(feats, weights)
    c_p, n_p = weights.shape[1], weights.shape[2]
    bn = _lane_block(n_p, block_n)
    out = pl.pallas_call(
        _pregathered_kernel,
        grid=(t, n_p // bn),
        in_specs=[
            pl.BlockSpec((1, d_i, c_p), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, d_o, k), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((k, c_p, bn), lambda i, j: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, d_o, bn), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((t, d_o, n_p), feats.dtype),
        interpret=interpret,
    )(feats, local_idx, weights)
    return out[..., :n]


# ---------------------------------------------------------------------------
# Fused gather-GEMM-scatter kernel (global features in, global rows out)
# ---------------------------------------------------------------------------

def _fused_kernel(counts_ref, in_rows_ref, next_rows_ref, out_rows_ref,
                  idx_ref, feats_hbm, zeros_hbm, w_ref, out_hbm, ws, obuf,
                  in_sems, out_sem, *, n_tiles):
    del zeros_hbm  # aliased into out_hbm: provides the zero/trash-row init
    i = pl.program_id(0)
    j = pl.program_id(1)
    n_groups, d_i = ws.shape[1], ws.shape[2]
    out_groups, d_o = obuf.shape[0], obuf.shape[1]

    def row_dma(rows_ref, slot, r, g):
        """Per-voxel entry of the unordered-datatype DMA table (§V-A-3),
        one lane group of the row."""
        row = rows_ref[0, r]
        return pltpu.make_async_copy(
            feats_hbm.at[g, pl.ds(row, 1), :],
            ws.at[slot, g, pl.ds(r, 1), :],
            in_sems.at[slot],
        )

    def for_rows(rows_ref, slot, op):
        def body(r, carry):
            for g in range(n_groups):
                op(row_dma(rows_ref, slot, r, g))
            return carry

        jax.lax.fori_loop(0, d_i, body, 0)

    # N-blocks revisit the same working set: DMA choreography runs once per
    # tile (j == 0). Double buffering: tile i+1's gather (its rows arrive as
    # the `next_rows` block) is in flight while tile i's MACs run; dead
    # tiles (pair_counts == 0) issue nothing.
    @pl.when(j == 0)
    def _():
        @pl.when((i == 0) & (counts_ref[0] > 0))
        def _():
            for_rows(in_rows_ref, 0, lambda c: c.start())

        # clamp the lookahead read: `&` evaluates both sides, and the last
        # tile has no successor
        nxt = jnp.minimum(i + 1, n_tiles - 1)

        @pl.when((i + 1 < n_tiles) & (counts_ref[nxt] > 0))
        def _():
            for_rows(next_rows_ref, (i + 1) % 2, lambda c: c.start())

        @pl.when(counts_ref[i] > 0)
        def _():
            for_rows(in_rows_ref, i % 2, lambda c: c.wait())

    @pl.when(counts_ref[i] > 0)
    def _():
        slot = i % 2
        acc = _tile_compute([ws[slot, g] for g in range(n_groups)],
                            idx_ref[0], w_ref)
        for g in range(out_groups):
            obuf[g] = acc[:, g * LANES:(g + 1) * LANES].astype(obuf.dtype)

        def out_dma(o, g):
            # ordered-datatype DMA: each output slot streams straight to its
            # global row (pad slots land on the trash row and are sliced off)
            row = out_rows_ref[0, o]
            return pltpu.make_async_copy(
                obuf.at[g, pl.ds(o, 1), :],
                out_hbm.at[j * out_groups + g, pl.ds(row, 1), :],
                out_sem,
            )

        def for_slots(op):
            def body(o, carry):
                for g in range(out_groups):
                    op(out_dma(o, g))
                return carry

            jax.lax.fori_loop(0, d_o, body, 0)

        # start all d_o row copies, then drain: latencies overlap instead of
        # serializing; obuf reuse is safe since every wait precedes the next
        # grid step's write
        for_slots(lambda c: c.start())
        for_slots(lambda c: c.wait())


def sspnna_fused(
    feats: jax.Array,        # (V, C) global input features
    weights: jax.Array,      # (K, C, N)
    out_rows: jax.Array,     # (T, dO) global output rows (-1 pad ok)
    in_rows: jax.Array,      # (T, dI) global input rows (-1 pad ok)
    local_idx: jax.Array,    # (T, dO, K) tile-local partner indices, -1 holes
    pair_counts: jax.Array,  # (T,) valid pairs per tile (0 => dead tile)
    *,
    n_out: int,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused gather-GEMM-scatter sparse conv -> (n_out, N) (no bias/mask).

    Accepts tile tables in either the raw ``TilePlan`` layout (-1 pads) or
    the DMA-table layout of ``core.tiles.dma_tile_tables`` — normalization
    is idempotent integer ops. Tiles must own disjoint output rows (the
    output DMA overwrites): plans with ``n_row_splits > 0`` need the
    accumulating pre-gathered path instead.

    ``interpret`` resolves *before* the jit boundary (see
    ``kernels.runtime.resolve_interpret``)."""
    return _sspnna_fused(feats, weights, out_rows, in_rows, local_idx,
                         pair_counts, n_out=n_out, block_n=block_n,
                         interpret=resolve_interpret(interpret))


@functools.partial(
    jax.jit, static_argnames=("n_out", "block_n", "interpret"))
def _sspnna_fused(
    feats: jax.Array,
    weights: jax.Array,
    out_rows: jax.Array,
    in_rows: jax.Array,
    local_idx: jax.Array,
    pair_counts: jax.Array,
    *,
    n_out: int,
    block_n: int | None,
    interpret: bool,
) -> jax.Array:
    t, d_o, k = local_idx.shape
    d_i = in_rows.shape[1]
    n = weights.shape[2]
    if t == 0:
        return jnp.zeros((n_out, n), feats.dtype)
    feats, weights = _pad_channels(feats, weights)
    c_p, n_p = weights.shape[1], weights.shape[2]
    bn = _lane_block(n_p, block_n)
    # (V, G*LANES) -> (G, V, LANES): one legal single-tile DMA per group
    groups = feats.reshape(-1, c_p // LANES, LANES).transpose(1, 0, 2)
    # normalize to DMA-table layout (idempotent when the caller already
    # holds `dma_tile_tables` output): every in-entry a safe HBM source,
    # every out-entry a real row or the trash row n_out. (T, 1, d) keeps a
    # one-tile block equal to the array's trailing dims.
    in_dma = jnp.maximum(in_rows, 0).astype(jnp.int32)[:, None]
    out_dma = jnp.where(out_rows < 0, n_out,
                        out_rows).astype(jnp.int32)[:, None]
    counts = pair_counts.astype(jnp.int32)
    zeros = jnp.zeros((n_p // LANES, n_out + 1, LANES), feats.dtype)
    # only the (T,) dead-tile predicate is scalar-prefetched: the row
    # tables grow with the scene and outgrow SMEM, so each step gets its
    # tile's rows (and the next tile's, for the lookahead) as SMEM blocks
    smem_rows = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t, n_p // bn),
        in_specs=[
            smem_rows((None, 1, d_i), lambda i, j, *_: (i, 0, 0)),
            smem_rows((None, 1, d_i),
                      lambda i, j, *_: (jnp.minimum(i + 1, t - 1), 0, 0)),
            smem_rows((None, 1, d_o), lambda i, j, *_: (i, 0, 0)),
            pl.BlockSpec((1, d_o, k), lambda i, j, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.HBM),  # feats stay in HBM
            pl.BlockSpec(memory_space=pltpu.HBM),  # zero-init (aliased)
            pl.BlockSpec((k, c_p, bn), lambda i, j, *_: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.HBM),
        scratch_shapes=[
            # double-buffered dM working set, by lane group
            pltpu.VMEM((2, c_p // LANES, d_i, LANES), feats.dtype),
            pltpu.VMEM((bn // LANES, d_o, LANES), feats.dtype),  # staging
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_fused_kernel, n_tiles=t),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(zeros.shape, feats.dtype),
        # input index 6 = zeros (scalar-prefetch args count in the numbering)
        input_output_aliases={6: 0},
        # the manual double buffer carries state from step i to i+1
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(counts, in_dma, in_dma, out_dma, local_idx, groups, zeros, weights)
    out = out.transpose(1, 0, 2).reshape(n_out + 1, n_p)
    return out[:n_out, :n]
