"""Spatially-sparse 3D convolution on COIR metadata (gather-GEMM-scatter).

Three layer types, matching SCN U-Nets (Graham et al. 2018):

* **submanifold** (k=3, s=1): output active set == input active set; only
  active neighbours contribute (Valid Sparse Convolution).
* **strided** (k=2, s=2): output set = unique(coords // 2); downsamples.
* **transposed** (k=2, s=2): restores a saved finer active set; upsamples.

The reference dataflow is the paper's coarse M-V dispatch batched to a full
einsum: gather partner features per weight plane, one fused
``(V, K, C) x (K, C, N)`` contraction, which XLA maps onto the MXU — the
whole layer is a single coarse dispatch (Table III taken to its limit).
``repro/kernels/sspnna`` provides the tiled Pallas version driven by SPADE
tile plans; this module is also its numerical oracle.
"""
from __future__ import annotations

import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.coir import COIR, build_cirf, build_corf
from repro.core.hashgrid import downsample_coords, kernel_offsets
from repro.sparse.tensor import SparseVoxelTensor


class SparseConvParams(NamedTuple):
    weight: jax.Array  # (K, C, N)
    bias: jax.Array | None  # (N,); None for a bias-free conv


def init_sparse_conv(
    key: jax.Array, kernel_volume: int, c_in: int, c_out: int, dtype=jnp.float32
) -> SparseConvParams:
    fan_in = kernel_volume * c_in
    w = jax.random.normal(key, (kernel_volume, c_in, c_out), dtype) / np.sqrt(fan_in)
    return SparseConvParams(w, jnp.zeros((c_out,), dtype))


def gather_partners(feats: jax.Array, coir: COIR) -> jax.Array:
    """(V, K, C) partner features; zeros at holes. The 'Input Gather' stage
    that dominates the CPU profile (Fig 4) — here a single vectorized take."""
    idx = jnp.maximum(coir.indices, 0)
    g = jnp.take(feats, idx, axis=0)  # (V, K, C)
    return jnp.where(coir.valid()[..., None], g, 0)


def reference_conv_cirf(
    feats_in: jax.Array, coir: COIR, params: SparseConvParams
) -> jax.Array:
    """Out-major (CIRF) evaluation: gather + one fused contraction.

    This is the engine's ``backend="reference"`` implementation and the
    numerical oracle for the tiled SSpNNA path (``repro.engine.sparse_conv``).
    """
    g = gather_partners(feats_in, coir)
    out = jnp.einsum(
        "okc,kcn->on", g, params.weight, preferred_element_type=jnp.float32
    ).astype(feats_in.dtype)
    if params.bias is not None:
        out = out + params.bias.astype(out.dtype)
    return out * coir.mask[:, None].astype(out.dtype)


def reference_conv_ws(
    feats_in: jax.Array, coir: COIR, params: SparseConvParams
) -> jax.Array:
    """Out-major evaluation one weight plane at a time (weight-stationary):
    per plane, gather its partners ``(V, C)`` and add their product into an
    f32 sum, in plane order. It holds one plane's rows where
    ``reference_conv_cirf`` holds all K: a TPU pads a row to 128 lanes, so
    a 5^3 stem's ``(V, 125, 4)`` block would take 32x its size."""
    def plane(acc, kw):
        rows, ok, w = kw
        g = jnp.where(ok[:, None], jnp.take(feats_in, rows, axis=0), 0)
        return acc + jnp.dot(g, w, preferred_element_type=jnp.float32), None

    v, n = coir.indices.shape[0], params.weight.shape[2]
    out, _ = jax.lax.scan(
        plane, jnp.zeros((v, n), jnp.float32),
        (jnp.maximum(coir.indices, 0).T, coir.valid().T, params.weight))
    out = out.astype(feats_in.dtype)
    if params.bias is not None:
        out = out + params.bias.astype(out.dtype)
    return out * coir.mask[:, None].astype(out.dtype)


def sparse_conv_cirf(
    feats_in: jax.Array, coir: COIR, params: SparseConvParams
) -> jax.Array:
    """Deprecated: call ``repro.engine.sparse_conv`` with a plan instead."""
    warnings.warn(
        "sparse_conv_cirf is deprecated; use repro.engine.sparse_conv with a "
        "ConvPlan (backend='reference' reproduces these numerics exactly)",
        DeprecationWarning, stacklevel=2)
    from repro.engine import api as engine_api  # local: engine imports us

    # omitting ctx= dispatches through the ambient ExecutionContext's
    # registry, exactly like a modern call site
    return engine_api.sparse_conv(
        feats_in, params, engine_api.reference_plan(coir),
        backend="reference")


def _normalize(x, mask, scale, offset, eps):
    """Batch norm over the active rows (biased variance) -> (y, row mask)."""
    m = mask[:, None].astype(x.dtype)
    n = jnp.maximum(jnp.sum(m), 1.0)
    mean = jnp.sum(x * m, axis=0) / n
    var = jnp.sum(jnp.square(x - mean) * m, axis=0) / n
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + offset, m


def masked_batchnorm_relu(x, mask, scale, offset, eps: float = 1e-5):
    """BN + ReLU over active rows only (the SCN conv-block epilogue)."""
    y, m = _normalize(x, mask, scale, offset, eps)
    return jax.nn.relu(y) * m


def masked_batchnorm(x, mask, scale, offset, eps: float = 1e-5):
    """BN over active rows only, zero elsewhere (a residual branch's last
    norm, before the add)."""
    y, m = _normalize(x, mask, scale, offset, eps)
    return y * m


def sparse_conv_corf(
    feats_in: jax.Array,
    coir_in_major: COIR,
    params: SparseConvParams,
    n_out: int,
) -> jax.Array:
    """In-major (CORF) evaluation: per-plane product then scatter-add to the
    response field ('Output Write' in the paper's profile)."""
    contrib = jnp.einsum(
        "ic,kcn->ikn",
        feats_in * coir_in_major.mask[:, None].astype(feats_in.dtype),
        params.weight,
        preferred_element_type=jnp.float32,
    )
    idx = coir_in_major.indices  # (Vi, K) -> output rows
    ok = coir_in_major.valid()
    rows = jnp.where(ok, idx, n_out)
    out = jnp.zeros((n_out, params.weight.shape[-1]), jnp.float32)
    out = out.at[rows.reshape(-1)].add(
        jnp.where(ok[..., None], contrib, 0).reshape(-1, params.weight.shape[-1]),
        mode="drop",
    )
    out = out.astype(feats_in.dtype) + params.bias.astype(feats_in.dtype)
    valid_row = jnp.zeros((n_out,), bool).at[rows.reshape(-1)].set(
        ok.reshape(-1), mode="drop"
    )
    return out * valid_row[:, None].astype(out.dtype)


# ---------------------------------------------------------------------------
# Layer-level helpers on SparseVoxelTensor
# ---------------------------------------------------------------------------

def submanifold_coir(
    t: SparseVoxelTensor, resolution: int, kernel_size: int = 3
) -> COIR:
    offs = jnp.asarray(kernel_offsets(kernel_size))
    return build_cirf(t.coords, t.mask, t.coords, t.mask, offs, resolution)


def submanifold_conv(
    t: SparseVoxelTensor, coir: COIR, params: SparseConvParams
) -> SparseVoxelTensor:
    return t.replace_feats(reference_conv_cirf(t.feats, coir, params))


def strided_conv(
    t: SparseVoxelTensor,
    resolution: int,
    params: SparseConvParams,
    kernel_size: int = 2,
    stride: int = 2,
    capacity_out: int | None = None,
):
    """Downsampling conv; returns (out tensor, out resolution, coir)."""
    out_coords, out_mask = downsample_coords(
        t.coords, t.mask, resolution, stride, capacity_out
    )
    offs = jnp.asarray(kernel_offsets(kernel_size, centered=False))
    coir = build_cirf(
        out_coords, out_mask, t.coords, t.mask, offs, resolution, stride
    )
    feats = reference_conv_cirf(t.feats, coir, params)
    return SparseVoxelTensor(out_coords, feats, out_mask), resolution // stride, coir


def transposed_coir(
    coarse: SparseVoxelTensor,
    fine_coords: jax.Array,
    fine_mask: jax.Array,
    fine_resolution: int,
    kernel_size: int = 2,
    stride: int = 2,
) -> COIR:
    """CIRF of a transposed conv restoring the saved finer active set.

    Fine output o draws from coarse input i when ``o == i*stride + d``; this
    is exactly the CORF probe with roles swapped.
    """
    offs = jnp.asarray(kernel_offsets(kernel_size, centered=False))
    return build_corf(
        coarse.coords, coarse.mask, fine_coords, fine_mask, offs,
        fine_resolution, stride,
    )


def transposed_conv(
    coarse: SparseVoxelTensor,
    coir_fine_major: COIR,
    fine_coords: jax.Array,
    fine_mask: jax.Array,
    params: SparseConvParams,
) -> SparseVoxelTensor:
    feats = reference_conv_cirf(coarse.feats, coir_fine_major, params)
    return SparseVoxelTensor(fine_coords, feats, fine_mask)


def batchnorm_relu(
    t: SparseVoxelTensor, scale: jax.Array, offset: jax.Array, eps: float = 1e-5
) -> SparseVoxelTensor:
    """Masked batch-norm + ReLU over active voxels only."""
    return t.replace_feats(
        masked_batchnorm_relu(t.feats, t.mask, scale, offset, eps))


# ---------------------------------------------------------------------------
# Dense oracle (for property tests): sparse conv == masked dense conv
# ---------------------------------------------------------------------------

def dense_submanifold_reference(
    dense: np.ndarray, weight: np.ndarray, bias: np.ndarray
) -> np.ndarray:
    """O(R^3 K C N) dense evaluation of a submanifold conv, numpy oracle.

    dense: (R, R, R, C); weight: (K^3, C, N) in lexicographic offset order.
    Output voxel active iff input voxel active (submanifold rule).
    """
    r = dense.shape[0]
    occ = np.any(dense != 0, axis=-1)
    k3 = weight.shape[0]
    k = round(k3 ** (1 / 3))
    offs = kernel_offsets(k)
    out = np.zeros(dense.shape[:3] + (weight.shape[-1],), np.float32)
    for ki, (dx, dy, dz) in enumerate(offs):
        src = np.zeros_like(dense, dtype=np.float32)
        xs = slice(max(0, -dx), r - max(0, dx))
        xd = slice(max(0, dx), r - max(0, -dx))
        ys = slice(max(0, -dy), r - max(0, dy))
        yd = slice(max(0, dy), r - max(0, -dy))
        zs = slice(max(0, -dz), r - max(0, dz))
        zd = slice(max(0, dz), r - max(0, -dz))
        src[xs, ys, zs] = dense[xd, yd, zd]
        out += src.astype(np.float32) @ weight[ki].astype(np.float32)
    out += bias.astype(np.float32)
    return out * occ[..., None]
