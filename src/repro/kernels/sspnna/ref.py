"""Pure-jnp oracle for the SSpNNA tile kernel.

Semantics: for tile t, output slot o, weight plane k, the partner feature is
``feats[t, local_idx[t, o, k]]`` (zeros when the index is -1); the output is
the contraction of the gathered ``(dO, K, C)`` block with the ``(K, C, N)``
weights, accumulated in f32.

The contraction is written plane by plane and, within a plane, one
128-lane channel group at a time: ``acc += gathered[k, g] @ w[k, g]``, each
a ``(dO, LANES) @ (LANES, N)`` ``dot`` over the lane-padded widths, one tile
at a time — the same reduction the kernels perform after their per-plane
partial-permutation gather matmul, at the same shapes. So the Pallas paths
are **bitwise identical** to this oracle off-TPU (the fused-kernel property
tests assert exact equality, not allclose). An einsum over ``(k, c)``
jointly is the same math but XLA may reduce it in a different order (a
one-row product may even lower as a vector reduction), which is why this
form is the pinned spec.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.sspnna.sspnna import LANES, _pad_channels


def sspnna_tile_ref(feats, local_idx, weights):
    """feats: (T, dI, C); local_idx: (T, dO, K) -1 holes; weights: (K, C, N)
    -> (T, dO, N) in feats.dtype."""
    n = weights.shape[2]
    feats, weights = _pad_channels(feats, weights)
    valid = local_idx >= 0
    idx = jnp.maximum(local_idx, 0)
    # (T, 1, dI, C) gathered along dI by (T, dO, K, 1) -> (T, dO, K, C)
    gathered = jnp.take_along_axis(feats[:, None, :, :], idx[..., None], axis=2)
    gathered = jnp.where(valid[..., None], gathered, 0)

    def tile(x):  # (dO, K, Cp) -> (dO, Np): the kernel's per-tile reduction
        acc = jnp.zeros((x.shape[0], weights.shape[2]), jnp.float32)
        for p in range(x.shape[1]):
            for c0 in range(0, x.shape[2], LANES):
                acc = acc + jnp.dot(x[:, p, c0:c0 + LANES],
                                    weights[p, c0:c0 + LANES],
                                    preferred_element_type=jnp.float32)
        return acc

    out = jax.lax.map(tile, gathered)
    return out[..., :n].astype(feats.dtype)
