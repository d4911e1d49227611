"""SCN U-Net: submanifold sparse conv network for 3D semantic segmentation.

The paper's primary workload (Graham et al. 2018 [18]): a U-net over a
sparse voxel grid — submanifold 3^3 conv blocks at each level, 2^3-stride-2
convs down, transposed convs back up with skip concatenation, and a linear
classifier over active voxels.

Execution lives in ``repro.engine``: build a ``ScenePlan`` once per input
(``engine.build_scene_plan`` — the AdMAC + SOAR + SPADE pass) and run
``engine.apply_unet(params, feats, plan)``. This module keeps the model
definition (config, parameter init, losses) plus deprecation shims for the
pre-engine entry points ``build_unet_metadata`` / ``apply_unet``.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Any, ClassVar, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import engine
from repro.core.coir import COIR
from repro.core.sparse_conv import init_sparse_conv
from repro.sparse.tensor import SparseVoxelTensor


@dataclass(frozen=True)
class UNetConfig:
    name: str = "scn_unet"
    in_channels: int = 4
    n_classes: int = 20
    widths: tuple[int, ...] = (16, 32, 48, 64)
    reps: int = 2
    resolution: int = 64
    capacity: int = 8192
    dtype: Any = jnp.float32

    #: the stem is a 3^3 conv on level 0's submanifold plan
    stem_kernel: ClassVar[int] = 3
    #: the decoder concatenates [skip, upsampled]
    concat: ClassVar[str] = "skip_up"

    @property
    def n_levels(self) -> int:
        return len(self.widths)

    @property
    def stem_width(self) -> int:
        return self.widths[0]


class LevelMeta(NamedTuple):
    """Pre-engine per-level metadata bundle (kept for the shims)."""

    coords: jax.Array
    mask: jax.Array
    sub_coir: COIR          # submanifold 3^3 metadata at this level
    down_coir: COIR | None  # strided 2^3 s2 conv to the next level
    up_coir: COIR | None    # transposed conv back to this level


def meta_to_plan(meta: list[LevelMeta]) -> engine.ScenePlan:
    """Adapt legacy LevelMeta lists to an (all-reference) engine ScenePlan."""
    levels = tuple(
        engine.LevelPlan(
            m.coords, m.mask, engine.ConvPlan(m.sub_coir),
            engine.ConvPlan(m.down_coir) if m.down_coir is not None else None,
            engine.ConvPlan(m.up_coir) if m.up_coir is not None else None,
        )
        for m in meta
    )
    return engine.ScenePlan(levels)


def build_unet_metadata(t: SparseVoxelTensor, cfg: UNetConfig) -> list[LevelMeta]:
    """Deprecated: use ``repro.engine.build_scene_plan`` (same AdMAC pass,
    plus SOAR/SPADE planning when requested)."""
    warnings.warn(
        "build_unet_metadata is deprecated; use repro.engine.build_scene_plan",
        DeprecationWarning, stacklevel=2)
    plan = engine.build_scene_plan(t, cfg, plan_tiles=False)
    return [
        LevelMeta(lvl.coords, lvl.mask, lvl.sub.coir,
                  lvl.down.coir if lvl.down is not None else None,
                  lvl.up.coir if lvl.up is not None else None)
        for lvl in plan.levels
    ]


def apply_unet(params: dict, feats: jax.Array,
               meta: "list[LevelMeta] | engine.ScenePlan") -> jax.Array:
    """Deprecated: use ``repro.engine.apply_unet`` with a ScenePlan."""
    warnings.warn(
        "models.scn.apply_unet is deprecated; use repro.engine.apply_unet",
        DeprecationWarning, stacklevel=2)
    plan = meta if isinstance(meta, engine.ScenePlan) else meta_to_plan(meta)
    # the pre-engine semantics were the reference einsum on every layer;
    # omitting ctx= dispatches through the ambient ExecutionContext
    return engine.apply_unet(params, feats, plan, backend="reference")


def init_unet(key: jax.Array, cfg: UNetConfig) -> dict:
    keys = iter(jax.random.split(key, 1024))
    w = cfg.widths
    params: dict = {"levels": []}
    params["stem"] = init_sparse_conv(next(keys), 27, cfg.in_channels, w[0], cfg.dtype)
    for li in range(cfg.n_levels):
        lvl = {
            "enc": [
                _block_params(next(keys), w[li], w[li], cfg.dtype)
                for _ in range(cfg.reps)
            ]
        }
        if li < cfg.n_levels - 1:
            lvl["down"] = init_sparse_conv(next(keys), 8, w[li], w[li + 1], cfg.dtype)
            lvl["up"] = init_sparse_conv(next(keys), 8, w[li + 1], w[li], cfg.dtype)
            # decoder blocks see concat(skip, upsampled) = 2*w[li]
            lvl["dec"] = [
                _block_params(next(keys), 2 * w[li] if r == 0 else w[li],
                              w[li], cfg.dtype)
                for r in range(cfg.reps)
            ]
        params["levels"].append(lvl)
    params["head"] = {
        "w": jax.random.normal(next(keys), (w[0], cfg.n_classes), cfg.dtype)
        / np.sqrt(w[0]),
        "b": jnp.zeros((cfg.n_classes,), cfg.dtype),
    }
    return params


def _block_params(key, c_in, c_out, dtype):
    k1, _ = jax.random.split(key)
    return {
        "conv": init_sparse_conv(k1, 27, c_in, c_out, dtype),
        "bn_scale": jnp.ones((c_out,), dtype),
        "bn_offset": jnp.zeros((c_out,), dtype),
    }


def segmentation_loss(logits, labels, mask):
    """Masked mean CE over active voxels + accuracy/mIoU-ready predictions."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    m = mask.astype(jnp.float32)
    loss = -jnp.sum(ll * m) / jnp.maximum(jnp.sum(m), 1.0)
    acc = jnp.sum((jnp.argmax(logits, -1) == labels) * m) / jnp.maximum(jnp.sum(m), 1)
    return loss, acc


def miou(pred: np.ndarray, labels: np.ndarray, mask: np.ndarray,
         n_classes: int) -> float:
    pred, labels = np.asarray(pred)[mask], np.asarray(labels)[mask]
    ious = []
    for c in range(n_classes):
        inter = np.sum((pred == c) & (labels == c))
        union = np.sum((pred == c) | (labels == c))
        if union:
            ious.append(inter / union)
    return float(np.mean(ious)) if ious else 0.0
