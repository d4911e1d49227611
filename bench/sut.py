"""The system under test, as the benchmark drives it: the program's public
serving surface (``SceneEngine``, ``build_plan_spec``) and nothing else.

This is the one module of the benchmark that imports the program. The rest
of the yardstick (traffic, reference, work counts, trace reduction) does not.
"""
from __future__ import annotations

import numpy as np

from repro import engine
from repro.core.sparse_conv import SparseConvParams
from repro.models.scn import UNetConfig
from repro.serving.api import AdmissionPolicy
from repro.serving.scene_engine import SceneEngine, SceneRequest
from repro.sparse.tensor import SparseVoxelTensor

#: the program's name for the fused kernel's backend
KERNEL_BACKEND = engine.SSPNNA


def unet_config(cfg: dict) -> UNetConfig:
    return UNetConfig(name=cfg["name"], in_channels=cfg["input_features"],
                      n_classes=cfg["nClasses"], widths=tuple(cfg["n_planes"]),
                      reps=cfg["block_reps"], resolution=cfg["full_scale"],
                      capacity=cfg["capacity"])


def program_params(w: dict, widths, reps: int) -> dict:
    """The benchmark's flat weights in the program's parameter tree (no
    copies: the same device arrays)."""
    def conv(p):
        return SparseConvParams(w[p + ".w"], w[p + ".b"])

    def block(p):
        return {"conv": conv(p), "bn_scale": w[p + ".scale"],
                "bn_offset": w[p + ".offset"]}

    levels = []
    for li in range(len(widths)):
        lvl = {"enc": [block(f"l{li}.enc{r}") for r in range(reps)]}
        if li + 1 < len(widths):
            lvl["down"] = conv(f"l{li}.down")
            lvl["up"] = conv(f"l{li}.up")
            lvl["dec"] = [block(f"l{li}.dec{r}") for r in range(reps)]
        levels.append(lvl)
    return {"stem": conv("stem"), "levels": levels,
            "head": {"w": w["head.w"], "b": w["head.b"]}}


def scene(coords: np.ndarray, feats: np.ndarray, mask: np.ndarray):
    """A client's upload: host arrays, as a sensor or an app sends them."""
    return SparseVoxelTensor(coords, feats, mask)


def pin_spec(ucfg: UNetConfig, reps: list):
    """The pinned plan spec (tile budgets, per-level dispatch) from
    representative scenes; ``None`` levels run the reference einsum."""
    return engine.build_plan_spec(reps, ucfg, mem_budget=64 * 1024)


def kernel_levels(spec) -> list[int]:
    """Levels whose submanifold convs the spec sends to the fused kernel."""
    return [li for li, d in enumerate(spec.levels)
            if d.backend == KERNEL_BACKEND]


def build_engine(ucfg: UNetConfig, params: dict, batch: int, spec):
    """The async engine, with failures contained: a request whose plan
    overflows the pinned tile budget fails on its own instead of stopping
    the server."""
    return SceneEngine(ucfg, params, batch=batch, spec=spec, sync=False,
                       policy=AdmissionPolicy(max_retries=1,
                                              retry_backoff_ms=1.0))
