"""The program's side of the SCN U-Net: its ``UNetConfig``, the benchmark's
flat weights in its parameter tree, the pinned plan spec and the serving
engine. Loaded by ``bench/sut.py`` alone; ``scn_unet.py`` is the yardstick's
side.
"""
from __future__ import annotations

from repro import engine
from repro.core.sparse_conv import SparseConvParams
from repro.models.scn import UNetConfig
from repro.serving.api import AdmissionPolicy
from repro.serving.scene_engine import SceneEngine


def model_config(cfg: dict) -> UNetConfig:
    return UNetConfig(name=cfg["name"], in_channels=cfg["input_features"],
                      n_classes=cfg["nClasses"], widths=tuple(cfg["n_planes"]),
                      reps=cfg["block_reps"], resolution=cfg["full_scale"],
                      capacity=cfg["capacity"])


def params(w: dict, cfg: dict) -> dict:
    """The benchmark's flat weights in the program's parameter tree (no
    copies: the same device arrays)."""
    widths, reps = cfg["n_planes"], cfg["block_reps"]

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


def pin_spec(ucfg: UNetConfig, scenes: list):
    """The pinned plan spec (tile budgets, per-level dispatch) from
    representative scenes; ``None`` levels run the reference einsum."""
    return engine.build_plan_spec(scenes, ucfg, mem_budget=64 * 1024)


def kernel_sites(spec) -> set[tuple[str, int]]:
    """(site, level) of the convs that the spec sends to the fused kernel:
    a level's submanifold convs and, at level 0, the stem."""
    levels = [li for li, d in enumerate(spec.levels)
              if d.backend == engine.SSPNNA]
    return {("sub", li) for li in levels} | (
        {("stem", 0)} if 0 in levels else set())


def build_engine(ucfg: UNetConfig, params: dict, batch: int, spec):
    """The async engine, with failures contained: a request whose plan
    overflows the pinned tile budget fails on its own instead of stopping
    the server."""
    return SceneEngine(ucfg, params, batch=batch, spec=spec, sync=False,
                       policy=AdmissionPolicy(max_retries=1,
                                              retry_backoff_ms=1.0))
