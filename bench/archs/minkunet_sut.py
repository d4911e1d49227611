"""The program's side of MinkUNet: its ``MinkUNetConfig``, the benchmark's
flat weights in the parameter tree of ``engine.apply_unet``, the pinned plan
spec and the serving engine. Loaded by ``bench/sut.py`` alone;
``minkunet.py`` is the yardstick's side.
"""
from __future__ import annotations

from repro import engine
from repro.core.sparse_conv import SparseConvParams
from repro.models.minkunet import MinkUNetConfig
from repro.serving.api import AdmissionPolicy
from repro.serving.scene_engine import SceneEngine


def model_config(cfg: dict) -> MinkUNetConfig:
    return MinkUNetConfig(name=cfg["name"], in_channels=cfg["input_features"],
                          n_classes=cfg["nClasses"], init_dim=cfg["init_dim"],
                          planes=tuple(cfg["planes"]),
                          layers=tuple(cfg["layers"]),
                          stem_kernel=cfg["stem_kernel"],
                          resolution=cfg["full_scale"],
                          capacity=cfg["capacity"])


def params(w: dict, cfg: dict) -> dict:
    """The benchmark's flat weights in the program's parameter tree (the
    same device arrays, but a projection's (C_in, C_out) slice)."""
    mcfg = model_config(cfg)

    def conv(p):
        return SparseConvParams(w[p + ".w"], None)

    def bn(p):
        return {"scale": w[p + ".scale"], "offset": w[p + ".offset"]}

    def block(p):
        out = {"conv1": conv(p + "conv1"), "bn1": bn(p + "bn1"),
               "conv2": conv(p + "conv2"), "bn2": bn(p + "bn2")}
        if p + "proj.w" in w:
            out["proj"] = w[p + "proj.w"][0]
            out["proj_bn"] = bn(p + "proj_bn")
        return out

    levels = []
    for li in range(mcfg.n_levels):
        lvl = {"enc": [block(f"l{li}.enc{b}.")
                       for b in range(mcfg.enc_stage(li)[1])]}
        if li < mcfg.n_levels - 1:
            lvl.update(down=conv(f"l{li}.down"), down_bn=bn(f"l{li}.down_bn"),
                       up=conv(f"l{li}.up"), up_bn=bn(f"l{li}.up_bn"),
                       dec=[block(f"l{li}.dec{b}.")
                            for b in range(mcfg.dec_stage(li)[1])])
        levels.append(lvl)
    return {"stem": conv("stem"), "stem_bn": bn("stem"), "levels": levels,
            "head": {"w": w["head.w"], "b": w["head.b"]}}


def pin_spec(mcfg: MinkUNetConfig, scenes: list):
    """The pinned plan spec (tile budgets, per-level and stem dispatch)
    from representative scenes; reference sites run the einsum."""
    return engine.build_plan_spec(scenes, mcfg, mem_budget=64 * 1024)


def kernel_sites(spec) -> set[tuple[str, int]]:
    """(site, level) of the convs that the spec sends to the fused kernel:
    a level's 3^3 block convs and the stem where its own dispatch does."""
    sites = {("sub", li) for li, d in enumerate(spec.levels)
             if d.backend == engine.SSPNNA}
    if spec.stem is not None and spec.stem.backend == engine.SSPNNA:
        sites.add(("stem", 0))
    return sites


def build_engine(mcfg: MinkUNetConfig, params: dict, batch: int, spec):
    """The async engine, with failures contained: a request whose plan
    overflows the pinned tile budget fails on its own instead of stopping
    the server."""
    return SceneEngine(mcfg, params, batch=batch, spec=spec, sync=False,
                       policy=AdmissionPolicy(max_retries=1,
                                              retry_backoff_ms=1.0))
