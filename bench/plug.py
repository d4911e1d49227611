"""Modules that the benchmark's data brings, loaded from their files.

A configuration's ``arch`` names two modules under ``bench/archs/``:

* ``<arch>.py``, the yardstick's side, imports nothing of the program:
  ``weight_shapes(cfg)`` -> {name: shape} of every weight, drawn by
  ``bench/weights.py`` (``.w`` a conv's (K, C_in, C_out) or a linear
  layer's (C_in, C_out), ``.scale`` a norm's scale, the rest biases and
  offsets); ``logits(weights, coords, feats, cfg, *, operand_dtype=None,
  store_dtype=None)``, the plain reference's float32 logits at
  ``highest`` precision of one scene's active voxels, built from
  ``bench/reference.py``'s tables and layers; ``scene_convs(coords,
  cfg)``, every conv of one forward pass as (site, level, FLOPs, bytes)
  (``bench/work.py``'s ``conv``).
* ``<arch>_sut.py``, the program's side, loaded by ``bench/sut.py`` alone:
  ``model_config(cfg)``; ``params(weights, cfg)``, the program's parameter
  tree of the flat weights; ``pin_spec(model_config, scenes)``;
  ``build_engine(model_config, params, batch, spec)``; and
  ``kernel_sites(spec)``, the (site, level) pairs whose convs the fused
  kernel runs.

A per-layer metric's reader is ``bench/metrics/<base>.py``, whose ``read``
takes the run's context.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path


@functools.cache
def load(path: Path):
    """The module of a file, once per path."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def arch(cfg: dict, root: Path, side: str = ""):
    """The module of the configuration's architecture: ``side`` ``""``
    for the yardstick's, ``"_sut"`` for the program's."""
    return load(root / "bench" / "archs" / f"{cfg['arch']}{side}.py")
