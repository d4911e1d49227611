"""The system under test, as the benchmark drives it: the program's public
serving surface and nothing else.

This module, and the architectures' program sides that it loads
(``bench/archs/<arch>_sut.py``, ``bench/plug.py``), are the only ones of
the benchmark that import the program. The rest of the yardstick (traffic,
reference, work counts, trace reduction) does not.
"""
from __future__ import annotations

from pathlib import Path

import numpy as np

import plug
from repro.launch.compile_cache import use_compile_cache  # noqa: F401
from repro.serving.scene_engine import SceneRequest  # noqa: F401
from repro.sparse.tensor import SparseVoxelTensor


def program(cfg: dict, root: Path):
    """The program's side of the configuration's architecture."""
    return plug.arch(cfg, root, "_sut")


def scene(coords: np.ndarray, feats: np.ndarray, mask: np.ndarray):
    """A client's upload: host arrays, as a sensor or an app sends them."""
    return SparseVoxelTensor(coords, feats, mask)
