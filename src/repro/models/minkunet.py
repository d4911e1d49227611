"""MinkUNet: MinkowskiEngine's residual sparse U-Net for 3D segmentation.

``MinkUNet34C`` of NVIDIA MinkowskiEngine ``examples/minkunet.py`` (Choy,
Gwak, Savarese, CVPR 2019), the standard ScanNet model at 2 cm voxels:

* a ``stem_kernel``^3 stem (``conv0p1s1``, 5^3) to ``init_dim`` channels,
  then BN and ReLU, on the input's own voxels;
* ``h = len(planes) // 2`` strided 2^3 convs down, each keeping its width
  and followed by BN and ReLU; level l (tensor stride 2^l) then runs
  ``layers[l-1]`` residual ``BasicBlock``s of ``planes[l-1]`` channels;
* from the coarsest level up, a 2^3 transposed conv onto the finer level's
  own voxels to ``planes[2h-1-l]`` channels, BN and ReLU,
  ``ME.cat(up, skip)``, then ``layers[2h-1-l]`` blocks of that width;
* a ``BasicBlock`` is conv3^3-BN-ReLU-conv3^3-BN plus the shortcut, then
  ReLU; where the width changes the shortcut is a 1x1 conv and BN;
* no conv has a bias but the 1x1 classifier ``final``.

The level walk is ``repro.engine.apply_unet``'s, shared with the SCN U-Net:
``init_minkunet`` lays the parameters out in its tree (residual blocks with
``conv1``/``conv2``, a ``stem_bn``, ``down_bn``/``up_bn`` per level, biases
``None``), and ``concat`` tells it the decoder's order.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, ClassVar

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sparse_conv import SparseConvParams


@dataclass(frozen=True)
class MinkUNetConfig:
    name: str = "minkunet34c"
    in_channels: int = 3
    n_classes: int = 20
    init_dim: int = 32
    planes: tuple[int, ...] = (32, 64, 128, 256, 256, 128, 96, 96)
    layers: tuple[int, ...] = (2, 3, 4, 6, 2, 2, 2, 2)
    stem_kernel: int = 5
    resolution: int = 384
    capacity: int = 131072
    dtype: Any = jnp.float32

    #: ``ME.cat(up, skip)``: the upsampled features come first
    concat: ClassVar[str] = "up_skip"

    def __post_init__(self):
        if len(self.planes) % 2 or len(self.planes) != len(self.layers):
            raise ValueError(
                f"planes {self.planes} and layers {self.layers} must hold "
                "one entry per encoder and decoder stage, as many of each")

    @property
    def n_levels(self) -> int:
        return len(self.planes) // 2 + 1

    @property
    def stem_width(self) -> int:
        return self.init_dim

    def enc_stage(self, li: int) -> tuple[int, int]:
        """(width, blocks) of level li's encoder; level 0 has the stem
        alone."""
        if li == 0:
            return self.init_dim, 0
        return self.planes[li - 1], self.layers[li - 1]

    def dec_stage(self, li: int) -> tuple[int, int]:
        """(width, blocks) of level li's decoder (li < n_levels - 1)."""
        j = len(self.planes) - 1 - li
        return self.planes[j], self.layers[j]

    @property
    def widths(self) -> tuple[int, ...]:
        """Each level's widest block width: its 3^3 convs' largest output
        width, which the plan spec sizes the level's kernel at."""
        out = []
        for li in range(self.n_levels):
            ws = [self.enc_stage(li)[0]] if self.enc_stage(li)[1] else []
            if li < self.n_levels - 1:
                ws.append(self.dec_stage(li)[0])
            out.append(max(ws))
        return tuple(out)

    def out_width(self, li: int) -> int:
        """Width of what level li hands up (or to the head, at level 0)."""
        if li == self.n_levels - 1:
            return self.enc_stage(li)[0]
        return self.dec_stage(li)[0]


def _conv(key, k: int, c_in: int, c_out: int, dtype) -> SparseConvParams:
    w = jax.random.normal(key, (k, c_in, c_out), dtype) / np.sqrt(k * c_in)
    return SparseConvParams(w, None)


def _bn(c: int, dtype) -> dict:
    return {"scale": jnp.ones((c,), dtype), "offset": jnp.zeros((c,), dtype)}


def basic_block_params(key, c_in: int, c_out: int, dtype) -> dict:
    """One ``BasicBlock``: ``conv1``/``bn1``, ``conv2``/``bn2`` and, where
    the width changes, the 1x1 ``proj`` (C_in, C_out) and ``proj_bn``."""
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"conv1": _conv(k1, 27, c_in, c_out, dtype), "bn1": _bn(c_out, dtype),
         "conv2": _conv(k2, 27, c_out, c_out, dtype), "bn2": _bn(c_out, dtype)}
    if c_in != c_out:
        p["proj"] = jax.random.normal(k3, (c_in, c_out), dtype) / np.sqrt(c_in)
        p["proj_bn"] = _bn(c_out, dtype)
    return p


def _stage(keys, blocks: int, c_in: int, width: int, dtype) -> list:
    return [basic_block_params(next(keys), c_in if b == 0 else width, width,
                               dtype) for b in range(blocks)]


def init_minkunet(key: jax.Array, cfg: MinkUNetConfig) -> dict:
    """Seeded parameters in ``engine.apply_unet``'s tree."""
    keys = iter(jax.random.split(key, 1024))
    dt, n = cfg.dtype, cfg.n_levels
    params: dict = {
        "stem": _conv(next(keys), cfg.stem_kernel ** 3, cfg.in_channels,
                      cfg.init_dim, dt),
        "stem_bn": _bn(cfg.init_dim, dt), "levels": []}
    for li in range(n):
        # the encoder's input: the stem, or the down conv, which keeps the
        # width of the level above
        c_in = cfg.init_dim if li == 0 else cfg.enc_stage(li - 1)[0]
        width, blocks = cfg.enc_stage(li)
        lvl = {"enc": _stage(keys, blocks, c_in, width, dt)}
        if li < n - 1:
            dec_w, dec_blocks = cfg.dec_stage(li)
            lvl["down"] = _conv(next(keys), 8, width, width, dt)
            lvl["down_bn"] = _bn(width, dt)
            lvl["up"] = _conv(next(keys), 8, cfg.out_width(li + 1), dec_w, dt)
            lvl["up_bn"] = _bn(dec_w, dt)
            lvl["dec"] = _stage(keys, dec_blocks, dec_w + width, dec_w, dt)
        params["levels"].append(lvl)
    c = cfg.out_width(0)
    params["head"] = {
        "w": jax.random.normal(next(keys), (c, cfg.n_classes), dt)
        / np.sqrt(c),
        "b": jnp.zeros((cfg.n_classes,), dt)}
    return params
