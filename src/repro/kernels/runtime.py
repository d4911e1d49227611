"""Shared Pallas execution-mode policy for every kernel wrapper.

The kernels in this package are written for the TPU Pallas lowering; on any
other backend they must run in interpret mode. ``resolve_interpret`` is the
single gate: an explicit ``True``/``False`` always wins, and the default
(``None``) compiles on TPU and interprets everywhere else. Nothing in the
environment can put a TPU into interpret mode.

The public kernel wrappers resolve *before* their jit boundary, so the jit
cache is keyed on the concrete mode. Long-lived jitted closures above them
(``SceneEngine._apply``, the LM engine's prefill/step) capture the resolved
mode at their own first trace — pass ``interpret=`` explicitly when
constructing them to choose another.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Resolve an ``interpret=`` knob to a concrete bool: an explicit
    ``True``/``False`` as-is, ``None`` compiled on TPU and interpreted on
    every other backend."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"
