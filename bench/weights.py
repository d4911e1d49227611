"""Random U-Net weights from a seed, made on the device in one jitted call.

A flat dict of float32 arrays: ``stem.{w,b}``, ``l<i>.enc<r>.{w,b,scale,
offset}``, ``l<i>.{down,up}.{w,b}``, ``l<i>.dec<r>.{w,b,scale,offset}`` and
``head.{w,b}``; conv weights are ``(K, C_in, C_out)`` in the plane order of
``bench/reference.py``. Biases, norm scales and offsets are drawn away from
their identity values so that the check sees every term.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def shapes(widths, reps: int, in_channels: int, n_classes: int) -> dict:
    """name -> shape of every weight."""
    s = {"stem.w": (27, in_channels, widths[0]), "stem.b": (widths[0],)}
    n = len(widths)
    for li, c in enumerate(widths):
        for r in range(reps):
            s[f"l{li}.enc{r}.w"] = (27, c, c)
            for k in ("b", "scale", "offset"):
                s[f"l{li}.enc{r}.{k}"] = (c,)
        if li + 1 < n:
            s[f"l{li}.down.w"] = (8, c, widths[li + 1])
            s[f"l{li}.down.b"] = (widths[li + 1],)
            s[f"l{li}.up.w"] = (8, widths[li + 1], c)
            s[f"l{li}.up.b"] = (c,)
            for r in range(reps):
                s[f"l{li}.dec{r}.w"] = (27, 2 * c if r == 0 else c, c)
                for k in ("b", "scale", "offset"):
                    s[f"l{li}.dec{r}.{k}"] = (c,)
    s["head.w"] = (widths[0], n_classes)
    s["head.b"] = (n_classes,)
    return s


@functools.partial(jax.jit, static_argnames=("spec",))
def _make(key, spec):
    """One normal draw for every weight, sliced and scaled per leaf."""
    sizes = [math.prod(shape) for _, shape in spec]
    z = jax.random.normal(key, (sum(sizes),), jnp.float32)
    out, at = {}, 0
    for (name, shape), n in zip(spec, sizes):
        x = z[at:at + n].reshape(shape)
        at += n
        if name.endswith(".w"):
            fan_in = shape[0] * shape[1] if len(shape) == 3 else shape[0]
            out[name] = x / jnp.sqrt(float(fan_in))
        elif name.endswith(".scale"):
            out[name] = 1.0 + 0.1 * x
        else:  # biases and norm offsets
            out[name] = 0.1 * x
    return out


def make_weights(seed: int, widths, reps: int, in_channels: int,
                 n_classes: int) -> dict:
    spec = tuple(sorted(shapes(widths, reps, in_channels, n_classes).items()))
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed % (2 ** 32))
    key = jax.random.fold_in(key, seed // (2 ** 32))
    return _make(key, spec)
