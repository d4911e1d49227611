"""Random weights from a seed, made on the device in one jitted call.

A flat dict of float32 arrays, one per name of the architecture's
``weight_shapes`` (``bench/plug.py``). Biases, norm scales and offsets are
drawn away from their identity values so that the check sees every term.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


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


def make_weights(seed: int, shapes: dict) -> dict:
    """Every weight of ``shapes`` (name -> shape), drawn from the seed."""
    spec = tuple(sorted((k, tuple(v)) for k, v in shapes.items()))
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed % (2 ** 32))
    key = jax.random.fold_in(key, seed // (2 ** 32))
    return _make(key, spec)
