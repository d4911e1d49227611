"""Deterministic mini-shim for the slice of hypothesis the suite uses.

The property tests prefer real hypothesis (CI installs it); on images
without it this shim keeps them running instead of dying at collection.
It draws ``max_examples`` pseudo-random samples per test from a seed
derived from the test name, biased toward the strategy boundaries (where
off-by-ones live). Supported surface: ``given``, ``example``, ``settings`` with
``max_examples``/``deadline``, and ``strategies.integers/floats/lists``.
"""
from __future__ import annotations

import inspect
import types
import zlib

import numpy as np

_DEFAULT_MAX_EXAMPLES = 25
_BOUNDARY_P = 0.15


class _Strategy:
    def __init__(self, draw):
        self._draw = draw

    def draw(self, rng):
        return self._draw(rng)


def _integers(min_value, max_value):
    def draw(rng):
        if rng.random() < _BOUNDARY_P:
            return int(min_value if rng.random() < 0.5 else max_value)
        return int(rng.integers(min_value, max_value + 1))

    return _Strategy(draw)


def _floats(min_value, max_value):
    def draw(rng):
        if rng.random() < _BOUNDARY_P:
            return float(min_value if rng.random() < 0.5 else max_value)
        # log-uniform when the range spans decades, like hypothesis explores
        if min_value > 0 and max_value / min_value > 1e3:
            lo, hi = np.log(min_value), np.log(max_value)
            return float(np.exp(rng.uniform(lo, hi)))
        return float(rng.uniform(min_value, max_value))

    return _Strategy(draw)


def _lists(elements, *, min_size=0, max_size=20):
    def draw(rng):
        n = int(rng.integers(min_size, max_size + 1))
        return [elements.draw(rng) for _ in range(n)]

    return _Strategy(draw)


strategies = types.SimpleNamespace(
    integers=_integers, floats=_floats, lists=_lists)


def settings(**kwargs):
    max_examples = kwargs.get("max_examples", _DEFAULT_MAX_EXAMPLES)

    def deco(fn):
        fn._max_examples = max_examples
        return fn

    return deco


def example(**drawn):
    """Pin one explicit case; ``given`` runs pinned cases first."""
    def deco(fn):
        fn._examples = [drawn] + getattr(fn, "_examples", [])
        return fn

    return deco


def given(*strats):
    def deco(fn):
        def wrapper(*args, **kwargs):
            for pinned in getattr(wrapper, "_examples", []):
                fn(*args, **pinned, **kwargs)
            n = getattr(wrapper, "_max_examples", _DEFAULT_MAX_EXAMPLES)
            rng = np.random.default_rng(zlib.crc32(fn.__name__.encode()))
            for i in range(n):
                drawn = tuple(s.draw(rng) for s in strats)
                try:
                    fn(*args, *drawn, **kwargs)
                except Exception as e:
                    raise AssertionError(
                        f"{fn.__name__} falsified on example {i}: "
                        f"{drawn!r}") from e

        # hide the strategy params from pytest's fixture resolution
        wrapper.__name__ = fn.__name__
        wrapper.__doc__ = fn.__doc__
        wrapper.__module__ = fn.__module__
        wrapper.__signature__ = inspect.Signature()
        wrapper._max_examples = getattr(fn, "_max_examples",
                                        _DEFAULT_MAX_EXAMPLES)
        wrapper._examples = getattr(fn, "_examples", [])
        return wrapper

    return deco
