"""Shared benchmark scaffolding: scene building + timing + CSV rows.

Timing is warmup + median-of-k wall clock (:func:`measure`)."""
from __future__ import annotations

import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import soar
from repro.core.hashgrid import build_neighbor_table, kernel_offsets
from repro.core.sparse_conv import submanifold_coir
from repro.data.scenes import make_scene
from repro.sparse.tensor import SparseVoxelTensor

ROWS: list[tuple[str, float, str]] = []


def emit(name: str, us_per_call: float, derived: str):
    ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.1f},{derived}")


@dataclass(frozen=True)
class Measurement:
    """One timed function: median and IQR spread of ``k`` samples (us)."""

    median_us: float
    spread_us: float
    k: int
    times_us: tuple = ()


def measure(fn, *args, warmup: int = 1, k: int = 5) -> Measurement:
    """Warmup + median-of-``k`` wall-clock of ``fn(*args)`` in us.

    Every call — warmup included — is ``jax.block_until_ready``'d, so
    async dispatch can't leak device time out of (or host time into) the
    sample. The median defeats one-off scheduler hiccups; ``spread_us``
    (interquartile range) is the noise floor callers can gate on.
    """
    for _ in range(max(int(warmup), 0)):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(max(int(k), 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e6)
    times.sort()
    spread = float(np.percentile(times, 75) - np.percentile(times, 25))
    return Measurement(float(np.median(times)), spread, len(times),
                       tuple(times))


def time_fn(fn, *args, iters=3, warmup=1, reps=1):
    """Median us/call over ``iters * reps`` timed calls (median is robust
    to background load on shared CI hosts)."""
    k = max(int(iters) * int(reps), 1)
    return measure(fn, *args, warmup=warmup, k=k).median_us


def build_scene(seed=0, resolution=48, capacity=16384):
    coords, feats, labels, mask = make_scene(seed, resolution, capacity)
    t = SparseVoxelTensor(jnp.asarray(coords), jnp.asarray(feats),
                          jnp.asarray(mask))
    return t, labels


def scene_metadata(t: SparseVoxelTensor, resolution: int):
    coir = submanifold_coir(t, resolution, 3)
    nbr = np.asarray(build_neighbor_table(
        t.coords, t.mask, jnp.asarray(kernel_offsets(3)), resolution))
    order = soar.soar_order(nbr, np.asarray(t.mask), 512)
    return coir, nbr, order


# -- standalone bench CLIs ---------------------------------------------------

def standalone_bench_main(run, module_name: str, quick_help: str,
                          description: str | None = None,
                          argv=None) -> None:
    """Shared ``main()`` for benches with their own CI smoke CLI
    (``--quick`` / ``--json``): one place owns the CSV header, timing and
    the ``bench-rows/v1`` JSON artifact schema."""
    import argparse
    import json
    import sys

    ap = argparse.ArgumentParser(description=description)
    ap.add_argument("--quick", action="store_true", help=quick_help)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as a JSON artifact (CI perf log)")
    args = ap.parse_args(argv)
    print("name,us_per_call,derived")
    t0 = time.time()
    run(quick=args.quick)
    total_s = time.time() - t0
    print(f"# total {total_s:.1f}s", file=sys.stderr)
    if args.json:
        payload = {
            "schema": "bench-rows/v1",
            "unix_time": int(t0),
            "total_seconds": round(total_s, 2),
            "modules": [module_name],
            "rows": [{"name": n, "us_per_call": u, "derived": d}
                     for n, u, d in ROWS],
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {len(payload['rows'])} rows to {args.json}",
              file=sys.stderr)

