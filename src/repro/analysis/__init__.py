"""`repro.analysis` — static analysis over the repo's own invariants.

Four passes, one CLI (``python -m repro.analysis``), all returning
:class:`~repro.analysis.findings.Finding` lists:

* ``lint`` — repo-specific AST rules (deprecated shims, host syncs in
  hot paths, unnamed/non-daemon threads, contextvars on serving seams).
* ``concurrency`` — AST lock-graph extraction over ``src/repro`` checked
  against the documented global lock order
  (:data:`repro.analysis.runtime.LOCK_ORDER`), plus blocking-call-under-
  lock detection; the runtime counterpart is ``REPRO_LOCK_CHECK=1``.
* ``plan_check`` — structural validation of built
  ``ScenePlan``/``ShardedScenePlan``/``StreamPlanState`` objects: COIR
  bounds, SOAR/tile pair coverage, DMA table bounds, halo send tables,
  cache-key version/generation mixing.
* ``hlo_gates`` — compiled-artifact gates on top of
  ``launch.hlo_analysis``: forbidden-op sets, recompile budgets, modeled
  VMEM footprints.

Beside the passes, ``spans`` holds the runtime's one timing helper: spans
on the profiler's clock that the serving and plan layers' counters are
read from.

Submodules are imported lazily: lock-owning modules under ``src/repro``
import ``repro.analysis.runtime`` at module load, and the passes import
those same modules — eager imports here would cycle.
"""
from __future__ import annotations

from repro.analysis.findings import Finding, render

_SUBMODULES = ("concurrency", "findings", "hlo_gates", "lint",
               "plan_check", "runtime", "spans")

__all__ = ["Finding", "render", *_SUBMODULES]


def __getattr__(name: str):
    if name in _SUBMODULES:
        import importlib
        return importlib.import_module(f"repro.analysis.{name}")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
