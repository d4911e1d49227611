"""ExecutionContext: the object that owns mesh + backends + caches.

Pre-context, the engine's moving parts were loose globals — the backend
choice a string enum, the plan cache and scheduler knobs constructor
arguments scattered over call sites, and *no* home at all for a device
mesh. ``ExecutionContext`` bundles them:

* ``mesh`` / ``shard_axis`` — where sharded scene plans execute. ``None``
  (the default) means single-device: sharded plans still run, on the
  serial single-device reference path (``engine.shard``).
* ``registry`` — a scoped :class:`~repro.engine.backends.BackendRegistry`
  view chained to the process default, so per-context backend overlays
  never leak.
* ``plan_cache`` — the content-keyed :class:`~repro.engine.plan.PlanCache`
  serving layers share. Cache keys mix in :meth:`topology_key`, so a plan
  built for one mesh can never be served to another.
* scheduler wiring defaults (``sync`` / ``depth`` / ``planner_threads``)
  that ``serving`` engines pick up when built from a context.

Call sites pass ``ctx=`` to ``engine.sparse_conv`` / ``engine.apply_unet``
/ ``SceneEngine``; omitting it resolves the ambient context — either the
innermost ``use_context(...)`` block or the module-level default — so
pre-context call sites (and the deprecation shims) keep working unchanged.
"""
from __future__ import annotations

import contextlib
import contextvars
from dataclasses import dataclass, field

from repro.engine.backends import AUTO, Backend, BackendRegistry, default_registry
from repro.engine.plan import PlanCache


@dataclass
class ExecutionContext:
    """Mesh + backend registry + plan cache + scheduler defaults."""

    #: device mesh sharded scene plans execute on (None = single device)
    mesh: object | None = None
    #: mesh axis the scene capacity axis is sharded over
    shard_axis: str = "shard"
    #: scoped backend registry (chains to the process default)
    registry: BackendRegistry = field(
        default_factory=lambda: default_registry().view())
    #: content-keyed scene-plan cache (topology mixed into every key)
    plan_cache: PlanCache = field(default_factory=PlanCache)
    #: serving defaults picked up by engines built from this context
    sync: bool = True
    depth: int = 2
    planner_threads: int = 2
    #: default ``serving.AdmissionPolicy`` for engines built from this
    #: context (typed loosely so the engine layer doesn't import serving);
    #: None = FIFO admission
    admission: object | None = None

    def __post_init__(self):
        # a breaker trip reroutes new plan builds (engine.plan consults
        # registry.breakers), so plans cached under the old routing must
        # rotate out — the board's generation is repr'd into keys AND the
        # cache is invalidated eagerly on every breaker state change
        board = getattr(self.registry, "breakers", None)
        if board is not None:
            board.add_hook(self.plan_cache.invalidate)

    @property
    def n_shards(self) -> int:
        """Size of the shard axis (1 when no mesh / axis is absent)."""
        if self.mesh is None:
            return 1
        if self.shard_axis not in getattr(self.mesh, "axis_names", ()):
            return 1
        return int(self.mesh.shape[self.shard_axis])

    def topology_key(self) -> str:
        """Hashable description of the execution topology, mixed into plan
        cache keys: a plan built for one mesh/shard layout must never be
        served to another."""
        if self.mesh is None:
            return "host"
        axes = ",".join(
            f"{a}={self.mesh.shape[a]}" for a in self.mesh.axis_names)
        return f"mesh({axes})|shard_axis={self.shard_axis}"

    def resolve_backend(self, plan, backend: str = AUTO) -> str:
        """The backend name a call under this context will actually run."""
        return self.registry.resolve(plan, backend)

    def backend(self, name: str) -> Backend:
        return self.registry.get(name)


_DEFAULT: ExecutionContext | None = None
#: innermost use_context() override, if any
_ACTIVE: contextvars.ContextVar = contextvars.ContextVar(
    "repro_engine_active_ctx", default=None)


def default_context() -> ExecutionContext:
    """The module-level default context legacy call sites resolve to."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = ExecutionContext()
    return _DEFAULT


def set_default_context(ctx: ExecutionContext) -> ExecutionContext | None:
    """Replace the module-level default; returns the previous one."""
    global _DEFAULT
    prev, _DEFAULT = _DEFAULT, ctx
    return prev


def current_context() -> ExecutionContext:
    """The ambient context: innermost ``use_context`` block, else the
    module default."""
    active = _ACTIVE.get()
    return active if active is not None else default_context()


@contextlib.contextmanager
def use_context(ctx: ExecutionContext):
    """Make ``ctx`` the ambient context for the dynamic extent of the
    block (thread/task-local, like ``dist.hints.use_mesh``)."""
    token = _ACTIVE.set(ctx)
    try:
        yield ctx
    finally:
        _ACTIVE.reset(token)
