"""Backend registry: pluggable execution paths behind one dispatcher.

AccSS3D's co-design premise is that metadata and execution are decided
together — SPADE emits a *dataflow decision*, and the engine maps it onto an
execution path. Pre-registry, that mapping was a closed string enum and an
if/elif chain in ``engine.api``; every new path (sharded scenes, future
TPU-tuned kernels) meant editing the dispatcher. Now the seam is explicit:

* a ``Backend`` implements ``supports(plan)`` / ``run(x, params, plan,
  ctx=...)`` (and optionally ``run_unet`` for scene-level paths that own the
  whole forward, e.g. mesh-sharded execution);
* a ``BackendRegistry`` resolves the *name* recorded in a plan's
  ``Dispatch`` to an implementation, following each backend's declared
  ``fallback`` when a plan lacks what the backend needs (the classic case:
  an SSpNNA decision whose tile budget overflowed falls back to the
  reference einsum);
* registries chain: ``registry.view()`` makes a scoped child, so an
  ``ExecutionContext`` can overlay experimental backends without mutating
  the process-wide default registry.

``Dispatch``/SPADE emit backend *names*; nothing in the planner or the
dispatcher enumerates implementations, so a new backend registers from
anywhere (``engine.register_backend``) and is immediately routable.

**Circuit breakers.** Every registry carries a :class:`BreakerBoard`
(``registry.breakers``): per-backend :class:`CircuitBreaker` state
machines fed by the serving layer (``N`` consecutive dispatch failures
attributed to a backend trip it OPEN). A tripped breaker makes the
*planner* reroute new plans along the backend's declared ``fallback``
chain (``BreakerBoard.route``) — rerouting must happen at plan-build
time, not at ``resolve()`` time, because ``resolve`` runs inside jit
traces and its answer is baked into the compiled call. Each state change
bumps the board's ``generation``, which the plan-cache key mixes in (via
the board's ``repr``), so cached plans built for the old routing rotate
out; a hook (wired by ``ExecutionContext``) also invalidates the cache
eagerly. After ``cooldown_s`` the breaker goes HALF_OPEN and lets one
probe plan through; a success closes it, a failure re-opens it.
"""
from __future__ import annotations

import time

from repro.analysis.runtime import ordered_rlock
from repro.core.sparse_conv import reference_conv_cirf, reference_conv_ws
from repro.engine.plan import REFERENCE, SSPNNA, ConvPlan
from repro.kernels.sspnna.ops import run_sspnna_conv

AUTO = "auto"


def _fault_injector():
    """The ambient serving-layer fault injector, if any (lazy import so
    the engine layer has no hard dependency on serving)."""
    try:
        from repro.serving import faults
    except ImportError:  # pragma: no cover - serving always ships
        return None
    return faults.active()


# breaker states
CLOSED = "closed"
OPEN = "open"
HALF_OPEN = "half_open"


class CircuitBreaker:
    """Per-backend consecutive-failure circuit breaker.

    CLOSED counts consecutive failures; at ``failure_threshold`` it trips
    OPEN (the board stops routing plans to the backend). After
    ``cooldown_s`` the next ``allow()`` moves it HALF_OPEN, admitting one
    probe: ``record_success`` closes it again, ``record_failure``
    re-opens it (and restarts the cooldown). ``clock`` is injectable for
    tests. Not thread-safe on its own — :class:`BreakerBoard` serializes
    access.
    """

    def __init__(self, name: str, *, failure_threshold: int = 5,
                 cooldown_s: float = 1.0, clock=time.monotonic):
        if failure_threshold < 1:
            raise ValueError(
                f"failure_threshold must be >= 1, got {failure_threshold}")
        self.name = name
        self.failure_threshold = failure_threshold
        self.cooldown_s = cooldown_s
        self._clock = clock
        self.state = CLOSED
        self.consecutive_failures = 0
        self.trips = 0           # total CLOSED/HALF_OPEN -> OPEN transitions
        self._opened_at: float | None = None

    def allow(self) -> bool:
        """May a *new plan* route to this backend right now? OPEN flips
        to HALF_OPEN (one probe allowed) once the cooldown has passed."""
        if self.state == OPEN:
            if (self._opened_at is not None
                    and self._clock() - self._opened_at >= self.cooldown_s):
                self.state = HALF_OPEN
                return True
            return False
        return True

    def record_failure(self) -> bool:
        """Count one attributed failure; returns True when the breaker
        state changed (tripped or re-opened)."""
        self.consecutive_failures += 1
        if self.state == HALF_OPEN or (
                self.state == CLOSED
                and self.consecutive_failures >= self.failure_threshold):
            self.state = OPEN
            self.trips += 1
            self._opened_at = self._clock()
            return True
        return False

    def record_success(self) -> bool:
        """Count one success; returns True when the state changed (a
        HALF_OPEN probe succeeded and the breaker closed)."""
        self.consecutive_failures = 0
        if self.state == HALF_OPEN:
            self.state = CLOSED
            self._opened_at = None
            return True
        return False

    def snapshot(self) -> dict:
        return {"state": self.state,
                "consecutive_failures": self.consecutive_failures,
                "trips": self.trips}

    def __repr__(self):
        return (f"<CircuitBreaker {self.name!r} {self.state} "
                f"fails={self.consecutive_failures}>")


class BreakerBoard:
    """All circuit breakers of one registry, plus the routing logic.

    ``record_failure``/``record_success`` are fed by the serving layer
    with backend *names* (lazily creating breakers on first failure).
    ``route(name)`` is consulted by the planner: it follows the
    registry's fallback chain past backends whose breaker is not
    ``allow()``-ing traffic. Every state change bumps ``generation`` —
    mixed into plan-cache keys through ``repr(board)`` — and fires the
    registered hooks (``ExecutionContext`` wires
    ``plan_cache.invalidate`` here).
    """

    def __init__(self, registry: "BackendRegistry", *,
                 failure_threshold: int = 5, cooldown_s: float = 1.0,
                 clock=time.monotonic):
        self._registry = registry
        self.failure_threshold = failure_threshold
        self.cooldown_s = cooldown_s
        self._clock = clock
        self.generation = 0
        self._breakers: dict[str, CircuitBreaker] = {}
        self._hooks: list = []
        self._lock = ordered_rlock("breakers")

    def configure(self, *, failure_threshold: int | None = None,
                  cooldown_s: float | None = None) -> "BreakerBoard":
        """Adjust defaults for breakers created after this call."""
        with self._lock:
            if failure_threshold is not None:
                self.failure_threshold = failure_threshold
            if cooldown_s is not None:
                self.cooldown_s = cooldown_s
        return self

    def add_hook(self, hook) -> None:
        """``hook()`` fires (outside the lock) on every generation bump."""
        self._hooks.append(hook)

    def breaker(self, name: str) -> CircuitBreaker:
        with self._lock:
            br = self._breakers.get(name)
            if br is None:
                br = CircuitBreaker(
                    name, failure_threshold=self.failure_threshold,
                    cooldown_s=self.cooldown_s, clock=self._clock)
                self._breakers[name] = br
            return br

    def _bump(self) -> None:
        for hook in list(self._hooks):
            try:
                hook()
            except Exception:
                pass  # observers must not take down serving

    def record_failure(self, name: str) -> bool:
        """Attribute one failure to ``name``; True if its breaker state
        changed (hooks fire and the generation bumps)."""
        with self._lock:
            changed = self.breaker(name).record_failure()
            if changed:
                self.generation += 1
        if changed:
            self._bump()
        return changed

    def record_success(self, name: str) -> bool:
        with self._lock:
            br = self._breakers.get(name)
            changed = br.record_success() if br is not None else False
            if changed:
                self.generation += 1
        if changed:
            self._bump()
        return changed

    def allow(self, name: str) -> bool:
        """True unless ``name`` has a tripped (still-cooling) breaker.
        Doesn't create breakers: unknown names are allowed."""
        with self._lock:
            br = self._breakers.get(name)
            return True if br is None else br.allow()

    def route(self, name: str) -> str:
        """The backend new plans should target: ``name`` itself when its
        breaker admits traffic, else the first allowed backend along the
        registry's fallback chain (cycle-safe; the chain's last resort is
        returned even when itself blocked — something must serve)."""
        with self._lock:
            seen = set()
            current = name
            while current not in seen:
                seen.add(current)
                br = self._breakers.get(current)
                if br is None or br.allow():
                    return current
                try:
                    impl = self._registry.get(current)
                except ValueError:
                    return current
                if impl.fallback is None:
                    return current
                current = impl.fallback
            return current

    def states(self) -> dict:
        """Snapshot for ``health()``: name -> breaker state dict."""
        with self._lock:
            return {n: b.snapshot() for n, b in self._breakers.items()}

    def __repr__(self):
        # repr participates in plan-cache keys: the generation is the
        # only state that must rotate them
        return f"<BreakerBoard gen={self.generation}>"


class Backend:
    """One execution path for plan-driven sparse convolution.

    Subclasses set ``name`` (the registry key ``Dispatch.backend`` refers
    to), optionally ``plan_requirements`` (plan attributes that must be
    non-None for ``run`` to serve the plan) and ``fallback`` (the registry
    name resolution degrades to when ``supports`` says no).

    ``run`` executes one conv site. Scene-level backends (which own the
    whole U-Net forward, e.g. mesh-sharded execution) additionally
    implement ``run_unet``; ``engine.apply_unet`` routes plans that carry a
    ``scene_backend`` attribute there instead of walking levels itself.
    """

    name: str = ""
    #: plan attributes that must be present (non-None) for run() to work
    plan_requirements: tuple[str, ...] = ()
    #: registry name to resolve to instead when supports() is False
    fallback: str | None = None
    #: True for backends that execute whole scenes via run_unet
    scene_level: bool = False

    def supports(self, plan) -> bool:
        return all(getattr(plan, req, None) is not None
                   for req in self.plan_requirements)

    def run(self, x, params, plan: ConvPlan, *, ctx, **kw):
        raise NotImplementedError(
            f"backend {self.name!r} does not implement per-conv run()")

    def run_unet(self, params, feats, plan, *, ctx, **kw):
        raise NotImplementedError(
            f"backend {self.name!r} does not implement scene-level run_unet()")

    def __repr__(self):
        return f"<{type(self).__name__} {self.name!r}>"


class BackendRegistry:
    """Name -> Backend mapping with parent chaining and fallback resolution.

    Lookup walks ``self`` then ``parent``; registration always writes to
    ``self``, so a ``view()`` child can shadow or extend the process
    default without mutating it (an ``ExecutionContext`` holds such a
    view).
    """

    def __init__(self, parent: "BackendRegistry | None" = None):
        self._impls: dict[str, Backend] = {}
        self._parent = parent
        #: per-registry circuit breakers (views get their own board, so
        #: a context's breaker trips stay scoped to that context)
        self.breakers = BreakerBoard(self)

    def register(self, name: str, impl: Backend, *,
                 overwrite: bool = False) -> Backend:
        if not name or name == AUTO:
            raise ValueError(f"invalid backend name {name!r}")
        if not overwrite and name in self:
            raise ValueError(
                f"backend {name!r} already registered; pass overwrite=True "
                "to replace it")
        if not callable(getattr(impl, "run", None)):
            raise TypeError(f"backend impl {impl!r} has no run() hook")
        self._impls[name] = impl
        return impl

    def unregister(self, name: str) -> None:
        """Remove a registration made on *this* registry (not the parent)."""
        self._impls.pop(name, None)

    def get(self, name: str) -> Backend:
        reg: BackendRegistry | None = self
        while reg is not None:
            impl = reg._impls.get(name)
            if impl is not None:
                return impl
            reg = reg._parent
        raise ValueError(
            f"backend {name!r} not one of {(AUTO,) + self.names()}")

    def names(self) -> tuple[str, ...]:
        seen: dict[str, None] = {}
        reg: BackendRegistry | None = self
        while reg is not None:
            for n in reg._impls:
                seen.setdefault(n)
            reg = reg._parent
        return tuple(sorted(seen))

    def __contains__(self, name: str) -> bool:
        reg: BackendRegistry | None = self
        while reg is not None:
            if name in reg._impls:
                return True
            reg = reg._parent
        return False

    def view(self) -> "BackendRegistry":
        """A scoped child registry: reads chain to this one, writes stay
        local. This is what a fresh ``ExecutionContext`` holds."""
        return BackendRegistry(parent=self)

    def resolve(self, plan, backend: str = AUTO) -> str:
        """The backend name a call will actually run.

        ``"auto"`` reads the name the planner recorded in
        ``plan.dispatch``; a backend that can't serve the plan degrades
        along its declared ``fallback`` chain (e.g. SSpNNA without tile
        metadata -> reference).
        """
        if backend == AUTO:
            backend = plan.dispatch.backend
        inj = _fault_injector()
        if inj is not None:
            inj.maybe_fail("backend_resolve", key=backend)
        impl = self.get(backend)  # raises ValueError on unknown names
        seen = {backend}
        while not impl.supports(plan):
            if impl.fallback is None or impl.fallback in seen:
                raise ValueError(
                    f"backend {backend!r} cannot serve this plan and "
                    "declares no (acyclic) fallback")
            backend = impl.fallback
            seen.add(backend)
            impl = self.get(backend)
        return backend


class ReferenceBackend(Backend):
    """Gather + one fused einsum over all weight planes — the coarse M-V
    dispatch and the numerical oracle (``core.sparse_conv``). A plan the
    planner sent here on a weight-stationary walk (``"WS"``: a site SPADE
    tiled for the kernel, which the kernel's VMEM cannot hold) walks the
    planes one at a time instead."""

    name = REFERENCE

    def run(self, x, params, plan: ConvPlan, *, ctx, **kw):
        del ctx, kw  # kernel knobs don't apply to the einsum path
        if plan.dispatch.backend == REFERENCE and plan.dispatch.walk == "WS":
            return reference_conv_ws(x, plan.coir, params)
        return reference_conv_cirf(x, plan.coir, params)


class SSpNNABackend(Backend):
    """The fused gather-GEMM-scatter Pallas path driven by the plan's
    ``TileArrays`` (see ``kernels.sspnna``); plans without tile metadata
    (resolution-changing convs, tile-budget overflows) fall back to
    reference."""

    name = SSPNNA
    plan_requirements = ("tiles",)
    fallback = REFERENCE

    def run(self, x, params, plan: ConvPlan, *, ctx,
            use_kernel: bool = True, interpret: bool | None = None,
            block_n: int | None = None, **kw):
        del ctx, kw
        raw = run_sspnna_conv(
            x, params.weight, plan.tiles.out_rows, plan.tiles.in_rows,
            plan.tiles.local_idx, n_out=plan.coir.mask.shape[0],
            pair_counts=plan.tiles.pair_counts,
            use_kernel=use_kernel, interpret=interpret,
            block_n=block_n or (plan.dispatch.block_n or None))
        out = raw.astype(x.dtype)
        if params.bias is not None:
            out = out + params.bias.astype(x.dtype)
        return out * plan.coir.mask[:, None].astype(out.dtype)


_DEFAULT_REGISTRY: BackendRegistry | None = None


def default_registry() -> BackendRegistry:
    """The process-wide registry ``reference``/``sspnna`` (and ``sharded``,
    registered by ``engine.shard`` on import) live on."""
    global _DEFAULT_REGISTRY
    if _DEFAULT_REGISTRY is None:
        _DEFAULT_REGISTRY = BackendRegistry()
        _DEFAULT_REGISTRY.register(REFERENCE, ReferenceBackend())
        _DEFAULT_REGISTRY.register(SSPNNA, SSpNNABackend())
    return _DEFAULT_REGISTRY


def register_backend(name: str, impl: Backend, *,
                     overwrite: bool = False) -> Backend:
    """Register an execution backend process-wide.

    After this, any plan whose ``Dispatch.backend`` names ``name`` (or any
    explicit ``backend=name`` call) routes to ``impl`` — no engine code
    changes needed. Scoped alternative: register on
    ``ExecutionContext.registry`` to confine the backend to one context.
    """
    return default_registry().register(name, impl, overwrite=overwrite)
