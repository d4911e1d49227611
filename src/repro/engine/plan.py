"""Scene plans: the engine's unit of metadata building and caching.

A ``ScenePlan`` is everything the paper builds *before* running a layer,
bundled per input scene: per-level COIR metadata (the AdMAC pass), the SOAR
permutation, the SPADE-selected dataflow, and the tile metadata the SSpNNA
kernel consumes. It is a jax pytree — array leaves (COIR blocks, tile
tables) are traced, while the per-conv ``Dispatch`` decision rides in the
treedef as static aux data, so forcing a different backend or tile shape is
a (cached) recompile and everything else is a cache hit.

Two plan-building modes:

* **adaptive** (``spec=None``): full SPADE ``explore`` per level on this
  scene's own sparsity attributes — the paper's input-specific (JSA) flow.
  Tile counts match the scene, so plans for different scenes may differ in
  shape/static signature.
* **pinned** (``spec=build_plan_spec(...)``): dataflow decisions and tile
  counts are frozen from representative scenes (the offline/MSA flow,
  §V-C). Every plan built from one spec shares its jit signature — this is
  what ``serving.scene_engine`` batches through a single compilation.
"""
from __future__ import annotations

import hashlib
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, replace
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.hlo_gates import gate_vmem_budget
from repro.core import spade
from repro.core.coir import COIR
from repro.core.hashgrid import kernel_offsets
from repro.core.host_meta import (
    StreamMetaState,
    build_cirf_np,
    down_up_coirs_np,
    downsample_coords_np,
)
from repro.core.soar import raster_order, soar_order
from repro.analysis.runtime import ordered_condition, ordered_lock
from repro.analysis.spans import span
from repro.core.tiles import build_tile_plan, dma_tile_tables, max_tiles
from repro.kernels.sspnna.sspnna import allocated_widths
from repro.sparse.tensor import SparseVoxelTensor

REFERENCE = "reference"
SSPNNA = "sspnna"

# Layout version of the plan's array leaves; mixed into every PlanCache key
# so cached plans from an older table layout can never be served to a kernel
# expecting the new one. v2: TileArrays carries DMA-table-layout rows plus
# pair_counts for the fused kernel's dead-tile skip. v3: keys additionally
# carry the execution topology (mesh axes + shard layout), so a plan built
# for one mesh can never be served to another. v4: plan builds may consult
# circuit breakers (``breakers=`` build_kw, whose repr carries the board
# generation) and reroute dispatch away from tripped backends. v5: level 0
# may carry a stem site of its own kernel (``LevelPlan.stem``), and a COIR of
# more than 32 planes packs several bitmask words per row.
_PLAN_VERSION = 5


def _fault_injector():
    """The ambient serving-layer fault injector, if any (lazy import so
    the engine layer has no hard dependency on serving)."""
    try:
        from repro.serving import faults
    except ImportError:  # pragma: no cover - serving always ships
        return None
    return faults.active()


@dataclass(frozen=True)
class Dispatch:
    """Static per-conv execution decision (hashable -> jit aux data)."""

    backend: str = REFERENCE
    flavor: str = "CIRF"
    walk: str = "OS"
    delta_o: int = 0
    delta_i: int = 0
    n_tiles: int = 0
    block_n: int = 0  # pinned kernel N-block (0 = full N); see autotune_block_n


REFERENCE_DISPATCH = Dispatch()


class TileArrays(NamedTuple):
    """Device-side tile metadata in DMA-table layout
    (``core.tiles.dma_tile_tables``): ``in_rows`` pad slots are clamped to a
    safe source row, ``out_rows`` pad slots point at the trash row ``n_out``,
    and ``pair_counts`` is the fused kernel's dead-tile predicate."""

    out_rows: jax.Array     # (T, dO) int32, pads -> n_out (trash row)
    in_rows: jax.Array      # (T, dI) int32, pads clamped to 0
    local_idx: jax.Array    # (T, dO, K) int32, -1 holes
    pair_counts: jax.Array  # (T,) int32; 0 => dead tile


@jax.tree_util.register_pytree_node_class
@dataclass
class ConvPlan:
    """Plan for one conv site: COIR metadata + optional tile metadata."""

    coir: COIR
    tiles: TileArrays | None = None
    dispatch: Dispatch = REFERENCE_DISPATCH

    def tree_flatten(self):
        return (self.coir, self.tiles), self.dispatch

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux)


class LevelPlan(NamedTuple):
    """One U-Net level: active set + its conv sites."""

    coords: jax.Array
    mask: jax.Array
    sub: ConvPlan           # submanifold 3^3 metadata at this level
    down: ConvPlan | None   # strided 2^3 s2 conv to the next level
    up: ConvPlan | None     # transposed conv back to this level
    #: level 0 only: a stem whose kernel differs from the level's 3^3
    #: (MinkUNet's 5^3); None where the stem reads ``sub`` (SCN)
    stem: ConvPlan | None = None


@jax.tree_util.register_pytree_node_class
@dataclass
class ScenePlan:
    """Per-scene execution plan. ``stats`` is host-only diagnostics (ARF,
    chosen dataflows, tile fill) and is dropped across jit boundaries."""

    levels: tuple[LevelPlan, ...]
    stats: list[dict] | None = None

    @property
    def n_levels(self) -> int:
        return len(self.levels)

    def device_upload(self) -> "ScenePlan":
        """Device copy of a host-built plan (``PlanCache`` memoizes this;
        plan types with different leaves override it)."""
        return upload_scene_plan(self)

    def tree_flatten(self):
        return (tuple(self.levels),), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(children[0], None)


@dataclass(frozen=True)
class PlanSpec:
    """Pinned per-level dispatch decisions: every plan built from one spec
    has the same treedef and static shapes (one jit signature). ``stem`` is
    the stem site's decision where the config's stem has a plan of its own
    (``stem_site``), else None."""

    levels: tuple[Dispatch, ...]
    stem: Dispatch | None = None


@dataclass(frozen=True)
class SignatureFamily:
    """A small family of pinned jit signatures: voxel-capacity buckets.

    Single-signature serving pads every scene to one capacity — great for
    compilation count, wasteful under heavy mixed-size traffic (a 300-voxel
    scan pays a 4096-voxel wave). A ``SignatureFamily`` is the middle
    ground: a handful of capacity tiers chosen from *observed* request
    sizes (the TorchSparse measured-over-modeled philosophy), each tier its
    own pinned ``PlanSpec``/jit signature. The serving engine compiles each
    bucket's signature on first use, so total compilations are bounded by
    ``n_buckets`` — and warm single-size traffic still compiles exactly 1.

    ``capacities`` must be ascending; ``specs`` pairs each capacity with a
    pinned :class:`PlanSpec` (or ``None`` for the always-single-signature
    reference plan at that capacity).
    """

    capacities: tuple[int, ...]
    specs: tuple[PlanSpec | None, ...] = ()

    def __post_init__(self):
        if not self.capacities:
            raise ValueError("SignatureFamily needs at least one capacity")
        if list(self.capacities) != sorted(set(self.capacities)):
            raise ValueError(
                f"capacities must be ascending+unique, got {self.capacities}")
        if not self.specs:
            object.__setattr__(
                self, "specs", (None,) * len(self.capacities))
        if len(self.specs) != len(self.capacities):
            raise ValueError(
                f"{len(self.specs)} specs for {len(self.capacities)} buckets")

    @property
    def n_buckets(self) -> int:
        return len(self.capacities)

    @property
    def max_capacity(self) -> int:
        return self.capacities[-1]

    def bucket_for(self, n_voxels: int) -> int | None:
        """Smallest bucket capacity fitting ``n_voxels`` active voxels;
        None when the scene exceeds every bucket (callers shed it)."""
        for cap in self.capacities:
            if n_voxels <= cap:
                return cap
        return None

    def spec_for(self, capacity: int) -> PlanSpec | None:
        return self.specs[self.capacities.index(capacity)]


def choose_buckets(sizes, max_buckets: int = 4, *,
                   quantum: int = 64) -> tuple[int, ...]:
    """Capacity tiers from observed request sizes (active-voxel counts).

    Quantile cuts over the observed distribution, rounded up to ``quantum``
    multiples and deduplicated — so dense regions of the size distribution
    get finer tiers and the top tier always covers the largest observed
    scene. Returns ascending capacities, at most ``max_buckets`` of them.
    """
    sizes = [int(s) for s in sizes]
    if not sizes:
        raise ValueError("choose_buckets needs at least one observed size")
    if max_buckets < 1:
        raise ValueError(f"max_buckets must be >= 1, got {max_buckets}")
    arr = np.sort(np.asarray(sizes))
    qs = np.linspace(0.0, 1.0, max_buckets + 1)[1:]
    caps = sorted({
        int(np.ceil(float(np.quantile(arr, q)) / quantum)) * quantum
        for q in qs})
    return tuple(caps)


def build_signature_family(
    scenes: list[SparseVoxelTensor],
    cfg,
    *,
    max_buckets: int = 4,
    quantum: int = 64,
    pin_specs: bool = True,
    **spec_kw,
) -> SignatureFamily:
    """Freeze a bucket family from representative scenes.

    Buckets come from the scenes' active-voxel counts (``choose_buckets``);
    with ``pin_specs=True`` each bucket gets its own offline-SPADE
    ``PlanSpec`` built from the representative scenes that fit it,
    compacted to the bucket capacity (``spec_kw`` forwards to
    ``build_plan_spec``). Buckets no representative scene fits keep
    ``spec=None`` (reference plans — still one signature per bucket).
    """
    from dataclasses import replace

    from repro.sparse.tensor import compact_to_capacity

    sizes = [int(np.asarray(t.mask).sum()) for t in scenes]
    caps = choose_buckets(sizes, max_buckets, quantum=quantum)
    specs: list[PlanSpec | None] = []
    for cap in caps:
        reps = [compact_to_capacity(t, cap)[0]
                for t, n in zip(scenes, sizes) if n <= cap]
        if pin_specs and reps:
            specs.append(build_plan_spec(reps, replace(cfg, capacity=cap),
                                         **spec_kw))
        else:
            specs.append(None)
    return SignatureFamily(caps, tuple(specs))


# ---------------------------------------------------------------------------
# Scene keys + plan cache
# ---------------------------------------------------------------------------

def scene_key(t: SparseVoxelTensor, tag: str = "") -> str:
    """Content hash of a scene's active geometry (features don't change the
    plan, so they are deliberately excluded)."""
    h = hashlib.sha1()
    h.update(np.asarray(t.coords).tobytes())
    h.update(np.asarray(t.mask).tobytes())
    h.update(tag.encode())
    return h.hexdigest()


class PlanCache:
    """Thread-safe LRU cache of ScenePlans keyed by scene content + config.

    Concurrent ``get_or_build`` calls for the same scene coalesce: the first
    caller builds (outside the lock), everyone else waits on a per-key event
    and returns the same plan object. Each entry holds the host-side plan
    (numpy leaves, what planner threads produce) and a lazily uploaded
    device copy — ``device=True`` (the default) returns the device plan,
    ``device=False`` the host plan, so an async pipeline can run the heavy
    numpy pass in a worker thread and defer the upload to dispatch time.

    If a build raises, the key is released and the failure propagates to
    every waiter coalesced on it (each raises the builder's exception
    instead of silently re-building); callers arriving *after* the
    failure start a fresh build — a poisoned scene never wedges the
    cache, and a transient failure never poisons the key.

    ``max_entries`` bounds the number of cached entries with LRU eviction
    (host *and* memoized device copies go together, so a long-running
    stream whose geometry drifts — every frame a fresh key — cannot leak
    plan entries without bound). It defaults to ``capacity`` so existing
    behavior is unchanged; pass a smaller value to tighten memory.
    """

    def __init__(self, capacity: int = 128, *,
                 max_entries: int | None = None):
        self.capacity = capacity
        self.max_entries = capacity if max_entries is None else int(max_entries)
        if self.max_entries < 1:
            raise ValueError(f"max_entries must be >= 1, got {max_entries}")
        self._plans: OrderedDict[str, dict] = OrderedDict()
        # key -> {"ev": Event, "error": BaseException | None}; the error
        # is set before the event so coalesced waiters see the failure
        self._building: dict[str, dict] = {}
        self._lock = ordered_lock("plan_cache")
        self.hits = 0
        self.misses = 0
        self.invalidations = 0

    def invalidate(self) -> int:
        """Drop every cached entry (in-flight builds are unaffected: they
        insert fresh entries when they land). This is the autotune
        winner-flip hook — ``engine.autotune.CostTable`` fires it when a
        measured winner changes, so plans keyed under the old decision are
        rebuilt instead of served stale. Returns the number of entries
        dropped."""
        with self._lock:
            n = len(self._plans)
            self._plans.clear()
            self.invalidations += 1
        return n

    @staticmethod
    def _resolve(entry: dict, device: bool) -> ScenePlan:
        """Host plan, or the memoized device upload (done outside the global
        lock so planner threads never stall behind an upload)."""
        if not device:
            return entry["host"]
        if entry["device"] is None:
            with entry["dev_lock"]:
                if entry["device"] is None:
                    entry["device"] = entry["host"].device_upload()
        return entry["device"]

    def key_for(self, t: SparseVoxelTensor, cfg, *, topology: str | None = None,
                **build_kw) -> str:
        """Cache key for scene ``t`` under ``cfg`` + build mode: the same
        geometry under a different config/spec is a different plan. The key
        is an O(V) content hash — callers on a hot path should compute it
        once and pass it back via ``key=``. The table-layout version is
        mixed in so a layout bump invalidates every previously cached plan,
        and ``topology`` (``ExecutionContext.topology_key()``: mesh axes +
        shard axis) is mixed in so a plan built for one mesh topology is
        never served to another — sharded plans embed mesh-shaped halo
        tables that would silently misroute rows on a different mesh."""
        tag = (f"v{_PLAN_VERSION}|top={topology}|{cfg!r}|"
               f"{sorted(build_kw.items())!r}")
        with span("plan.key"):
            return scene_key(t, tag)

    def get_or_build(self, t: SparseVoxelTensor, cfg, *, device: bool = True,
                     key: str | None = None, topology: str | None = None,
                     builder=None, **build_kw) -> ScenePlan:
        """Return the plan for scene ``t`` under ``cfg``, building at most
        once across threads (concurrent callers for the same key coalesce
        onto one build). ``key`` skips re-hashing when the caller already
        holds ``key_for(t, cfg, topology=..., **build_kw)``. ``builder``
        swaps the host plan builder (default ``build_scene_plan_host``;
        sharded serving passes ``engine.shard``'s) — callers must route
        distinguishing builder config through ``build_kw``/``topology`` so
        different builders never collide on a key."""
        if builder is None:
            builder = build_scene_plan_host
        if key is None:
            key = self.key_for(t, cfg, topology=topology, **build_kw)
        while True:
            with self._lock:
                entry = self._plans.get(key)
                if entry is not None:
                    self.hits += 1
                    self._plans.move_to_end(key)
                else:
                    rec = self._building.get(key)
                    if rec is None:  # this thread builds
                        rec = {"ev": threading.Event(), "error": None}
                        self._building[key] = rec
                        break
            if entry is not None:
                return self._resolve(entry, device)
            rec["ev"].wait()  # another thread is building this plan
            err = rec["error"]
            if err is not None:
                # the build we coalesced onto failed: every waiter gets
                # the builder's exception (a caller arriving after the
                # key was released starts a fresh build instead)
                raise err
            # build landed: loop re-checks the cache
        try:
            inj = _fault_injector()
            if inj is not None:
                inj.maybe_fail("plan_build", key=key)
            host = builder(t, cfg, **build_kw)
        except BaseException as e:
            with self._lock:
                self._building.pop(key, None)
            rec["error"] = e
            rec["ev"].set()
            raise
        entry = {"host": host, "device": None,
                 "dev_lock": ordered_lock("plan_cache.dev")}
        with self._lock:
            self.misses += 1
            self._plans[key] = entry
            while len(self._plans) > self.max_entries:
                self._plans.popitem(last=False)
            self._building.pop(key, None)
            rec["ev"].set()
        return self._resolve(entry, device)

    def adopt(self, key: str, host_plan: ScenePlan, *,
              device: bool = True) -> ScenePlan:
        """Fetch the cache entry at ``key`` (from ``key_for``) for an
        already-built host plan, re-inserting ``host_plan`` if the entry was
        evicted in the meantime — never rebuilds, never re-hashes, never
        counts. This is the dispatch-stage path: the plan stage built (and
        counted) the plan; dispatch just needs the memoized device copy even
        if LRU pressure evicted the entry between stages."""
        with self._lock:
            entry = self._plans.get(key)
            if entry is not None:
                self._plans.move_to_end(key)
            else:
                entry = {"host": host_plan, "device": None,
                         "dev_lock": ordered_lock("plan_cache.dev")}
                self._plans[key] = entry
                while len(self._plans) > self.max_entries:
                    self._plans.popitem(last=False)
        return self._resolve(entry, device)

    def __len__(self) -> int:
        return len(self._plans)


# ---------------------------------------------------------------------------
# Plan building
# ---------------------------------------------------------------------------

def level_geometry(t: SparseVoxelTensor, cfg) -> list[tuple]:
    """(coords, mask, resolution) of each U-Net pyramid level, as numpy.

    ``cfg`` is any UNet-like config exposing ``resolution`` and ``widths``
    (``models.scn.UNetConfig`` satisfies this; the engine takes the duck
    type to avoid depending on the model zoo). Runs entirely on the host —
    part of the plan pass an async pipeline keeps off the device."""
    out = []
    with span("plan.geometry"):
        coords, mask, res = np.asarray(t.coords), np.asarray(t.mask), cfg.resolution
        for li in range(len(cfg.widths)):
            out.append((coords, mask, res))
            if li < len(cfg.widths) - 1:
                coords, mask = downsample_coords_np(coords, mask, res, 2)
                res //= 2
    return out


def stem_site(cfg) -> tuple[int, int, int] | None:
    """``(kernel size, C_in, C_out)`` of the config's stem where it has a
    plan of its own: a stem kernel other than the levels' 3^3 (MinkUNet's
    5^3 ``conv0p1s1``). None where the stem reads level 0's submanifold
    plan, as SCN's 3^3 stem does."""
    if cfg.stem_kernel == 3:
        return None
    return cfg.stem_kernel, cfg.in_channels, cfg.stem_width


def _order_rows(sub_coir: COIR, coords, mask, how: str, chunk: int) -> np.ndarray:
    """Ordering of active rows for tiling: SOAR (paper), raster, or active
    (occupancy order, cheapest)."""
    mask_np = np.asarray(mask)
    with span("plan.order"):
        if how == "soar":
            # the submanifold CIRF *is* the adjacency map (self at the center)
            return soar_order(np.asarray(sub_coir.indices), mask_np, chunk).order
        if how == "raster":
            return raster_order(np.asarray(coords), mask_np)
        return np.flatnonzero(mask_np)


def dispatch_from_dataflow(
    df: spade.Dataflow,
    attrs: spade.SparsityAttributes,
    n_majors: int,
    kernel_volume: int,
    n_tiles: int | None = None,
) -> Dispatch:
    """Map a SPADE dataflow onto an engine backend decision.

    Rules: the tiled SSpNNA path serves out-major (CIRF) plans whose tile
    height is an actual tiling (``delta_o < n_majors``); everything else —
    CORF-flavored plans and whole-layer tiles — is the coarse single
    dispatch, i.e. the reference einsum. ``delta_i`` is sized from the SST
    allocation attribute so tiles fit without splitting in the common case.
    """
    if df.flavor != "CIRF" or df.delta_major >= n_majors:
        return REFERENCE_DISPATCH
    d_o = int(df.delta_major)
    d_i = min(
        n_majors,
        int(np.ceil(d_o * attrs.at(d_o, "sa_minor_alloc_sst"))) + kernel_volume,
    )
    return Dispatch(SSPNNA, df.flavor, df.walk, d_o, d_i,
                    n_tiles if n_tiles is not None else 0)


def _layer_spec(name: str, v: int, k: int, c_in: int,
                c_out: int) -> spade.LayerSpec:
    return spade.LayerSpec(name, v, v, k, c_in, c_out, 2)


def _explore(name: str, attrs, n_majors: int, k: int, c_in: int, c_out: int,
             mem_budget: int) -> tuple[Dispatch, spade.Dataflow]:
    """SPADE's sweep for one conv site, mapped onto a backend."""
    df = spade.explore(_layer_spec(name, n_majors, k, c_in, c_out),
                       {"CIRF": attrs, "CORF": attrs}, mem_budget)
    return dispatch_from_dataflow(df, attrs, n_majors, k), df


def _fits_vmem(d: Dispatch, c_in: int, c_out: int, k: int) -> bool:
    """Whether a fused-kernel dispatch passes REPRO-H003
    (``analysis.hlo_gates``) at the widths the kernel allocates: channels
    padded to whole lane tiles and the N-block ``block_n`` resolves to
    (``kernels.sspnna.allocated_widths``). A 5^3 stem from 4 to 32
    channels alone holds a 125 x 128 x 128 float32 weight slab,
    double-buffered."""
    c_p, bn = allocated_widths(c_in, c_out, d.block_n or None)
    return not gate_vmem_budget(replace(d, block_n=bn), c_p, k=k)


def _kernel_refused(d: Dispatch) -> Dispatch:
    """The reference dispatch of a site the kernel cannot hold: it keeps
    SPADE's walk, so a weight-stationary site runs the reference one plane
    at a time (``ReferenceBackend``)."""
    return Dispatch(REFERENCE, d.flavor, d.walk)


def build_plan_spec(
    scenes: list[SparseVoxelTensor],
    cfg,
    *,
    mem_budget: int = 64 * 1024,
    order: str = "soar",
    soar_chunk: int = 512,
    tile_margin: float = 2.0,
    tune_block_n=None,
    autotune=None,
) -> PlanSpec:
    """Freeze per-level dispatch decisions from representative scenes.

    The offline-SPADE flow (§V-C): extract sparsity attributes per scene and
    level, aggregate into meta-attributes (MSA), run the design-space sweep
    once, and pin the winning dataflow. Tile budgets take the analytic bound
    capped at ``tile_margin`` times the worst observed count, so per-scene
    plans keep their static shapes without drowning in padding tiles.

    A level's 3^3 convs are sized at ``cfg.widths[li]``, its widest block
    width; a stem with a plan of its own (``stem_site``) is a site of its
    own, its rows in level 0's order. A site pinned to the fused kernel
    whose VMEM fails REPRO-H003 (``_fits_vmem``) is pinned to the
    reference backend instead, on SPADE's walk (``_kernel_refused``).

    ``tune_block_n`` is an optional ``(c_in, n_out, delta_o, delta_i) -> int``
    hook (e.g. ``repro.engine.autotune.autotune_block_n``) that picks the
    fused kernel's N-block per layer signature; the choice is pinned in each
    level's ``Dispatch.block_n`` so every plan built from this spec runs the
    tuned block instead of defaulting to full-N.

    ``autotune`` is an optional measured :class:`~repro.engine.autotune.
    CostTable`: each level's analytical decision is overridden by the
    cheapest *measured* backend at the level's shape signature when the
    table has one, and left untouched (miss recorded) when it doesn't — a
    cold table reproduces the analytical spec bitwise.
    """
    n_levels = len(cfg.widths)
    stem = stem_site(cfg)
    # (kernel size, C_in, C_out) of each site: the levels, then the stem
    sites = [(3, w, w) for w in cfg.widths] + ([stem] if stem else [])
    per_site: list[list[spade.SparsityAttributes]] = [[] for _ in sites]
    site_density: list[float] = [0.0] * len(sites)
    geo_attrs = []
    for t in scenes:
        geometry = level_geometry(t, cfg)
        rows = []
        for coords, mask, res in geometry:
            coir = build_cirf_np(coords, mask, coords, mask,
                                 kernel_offsets(3), res)
            rows.append((coir, _order_rows(coir, coords, mask, order,
                                           soar_chunk), mask, res))
        if stem is not None:
            coords, mask, res = geometry[0]
            rows.append((build_cirf_np(coords, mask, coords, mask,
                                       kernel_offsets(stem[0]), res),
                         rows[0][1], mask, res))
        for si, (coir, ordering, mask, res) in enumerate(rows):
            per_site[si].append(spade.extract_attributes(
                np.asarray(coir.indices), np.asarray(mask), ordering))
            site_density[si] += (float(np.asarray(mask).sum())
                                 / float(max(res, 1)) ** 3 / len(scenes))
        geo_attrs.append(rows)

    dispatches = []
    for si, (size, c_in, c_out) in enumerate(sites):
        k = size ** 3
        msa = spade.meta_attributes(per_site[si])
        d, _ = _explore(f"level{si}" if si < n_levels else "stem", msa,
                        cfg.capacity, k, c_in, c_out, mem_budget)
        if autotune is not None:
            d = autotune.adjust_dispatch(
                d, n_in=cfg.capacity, n_out=cfg.capacity,
                c_in=c_in, c_out=c_out,
                density=site_density[si], kernel_volume=k)
        if d.backend == SSPNNA:
            # worst observed budgeted tile count across the rep scenes
            observed = max(
                build_tile_plan(np.asarray(rows[si][0].indices),
                                rows[si][1], d.delta_o, d.delta_i).n_tiles
                for rows in geo_attrs)
            bound = max_tiles(cfg.capacity, d.delta_o, d.delta_i, k)
            n_tiles = min(bound, int(np.ceil(tile_margin * observed)) + 2)
            block_n = (int(tune_block_n(c_in, c_out, d.delta_o, d.delta_i))
                       if tune_block_n is not None else d.block_n)
            d = Dispatch(d.backend, d.flavor, d.walk, d.delta_o, d.delta_i,
                         n_tiles, block_n)
            if not _fits_vmem(d, c_in, c_out, k):
                d = _kernel_refused(d)
        dispatches.append(d)
    return PlanSpec(tuple(dispatches[:n_levels]),
                    dispatches[n_levels] if stem is not None else None)


def _tile_arrays(cirf_indices, ordering, dispatch: Dispatch,
                 n_out: int) -> TileArrays | None:
    """Build fixed-shape tile metadata (DMA-table layout) for one conv;
    None on budget overflow or when the plan needs shared-output-row tiles
    the fused kernel can't serve (callers fall back to reference)."""
    with span("plan.tiles"):
        try:
            tp = build_tile_plan(
                np.asarray(cirf_indices), ordering, dispatch.delta_o,
                dispatch.delta_i,
                n_tiles=dispatch.n_tiles if dispatch.n_tiles else None)
        except ValueError:
            return None
        if tp.n_row_splits:  # fused output DMA overwrites; can't share rows
            return None
        dma = dma_tile_tables(tp, n_out)
    return TileArrays(dma.out_rows, dma.in_rows,
                      np.asarray(tp.local_idx), dma.pair_counts)


def conv_plan_for_layer(
    coir: COIR,
    ordering: np.ndarray,
    delta_o: int,
    delta_i: int,
    *,
    walk: str = "OS",
    n_tiles: int | None = None,
) -> ConvPlan:
    """Tiled ConvPlan for a standalone conv site (benchmarks / tests).

    Plane-split plans (``delta_i`` < kernel volume forcing shared output
    rows) are rejected here — pick a working-set budget that fits one row.
    """
    tp = build_tile_plan(np.asarray(coir.indices), ordering, delta_o, delta_i,
                         n_tiles=n_tiles)
    if tp.n_row_splits:
        raise ValueError(
            f"delta_i={delta_i} forces {tp.n_row_splits} plane-split tiles; "
            "the fused kernel needs disjoint output rows — raise delta_i")
    dma = dma_tile_tables(tp, int(coir.mask.shape[0]))
    tiles = TileArrays(jnp.asarray(dma.out_rows), jnp.asarray(dma.in_rows),
                       jnp.asarray(tp.local_idx), jnp.asarray(dma.pair_counts))
    return ConvPlan(coir, tiles,
                    Dispatch(SSPNNA, "CIRF", walk, delta_o, delta_i,
                             tp.n_tiles))


def _map_leaves(plan: ScenePlan, convert) -> ScenePlan:
    """Apply ``convert`` to every array leaf, preserving host-only stats."""
    out = jax.tree.map(convert, plan)
    return ScenePlan(out.levels, plan.stats)


def upload_scene_plan(plan: ScenePlan) -> ScenePlan:
    """Device-upload step: host (numpy) plan leaves -> jax arrays.

    The only part of plan building that touches the device; everything
    upstream (``build_scene_plan_host``) is host work, so an async serving
    pipeline can build plans in worker threads and upload at dispatch time.
    """
    return _map_leaves(plan, jnp.asarray)


def build_scene_plan_host(
    t: SparseVoxelTensor,
    cfg,
    *,
    spec: PlanSpec | None = None,
    plan_tiles: bool = True,
    mem_budget: int = 64 * 1024,
    order: str = "soar",
    soar_chunk: int = 512,
    autotune=None,
    breakers=None,
) -> ScenePlan:
    """Host half of ``build_scene_plan``: all array leaves are numpy.

    This is the paper's offline pass (AdMAC metadata + SOAR reordering +
    SPADE selection + tile tables) with the device upload factored out —
    pair with ``upload_scene_plan``. Safe to call from planner threads.
    ``autotune`` (a measured ``engine.autotune.CostTable``) overrides
    adaptive-mode dispatch decisions with measured winners; see
    ``build_plan_spec``. ``breakers`` (a ``backends.BreakerBoard``)
    reroutes dispatch away from backends whose circuit breaker is open —
    its repr (carrying the board generation) participates in plan-cache
    keys, so routing changes rotate cached plans.
    """
    plan = _build_scene_plan(t, cfg, spec=spec, plan_tiles=plan_tiles,
                             mem_budget=mem_budget, order=order,
                             soar_chunk=soar_chunk, autotune=autotune,
                             breakers=breakers)
    return _map_leaves(plan, np.asarray)


def build_scene_plan(
    t: SparseVoxelTensor,
    cfg,
    *,
    spec: PlanSpec | None = None,
    plan_tiles: bool = True,
    mem_budget: int = 64 * 1024,
    order: str = "soar",
    soar_chunk: int = 512,
    autotune=None,
    breakers=None,
) -> ScenePlan:
    """One AdMAC + SOAR + SPADE pass -> a device-ready ScenePlan.

    ``plan_tiles=False`` skips ordering/attribute extraction entirely and
    produces an all-reference plan (metadata identical to the legacy
    ``models.scn.build_unet_metadata``, at the same cost). Composition of
    ``build_scene_plan_host`` (numpy) + ``upload_scene_plan`` (device).
    """
    return upload_scene_plan(build_scene_plan_host(
        t, cfg, spec=spec, plan_tiles=plan_tiles, mem_budget=mem_budget,
        order=order, soar_chunk=soar_chunk, autotune=autotune,
        breakers=breakers))


def _build_scene_plan(
    t: SparseVoxelTensor,
    cfg,
    *,
    spec: PlanSpec | None = None,
    plan_tiles: bool = True,
    mem_budget: int = 64 * 1024,
    order: str = "soar",
    soar_chunk: int = 512,
    autotune=None,
    breakers=None,
) -> ScenePlan:
    stem = stem_site(cfg)
    if spec is not None and (len(spec.levels) != len(cfg.widths)
                             or (spec.stem is None) != (stem is None)):
        raise ValueError(
            f"spec has {len(spec.levels)} levels and "
            f"{'a' if spec.stem is not None else 'no'} stem site but cfg "
            f"has {len(cfg.widths)} and {'a' if stem else 'no'} stem site "
            "— was it built from another config?")
    offs3 = kernel_offsets(3)
    geometry = level_geometry(t, cfg)
    site_kw = dict(plan_tiles=plan_tiles, mem_budget=mem_budget, order=order,
                   soar_chunk=soar_chunk, autotune=autotune,
                   breakers=breakers)
    levels: list[LevelPlan] = []
    stats: list[dict] = []
    ordering0 = None
    for li, (coords, mask, res) in enumerate(geometry):
        with span("plan.level", level=li):
            down = up = None
            with span("plan.cirf"):
                sub_coir = build_cirf_np(coords, mask, coords, mask, offs3, res)
                if li < len(cfg.widths) - 1:
                    dn_coords, dn_mask, _ = geometry[li + 1]
                    down_coir, up_coir = down_up_coirs_np(
                        dn_coords, dn_mask, coords, mask, res)
                    # resolution-changing convs stay on the coarse single dispatch
                    down = ConvPlan(down_coir)
                    up = ConvPlan(up_coir)

            w = cfg.widths[li]
            sub, info, ordering = _assemble_site(
                sub_coir, coords, mask, li, w, w, cfg.resolution,
                pinned=spec.levels[li] if spec is not None else None,
                **site_kw)
        if li == 0:
            ordering0 = ordering
        stats.append(info)
        levels.append(LevelPlan(coords, mask, sub, down, up))
    if stem is not None:
        size, c_in, c_out = stem
        coords, mask, res = geometry[0]
        with span("plan.stem", k=size ** 3, rows=int(np.asarray(mask).sum())):
            with span("plan.cirf"):
                stem_coir = build_cirf_np(coords, mask, coords, mask,
                                          kernel_offsets(size), res)
            # the stem's rows keep level 0's order (its 3^3 adjacency)
            stem_plan, info, _ = _assemble_site(
                stem_coir, coords, mask, 0, c_in, c_out, cfg.resolution,
                pinned=spec.stem if spec is not None else None,
                ordering=ordering0, order_coir=levels[0].sub.coir, **site_kw)
        info["site"] = "stem"
        stats.append(info)
        levels[0] = levels[0]._replace(stem=stem_plan)
    return ScenePlan(tuple(levels), stats)


def _assemble_site(
    coir: COIR,
    coords,
    mask,
    li: int,
    c_in: int,
    c_out: int,
    resolution: int,
    *,
    pinned: Dispatch | None,
    plan_tiles: bool,
    mem_budget: int,
    order: str,
    soar_chunk: int,
    autotune=None,
    breakers=None,
    ordering=None,
    order_coir: COIR | None = None,
) -> tuple[ConvPlan, dict, np.ndarray | None]:
    """Dispatch/ordering/tile assembly for one stride-1 conv site at level
    ``li`` from ``c_in`` to ``c_out`` channels -> (plan, stats, the row
    ordering if one was computed). The kernel volume is the COIR's.
    ``pinned`` is the spec's decision (None: adaptive SPADE on this scene).
    Rows are ordered from ``order_coir``'s adjacency (default: ``coir``)
    unless ``ordering`` is given.

    Deterministic in ``(coir, coords, mask)`` for a fixed ``autotune``
    table state — the streaming planner relies on this: running it on a
    patched (bitwise-equal) COIR yields bitwise-equal orderings, tiles and
    dispatch decisions.
    """
    k = int(np.asarray(coir.indices).shape[1])
    n_active = int(np.asarray(mask).sum())
    info: dict = {"level": li, "n_active": n_active}
    dispatch = REFERENCE_DISPATCH
    tiles = None

    def rows_in_order():
        if ordering is not None:
            return ordering
        return _order_rows(order_coir if order_coir is not None else coir,
                           coords, mask, order, soar_chunk)

    if plan_tiles and n_active > 0:
        if pinned is not None:
            dispatch = pinned
        else:
            ordering = rows_in_order()
            with span("plan.spade"):
                attrs = spade.extract_attributes(
                    np.asarray(coir.indices), np.asarray(mask), ordering)
                dispatch, df = _explore(f"level{li}", attrs, n_active, k,
                                        c_in, c_out, mem_budget)
            info["arf"] = float(attrs.arf_avg[0])
            info["da_elems"] = df.da_elems
            if autotune is not None:
                # measured-winner consult; a miss (recorded) keeps the
                # analytical decision bitwise-unchanged
                res3 = float(max(resolution >> li, 1)) ** 3
                dispatch = autotune.adjust_dispatch(
                    dispatch, n_in=n_active, n_out=n_active,
                    c_in=c_in, c_out=c_out,
                    density=n_active / res3, kernel_volume=k)
                info["autotuned"] = dispatch.backend
            if dispatch.backend == SSPNNA and not _fits_vmem(
                    dispatch, c_in, c_out, k):
                info["vmem_refused"] = True
                dispatch = _kernel_refused(dispatch)
        if breakers is not None and dispatch.backend != REFERENCE:
            # circuit-breaker consult: a tripped backend routes new plans
            # along its fallback chain. This happens at *build* time (not
            # resolve time) so the rerouted Dispatch lands in the plan's
            # treedef and the jitted call actually changes.
            routed = breakers.route(dispatch.backend)
            if routed != dispatch.backend:
                info["breaker_rerouted"] = (dispatch.backend, routed)
                dispatch = (REFERENCE_DISPATCH if routed == REFERENCE
                            else Dispatch(routed, dispatch.flavor,
                                          dispatch.walk, dispatch.delta_o,
                                          dispatch.delta_i, dispatch.n_tiles,
                                          dispatch.block_n))
        if dispatch.backend == SSPNNA:
            ordering = rows_in_order()
            tiles = _tile_arrays(coir.indices, ordering, dispatch,
                                 int(np.asarray(mask).shape[0]))
            if tiles is None:  # tile budget overflow: coarse dispatch
                info["tile_overflow"] = True
                dispatch = REFERENCE_DISPATCH
            else:
                # the grid's tiles, and those holding a pair (the kernel
                # skips the others as dead)
                info["n_tiles"] = int(tiles.pair_counts.shape[0])
                info["n_live_tiles"] = int(np.count_nonzero(
                    tiles.pair_counts))
                if not dispatch.n_tiles:
                    # adaptive mode: record the realized tile count
                    dispatch = Dispatch(
                        dispatch.backend, dispatch.flavor, dispatch.walk,
                        dispatch.delta_o, dispatch.delta_i,
                        int(tiles.out_rows.shape[0]), dispatch.block_n)
    info["dispatch"] = dispatch
    return ConvPlan(coir, tiles, dispatch), info, ordering


# ---------------------------------------------------------------------------
# Streaming plans
# ---------------------------------------------------------------------------

class StreamPlanState:
    """Per-stream incremental planner: cached host plan + device buffers.

    One instance per LiDAR stream. ``plan_frame`` diffs each frame against
    the stream's cached previous frame (``core.host_meta.StreamMetaState``),
    patches the host plan's metadata tables instead of rebuilding them, and
    reuses the previous frame's ``ConvPlan`` objects outright for levels the
    delta did not touch (a pure ego shift leaves the whole row graph — and
    therefore SOAR orderings and tile tables — intact). Every frame's host
    plan is also registered in the shared :class:`PlanCache` under a
    version key (``stream|<id>|...|f<frame_no>``) so stream plans live under
    the same LRU budget as batch plans.

    Frames must be planned in order; ``plan_frame`` blocks until the
    previous frame of this stream has been planned. If the wait exceeds
    ``wait_s`` (a predecessor was shed or errored), the frame is planned as
    a full rebuild so a lost frame can never wedge the stream.

    ``device_plan`` memoizes uploads per leaf *identity*: unchanged tables
    keep their device buffers across frames, so a steady-state patched
    frame uploads only the arrays that actually changed. It is not
    thread-safe — call it from a single dispatch thread (as
    ``serving.scene_engine`` does).
    """

    def __init__(self, cfg, *, cache: PlanCache | None = None,
                 spec: PlanSpec | None = None,
                 plan_tiles: bool | None = None,
                 mem_budget: int = 64 * 1024, order: str = "soar",
                 soar_chunk: int = 512, min_overlap: float = 0.5,
                 stream_id: str | None = None, topology: str | None = None,
                 wait_s: float = 5.0):
        if stem_site(cfg) is not None:
            raise ValueError(
                "stream plans patch the levels' 3^3 tables only; a config "
                "whose stem has a plan of its own is served whole scenes")
        self.cfg = cfg
        self.cache = cache if cache is not None else PlanCache()
        self.spec = spec
        self.plan_tiles = (spec is not None) if plan_tiles is None \
            else bool(plan_tiles)
        self.mem_budget = mem_budget
        self.order = order
        self.soar_chunk = soar_chunk
        self.min_overlap = float(min_overlap)
        self.wait_s = float(wait_s)
        self.stream_id = stream_id if stream_id is not None \
            else f"s{id(self):x}"
        self._tag = (f"stream|{self.stream_id}|v{_PLAN_VERSION}"
                     f"|top={topology}|{cfg!r}|spec={spec is not None}"
                     f"|tiles={self.plan_tiles}|{order}|{soar_chunk}")
        self.meta = StreamMetaState(cfg.resolution, cfg.capacity,
                                    len(cfg.widths))
        self._cond = ordered_condition("stream.plan")
        self._next_frame = 0
        self._gap = False
        self._prev_plan: ScenePlan | None = None
        self._memo: dict = {}
        self.counts = {"reused": 0, "patched": 0, "rebuilt": 0}
        self._overlap_sum = 0.0
        self._plan_ms_sum = 0.0

    # -- planning ----------------------------------------------------------

    def plan_frame(self, t: SparseVoxelTensor, frame_no: int,
                   ego_shift=(0, 0, 0)) -> tuple[str, ScenePlan, np.ndarray,
                                                 dict]:
        """Plan one stream frame; returns ``(key, host_plan, frame_rows,
        info)``. ``frame_rows`` maps the caller's rows into the stream's
        canonical layout (feed it to ``pack_stream_frame_np`` for features
        and to scatter per-row results back out)."""
        with self._cond:
            deadline = time.monotonic() + self.wait_s
            while self._next_frame < frame_no:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._cond.wait(remaining)
            try:
                with span("plan.frame", frame=frame_no) as sp:
                    if self._next_frame != frame_no or self._gap:
                        # gap in the stream (shed/failed predecessor, or an
                        # out-of-order replay): the cached delta base is stale
                        self.meta.n = None
                    self._gap = False
                    meta = self.meta.step(np.asarray(t.coords),
                                          np.asarray(t.mask), ego_shift,
                                          min_overlap=self.min_overlap)
                    with span("plan.rebuild" if meta.mode == "rebuilt"
                              else "plan.patch", mode=meta.mode,
                              overlap=meta.overlap):
                        plan = self._assemble(meta)
                plan_ms = sp.wall_ms
                self._prev_plan = plan
                self.counts[meta.mode] += 1
                self._overlap_sum += meta.overlap
                self._plan_ms_sum += plan_ms
                key = f"{self._tag}|f{frame_no}"
                self.cache.adopt(key, plan, device=False)
                info = {"mode": meta.mode, "overlap": meta.overlap,
                        "plan_ms": plan_ms,
                        "n_active": meta.info.get("n_active")}
                if "fallback" in meta.info:
                    info["fallback"] = meta.info["fallback"]
                return key, plan, meta.frame_rows, info
            finally:
                self._next_frame = max(self._next_frame, frame_no + 1)
                self._cond.notify_all()

    def skip_frame(self, frame_no: int) -> None:
        """Mark a shed/failed frame so its successors stop waiting for it.

        The serving layer calls this when admission sheds a stream frame
        (deadline/overload): the next planned frame rebuilds from scratch
        — its delta base, and the reference point of the caller's
        ``ego_shift``, is the frame that never arrived."""
        with self._cond:
            if frame_no >= self._next_frame:
                self._gap = True
                self._next_frame = frame_no + 1
                self._cond.notify_all()

    def _assemble(self, meta) -> ScenePlan:
        prev = self._prev_plan
        if meta.mode == "reused" and prev is not None:
            return prev
        n_levels = self.meta.n_levels
        levels: list[LevelPlan] = []
        stats: list[dict] = []
        for li in range(n_levels):
            coords, mask, sub_coir = meta.levels[li]
            if prev is not None and not meta.changed[li]:
                # untouched level: identical tables => identical ordering,
                # tiles and dispatch; reuse the ConvPlan object wholesale
                sub = prev.levels[li].sub
                info = dict(prev.stats[li]) if prev.stats else {"level": li}
            else:
                w = self.cfg.widths[li]
                sub, info, _ = _assemble_site(
                    sub_coir, coords, mask, li, w, w, self.cfg.resolution,
                    pinned=(self.spec.levels[li] if self.spec is not None
                            else None),
                    plan_tiles=self.plan_tiles, mem_budget=self.mem_budget,
                    order=self.order, soar_chunk=self.soar_chunk)
            down = up = None
            if li < n_levels - 1:
                if prev is not None and not meta.pair_changed[li]:
                    down = prev.levels[li].down
                    up = prev.levels[li].up
                else:
                    down_coir, up_coir = meta.pairs[li]
                    down = ConvPlan(down_coir)
                    up = ConvPlan(up_coir)
            levels.append(LevelPlan(coords, mask, sub, down, up))
            stats.append(info)
        return ScenePlan(tuple(levels), stats)

    # -- device upload with per-leaf memoization ---------------------------

    def device_plan(self, host_plan: ScenePlan) -> ScenePlan:
        """Upload a stream host plan, reusing device buffers for leaves
        that are the *same array object* as the previous frame's (patched
        frames share every untouched table). Single-threaded by contract."""
        new_memo: dict = {}
        old_memo = self._memo

        def convert(x):
            k = id(x)
            hit = old_memo.get(k)
            if hit is None or hit[0] is not x:
                hit = (x, jnp.asarray(x))
            new_memo[k] = hit
            return hit[1]

        out = jax.tree.map(convert, host_plan)
        self._memo = new_memo
        return ScenePlan(out.levels, host_plan.stats)

    # -- stats -------------------------------------------------------------

    def stats(self) -> dict:
        """Aggregate per-stream reuse counters (for ``WaveStats.notes``)."""
        frames = sum(self.counts.values())
        return {
            "frames": frames,
            **self.counts,
            "mean_overlap": self._overlap_sum / max(frames, 1),
            "mean_plan_ms": self._plan_ms_sum / max(frames, 1),
        }
