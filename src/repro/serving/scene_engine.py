"""Batched 3D-scene serving: fixed-capacity slots, cached plans, few jits.

The 3D face of the shared ``serving.scheduler.WaveScheduler``: the host
packs up to ``batch`` scene requests per wave, builds (or cache-hits) each
scene's plan, and runs the wave through one jitted forward. All shapes are
static — scene capacity is fixed per signature, and a pinned ``PlanSpec``
(or, sharded, a pinned halo budget) freezes the plan signature — so every
wave after the first is a jit cache hit.

The engine executes under an :class:`~repro.engine.context.ExecutionContext`
(``ctx=``): the context owns the plan cache (topology mixed into every
key), the backend registry the jitted forward dispatches through, the
default admission policy, and — for sharded serving — the device mesh.
Three serving modes:

* **batched** (default): plans stack along a leading scene axis and one
  vmapped U-Net forward serves the wave at a single pinned capacity
  (``n_compilations`` stays 1).
* **bucketed** (``family=SignatureFamily(...)``): continuous batching over
  a small family of capacity tiers. Each request is assigned the smallest
  bucket its *active* voxels fit at submit time; the plan stage re-packs
  the scene to the bucket capacity (active rows first — so a client can
  over-pad its upload and still serve from a small bucket) and admission
  fills each wave from same-bucket requests. One jit signature per bucket,
  compiled on first use — mixed traffic compiles at most
  ``family.n_buckets`` signatures, warm single-size traffic exactly 1.
  Pair with a :class:`~repro.serving.scheduler.AdmissionPolicy`
  (``policy=`` or ``ctx.admission``) for priority/deadline admission,
  weighted tenant fairness, and backpressure shedding.
* **sharded** (``layout=ShardLayout(...)`` with a pinned ``halo`` budget):
  each scene's capacity axis is split over ``ctx.mesh``'s shard axis; the
  plan stage builds per-shard metadata + halo send tables (pure numpy, on
  planner threads), and dispatch enqueues one sharded forward per scene.
  Each wave's ``WaveStats.notes`` records the per-shard plan builds and
  halo rows.

On top of the batched mode, ``open_stream()`` / ``serve_stream()`` add a
**streaming** path for LiDAR sweeps: frames submitted through a
:class:`StreamHandle` are planned *incrementally* — each frame diffs
against the stream's previous frame (after ego-motion re-basing) and
patches the cached host plan's metadata tables instead of rebuilding
them, with a full-rebuild fallback under heavy churn. Admission keeps
frames FIFO within a stream (they are order-dependent) while the policy
still arbitrates between streams and one-shot requests; each wave's
``WaveStats.notes`` reports ``stream_reused`` / ``stream_patched`` /
``stream_rebuilt`` counts, mean ``stream_overlap`` and summed
``stream_plan_ms``.

Stage split (the paper's offline-pass/execution overlap, served):

* **plan** — ``PlanCache.get_or_build(device=False)``: the AdMAC + SOAR +
  SPADE (+ bucket re-pack / halo split) numpy pass, run on planner threads
  up to ``depth`` waves ahead;
* **dispatch** — fetch the (memoized) device upload of each plan and
  enqueue the jitted forward without blocking;
* **drain** — block on the previous wave's logits and fill the requests
  (bucketed scenes scatter back to their original row positions).

``sync=True`` (default) runs the same stages back-to-back — bitwise
identical results given the same admitted wave order, no overlap;
``sync=False`` pipelines them and reports ``plan_ms`` / ``inflight_ms`` /
``overlap_frac`` per wave via ``wave_stats`` / ``timings()``.

Short waves are padded with a copy of the first scene's plan and zero
features; padding slots are dropped before results are handed back.

The driver API (``submit() -> RequestHandle``, ``serve()``, ``timings()``,
``slo_stats()``) comes from :class:`repro.serving.api.ServingBase`; the
pre-handle ``run()`` / ``.completed`` surface survives as deprecation
shims there.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.runtime import ordered_lock
from repro.analysis.spans import span
from repro.core.host_meta import pack_stream_frame_np
from repro.engine import api as engine_api
from repro.engine.context import ExecutionContext
from repro.engine.plan import (
    REFERENCE,
    PlanCache,
    PlanSpec,
    SignatureFamily,
    StreamPlanState,
)
from repro.engine.shard import ShardLayout, build_sharded_scene_plan_host
from repro.serving.api import AdmissionPolicy, ServeRequest, ServingBase
from repro.serving.scheduler import WaveScheduler
from repro.sparse.tensor import SparseVoxelTensor, compact_to_capacity


@dataclass
class SceneRequest(ServeRequest):
    """One scene to segment; SLO fields (tenant/priority/deadline_ms) come
    from :class:`~repro.serving.api.ServeRequest` as keyword-only args."""

    scene: SparseVoxelTensor = None
    logits: np.ndarray | None = None   # (capacity, n_classes)
    pred: np.ndarray | None = None     # (capacity,) argmax classes
    done: bool = False


@dataclass
class StreamFrameRequest(SceneRequest):
    """One frame of an open LiDAR stream (made by ``StreamHandle.submit``).

    Carries the stream handle, its monotonically assigned ``frame_no`` and
    the ``ego_shift`` from the previous frame. After serving, ``logits`` /
    ``pred`` are in the *caller's* row layout (the drain stage scatters the
    stream's canonical rows back through ``frame_rows``), and
    ``plan_info`` records how the frame was planned: ``mode`` in
    {``reused``, ``patched``, ``rebuilt``}, voxel ``overlap`` fraction with
    the previous frame, host ``plan_ms``."""

    stream: "StreamHandle | None" = None
    frame_no: int = -1
    ego_shift: tuple = (0, 0, 0)
    plan_info: dict | None = None

    # scheduler hooks: per-stream FIFO admission keys
    @property
    def _stream_key(self):
        return None if self.stream is None else self.stream.stream_id

    @property
    def _stream_frame(self) -> int:
        return self.frame_no


class StreamHandle:
    """Client view of one open stream on a :class:`SceneEngine`.

    ``submit(scene, ego_shift)`` queues the stream's next frame (frame
    numbers are assigned monotonically; admission keeps them FIFO within
    the stream even under an urgency policy) and returns the usual
    :class:`~repro.serving.api.RequestHandle`. ``stats()`` reports the
    stream's plan-reuse counters."""

    def __init__(self, engine: "SceneEngine", state: StreamPlanState):
        self.engine = engine
        self.state = state
        self._next_frame = 0
        self._lock = ordered_lock("stream.handle")

    @property
    def stream_id(self) -> str:
        return self.state.stream_id

    def submit(self, scene: SparseVoxelTensor, ego_shift=(0, 0, 0), *,
               rid: int | None = None, **slo):
        """Queue the next frame of this stream; ``ego_shift`` is the ego
        translation (in voxels) since the *previous* submitted frame.
        SLO kwargs (tenant/priority/deadline_ms) pass through."""
        with self._lock:
            frame_no = self._next_frame
            self._next_frame += 1
        req = StreamFrameRequest(
            rid=frame_no if rid is None else rid, scene=scene,
            stream=self, frame_no=frame_no, ego_shift=tuple(ego_shift),
            **slo)
        return self.engine.submit(req)

    def stats(self) -> dict:
        """Aggregate plan-reuse stats: frames, reused/patched/rebuilt
        counts, mean overlap, mean host plan ms."""
        return self.state.stats()


class SceneEngine(ServingBase):
    """Host-side batched scene driver (fixed shapes, plan-cached).

    ``spec=None`` serves every scene on the reference backend (always a
    single jit signature); pass ``spec=build_plan_spec(rep_scenes, cfg)``
    to serve the SPADE-planned reference/SSpNNA mix at pinned tile shapes,
    ``family=build_signature_family(rep_scenes, cfg)`` for bucketed
    continuous batching over a family of capacity tiers, or
    ``layout=pin_halo(rep_scenes, cfg, ShardLayout(...))`` (with a
    mesh-carrying ``ctx``) to serve mesh-sharded scenes. ``sync=False``
    turns on the asynchronous wave pipeline: plan building for wave *k+1*
    overlaps device execution of wave *k* and readback of wave *k−1*
    (``depth`` device waves in flight, ``planner_threads`` host builders).
    ``sync`` / ``depth`` / ``planner_threads`` / ``policy`` default to the
    context's scheduler wiring when left ``None``.

    Plans SPADE sends to ``"sspnna"`` run the fused Pallas kernel (compiled
    on TPU, interpreted elsewhere); ``use_kernel=False`` swaps in its jnp
    oracle, for CPU tests that need the speed.
    """

    def __init__(self, cfg, params, batch: int,
                 spec: PlanSpec | None = None, *,
                 ctx: ExecutionContext | None = None,
                 layout: ShardLayout | None = None,
                 family: SignatureFamily | None = None,
                 policy: AdmissionPolicy | None = None,
                 backend: str = "auto", use_kernel: bool = True,
                 interpret: bool | None = None,
                 plan_cache_size: int | None = None,
                 order: str = "soar", soar_chunk: int = 512,
                 sync: bool | None = None, depth: int | None = None,
                 planner_threads: int | None = None,
                 faults=None):
        if ctx is None:
            ctx = ExecutionContext(
                plan_cache=PlanCache(plan_cache_size or 128))
        elif plan_cache_size is not None:
            raise ValueError(
                "plan_cache_size only applies when the engine builds its "
                "own context; size ctx.plan_cache when passing ctx=")
        self.cfg, self.params, self.batch, self.spec = cfg, params, batch, spec
        self.ctx, self.layout, self.family = ctx, layout, family
        self.cache = ctx.plan_cache
        self._topology = ctx.topology_key()
        self._plan_sig = None  # sharded mode: pinned wave plan signature
        #: the context registry's circuit breakers; dispatch failures feed
        #: them (via the scheduler's on_wave_error) and plan builds consult
        #: them, so a failing backend reroutes to its fallback
        self._breakers = getattr(ctx.registry, "breakers", None)
        if policy is None:
            policy = ctx.admission
        if family is not None:
            if spec is not None:
                raise ValueError(
                    "spec= and family= are mutually exclusive: the family "
                    "carries a pinned spec per capacity bucket")
            if layout is not None:
                raise ValueError(
                    "family= and layout= are mutually exclusive: sharded "
                    "serving pins a single halo-budget signature")
            # per-bucket configs share params; only the capacity tier (and
            # with it the plan/jit signature) differs
            self._bucket_cfgs = {
                cap: dataclasses.replace(cfg, capacity=cap)
                for cap in family.capacities}
            self._bucket_kw = {
                cap: dict(spec=family.spec_for(cap),
                          plan_tiles=family.spec_for(cap) is not None,
                          order=order, soar_chunk=soar_chunk)
                for cap in family.capacities}
            if self._breakers is not None:
                for kw in self._bucket_kw.values():
                    kw["breakers"] = self._breakers
            self._builder = None
        elif layout is not None:
            if spec is not None:
                raise ValueError(
                    "spec= and layout= are mutually exclusive: sharded "
                    "serving plans its own per-shard metadata")
            if layout.halo < 1:
                raise ValueError(
                    "sharded serving needs a pinned halo budget for a "
                    "single jit signature; pin one with engine.pin_halo")
            if ctx.mesh is not None:
                axes = getattr(ctx.mesh, "axis_names", ())
                if (layout.axis not in axes
                        or int(ctx.mesh.shape[layout.axis]) != layout.n_shards):
                    raise ValueError(
                        f"layout needs mesh axis {layout.axis!r} of size "
                        f"{layout.n_shards}; ctx mesh has axes "
                        f"{dict(getattr(ctx.mesh, 'shape', {}))}")
            self._plan_kw = dict(layout=layout)
            self._builder = build_sharded_scene_plan_host
        else:
            self._plan_kw = dict(spec=spec, plan_tiles=spec is not None,
                                 order=order, soar_chunk=soar_chunk)
            if self._breakers is not None:
                # the board's repr carries its generation, so a trip/close
                # rotates cache keys: cached plans never outlive the routing
                self._plan_kw["breakers"] = self._breakers
            self._builder = None  # PlanCache default (build_scene_plan_host)
        self._streams: dict[str, StreamHandle] = {}
        self.scheduler = WaveScheduler(
            batch=batch, plan=self._plan_stage, dispatch=self._dispatch_stage,
            drain=self._drain_stage,
            sync=ctx.sync if sync is None else sync,
            depth=ctx.depth if depth is None else depth,
            planner_threads=(ctx.planner_threads if planner_threads is None
                             else planner_threads),
            policy=policy,
            bucket_of=((lambda r: getattr(r, "_bucket", None))
                       if family is not None else None),
            on_shed=self._on_shed,
            faults=faults,
            on_wave_error=self._on_wave_error)

        concat = cfg.concat  # the decoder's order (SCN: [skip, up])
        if layout is not None:
            def sharded_apply(params, feats, plan):
                return engine_api.apply_unet(
                    params, feats, plan, concat=concat, backend=backend,
                    ctx=ctx, use_kernel=use_kernel, interpret=interpret)

            self._apply = jax.jit(sharded_apply)
        else:
            def batched_apply(params, feats, plans):
                # feats/plans arrive as length-`batch` lists; stacking
                # inside the jit keeps dispatch a single async enqueue (no
                # eager per-leaf stack ops racing the in-flight wave on the
                # device queue)
                batch_feats = jnp.stack(feats)
                batch_plan = jax.tree.map(lambda *xs: jnp.stack(xs), *plans)
                return jax.vmap(
                    lambda f, p: engine_api.apply_unet(
                        params, f, p, concat=concat, backend=backend,
                        ctx=ctx, use_kernel=use_kernel, interpret=interpret)
                )(batch_feats, batch_plan)

            self._apply = jax.jit(batched_apply)

    # -- introspection -------------------------------------------------------

    @property
    def n_compilations(self) -> int:
        """Distinct jit signatures compiled so far (bucketed serving pays
        one per bucket actually used)."""
        return int(self._apply._cache_size())

    # -- streaming -----------------------------------------------------------

    def open_stream(self, stream_id: str | None = None, *,
                    min_overlap: float = 0.5,
                    wait_s: float = 5.0) -> StreamHandle:
        """Open a LiDAR stream: subsequent frames submitted through the
        returned :class:`StreamHandle` are planned *incrementally* — each
        frame diffs against the previous one (after ``ego_shift``
        re-basing) and patches the cached host plan instead of rebuilding
        it, falling back to a full rebuild when voxel overlap drops below
        ``min_overlap``. Streams need the fixed-capacity batched mode
        (``family=`` re-packs rows per bucket and ``layout=`` pins a
        sharded signature; both are incompatible with a per-stream
        canonical row layout)."""
        if self.family is not None or self.layout is not None:
            raise ValueError(
                "open_stream needs the fixed-capacity batched mode; "
                "family= and layout= engines cannot serve streams")
        if stream_id is not None and stream_id in self._streams:
            raise ValueError(f"stream {stream_id!r} is already open")
        state = StreamPlanState(
            self.cfg, cache=self.cache, spec=self.spec,
            plan_tiles=self._plan_kw["plan_tiles"],
            order=self._plan_kw["order"],
            soar_chunk=self._plan_kw["soar_chunk"],
            min_overlap=min_overlap, stream_id=stream_id,
            topology=self._topology, wait_s=wait_s)
        handle = StreamHandle(self, state)
        self._streams[state.stream_id] = handle
        return handle

    def serve_stream(self, frames, ego_shifts=None, *,
                     stream: StreamHandle | None = None,
                     min_overlap: float = 0.5,
                     **slo) -> list[StreamFrameRequest]:
        """Serve a whole sweep through one stream: submit every frame in
        order (``ego_shifts[i]`` is frame *i*'s ego translation since
        frame *i−1*), pump the queue, and return the fulfilled requests.
        Pass ``stream=`` to continue an already-open stream; otherwise a
        fresh one is opened with ``min_overlap``."""
        frames = list(frames)
        if ego_shifts is None:
            ego_shifts = [(0, 0, 0)] * len(frames)
        ego_shifts = [tuple(s) for s in ego_shifts]
        if len(ego_shifts) != len(frames):
            raise ValueError(
                f"{len(frames)} frames but {len(ego_shifts)} ego_shifts")
        if stream is None:
            stream = self.open_stream(min_overlap=min_overlap)
        handles = [stream.submit(t, shift, **slo)
                   for t, shift in zip(frames, ego_shifts)]
        self.serve()
        return [h.result() for h in handles]

    def _on_shed(self, req) -> None:
        # a shed stream frame must not wedge its successors: advance the
        # stream's frame gate (the next planned frame rebuilds)
        if isinstance(req, StreamFrameRequest) and req.stream is not None:
            req.stream.state.skip_frame(req.frame_no)

    # -- admission -----------------------------------------------------------

    def _prepare(self, req: SceneRequest) -> str | None:
        """Bucket assignment at submit time (bucketed mode): the smallest
        family capacity the scene's active voxels fit; a scene exceeding
        every bucket is shed with reason ``"capacity"``."""
        if self.family is None:
            return None
        n_active = int(np.asarray(req.scene.mask).sum())
        cap = self.family.bucket_for(n_active)
        if cap is None:
            return "capacity"
        req._bucket = cap
        req._n_active = n_active
        return None

    # -- pipeline stages -----------------------------------------------------

    def _plan_stage(self, req: SceneRequest):
        """Host-side plan build (numpy leaves); runs on planner threads.

        The payload carries the cache key so the dispatch thread never
        re-hashes the scene on the critical path. Bucketed mode re-packs
        the scene to its bucket capacity first (active rows in original
        order) and remembers the row mapping for the drain scatter.

        Stream frames take the incremental path: ``StreamPlanState``
        blocks until the stream's previous frame has been planned, diffs
        against it, and patches (or reuses) the cached host plan; features
        are re-packed into the stream's canonical row layout here so
        dispatch stays a plain upload."""
        if isinstance(req, StreamFrameRequest):
            scene = req.scene
            inj = self.scheduler.faults
            if inj is not None:
                # corrupt-frame seam: scribble garbage over the frame's
                # coords before planning — exercises the stream's
                # gap/rebuild recovery (and plan-stage containment when
                # the corruption makes the build raise)
                coords = np.asarray(scene.coords)
                corrupted = inj.corrupt_coords(coords, rid=req.rid)
                if corrupted is not coords:
                    scene = SparseVoxelTensor(
                        jnp.asarray(corrupted), scene.feats, scene.mask)
            state = req.stream.state
            key, plan, frame_rows, info = state.plan_frame(
                scene, req.frame_no, req.ego_shift)
            req.plan_info = info
            req._frame_rows = frame_rows
            req._backends = self._plan_backends(plan)
            feats = pack_stream_frame_np(frame_rows,
                                         np.asarray(scene.feats))
            return "stream", key, plan, feats, state
        if self.family is not None:
            cap = req._bucket
            scene, active_idx = compact_to_capacity(req.scene, cap)
            req._active_idx = active_idx
            cfg, plan_kw = self._bucket_cfgs[cap], self._bucket_kw[cap]
        else:
            scene, cfg, plan_kw = req.scene, self.cfg, self._plan_kw
        key = self.cache.key_for(scene, cfg,
                                 topology=self._topology, **plan_kw)
        plan = self.cache.get_or_build(scene, cfg, device=False,
                                       key=key, builder=self._builder,
                                       **plan_kw)
        req._backends = self._plan_backends(plan)
        if self.family is not None:
            return key, plan, scene.feats  # re-packed feats (numpy)
        return key, plan

    @staticmethod
    def _plan_backends(plan) -> tuple:
        """Non-reference backends this plan dispatches to — the circuit
        breakers a failure of the request's wave is attributed to (when
        the exception itself doesn't name one)."""
        names = set()
        for info in getattr(plan, "stats", None) or ():
            d = info.get("dispatch") if isinstance(info, dict) else None
            name = getattr(d, "backend", None)
            if name is not None and name != REFERENCE:
                names.add(name)
        return tuple(sorted(names))

    def _on_wave_error(self, exc, reqs, stage: str) -> None:
        """Contained-wave-failure observer (scheduler ``on_wave_error``):
        attribute dispatch/drain failures to backend circuit breakers —
        the exception's ``backend`` attribute when it names one (e.g. an
        injected ``DeviceFaultError``), else every non-reference backend
        the wave's plans dispatch to."""
        board = self._breakers
        if board is None or stage not in ("dispatch", "drain"):
            return
        name = getattr(exc, "backend", None)
        names = ((name,) if name else
                 sorted({b for r in reqs
                         for b in getattr(r, "_backends", ())}))
        for n in names:
            board.record_failure(n)

    def _dispatch_stage(self, reqs: list[SceneRequest], payloads, stats):
        # the plan stage built (and counted) these host plans; adopt fetches
        # the memoized device upload without rebuilding (even if LRU
        # pressure evicted the entry) and without skewing hits/misses.
        # Stream frames upload through their StreamPlanState's per-leaf
        # identity memo instead, so a patched frame re-uploads only the
        # tables the delta actually touched.
        with span("serve.upload") as up:
            plans = [p[4].device_plan(p[2]) if p[0] == "stream"
                     else self.cache.adopt(p[0], p[1], device=True)
                     for p in payloads]
            if self.layout is None:
                feats = self._wave_feats(reqs, payloads)
        stats.notes["upload_ms"] = up.wall_ms
        if self.layout is not None:
            # the pinned halo budget promises one jit signature across
            # every wave; a diverging plan (wrong capacity, re-pinned
            # layout) must fail loudly, not silently recompile
            for r, p in zip(reqs, plans):
                leaves, td = jax.tree_util.tree_flatten(p)
                sig = (td, tuple(x.shape for x in leaves))
                if self._plan_sig is None:
                    self._plan_sig = sig
                elif sig != self._plan_sig:
                    raise RuntimeError(
                        f"scene {r.rid}: sharded plan signature diverged "
                        "from the pinned layout (capacity mismatch or a "
                        "re-pinned halo budget?); re-pin with "
                        "engine.pin_halo")
            stats.notes["plan_shards"] = self.layout.n_shards
            stats.notes["plan_builds"] = len(payloads)
            stats.notes["halo_rows"] = sum(
                p[1].halo_rows() for p in payloads)
            # per-scene sharded forwards; jax async dispatch keeps the
            # loop non-blocking, so the wave still pipelines as one unit
            return [self._apply(self.params, r.scene.feats, p)
                    for r, p in zip(reqs, plans)]
        t0 = jax.tree_util.tree_structure(plans[0])
        for r, p in zip(reqs, plans):
            if jax.tree_util.tree_structure(p) != t0:
                raise RuntimeError(
                    f"scene {r.rid}: plan signature diverged from "
                    "the wave (tile-budget overflow?); raise "
                    "tile_margin in build_plan_spec")
        if self.family is not None:
            # admission guarantees a single-bucket wave; a mixed wave here
            # means the bucket hook was bypassed — fail before compiling a
            # stray signature
            caps = {r._bucket for r in reqs}
            if len(caps) != 1:
                raise RuntimeError(
                    f"wave mixes capacity buckets {sorted(caps)}; bucketed "
                    "serving admits one bucket per wave")
        # tiles of the fused kernel's levels in the wave's own plans (not
        # the padding copies): live tiles hold at least one pair
        levels = [i for p in payloads
                  for i in (p[2] if p[0] == "stream" else p[1]).stats or ()
                  if "n_tiles" in i]
        if levels:
            stats.notes["sspnna_tiles"] = sum(i["n_tiles"] for i in levels)
            stats.notes["sspnna_live_tiles"] = sum(
                i["n_live_tiles"] for i in levels)
        s_infos = [r.plan_info for r in reqs
                   if isinstance(r, StreamFrameRequest)]
        if s_infos:
            for mode in ("reused", "patched", "rebuilt"):
                stats.notes[f"stream_{mode}"] = sum(
                    1 for i in s_infos if i["mode"] == mode)
            stats.notes["stream_overlap"] = float(
                sum(i["overlap"] for i in s_infos) / len(s_infos))
            stats.notes["stream_plan_ms"] = float(
                sum(i["plan_ms"] for i in s_infos))
        while len(plans) < self.batch:  # pad the wave to fixed batch
            plans.append(plans[0])
            feats.append(jnp.zeros_like(feats[0]))
        return self._apply(self.params, feats, plans)

    def _wave_feats(self, reqs: list[SceneRequest], payloads) -> list:
        """The wave's features on the device: a bucket's re-packed rows, a
        stream frame's canonical rows, or the scene's own."""
        if self.family is not None:
            return [jnp.asarray(p[2]) for p in payloads]
        return [jnp.asarray(p[3] if p[0] == "stream" else r.scene.feats)
                for r, p in zip(reqs, payloads)]

    def _drain_stage(self, reqs: list[SceneRequest], logits) -> None:
        if isinstance(logits, list):  # sharded mode: per-scene handles
            logits = np.stack([np.asarray(h) for h in logits])
        else:
            logits = np.asarray(logits)
        for i, r in enumerate(reqs):
            if isinstance(r, StreamFrameRequest):
                # scatter the stream's canonical rows back to the
                # caller's row positions (inactive rows stay zero-logit)
                fr = r._frame_rows
                out = np.zeros((r.scene.capacity, logits.shape[-1]),
                               logits.dtype)
                act = fr >= 0
                out[act] = logits[i][fr[act]]
                r.logits = out
            elif self.family is not None:
                # scatter compacted-bucket rows back to the request's
                # original row positions (padding rows stay zero-logit)
                idx = r._active_idx
                out = np.zeros((r.scene.capacity, logits.shape[-1]),
                               logits.dtype)
                out[idx] = logits[i][: len(idx)]
                r.logits = out
            else:
                r.logits = logits[i]
            r.pred = r.logits.argmax(-1)
            r.done = True
        if self._breakers is not None:
            # a drained wave is a success for every backend it exercised:
            # closes HALF_OPEN probes and resets consecutive-failure counts
            for n in sorted({b for r in reqs
                             for b in getattr(r, "_backends", ())}):
                self._breakers.record_success(n)

    def _health_extra(self) -> dict:
        board = self._breakers
        return {"breakers": board.states() if board is not None else {}}
