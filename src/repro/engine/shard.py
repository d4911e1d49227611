"""Mesh-sharded scenes: split one scene's capacity axis over a mesh axis.

One large scan does not fit one device arbitrarily far — the ROADMAP's top
open item is sharding a single scene's *capacity* axis over a mesh axis.
This module is that capability, built on the engine's new seams: the plan
is a first-class object (``ShardedScenePlan``), the execution path is a
registered backend (``"sharded"``), and the mesh rides in on the
``ExecutionContext``.

**Plan.** Shard ``s`` owns contiguous capacity rows ``[s*Vs, (s+1)*Vs)``
at every U-Net level (levels keep full capacity, so one split serves all).
The host pass (pure numpy — it slots into ``WaveScheduler``'s plan stage
and pipelines against device execution) builds, per conv site, the global
COIR block exactly as the unsharded planner would, then splits it with
``core.host_meta.shard_halo_tables_np``: per-shard local index blocks plus
*send tables* naming exactly which feature rows must cross which link —
the cross-shard receptive-field halo.

**Execution.** Each conv does one ``dist.collectives.halo_exchange_local``
(a single tiled ``all_to_all`` of only the halo rows), concatenates the
received rows after its own block, and runs the conv locally. Batch-norm
statistics are global: each shard contributes *chunked partial sums*
(fixed ``bn_chunk`` rows per partial), one tiny ``all_gather`` moves the
partials (``V/bn_chunk`` rows instead of ``V``), and a fixed-order scan
reduces them identically on every shard.

**Bitwise contract.** All cross-shard traffic is exact data movement, and
every floating-point reduction is *shape- and thread-configuration
stable*: the conv contraction accumulates per weight plane in fixed order
(each plane a short ``(Vo, C) @ (C, N)`` matmul XLA never re-tiles across
thread configs, unlike the fused ``(Vo, K*C)`` einsum), and BN totals come
from the fixed-order partial scan. Consequently executing a plan over a
2- or 4-device mesh (``shard_map``) is **bitwise identical** to the
single-device reference path (``vmap(axis_name=...)`` over the same local
function) — ``tests/test_sharded.py`` asserts this, plus fp-tolerance
agreement with the unsharded ``"reference"`` einsum backend.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.hashgrid import kernel_offsets
from repro.core.host_meta import (
    build_cirf_np,
    down_up_coirs_np,
    shard_halo_tables_np,
)
from repro.dist.collectives import halo_exchange_local
from repro.dist.compat import shard_map
from repro.engine.backends import Backend, default_registry
from repro.engine.plan import level_geometry
from repro.sparse.tensor import SparseVoxelTensor

SHARDED = "sharded"


@dataclass(frozen=True)
class ShardLayout:
    """Static description of how a scene's capacity axis is sharded.

    ``halo`` is the per-(owner, consumer) halo row budget each conv's send
    tables are padded to; 0 sizes it per scene (adaptive — a new jit
    signature per scene), a positive value pins it (one signature, the
    serving mode; overflow raises at plan-build time, rows are never
    dropped). ``bn_chunk`` is the deterministic BN partial-sum chunk; it
    is snapped down to a divisor of the shard size at plan build.
    """

    n_shards: int
    axis: str = "shard"
    halo: int = 0
    bn_chunk: int = 256

    def shard_size(self, capacity: int) -> int:
        if self.n_shards < 1 or capacity % self.n_shards:
            raise ValueError(
                f"capacity {capacity} not divisible into {self.n_shards} "
                "equal shards")
        return capacity // self.n_shards


class ShardedConvPlan(NamedTuple):
    """Per-conv sharded metadata (leading dim = shard).

    ``indices`` ``(S, Vs, K)`` — COIR block in local coding: ``[0, Vs)``
    own rows, ``Vs + d*H + j`` halo slot ``j`` from shard ``d``, ``-1``
    holes. ``mask`` ``(S, Vs)`` — output-major active rows. ``send_rows``
    ``(S, S, H)`` — ``send_rows[d, s]``: rows shard ``d`` sends shard
    ``s``, local to ``d``, ``-1`` pads.
    """

    indices: jax.Array
    mask: jax.Array
    send_rows: jax.Array


class ShardedLevelPlan(NamedTuple):
    """One U-Net level, sharded: active mask + its three conv sites."""

    mask: jax.Array
    sub: ShardedConvPlan
    down: ShardedConvPlan | None
    up: ShardedConvPlan | None


@jax.tree_util.register_pytree_node_class
@dataclass
class ShardedScenePlan:
    """Per-scene sharded execution plan. ``stats`` is host-only (per-shard
    occupancy, halo rows/budgets per conv) and drops across jit."""

    levels: tuple[ShardedLevelPlan, ...]
    layout: ShardLayout
    stats: list[dict] | None = None

    #: engine.apply_unet routes plans carrying this attribute to the named
    #: scene-level backend's run_unet hook
    scene_backend = SHARDED

    @property
    def n_shards(self) -> int:
        return self.layout.n_shards

    def halo_rows(self) -> int:
        """Total real cross-shard rows one forward exchanges (from stats;
        0 if stats were dropped)."""
        if not self.stats:
            return 0
        return sum(sum(lvl["halo_rows"].values()) for lvl in self.stats)

    def device_upload(self) -> "ShardedScenePlan":
        """Device copy of a host-built plan (PlanCache memoizes this)."""
        return upload_sharded_scene_plan(self)

    def tree_flatten(self):
        return (tuple(self.levels),), self.layout

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux, None)


# ---------------------------------------------------------------------------
# Plan building (host, pure numpy)
# ---------------------------------------------------------------------------

def _shard_conv(indices, out_mask, n_shards: int, halo: int):
    local_idx, send_rows, n_halo = shard_halo_tables_np(
        indices, n_shards, halo)
    mask = np.asarray(out_mask).reshape(n_shards, -1)
    return ShardedConvPlan(local_idx, mask, send_rows), n_halo


def build_sharded_scene_plan_host(
    t: SparseVoxelTensor,
    cfg,
    *,
    layout: ShardLayout,
) -> ShardedScenePlan:
    """AdMAC metadata + halo split for one scene -> host (numpy) plan.

    The global per-level COIR blocks are built with the same numpy
    builders the unsharded planner uses (bit-identical metadata), then
    split into per-shard local blocks + send tables. Safe to call from
    planner threads; pair with :func:`upload_sharded_scene_plan`.
    """
    vs = layout.shard_size(t.capacity)
    chunk = math.gcd(max(int(layout.bn_chunk), 1), vs)
    layout = replace(layout, bn_chunk=chunk)
    offs3 = kernel_offsets(3)
    geometry = level_geometry(t, cfg)
    levels: list[ShardedLevelPlan] = []
    stats: list[dict] = []
    for li, (coords, mask, res) in enumerate(geometry):
        sub_coir = build_cirf_np(coords, mask, coords, mask, offs3, res)
        sub, halo_sub = _shard_conv(sub_coir.indices, mask,
                                    layout.n_shards, layout.halo)
        down = up = None
        halo_rows = {"sub": halo_sub}
        halo_budget = {"sub": int(sub.send_rows.shape[-1])}
        if li < len(cfg.widths) - 1:
            dn_coords, dn_mask, _ = geometry[li + 1]
            down_coir, up_coir = down_up_coirs_np(dn_coords, dn_mask,
                                                  coords, mask, res)
            down, halo_rows["down"] = _shard_conv(
                down_coir.indices, dn_mask, layout.n_shards, layout.halo)
            up, halo_rows["up"] = _shard_conv(
                up_coir.indices, mask, layout.n_shards, layout.halo)
            halo_budget["down"] = int(down.send_rows.shape[-1])
            halo_budget["up"] = int(up.send_rows.shape[-1])
        shard_active = np.asarray(mask).reshape(layout.n_shards, -1).sum(1)
        stats.append({
            "level": li,
            "n_active": int(shard_active.sum()),
            "shard_active": [int(n) for n in shard_active],
            "halo_rows": halo_rows,
            "halo_budget": halo_budget,
        })
        levels.append(ShardedLevelPlan(
            np.asarray(mask).reshape(layout.n_shards, -1), sub, down, up))
    return ShardedScenePlan(tuple(levels), layout, stats)


def upload_sharded_scene_plan(plan: ShardedScenePlan) -> ShardedScenePlan:
    """Host (numpy) plan leaves -> jax arrays, preserving host-only stats."""
    out = jax.tree.map(jnp.asarray, plan)
    return ShardedScenePlan(out.levels, out.layout, plan.stats)


def build_sharded_scene_plan(
    t: SparseVoxelTensor,
    cfg,
    *,
    layout: ShardLayout,
) -> ShardedScenePlan:
    """Host build + device upload in one step (tests / direct use)."""
    return upload_sharded_scene_plan(
        build_sharded_scene_plan_host(t, cfg, layout=layout))


def pin_halo(scenes, cfg, layout: ShardLayout,
             margin: float = 1.5) -> ShardLayout:
    """Freeze the halo budget from representative scenes (serving mode).

    Sizes every conv's send tables to ``margin`` times the worst
    per-(owner, consumer) halo row count observed across ``scenes``, so
    every plan built from the returned layout shares one jit signature —
    the sharded analogue of ``build_plan_spec`` pinning tile counts.
    """
    worst = 0
    probe = replace(layout, halo=0)
    for t in scenes:
        plan = build_sharded_scene_plan_host(t, cfg, layout=probe)
        for lvl in plan.stats:
            worst = max(worst, *lvl["halo_budget"].values())
    return replace(layout, halo=int(np.ceil(margin * worst)) + 1)


# ---------------------------------------------------------------------------
# Execution (deterministic per-shard math + collectives)
# ---------------------------------------------------------------------------

def _plane_conv(buf, idx, weight):
    """Fixed-order plane-accumulated contraction -> (Vo, N) float32.

    Each weight plane is a ``(Vo, C) @ (C, N)`` matmul whose short
    per-row reduction XLA never re-tiles across thread configurations;
    accumulating planes in fixed k order keeps one shard's output rows
    bitwise independent of every other shard's — the property the
    fused ``(Vo, K*C)`` einsum does not have.
    """
    valid = idx >= 0
    g = jnp.where(valid[..., None],
                  jnp.take(buf, jnp.maximum(idx, 0), axis=0), 0)
    g = g.astype(jnp.float32)
    w = weight.astype(jnp.float32)
    out = g[:, 0, :] @ w[0]
    for k in range(1, w.shape[0]):
        out = out + g[:, k, :] @ w[k]
    return out


def _chunk_sums(x, chunk: int):
    """(rows, F) -> (rows // chunk, F) per-chunk column sums."""
    nc = x.shape[0] // chunk
    return jnp.sum(x.reshape(nc, chunk, x.shape[-1]), axis=1)


def _scan_sum(parts):
    """Fixed-order (sequential) total of stacked partial sums."""
    total, _ = jax.lax.scan(
        lambda c, p: (c + p, None),
        jnp.zeros(parts.shape[1:], parts.dtype), parts)
    return total


def _sharded_bn_relu(x, lvl_mask, scale, offset, axis: str, chunk: int,
                     eps: float = 1e-5):
    """Masked BN + ReLU with global statistics over the shard axis.

    Mirrors ``core.sparse_conv.masked_batchnorm_relu`` formula-for-formula;
    the only cross-shard traffic is the chunked partial sums
    (``V/chunk`` rows per gather instead of ``V``), reduced in fixed scan
    order so every shard computes bit-identical statistics.
    """
    mm = lvl_mask[:, None].astype(x.dtype)
    parts = _chunk_sums(jnp.concatenate([x * mm, mm], axis=1), chunk)
    tot = _scan_sum(jax.lax.all_gather(parts, axis, tiled=True))
    n = jnp.maximum(tot[-1], 1.0)
    mean = tot[:-1] / n
    vparts = _chunk_sums(jnp.square(x - mean) * mm, chunk)
    var = _scan_sum(jax.lax.all_gather(vparts, axis, tiled=True)) / n
    y = (x - mean) * jax.lax.rsqrt(var + eps) * scale + offset
    return jax.nn.relu(y) * mm


def _sharded_conv(x, cp: ShardedConvPlan, params, axis: str):
    """One conv site on this shard's rows: halo exchange + local conv."""
    recv = halo_exchange_local(x, cp.send_rows, axis)  # (S, H, C)
    buf = jnp.concatenate([x, recv.reshape(-1, x.shape[-1])], axis=0)
    out = _plane_conv(buf, cp.indices, params.weight)
    out = out.astype(x.dtype) + params.bias.astype(x.dtype)
    return out * cp.mask[:, None].astype(out.dtype)


def _scn_walk_only(params) -> None:
    """Refuse params of any block structure but SCN's U-Net (plain conv
    blocks, no norm after the stem or the down/up convs, biased convs): the
    sharded walk below knows no other, and would compute a wrong one."""
    levels = params["levels"]
    convs = [params["stem"]] + [lvl[k] for lvl in levels
                                for k in ("down", "up") if k in lvl]
    extra = ("stem_bn" in params
             or any(k in lvl for lvl in levels for k in ("down_bn", "up_bn"))
             or any("conv" not in blk for lvl in levels
                    for blk in lvl["enc"] + lvl.get("dec", []))
             or any(c.bias is None for c in convs))
    if extra:
        raise ValueError(
            "sharded scenes run the SCN U-Net walk only (plain conv "
            "blocks, biased convs, no norm after the stem or the down/up "
            "convs); these params describe another U-Net")


def _local_apply_unet(params, x, levels, layout: ShardLayout):
    """Per-shard U-Net forward: (Vs, C_in) -> (Vs, n_classes).

    Valid under ``shard_map`` over ``layout.axis`` *or* under
    ``vmap(axis_name=layout.axis)`` — the latter is the single-device
    reference path the mesh execution is bitwise-matched against. SCN's
    walk only (``_scn_walk_only``).
    """
    _scn_walk_only(params)
    axis, chunk = layout.axis, layout.bn_chunk

    def block(x, lvl_mask, cp, bp):
        y = _sharded_conv(x, cp, bp["conv"], axis)
        return _sharded_bn_relu(y, lvl_mask, bp["bn_scale"],
                                bp["bn_offset"], axis, chunk)

    x = _sharded_conv(x, levels[0].sub, params["stem"], axis)
    skips = []
    for li, lvl in enumerate(levels):
        p = params["levels"][li]
        for blk in p["enc"]:
            x = block(x, lvl.mask, lvl.sub, blk)
        if lvl.down is not None:
            skips.append(x)
            x = _sharded_conv(x, lvl.down, p["down"], axis)
    for li in range(len(levels) - 2, -1, -1):
        lvl, p = levels[li], params["levels"][li]
        up = _sharded_conv(x, lvl.up, p["up"], axis)
        x = jnp.concatenate([skips[li], up], axis=-1)
        for blk in p["dec"]:
            x = block(x, lvl.mask, lvl.sub, blk)
    return x @ params["head"]["w"] + params["head"]["b"]


def apply_unet_sharded(
    params: dict,
    feats: jnp.ndarray,
    plan: ShardedScenePlan,
    *,
    mesh=None,
    axis: str | None = None,
) -> jnp.ndarray:
    """U-Net forward off a ShardedScenePlan -> (V, n_classes) logits.

    With ``mesh`` (carrying ``plan.layout.axis``), shards execute SPMD via
    ``shard_map`` with real collectives; without one, the same local
    function runs under ``vmap(axis_name=...)`` on one device — the
    reference path, bitwise identical to the mesh execution.
    """
    layout = plan.layout
    S = layout.n_shards
    vs = layout.shard_size(feats.shape[0])
    if plan.levels[0].mask.shape[-1] != vs:
        raise ValueError(
            f"plan shard size {plan.levels[0].mask.shape[-1]} != "
            f"feats shard size {vs}")
    blocks = feats.reshape(S, vs, feats.shape[-1])
    axis = axis or layout.axis
    if mesh is not None:
        if axis not in mesh.axis_names:
            raise ValueError(
                f"mesh axes {mesh.axis_names} lack shard axis {axis!r}")
        if int(mesh.shape[axis]) != S:
            raise ValueError(
                f"plan has {S} shards but mesh axis {axis!r} has size "
                f"{mesh.shape[axis]}")
        if axis != layout.axis:
            layout = replace(layout, axis=axis)

        def local(p, x, lvls):
            lvls1 = jax.tree.map(lambda a: a[0], lvls)
            return _local_apply_unet(p, x[0], lvls1, layout)[None]

        out = shard_map(
            local, mesh,
            in_specs=(jax.tree.map(lambda _: P(), params), P(axis),
                      jax.tree.map(lambda _: P(axis), plan.levels)),
            out_specs=P(axis))(params, blocks, plan.levels)
    else:
        out = jax.vmap(
            lambda x, lvls: _local_apply_unet(params, x, lvls, layout),
            axis_name=layout.axis)(blocks, plan.levels)
    return out.reshape(feats.shape[0], -1)


# ---------------------------------------------------------------------------
# Backend registration
# ---------------------------------------------------------------------------

class ShardedBackend(Backend):
    """Scene-level backend: mesh-sharded execution with halo exchange.

    Reached via ``engine.apply_unet`` on a ``ShardedScenePlan`` (the plan
    names it through ``scene_backend``); the mesh comes from the call's
    ``ExecutionContext``. Per-conv ``run`` is intentionally unsupported —
    a sharded conv only makes sense inside the scene's SPMD program.
    """

    name = SHARDED
    scene_level = True

    def supports(self, plan) -> bool:
        return isinstance(plan, ShardedScenePlan)

    def run(self, x, params, plan, *, ctx, **kw):
        raise ValueError(
            "the sharded backend executes whole scenes; call "
            "engine.apply_unet with a ShardedScenePlan")

    def run_unet(self, params, feats, plan, *, ctx, **kw):
        mesh = ctx.mesh if ctx is not None else None
        return apply_unet_sharded(params, feats, plan, mesh=mesh)


default_registry().register(SHARDED, ShardedBackend(), overwrite=True)
