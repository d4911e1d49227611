"""One plan-driven entry point for sparse convolution.

``sparse_conv(x, params, plan, backend=..., ctx=...)`` is the execution API
the rest of the repo programs against; the COIR metadata, SOAR ordering,
SPADE dataflow decision and SSpNNA tile tables all arrive pre-packaged in
the ``ConvPlan`` (see ``repro.engine.plan``), so call sites never re-derive
them — the paper's co-design, surfaced as one function.

Dispatch goes through the backend registry (``repro.engine.backends``):
``Dispatch``/SPADE emit a backend *name*, the context's registry resolves
it to an implementation (following declared fallbacks — e.g. an SSpNNA
decision whose plan lost its tile metadata degrades to ``reference``), and
new paths plug in via ``engine.register_backend`` without touching this
module. The built-ins:

* ``"reference"`` — gather + one fused einsum over all weight planes
  (``core.sparse_conv.reference_conv_cirf``), the coarse M-V dispatch and
  the numerical oracle.
* ``"sspnna"`` — the fused gather-GEMM-scatter Pallas path
  (``kernels.sspnna``) driven by the plan's ``TileArrays``.
* ``"sharded"`` — mesh-sharded scene execution with halo exchange
  (``engine.shard``); scene-level, reached via ``apply_unet`` on a
  ``ShardedScenePlan``.
* ``"auto"`` — follow the decision recorded in ``plan.dispatch``.

``ctx=`` names the :class:`~repro.engine.context.ExecutionContext` (mesh,
registry view, plan cache) the call runs under; omitted, the ambient
context applies, so pre-context call sites keep working.

``apply_unet`` runs a whole U-Net (SCN's, or MinkowskiEngine's residual
MinkUNet) off a ``ScenePlan``; it is pure in
(params, feats, plan) and vmap/jit-friendly — the serving engine batches it
with a leading scene axis. Plans that carry a ``scene_backend`` attribute
(``ShardedScenePlan``) are handed whole to that backend's ``run_unet``.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.coir import COIR
from repro.core.sparse_conv import (
    SparseConvParams,
    masked_batchnorm,
    masked_batchnorm_relu,
)
from repro.engine.backends import AUTO, default_registry
from repro.engine.context import ExecutionContext, current_context
from repro.engine.plan import REFERENCE_DISPATCH, ConvPlan, ScenePlan


def __getattr__(name: str):
    # legacy alias for the closed enum this module used to hard-code;
    # computed on access so late registrations show up
    if name == "BACKENDS":
        return (AUTO,) + default_registry().names()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def available_backends(ctx: ExecutionContext | None = None) -> tuple[str, ...]:
    """Backend names resolvable under ``ctx`` (ambient context if None)."""
    ctx = ctx if ctx is not None else current_context()
    return (AUTO,) + ctx.registry.names()


def reference_plan(coir: COIR) -> ConvPlan:
    """Wrap bare COIR metadata as an einsum-only plan."""
    return ConvPlan(coir, None, REFERENCE_DISPATCH)


def resolve_backend(plan: ConvPlan, backend: str = AUTO,
                    ctx: ExecutionContext | None = None) -> str:
    """The backend a call will actually run, after plan-driven dispatch
    and fallback resolution through the context's registry."""
    ctx = ctx if ctx is not None else current_context()
    return ctx.registry.resolve(plan, backend)


def sparse_conv(
    x: jnp.ndarray,
    params: SparseConvParams,
    plan: ConvPlan,
    *,
    backend: str = AUTO,
    ctx: ExecutionContext | None = None,
    use_kernel: bool = True,
    interpret: bool | None = None,
    block_n: int | None = None,
) -> jnp.ndarray:
    """Run one sparse conv according to its plan -> (V_out, N) features."""
    ctx = ctx if ctx is not None else current_context()
    name = ctx.registry.resolve(plan, backend)
    return ctx.registry.get(name).run(
        x, params, plan, ctx=ctx, use_kernel=use_kernel, interpret=interpret,
        block_n=block_n)


def conv_block(x, mask, plan: ConvPlan, p, **conv_kw):
    """Conv + masked BN + ReLU, the SCN building block."""
    y = sparse_conv(x, p["conv"], plan, **conv_kw)
    return masked_batchnorm_relu(y, mask, p["bn_scale"], p["bn_offset"])


def _bn(x, mask, p, *, relu: bool = True):
    norm = masked_batchnorm_relu if relu else masked_batchnorm
    return norm(x, mask, p["scale"], p["offset"])


def residual_block(x, mask, plan: ConvPlan, p, **conv_kw):
    """MinkowskiEngine's ``BasicBlock``: conv-BN-ReLU-conv-BN plus the
    shortcut, then ReLU. Where the width changes the shortcut is a 1x1
    projection (``p["proj"]``, (C_in, C_out)) and BN."""
    y = _bn(sparse_conv(x, p["conv1"], plan, **conv_kw), mask, p["bn1"])
    y = _bn(sparse_conv(y, p["conv2"], plan, **conv_kw), mask, p["bn2"],
            relu=False)
    if "proj" in p:
        x = _bn(x @ p["proj"], mask, p["proj_bn"], relu=False)
    return jnp.maximum(y + x, 0)


def _block(x, mask, plan: ConvPlan, p, **conv_kw):
    block = residual_block if "conv2" in p else conv_block
    return block(x, mask, plan, p, **conv_kw)


#: decoder concat orders: SCN's ``[skip, up]`` and MinkowskiEngine's
#: ``ME.cat(up, skip)``
CONCATS = ("skip_up", "up_skip")


def apply_unet(
    params: dict,
    feats: jnp.ndarray,
    plan: "ScenePlan",
    *,
    concat: str = "skip_up",
    backend: str = AUTO,
    ctx: ExecutionContext | None = None,
    use_kernel: bool = True,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """U-Net forward off a ScenePlan -> (V, n_classes) level-0 logits.

    One walk serves every U-Net the params describe: the stem (on level
    0's ``stem`` site where the plan has one, else on its submanifold
    site; BN + ReLU after it where ``params["stem_bn"]`` exists), then per
    level the encoder blocks and the strided conv down, and from the
    coarsest level up the transposed conv, the concat in ``concat`` order
    and the decoder blocks, then the linear head. A block with ``conv2``
    is residual (``residual_block``), else SCN's ``conv_block``; a level's
    ``down_bn``/``up_bn`` put BN + ReLU after its down/up conv. A conv
    whose bias is None adds none.

    Plans carrying a ``scene_backend`` attribute (e.g. ``ShardedScenePlan``)
    are executed whole by that backend's ``run_unet`` hook — the level walk
    below only serves per-conv plans.
    """
    if concat not in CONCATS:
        raise ValueError(f"concat must be one of {CONCATS}, got {concat!r}")
    ctx = ctx if ctx is not None else current_context()
    scene_backend = getattr(plan, "scene_backend", None)
    if scene_backend is not None:
        if backend not in (AUTO, scene_backend):
            raise ValueError(
                f"plan is bound to scene-level backend {scene_backend!r}; "
                f"backend={backend!r} cannot serve it")
        if concat != "skip_up":
            raise ValueError(
                f"scene-level backend {scene_backend!r} walks SCN's "
                f"[skip, up] concat only, not {concat!r}")
        impl = ctx.registry.get(scene_backend)
        return impl.run_unet(params, feats, plan, ctx=ctx,
                             use_kernel=use_kernel, interpret=interpret)

    kw = dict(backend=backend, ctx=ctx, use_kernel=use_kernel,
              interpret=interpret)
    lvl0 = plan.levels[0]
    x = sparse_conv(feats, params["stem"],
                    lvl0.stem if lvl0.stem is not None else lvl0.sub, **kw)
    if "stem_bn" in params:
        x = _bn(x, lvl0.mask, params["stem_bn"])
    skips = []
    for li, lvl in enumerate(plan.levels):
        p = params["levels"][li]
        for blk in p["enc"]:
            x = _block(x, lvl.mask, lvl.sub, blk, **kw)
        if lvl.down is not None:
            skips.append(x)
            x = sparse_conv(x, p["down"], lvl.down, **kw)
            if "down_bn" in p:
                x = _bn(x, plan.levels[li + 1].mask, p["down_bn"])
    for li in range(len(plan.levels) - 2, -1, -1):
        lvl, p = plan.levels[li], params["levels"][li]
        up = sparse_conv(x, p["up"], lvl.up, **kw)
        if "up_bn" in p:
            up = _bn(up, lvl.mask, p["up_bn"])
        pair = [skips[li], up] if concat == "skip_up" else [up, skips[li]]
        x = jnp.concatenate(pair, axis=-1)
        for blk in p["dec"]:
            x = _block(x, lvl.mask, lvl.sub, blk, **kw)
    return x @ params["head"]["w"] + params["head"]["b"]
