"""repro.engine: plan-driven sparse-conv execution.

Build a ``ScenePlan`` once per input scene (COIR + SOAR + SPADE + tiles),
then run every conv through ``sparse_conv`` / every U-Net through
``apply_unet``. Dispatch goes through the backend registry
(``engine.backends``) under an ``ExecutionContext`` (``engine.context``)
that owns the mesh, registry view and plan cache; mesh-sharded scenes
(``engine.shard``) execute as the registered ``"sharded"`` backend with
halo exchange for cross-shard receptive fields.
"""
from repro.engine.api import (
    apply_unet,
    available_backends,
    conv_block,
    reference_plan,
    resolve_backend,
    sparse_conv,
)
from repro.engine.backends import (
    AUTO,
    Backend,
    BackendRegistry,
    default_registry,
    register_backend,
)
from repro.engine.context import (
    ExecutionContext,
    current_context,
    default_context,
    set_default_context,
    use_context,
)
from repro.engine.plan import (
    REFERENCE,
    SSPNNA,
    ConvPlan,
    Dispatch,
    LevelPlan,
    PlanCache,
    PlanSpec,
    ScenePlan,
    SignatureFamily,
    StreamPlanState,
    TileArrays,
    build_plan_spec,
    build_signature_family,
    choose_buckets,
    build_scene_plan,
    build_scene_plan_host,
    conv_plan_for_layer,
    dispatch_from_dataflow,
    level_geometry,
    scene_key,
    upload_scene_plan,
)
from repro.engine.shard import (  # noqa: F401  (registers the backend too)
    SHARDED,
    ShardLayout,
    ShardedScenePlan,
    apply_unet_sharded,
    build_sharded_scene_plan,
    build_sharded_scene_plan_host,
    pin_halo,
    upload_sharded_scene_plan,
)


def __getattr__(name: str):
    # legacy closed-enum alias; api owns the (single) definition
    if name == "BACKENDS":
        from repro.engine import api
        return api.BACKENDS
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "AUTO",
    "BACKENDS",
    "REFERENCE",
    "SHARDED",
    "SSPNNA",
    "Backend",
    "BackendRegistry",
    "ConvPlan",
    "Dispatch",
    "ExecutionContext",
    "LevelPlan",
    "PlanCache",
    "PlanSpec",
    "ScenePlan",
    "ShardLayout",
    "ShardedScenePlan",
    "SignatureFamily",
    "StreamPlanState",
    "TileArrays",
    "apply_unet",
    "apply_unet_sharded",
    "available_backends",
    "build_plan_spec",
    "build_scene_plan",
    "build_scene_plan_host",
    "build_sharded_scene_plan",
    "build_sharded_scene_plan_host",
    "build_signature_family",
    "choose_buckets",
    "conv_block",
    "conv_plan_for_layer",
    "current_context",
    "default_context",
    "default_registry",
    "dispatch_from_dataflow",
    "level_geometry",
    "pin_halo",
    "reference_plan",
    "register_backend",
    "resolve_backend",
    "scene_key",
    "set_default_context",
    "sparse_conv",
    "upload_scene_plan",
    "upload_sharded_scene_plan",
    "use_context",
]
