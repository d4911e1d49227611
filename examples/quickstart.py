"""Quickstart: the AccSS3D pipeline on one synthetic scene.

pointcloud -> voxelize -> AdMAC adjacency -> SOAR reorder -> COIR metadata
-> SPADE dataflow plan -> engine dispatch (reference einsum vs SSpNNA
Pallas kernel, one ``sparse_conv`` entry point).

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import engine
from repro.core import soar, spade
from repro.core.hashgrid import build_neighbor_table, kernel_offsets
from repro.core.sparse_conv import init_sparse_conv, submanifold_coir
from repro.data.scenes import make_scene
from repro.launch.compile_cache import use_compile_cache
from repro.sparse.tensor import SparseVoxelTensor

RES, CAP = 48, 16384
use_compile_cache()

coords, feats, labels, mask = make_scene(0, RES, CAP)
t = SparseVoxelTensor(jnp.asarray(coords), jnp.asarray(feats), jnp.asarray(mask))
print(f"scene: {int(t.n_active())} active voxels "
      f"({int(t.n_active()) / RES**3:.1%} occupancy — spatial sparsity)")

# AdMAC: adjacency + COIR metadata
coir = submanifold_coir(t, RES, 3)
print(f"COIR: ARF = {float(coir.arf()):.2f} active neighbours / voxel (of 27)")

# SOAR reordering
nbr = np.asarray(build_neighbor_table(
    t.coords, t.mask, jnp.asarray(kernel_offsets(3)), RES))
order = soar.soar_order(nbr, np.asarray(t.mask), 512)
print(f"SOAR: {order.n_chunks} chunks")

# SPADE dataflow plan (64 KB L1 budget, like the paper)
attrs = spade.extract_attributes(np.asarray(coir.indices), np.asarray(t.mask),
                                 order.order)
layer = spade.LayerSpec("demo", int(t.n_active()), int(t.n_active()),
                        27, 4, 32, 2)
plan_df = spade.explore(layer, {"CIRF": attrs, "CORF": attrs}, 64 * 1024)
print(f"SPADE: walk={plan_df.walk} flavor={plan_df.flavor} "
      f"tile dO={plan_df.delta_major} -> {plan_df.da_elems:.2e} data accesses")

# Engine: one ConvPlan, two backends through the same entry point
d_i = int(plan_df.delta_major * attrs.at(plan_df.delta_major,
                                         "sa_minor_alloc_rst")) + 27
conv_plan = engine.conv_plan_for_layer(coir, order.order,
                                       plan_df.delta_major, d_i,
                                       walk=plan_df.walk)
params = init_sparse_conv(jax.random.PRNGKey(0), 27, 4, 32)
out = engine.sparse_conv(t.feats, params, conv_plan, backend="sspnna",
                         use_kernel=True)
ref = engine.sparse_conv(t.feats, params, conv_plan, backend="reference")
err = float(jnp.max(jnp.abs(out[np.asarray(t.mask)] - ref[np.asarray(t.mask)])))
print(f"SSpNNA kernel over {conv_plan.dispatch.n_tiles} tiles: "
      f"max |err| vs reference = {err:.2e}")
print("OK")
