"""Compile the fused SSpNNA kernel for a described TPU v5e, without a chip.

The TPU compiler refuses what interpret mode accepts (unaligned DMA slices,
sublane-to-lane reshapes, SMEM and VMEM overflows), so these tests compile
the kernel at the published SCN U-Net widths for a ``v5e:2x2`` topology and
check that the program holds the Mosaic kernel (``tpu_custom_call``).
Nothing runs. The topology is described inside a fixture, never at import:
only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.sspnna.sspnna import sspnna_fused

V = 65536  # voxel capacity of a served indoor scene


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _conv_args(sharding, c, n, k, t, d_o, d_i, batch=()):
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(batch + shape, dtype, sharding=sharding)

    return (sds((V, c)), sds((k, c, n)), sds((t, d_o), jnp.int32),
            sds((t, d_i), jnp.int32), sds((t, d_o, k), jnp.int32),
            sds((t,), jnp.int32))


def _fused(*args):
    return sspnna_fused(*args, n_out=V, interpret=False)


# (c_in, c_out, tile dO, tile dI, tiles): the stem at the level-0 tile
# shape SPADE pins for 50k-voxel scenes at 5 cm (the largest one-hot), an
# m=16 block at the d_O=32 tiles of the coarser levels, the widest encoder
# block, and a decoder block reading a skip concat
SITES = [
    pytest.param(4, 16, 512, 884, 208, id="stem-4to16"),
    pytest.param(16, 16, 32, 148, 2278, id="width16"),
    pytest.param(112, 112, 32, 59, 4, id="level6-112"),
    pytest.param(224, 112, 32, 148, 64, id="concat-224to112"),
]


@pytest.mark.parametrize("k", [27, 8])
@pytest.mark.parametrize("c,n,d_o,d_i,t", SITES)
def test_fused_kernel_compiles_for_v5e(one_chip, c, n, d_o, d_i, t, k):
    args = _conv_args(one_chip, c, n, k, t, d_o, d_i)
    hlo = jax.jit(_fused).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_batched_fused_kernel_compiles_for_v5e(one_chip):
    """``SceneEngine`` vmaps the U-Net over a wave's scenes; every plan
    table is batched, which jax lowers as a loop of kernel calls."""
    args = _conv_args(one_chip, 16, 16, 27, 64, 32, 148, batch=(2,))
    hlo = jax.jit(jax.vmap(_fused)).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
