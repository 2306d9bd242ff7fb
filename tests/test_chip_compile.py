"""The main path's kernels compile for a TPU v5e, with no chip attached.

Interpret mode (every other test file) cannot see what Mosaic refuses:
block shapes off the (8, 128) tiling, narrow DMA windows, VMEM beyond the
scoped limit.  These tests compile the kernels with ``interpret=False``
for one chip of a described ``v5e:2x2`` topology, at the widths and under
the tile plans the int8 vgg_imagenet program runs on the chip:

* ``conv2d_ws`` whole-map at 56×56×64 → 128 with ReLU, pool and requant;
* ``conv2d_ws`` and ``conv2d_ws_pipe`` under the plans
  ``program_tile_plans`` emits for vgg_imagenet's first two layers at
  batch 8 — plans that must be spatially tiled once VMEM is counted as
  laid out;
* ``matmul_ws`` for the 8×256 @ 256×1000 int8 classifier head.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import network
from repro.core.convcore import ConvCoreConfig
from repro.kernels.conv2d_ws import conv2d_ws
from repro.kernels.conv2d_ws_pipe import conv2d_ws_pipe
from repro.kernels.matmul_ws import matmul_ws

BATCH = 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; raises what Mosaic raises."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _conv_shapes(h, w, c, k):
    return [((BATCH, h, w, c), jnp.int8), ((3, 3, c, k), jnp.int8),
            ((k,), jnp.int32), ((k,), jnp.float32)]


def test_conv_whole_map_pool_requant(one_chip):
    def f(x, w, b, s):
        return conv2d_ws(x, w, b, s, padding="SAME", cin_banks=1,
                         kout_banks=1, relu=True, pool=True,
                         interpret=False)
    compiled = _compile(f, one_chip, *_conv_shapes(56, 56, 64, 128))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", [conv2d_ws, conv2d_ws_pipe],
                         ids=["conv2d_ws", "conv2d_ws_pipe"])
@pytest.mark.parametrize("layer", [0, 1])
def test_vgg_imagenet_tiled_layer_plans(one_chip, layer, kernel):
    plan = network.vgg_imagenet()
    tp = network.program_tile_plans(plan, ConvCoreConfig(int8=True))[layer]
    sp = plan.layers[layer]
    h, w, c = ([plan.input_shape] + plan.activation_shapes())[layer]
    assert tp.tiled and tp.fits_vmem, tp

    def f(x, wt, b, s):
        return kernel(x, wt, b, s, padding=sp.padding,
                      cin_banks=tp.cin_banks, kout_banks=tp.kout_banks,
                      h_tile=tp.h_tile, w_tile=tp.w_tile, relu=sp.relu,
                      pool=sp.pool, interpret=False)
    compiled = _compile(f, one_chip, *_conv_shapes(h, w, c, sp.features))
    assert "tpu_custom_call" in compiled.as_text()


def test_matmul_classifier_head(one_chip):
    def f(x, w, b):
        return matmul_ws(x, w, b, interpret=False)
    compiled = _compile(f, one_chip, ((BATCH, 256), jnp.int8),
                        ((256, 1000), jnp.int8), ((1000,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()
