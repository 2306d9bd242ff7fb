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
* both conv kernels under VGG-16's conv4_2 plan (28×28×512 → 512, banks
  4×4) and conv5_3 plan (14×14×512 → 512 with the fused pool), where
  the map width is off the sublane tile: the planner's VMEM count must
  cover the scoped allocation Mosaic reports for the kernel;
* ``matmul_ws`` for the 8×256 @ 256×1000 int8 classifier head;
* ResNet-50's strided layers at batch 32 under the plans the program runs
  them on (the 7×7/2 stem, ``res3a_2``, ``res5a_2``, ``res4a_proj``):
  each compiles as the stride-1 conv over its space-to-depth phases,
  under the kernel's ``_strided`` name, within the planner's VMEM count.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import banking, network
from repro.core.convcore import ConvCoreConfig
from repro.kernels import conv2d_ws as seq_mod
from repro.kernels import conv2d_ws_pipe as pipe_mod
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


def _scoped_vmem_bytes(monkeypatch, mod, kernel, sharding, shapes, **kw):
    """The scoped VMEM Mosaic allocates for ``kernel``: compiled under a
    4 KiB limit, the compiler refuses it and names the size it needed."""
    monkeypatch.setattr(mod, "VMEM_LIMIT_BYTES", 4096)

    def f(x, wt, b, s):                # unjitted, so the limit is traced
        return kernel.__wrapped__(x, wt, b, s, interpret=False, **kw)
    with pytest.raises(Exception, match="Scoped allocation") as err:
        _compile(f, sharding, *shapes)
    size, unit = re.search(r"Scoped allocation with size ([\d.]+)([KM]?)",
                           str(err.value)).groups()
    return float(size) * {"": 1, "K": 2**10, "M": 2**20}[unit]


@pytest.mark.parametrize("kernel,mod", [(conv2d_ws, seq_mod),
                                        (conv2d_ws_pipe, pipe_mod)],
                         ids=["conv2d_ws", "conv2d_ws_pipe"])
@pytest.mark.parametrize("hw,pool", [(28, False), (14, True)],
                         ids=["conv4_2", "conv5_3"])
def test_vgg16_deep_layer_plans_fit_mosaic(one_chip, monkeypatch, kernel,
                                           mod, hw, pool):
    tp = banking.plan_tiles(hw, hw, 512, 512, 3, 3, padding="SAME",
                            pool=pool, in_bytes=1, out_bytes=1)
    assert (tp.cin_banks, tp.kout_banks, tp.tiled) == (4, 4, False), tp
    kw = dict(padding="SAME", cin_banks=4, kout_banks=4, relu=True,
              pool=pool)
    shapes = _conv_shapes(hw, hw, 512, 512)

    def f(x, wt, b, s):
        return kernel(x, wt, b, s, interpret=False, **kw)
    assert "tpu_custom_call" in _compile(f, one_chip, *shapes).as_text()
    scoped = _scoped_vmem_bytes(monkeypatch, mod, kernel, one_chip, shapes,
                                **kw)
    assert tp.working_set_bytes >= scoped, (tp.working_set_bytes, scoped)


def test_matmul_classifier_head(one_chip):
    def f(x, w, b):
        return matmul_ws(x, w, b, interpret=False)
    compiled = _compile(f, one_chip, ((BATCH, 256), jnp.int8),
                        ((256, 1000), jnp.int8), ((1000,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


# (H, W, C, K, kernel, ReLU) of ResNet-50 v1.5's strided layers
RESNET50_STRIDED = {
    "conv1": (224, 224, 3, 64, 7, True),
    "res3a_2": (56, 56, 128, 128, 3, True),
    "res5a_2": (14, 14, 512, 512, 3, True),
    "res4a_proj": (28, 28, 512, 1024, 1, False),
}


@pytest.mark.parametrize("layer", sorted(RESNET50_STRIDED))
def test_resnet50_strided_layer_plans_fit_mosaic(one_chip, monkeypatch,
                                                 layer):
    h, w, c, k, kk, relu = RESNET50_STRIDED[layer]
    tp = banking.plan_tiles(h, w, c, k, kk, kk, stride=2, padding="SAME",
                            in_bytes=1, out_bytes=1)
    assert tp.fits_vmem, tp
    kernel, mod = ((conv2d_ws_pipe, pipe_mod) if tp.pipelined
                   else (conv2d_ws, seq_mod))
    kw = dict(stride=2, padding="SAME", cin_banks=tp.cin_banks,
              kout_banks=tp.kout_banks, h_tile=tp.h_tile, relu=relu)
    shapes = [((32, h, w, c), jnp.int8), ((kk, kk, c, k), jnp.int8),
              ((k,), jnp.int32), ((k,), jnp.float32)]

    def f(x, wt, b, s):
        return kernel(x, wt, b, s, interpret=False, **kw)
    text = _compile(f, one_chip, *shapes).as_text()
    name = "conv2d_ws_pipe" if tp.pipelined else "conv2d_ws"
    assert re.search(rf"%{name}_strided\.\d+ = \S+ custom-call", text)
    scoped = _scoped_vmem_bytes(monkeypatch, mod, kernel, one_chip, shapes,
                                **kw)
    assert tp.working_set_bytes >= scoped, (tp.working_set_bytes, scoped)
