"""Strided convolutions and padded, strided pools — ResNet's stem, its
stage-entry 3×3/2 convs and 1×1/2 projections, and its 3×3/2 SAME max
pool — against the int8 oracles, bit for bit.

Both conv kernels run a stride-s layer as the stride-1 conv over the
space-to-depth phases of its padded map (``conv2d_ws.setup_conv``), so
these cases cover the layout (phases, zero taps past a kernel's edge, the
odd SAME pad at the bottom and right), whole-map and row-tiled, with the
fused ReLU → requantize epilogue and the 2×2 pool.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import banking, network
from repro.core.convcore import ConvCoreConfig
from repro.kernels import ref
from repro.kernels.conv2d_ws import conv2d_ws, setup_conv
from repro.kernels.conv2d_ws_pipe import conv2d_ws_pipe

RNG = np.random.default_rng(1512)


def _i8(*shape):
    return jnp.asarray(RNG.integers(-128, 128, size=shape), jnp.int8)


@pytest.mark.parametrize("kernel", [conv2d_ws, conv2d_ws_pipe],
                         ids=["conv2d_ws", "conv2d_ws_pipe"])
@pytest.mark.parametrize("k,c", [(1, 16), (3, 8), (7, 3)],
                         ids=["1x1", "3x3", "7x7"])
@pytest.mark.parametrize("h,w", [(14, 14), (15, 13)], ids=["even", "odd"])
@pytest.mark.parametrize("h_tile", [0, 2], ids=["whole", "tiled"])
def test_stride2_int8_matches_oracle(kernel, k, c, h, w, h_tile):
    x, wgt = _i8(2, h, w, c), _i8(k, k, c, 8)
    b = jnp.asarray(RNG.integers(-500, 500, size=(8,)), jnp.int32)
    scale = jnp.asarray(RNG.uniform(5e-4, 2e-3, size=(8,)), jnp.float32)
    kw = dict(stride=2, padding="SAME", relu=True)
    got = kernel(x, wgt, b, scale, cin_banks=1, kout_banks=1, h_tile=h_tile,
                 interpret=True, **kw)
    want = ref.conv2d_epilogue_ref(x, wgt, b, out_scale=scale, **kw)
    assert got.dtype == jnp.int8 and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    acc = kernel(x, wgt, b, cin_banks=1, kout_banks=1, h_tile=h_tile,
                 interpret=True, stride=2, padding="SAME")
    np.testing.assert_array_equal(
        np.asarray(acc),
        np.asarray(ref.conv2d_ref_int8(x, wgt, b, stride=2,
                                       padding="SAME")))


@pytest.mark.parametrize("groups,dilation,pool", [(1, 2, False),
                                                  (2, 1, True)],
                         ids=["dilated", "grouped-pooled"])
def test_stride2_dilated_and_grouped_layouts(groups, dilation, pool):
    """A dilated kernel's taps spread over its extent before the phases
    split them; a grouped layer's phases stay inside each group's channel
    slice."""
    x, wgt = _i8(1, 16, 14, 8), _i8(3, 3, 8 // groups, 8)
    kw = dict(stride=2, padding="SAME", groups=groups, dilation=dilation,
              pool=pool, relu=True)
    a = conv2d_ws(x, wgt, cin_banks=1, kout_banks=groups, interpret=True,
                  **kw)
    p = conv2d_ws_pipe(x, wgt, cin_banks=1, kout_banks=groups,
                       interpret=True, **kw)
    want = ref.conv2d_epilogue_ref(x, wgt, **kw)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(p), np.asarray(want))


@pytest.mark.parametrize("k,taps,channels", [(1, 1, 16), (3, 2, 64),
                                             (7, 4, 64)])
def test_strided_layer_is_laid_out_as_stride_1(k, taps, channels):
    """The kernels see a stride-1 VALID layer: ⌈k/2⌉ taps per axis over
    the phases that hold a tap (one for a 1×1 kernel: a subsample)."""
    x, wgt = _i8(1, 56, 56, 16), _i8(k, k, 16, 8)
    xs, ws, g = setup_conv(x, wgt, stride=2, padding="SAME", cin_banks=1,
                           kout_banks=1)
    assert g.strided and (g.kh, g.kw) == (taps, taps)
    assert ws.shape == (taps, taps, channels, 8)
    assert xs.shape == (1, 28 + taps - 1, 28 + taps - 1, channels)
    assert (g.poh, g.pow_) == (28, 28)
    _, _, g1 = setup_conv(x, wgt, padding="SAME", cin_banks=1,
                          kout_banks=1)
    assert not g1.strided


def test_strided_plan_counts_the_stride_1_form():
    """The planner counts a strided layer's VMEM on the form the kernels
    run; the plan's output and tiles are the layer's own."""
    p = banking.plan_tiles(56, 56, 128, 128, 3, 3, stride=2, padding="SAME",
                           in_bytes=1, out_bytes=1, vmem_budget=None)
    assert (p.out_h, p.out_w, p.h_tile, p.stride) == (28, 28, 28, 2)
    want = banking.conv_vmem_bytes(29, 29, 4 * 128, 2, 2, 128, 28, 28,
                                   False, 1, 1, 4)
    assert p.working_set_bytes == want


def test_maxpool_same_pads_with_the_least_value():
    """A SAME max pool's padding never wins a window: an all-negative int8
    map pools to the largest value each window covers (−128 padding),
    never to 0."""
    x = jnp.asarray(RNG.integers(-128, -1, size=(2, 8, 8, 3)), jnp.int8)
    got = np.asarray(ref.maxpool2d_ref(x, 3, 2, "SAME"))
    assert got.shape == (2, 4, 4, 3)
    xn = np.asarray(x)
    for i in range(4):
        for j in range(4):
            # SAME at 8 → 4 pads 0 above and left, 1 below and right
            win = xn[:, 2 * i:2 * i + 3, 2 * j:2 * j + 3]
            np.testing.assert_array_equal(got[:, i, j], win.max(axis=(1, 2)))


def test_avgpool_same_averages_the_positions_it_covers():
    x = jnp.asarray(RNG.integers(-100, 100, size=(1, 5, 5, 2)), jnp.int8)
    got = np.asarray(ref.avgpool2d_ref(x, 3, 2, "SAME"))
    xn = np.asarray(x).astype(np.float64)
    for i in range(3):
        for j in range(3):
            win = xn[:, max(0, 2 * i - 1):2 * i + 2,
                     max(0, 2 * j - 1):2 * j + 2]
            # 5 → 3 at 3/2 pads one on each side
            np.testing.assert_array_equal(
                got[:, i, j], np.clip(np.round(win.mean(axis=(1, 2))),
                                      -128, 127))


@pytest.mark.parametrize("kind,size,stride,padding,shape", [
    ("pool", 3, 2, "SAME", (8, 8, 8)),
    ("pool", 3, 1, "VALID", (14, 14, 8)),
    ("avgpool", 3, 2, "SAME", (8, 8, 8)),
    ("avgpool", 2, 1, "VALID", (15, 15, 8)),
])
def test_padded_strided_pools_through_the_network(kind, size, stride,
                                                  padding, shape):
    """Every walk honours a pool's window, stride and padding: the shape
    walk, the float oracle and the int8 program, Pallas and reference
    backends bit-identical."""
    make = network.maxpool if kind == "pool" else network.avgpool
    plan = network.NetworkPlan(
        name="pools", input_shape=(16, 16, 4),
        layers=(network.conv(8, relu=True),
                make(size, stride=stride, padding=padding),
                network.global_pool(), network.dense(5)))
    assert plan.activation_shapes()[1] == shape
    params = plan.init_params(RNG)
    x = jnp.asarray(RNG.normal(size=(2, 16, 16, 4)), jnp.float32)
    acts = [h for _, _, _, h in plan.forward_activations(params, x)]
    oracle = (ref.maxpool2d_ref if kind == "pool" else ref.avgpool2d_ref)
    np.testing.assert_allclose(np.asarray(acts[1]), np.asarray(
        oracle(acts[0], size, stride, padding)), rtol=1e-6)
    qnet = network.quantize_network(plan, params, x)
    a = network.make_int8_program(qnet, ConvCoreConfig(int8=True))(x)
    b = network.make_int8_program(
        qnet, ConvCoreConfig(backend="ref", int8=True))(x)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    want = plan.apply_ref(params, x)
    assert float(jnp.linalg.norm(a - want) / jnp.linalg.norm(want)) < 0.1


def _resnet_v15_mini():
    """ResNet-50 v1.5's node kinds at test size: the 7×7/2 stem, the
    3×3/2 SAME max pool, an identity bottleneck, a stage entry with the
    stride on its 3×3 and a 1×1/2 projection, the global pool and fc."""
    def block(n, src, mid, stride):
        out = [network.conv(mid, kernel=1, name=f"{n}_1", input=src),
               network.conv(mid, stride=stride, name=f"{n}_2"),
               network.conv(4 * mid, kernel=1, relu=False, name=f"{n}_3")]
        skip = src
        if stride > 1 or src == "pool1":
            skip = f"{n}_proj"
            out.append(network.conv(4 * mid, kernel=1, stride=stride,
                                    relu=False, name=skip, input=src))
        return out + [network.add(skip, f"{n}_3", relu=True, name=n)]

    layers = [network.conv(8, kernel=7, stride=2, name="conv1"),
              network.maxpool(3, stride=2, padding="SAME", name="pool1")]
    layers += block("res2a", "pool1", 4, 1) + block("res2b", "res2a", 4, 1)
    layers += block("res3a", "res2b", 8, 2)
    layers += [network.global_pool(name="pool5"),
               network.dense(10, name="fc")]
    return network.NetworkPlan(name="resnet_v15_mini",
                               input_shape=(30, 30, 3), layers=tuple(layers))


@pytest.mark.parametrize("make", [_resnet_v15_mini,
                                  network.resnet_bottleneck],
                         ids=["v1.5-mini", "zoo"])
def test_bottleneck_plan_through_int8_program(make):
    plan = make()
    params = plan.init_params(RNG)
    x = jnp.asarray(RNG.normal(size=(3, *plan.input_shape)), jnp.float32)
    qnet = network.quantize_network(plan, params, x)
    a = network.make_int8_program(qnet, ConvCoreConfig(int8=True))(x)
    b = network.make_int8_program(
        qnet, ConvCoreConfig(backend="ref", int8=True))(x)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    want = plan.apply_ref(params, x)
    assert float(jnp.linalg.norm(a - want) / jnp.linalg.norm(want)) < 0.1


def test_dense_over_global_pool_averages_its_accumulators():
    """The int8 program runs a dense layer that reads a global pool on
    every position of the pooled map and averages the int32 accumulators:
    the mean of the per-position products, which no rounding of the
    pooled mean onto the map's int8 grid reaches."""
    plan = network.NetworkPlan(
        name="gap", input_shape=(6, 6, 4),
        layers=(network.conv(8, relu=True, name="c"),
                network.global_pool(name="gap"),
                network.dense(3, name="fc")))
    params = plan.init_params(RNG)
    x = jnp.asarray(RNG.normal(size=(2, 6, 6, 4)), jnp.float32)
    qnet = network.quantize_network(plan, params, x)
    maps = {}
    network.int8_forward(
        qnet, x, backend=network.get_backend("ref"),
        tile_plans=network.program_tile_plans(
            plan, ConvCoreConfig(int8=True)),
        node_hook=lambda i, name, sp, h: maps.setdefault(name, h))
    c = np.asarray(maps["c"]).astype(np.int64).reshape(2, 36, 8)
    w = np.asarray(qnet.weights[2]).astype(np.int64)
    acc = (c @ w + np.asarray(qnet.biases[2])).mean(axis=1)
    np.testing.assert_allclose(
        np.asarray(maps["fc"]), acc * np.asarray(qnet.out_dequant),
        rtol=1e-6)
