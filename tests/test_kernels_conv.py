"""conv2d_ws Pallas kernel vs the pure-jnp oracle: shape/dtype sweeps,
banking variants, int8/wrap8 datapaths, bias preload, stride/padding
generality, and the fused ReLU → max-pool → requantize epilogue.

Every generalized case is checked against ``lax.conv_general_dilated``
(through kernels/ref.py) — the oracle itself is built on it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.conv2d_ws import conv2d_ws

RNG = np.random.default_rng(42)


def _f32(*shape):
    return jnp.asarray(RNG.normal(size=shape), jnp.float32)


def _i8(*shape):
    return jnp.asarray(RNG.integers(-128, 128, size=shape), jnp.int8)


@pytest.mark.parametrize("n,h,w,c,k,kh", [
    (1, 8, 8, 4, 4, 3),
    (2, 16, 12, 8, 8, 3),
    (1, 224, 224, 8, 8, 3),          # the paper's §5.2 workload
    (2, 10, 10, 16, 4, 1),           # 1×1 conv (≡ GEMM)
    (1, 9, 9, 4, 8, 5),              # 5×5 kernel
])
def test_float_matches_oracle(n, h, w, c, k, kh):
    x, wgt, b = _f32(n, h, w, c), _f32(kh, kh, c, k), _f32(k)
    got = ops.conv2d(x, wgt, b)
    want = ref.conv2d_ref(x, wgt, b)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("banks", [(1, 1), (2, 2), (4, 4), (4, 1), (1, 4),
                                   (8, 8)])
def test_banking_invariance(banks):
    """Any bank decomposition computes the same convolution (the paper's
    4-way split is a dataflow choice, not a semantic one)."""
    cb, kb = banks
    x, wgt, b = _f32(1, 12, 12, 8), _f32(3, 3, 8, 8), _f32(8)
    got = conv2d_ws(x, wgt, b, cin_banks=cb, kout_banks=kb, interpret=True)
    want = ref.conv2d_ref(x, wgt, b)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_divisibility_enforced():
    x, wgt = _f32(1, 8, 8, 6), _f32(3, 3, 6, 8)   # C=6 not divisible by 4
    with pytest.raises(ValueError, match="banking invariant"):
        conv2d_ws(x, wgt, interpret=True)


@pytest.mark.parametrize("c,k", [(4, 4), (8, 8), (16, 4)])
def test_int8_exact(c, k):
    x, wgt = _i8(1, 10, 10, c), _i8(3, 3, c, k)
    b = jnp.asarray(RNG.integers(-1000, 1000, size=(k,)), jnp.int32)
    got = ops.conv2d(x, wgt, b)
    want = ref.conv2d_ref_int8(x, wgt, b)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(got, want)


def test_wrap8_bit_faithful():
    """The Fig. 6 waveform mode: psums wrap in 8 bits."""
    x, wgt = _i8(1, 8, 8, 8), _i8(3, 3, 8, 4)
    got = ops.conv2d(x, wgt, wrap8=True)
    want = ref.conv2d_ref_wrap8(x, wgt)
    assert got.dtype == jnp.int8
    np.testing.assert_array_equal(got, want)
    # the wrap path has no requantize stage: combining it with out_scale
    # is a loud contract violation, not a silent drop
    with pytest.raises(ValueError, match="mutually exclusive"):
        ops.conv2d(x, wgt, wrap8=True, out_scale=jnp.float32(1e-3))


def test_bias_preload_equals_post_add():
    """M5: preloading bias into the accumulator == adding bias after."""
    x, wgt, b = _f32(1, 10, 10, 4), _f32(3, 3, 4, 4), _f32(4)
    with_bias = ops.conv2d(x, wgt, b)
    without = ops.conv2d(x, wgt, None)
    np.testing.assert_allclose(with_bias, without + b, rtol=1e-5, atol=1e-5)


def test_requantized_output():
    x, wgt = _i8(1, 8, 8, 4), _i8(3, 3, 4, 4)
    scale = jnp.float32(1e-3)
    got = ops.conv2d(x, wgt, out_scale=scale)
    assert got.dtype == jnp.int8
    acc = ref.conv2d_ref_int8(x, wgt)
    want = jnp.clip(jnp.round(acc.astype(jnp.float32) * scale),
                    -128, 127).astype(jnp.int8)
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Generalized conv: stride / padding / fused epilogue
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("padding", ["VALID", "SAME"])
def test_stride_padding_matches_lax(stride, padding):
    x, wgt, b = _f32(2, 13, 11, 8), _f32(3, 3, 8, 4), _f32(4)
    got = ops.conv2d(x, wgt, b, stride=stride, padding=padding)
    pad = ref.normalize_padding(padding, 3, 3, stride, 13, 11)
    want = jax.lax.conv_general_dilated(
        x, wgt, window_strides=(stride, stride), padding=pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + b
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_explicit_padding():
    x, wgt = _f32(1, 9, 9, 4), _f32(3, 3, 4, 4)
    got = ops.conv2d(x, wgt, padding=((2, 1), (0, 2)))
    want = jax.lax.conv_general_dilated(
        x, wgt, window_strides=(1, 1), padding=((2, 1), (0, 2)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("stride,padding", [(1, "SAME"), (2, "SAME"),
                                            (1, "VALID")])
def test_fused_relu_pool_epilogue(stride, padding):
    """ReLU + 2×2 max-pool fused in the kernel == lax conv + post ops."""
    x, wgt, b = _f32(1, 12, 14, 4), _f32(3, 3, 4, 8), _f32(8)
    got = ops.conv2d(x, wgt, b, stride=stride, padding=padding,
                     relu=True, pool=True)
    conv = jax.lax.conv_general_dilated(
        x, wgt, window_strides=(stride, stride),
        padding=ref.normalize_padding(padding, 3, 3, stride, 12, 14),
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + b
    want = ref.maxpool2d_ref(jnp.maximum(conv, 0))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_pool_floor_semantics_odd_output():
    """Odd conv outputs drop the trailing row/col (floor), like the oracle."""
    x, wgt = _f32(1, 9, 9, 4), _f32(3, 3, 4, 4)     # VALID → 7×7 conv out
    got = ops.conv2d(x, wgt, pool=True)
    want = ref.maxpool2d_ref(ref.conv2d_ref(x, wgt))
    assert got.shape == (1, 3, 3, 4)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("h,w,kh,stride,dilation,cin_banks", [
    pytest.param(12, 12, 3, 2, 1, 1, id="stride2"),
    pytest.param(9, 7, 3, 1, 1, 2, id="w7"),
    pytest.param(14, 14, 3, 1, 1, 4, id="w14-4banks"),
    pytest.param(12, 14, 2, 1, 1, 2, id="2x2"),
    pytest.param(10, 14, 1, 1, 1, 2, id="1x1"),
    pytest.param(14, 14, 3, 1, 2, 2, id="dilation2"),
])
@pytest.mark.parametrize("per_channel", [False, True])
def test_int8_fused_epilogue_exact(per_channel, h, w, kh, stride, dilation,
                                   cin_banks):
    """The production path: int8 in, fused ReLU→pool→requantize, int8 out —
    bit-exact vs the int32 oracle chain, with the taps folded into one
    contraction at widths off the sublane tile, for 1, 4 and 9 taps,
    dilated, over several cin banks."""
    x, wgt = _i8(1, h, w, 8), _i8(kh, kh, 8, 8)
    b = jnp.asarray(RNG.integers(-500, 500, size=(8,)), jnp.int32)
    scale = (jnp.asarray(RNG.uniform(5e-4, 2e-3, size=(8,)), jnp.float32)
             if per_channel else jnp.float32(1e-3))
    kw = dict(stride=stride, padding="SAME", relu=True, pool=True,
              dilation=dilation)
    got = conv2d_ws(x, wgt, b, scale, cin_banks=cin_banks, kout_banks=2,
                    interpret=True, **kw)
    want = ref.conv2d_epilogue_ref(x, wgt, b, out_scale=scale, **kw)
    assert got.dtype == jnp.int8
    np.testing.assert_array_equal(got, want)


def test_float_out_scale_requantizes():
    """Regression: float inputs with out_scale used to silently drop the
    requantize (f32 out while the ref path returned int8).  The fused
    epilogue now covers the float accumulator path too — integer-valued
    float inputs make both accumulations exact, so the comparison is
    bit-strict."""
    x = jnp.asarray(RNG.integers(-8, 8, (1, 10, 10, 4)), jnp.float32)
    wgt = jnp.asarray(RNG.integers(-4, 4, (3, 3, 4, 4)), jnp.float32)
    b = jnp.asarray(RNG.integers(-10, 10, (4,)), jnp.float32)
    scale = jnp.float32(0.05)
    got = ops.conv2d(x, wgt, b, relu=True, out_scale=scale)
    want = ref.conv2d_epilogue_ref(x, wgt, b, relu=True, out_scale=scale)
    assert got.dtype == jnp.int8 and want.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_int8_stride2_same_exact():
    x, wgt = _i8(2, 11, 11, 4), _i8(3, 3, 4, 8)
    got = ops.conv2d(x, wgt, stride=2, padding="SAME")
    want = ref.conv2d_ref_int8(x, wgt, stride=2, padding="SAME")
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(got, want)
