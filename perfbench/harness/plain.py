"""Plain float32 layers for the configurations' reference forward passes.

Straight ``jax.numpy``/``lax`` at ``Precision.HIGHEST`` (a float32 matrix
product on a TPU otherwise runs in bfloat16), NHWC maps and HWIO weights.
Nothing here imports the program under test.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def conv_same(x, w, b):
    """Stride-1 convolution, zero padding that keeps H x W."""
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    return y + b


def up_conv(x, w, b):
    """Transposed convolution whose kernel equals its stride (U-Net's 2x2/2
    up-convolution): input pixel (i, j) scatters ``x[i, j] @ w[a, b]`` to
    output pixel (s*i + a, s*j + b), so every output pixel gets one tap."""
    kh, kw, _, k = w.shape
    n, h, wd, _ = x.shape
    y = jnp.einsum("nijc,abck->niajbk", x, w, precision=HIGHEST)
    return y.reshape(n, h * kh, wd * kw, k) + b


def maxpool2(x):
    """2x2 max pool with stride 2; an odd trailing row or column is dropped."""
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


def conv(x, w, b, stride: int = 1, padding: str = "SAME", dilation: int = 1,
         groups: int = 1):
    """Convolution with ``stride``, SAME or VALID zero ``padding``, taps
    ``dilation`` apart and ``groups`` channel groups (``w`` is
    [KH, KW, C/groups, K]; SAME pads as TensorFlow does, the extra row or
    column at the bottom or right)."""
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding=padding,
        rhs_dilation=(dilation, dilation),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=HIGHEST)
    return y + b


def depthwise(x, w, b, stride: int = 1, padding: str = "SAME"):
    """Depthwise convolution: each channel of ``x`` filtered by its own
    kernel, ``w`` [KH, KW, 1, C]."""
    return conv(x, w, b, stride, padding, groups=x.shape[-1])


def maxpool(x, size: int, stride: Optional[int] = None,
            padding: str = "VALID"):
    """``size`` x ``size`` max pool with ``stride`` (None: ``size``); SAME
    pads with -inf, so a padded position never wins."""
    s = stride or size
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, size, size, 1), (1, s, s, 1), padding)


def global_mean(x):
    """Mean over the map, [N, H, W, C] -> [N, C]."""
    return jnp.mean(x, axis=(1, 2))


def dense(x, w, b):
    return jnp.dot(x, w, precision=HIGHEST) + b


def relu(x):
    return jnp.maximum(x, 0.0)


def fake_quant(x, scale, bits: int):
    """Symmetric quantize-dequantize onto a ``bits``-bit grid."""
    top = 2 ** (bits - 1) - 1
    return jnp.clip(jnp.round(x / scale), -top - 1, top) * scale


def symmetric_scale(x, bits: int):
    """max|x| / (2**(bits-1) - 1), the per-tensor symmetric scale."""
    top = 2 ** (bits - 1) - 1
    return jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / top


def he_shapes_init(key, shapes):
    """He-normal weights (std sqrt(2 / fan_in)) and N(0, 0.05) biases for
    ``shapes`` = [(w_shape, b_shape), ...], all float32."""
    keys = jax.random.split(key, 2 * len(shapes))
    out = []
    for i, (ws, bs) in enumerate(shapes):
        std = np.sqrt(2.0 / float(np.prod(ws[:-1])))
        out.append((jax.random.normal(keys[2 * i], ws, jnp.float32) * std,
                    jax.random.normal(keys[2 * i + 1], bs, jnp.float32)
                    * 0.05))
    return out
