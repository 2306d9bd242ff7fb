"""The reference layers for strided, grouped and pooled configurations
against plain NumPy loops at a tiny size."""

import numpy as np
import pytest

from perfbench.harness import plain


def _pads(n, extent, stride, padding):
    """(before, after) padding of one axis, SAME as TensorFlow pads it."""
    if padding == "VALID":
        return 0, 0
    total = max((-(-n // stride) - 1) * stride + extent - n, 0)
    return total // 2, total - total // 2


def _np_conv(x, w, b, stride, padding, dilation, groups):
    n, h, wd, c = x.shape
    kh, kw, cg, f = w.shape
    ext_h, ext_w = (kh - 1) * dilation + 1, (kw - 1) * dilation + 1
    ph, pw = _pads(h, ext_h, stride, padding), _pads(wd, ext_w, stride,
                                                     padding)
    xp = np.pad(x.astype(np.float64), ((0, 0), ph, pw, (0, 0)))
    oh = (xp.shape[1] - ext_h) // stride + 1
    ow = (xp.shape[2] - ext_w) // stride + 1
    fg = f // groups
    y = np.zeros((n, oh, ow, f))
    for i in range(oh):
        for j in range(ow):
            for a in range(kh):
                for e in range(kw):
                    px = xp[:, i * stride + a * dilation,
                            j * stride + e * dilation, :]
                    for g in range(groups):
                        y[:, i, j, g * fg:(g + 1) * fg] += (
                            px[:, g * cg:(g + 1) * cg]
                            @ w[a, e, :, g * fg:(g + 1) * fg])
    return y + b


def _draw(*shapes):
    rng = np.random.default_rng(7)
    return [rng.standard_normal(s).astype(np.float32) for s in shapes]


@pytest.mark.parametrize("k,stride,padding,dilation,groups", [
    (7, 2, "SAME", 1, 1),       # a stem: 7x7/2
    (3, 2, "SAME", 1, 1),       # a stage entry: 3x3/2
    (1, 2, "VALID", 1, 1),      # a projection: 1x1/2
    (3, 1, "SAME", 2, 2),       # dilated and grouped
    (3, 2, "VALID", 1, 4),
])
def test_conv_matches_loop(k, stride, padding, dilation, groups):
    x, w, b = _draw((2, 9, 8, 4), (k, k, 4 // groups, 8), (8,))
    got = plain.conv(x, w, b, stride, padding, dilation, groups)
    np.testing.assert_allclose(
        got, _np_conv(x, w, b, stride, padding, dilation, groups),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("stride,padding", [(1, "SAME"), (2, "SAME"),
                                            (2, "VALID")])
def test_depthwise_matches_loop(stride, padding):
    x, w, b = _draw((2, 7, 6, 5), (3, 3, 1, 5), (5,))
    got = plain.depthwise(x, w, b, stride, padding)
    np.testing.assert_allclose(
        got, _np_conv(x, w, b, stride, padding, 1, groups=5),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("size,stride,padding", [
    (3, 2, "SAME"),             # ResNet's stem pool
    (2, None, "VALID"),         # 2x2/2, an odd last row dropped
    (3, 2, "VALID"),
])
def test_maxpool_matches_loop(size, stride, padding):
    # all negative, so a zero pad would win where a -inf pad must not
    (x,) = _draw((2, 7, 8, 3))
    x = -np.abs(x) - 1.0
    s = stride or size
    ph, pw = _pads(7, size, s, padding), _pads(8, size, s, padding)
    xp = np.pad(x, ((0, 0), ph, pw, (0, 0)), constant_values=-np.inf)
    oh, ow = (xp.shape[1] - size) // s + 1, (xp.shape[2] - size) // s + 1
    want = np.array([[[xp[n, i * s:i * s + size, j * s:j * s + size].max(
        axis=(0, 1)) for j in range(ow)] for i in range(oh)]
        for n in range(2)])
    got = np.asarray(plain.maxpool(x, size, stride, padding))
    assert got.shape == want.shape
    np.testing.assert_array_equal(got, want)


def test_global_mean_matches_loop():
    (x,) = _draw((2, 7, 7, 6))
    want = [[x[n, :, :, c].astype(np.float64).sum() / 49 for c in range(6)]
            for n in range(2)]
    np.testing.assert_allclose(plain.global_mean(x), want, rtol=1e-6)
