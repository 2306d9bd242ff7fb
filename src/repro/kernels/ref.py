"""Pure-jnp oracles for every kernel (the correctness contract).

Includes the paper-faithful int8 datapath variants:
* int8 inputs with int32 accumulation (production),
* ``wrap8``: 8-bit wrap-around psum accumulation, bit-matching the Fig.6
  simulation waveform (psums stored in 8-bit BRAM slots).
"""

from __future__ import annotations

from typing import Tuple, Union

import jax
import jax.numpy as jnp

Padding = Union[str, int, Tuple[Tuple[int, int], Tuple[int, int]]]


def dilated_extent(k: int, dilation: int = 1) -> int:
    """Spatial extent of a dilated kernel: ``dilation·(k−1)+1`` taps apart.
    Every piece of halo/padding/output-shape math sees the dilated kernel
    only through this extent, so it is THE shared definition."""
    return dilation * (k - 1) + 1


def normalize_padding(padding: Padding, kh: int, kw: int,
                      stride: int = 1, h: int = 0, w: int = 0,
                      dilation: int = 1
                      ) -> Tuple[Tuple[int, int], Tuple[int, int]]:
    """Resolve SAME/VALID/int/explicit padding to ((top,bottom),(left,right)).

    SAME follows the TF/XLA convention: output = ceil(in/stride), with the
    extra pixel (odd total pad) on the bottom/right; a dilated kernel pads
    for its effective extent ``dilation·(k−1)+1``."""
    if isinstance(padding, int):
        return ((padding, padding), (padding, padding))
    if isinstance(padding, (tuple, list)):
        (a, b), (c, d) = padding
        return ((int(a), int(b)), (int(c), int(d)))
    if padding == "VALID":
        return ((0, 0), (0, 0))
    if padding == "SAME":
        def same(dim, k):
            out = -(-dim // stride)
            total = max((out - 1) * stride + dilated_extent(k, dilation)
                        - dim, 0)
            return (total // 2, total - total // 2)
        return (same(h, kh), same(w, kw))
    raise ValueError(f"unknown padding {padding!r}")


def conv_out_shape(h: int, w: int, kh: int, kw: int, stride: int = 1,
                   padding: Padding = "VALID",
                   dilation: int = 1) -> Tuple[int, int]:
    """Spatial output shape of a conv layer (shared by kernel/banking/perf)."""
    (pt, pb), (pl_, pr) = normalize_padding(padding, kh, kw, stride, h, w,
                                            dilation)
    return ((h + pt + pb - dilated_extent(kh, dilation)) // stride + 1,
            (w + pl_ + pr - dilated_extent(kw, dilation)) // stride + 1)


def halo_window(tile: int, stride: int, k: int, dilation: int = 1) -> int:
    """Input extent consumed by ``tile`` contiguous conv outputs: adjacent
    windows overlap by ``dilation·(k−1)+1 − stride`` (the halo).  The single
    definition shared by the tiled kernel's BlockSpecs, the TilePlan
    planner, and the spatial-shard band math — they must never disagree on
    this."""
    return (tile - 1) * stride + dilated_extent(k, dilation)


LANES = 128     # TPU vreg lane width: the minor dim of every VMEM tile
# Scoped VMEM a conv kernel may use: the kernels pass it to Mosaic as
# ``vmem_limit_bytes`` and banking.plan_tiles budgets against it, so the
# planner's promise and the compiler's limit are one number.
VMEM_LIMIT_BYTES = 16 * 1024 * 1024


def lane_legal_banks(dim: int, banks: int) -> bool:
    """Whether splitting ``dim`` channels into ``banks`` blocks gives a
    channel block Mosaic accepts as the minor (lane) dimension: the whole
    extent (one bank) or a multiple of 128 lanes."""
    return banks == 1 or (dim % banks == 0 and (dim // banks) % LANES == 0)


def divisor_banks(dim: int, want: int) -> int:
    """Largest bank count ≤ ``want`` that splits ``dim`` into lane-legal
    blocks (``lane_legal_banks``): the paper's 4-way banking only
    survives where each bank still holds a multiple of 128 channels, and
    anything narrower runs as one full-extent bank.  Lives here (with the
    other shared shape math) so kernels and the core planner agree
    without a layering inversion."""
    b = max(1, min(want, dim))
    while not lane_legal_banks(dim, b):
        b -= 1
    return b


def grouped_banks(c: int, k: int, groups: int = 1, want_cin: int = 4,
                  want_kout: int = 4) -> Tuple[int, int]:
    """Legal (cin_banks, kout_banks) for a grouped conv, degraded from the
    requested paper banking: cin banks must divide the per-group channel
    slice C/g (the only channels a kernel set reads), and kout banks must
    split along group boundaries — ``kout_banks % groups == 0`` with the
    banks-per-group count dividing K/g — so every kout bank's weight block
    stays inside one group's cin slice.  Depthwise (g == C) degenerates to
    one cin bank and one kout bank per channel."""
    check_groups(c, k, groups)
    cg, kg = c // groups, k // groups
    cin = divisor_banks(cg, want_cin)
    bpg = divisor_banks(kg, max(1, want_kout // groups))
    return cin, groups * bpg


def check_groups(c: int, k: int, groups: int) -> None:
    """The grouped-conv divisibility contract, shared by oracle / kernel /
    planner / compiler so they all reject the same shapes the same way:
    ``groups`` must divide both the input and output channel counts
    (``groups == c`` is the depthwise case)."""
    if groups < 1 or c % groups or k % groups:
        raise ValueError(
            f"groups={groups} must divide both C={c} and K={k} "
            f"(groups == C is depthwise)")


def conv2d_ref(x, w, bias=None, *, stride: int = 1,
               padding: Padding = "VALID", groups: int = 1,
               dilation: int = 1, accum_dtype=jnp.float32):
    """General convolution oracle.  x: [N,H,W,C]; w: [KH,KW,C/groups,K] →
    [N,OH,OW,K].

    The paper's Eq. (2): F(i,j) = Σ_d Σ_m Σ_n I(i·s+m, j·s+n, d) · K(m,n,d),
    extended with stride s, zero padding, grouped channel contraction
    (``groups > 1``): output kernel k only reads the C/groups input
    channels of its group — ``groups == C`` is the depthwise conv of the
    MobileNet workload family — and rhs/kernel dilation (``dilation > 1``
    spreads the taps ``dilation`` pixels apart, the atrous conv of
    dense-prediction context modules)."""
    check_groups(x.shape[3], w.shape[3], groups)
    pad = normalize_padding(padding, w.shape[0], w.shape[1], stride,
                            x.shape[1], x.shape[2], dilation)
    out = jax.lax.conv_general_dilated(
        x.astype(accum_dtype), w.astype(accum_dtype),
        window_strides=(stride, stride), padding=pad,
        rhs_dilation=(dilation, dilation),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
        preferred_element_type=accum_dtype)
    if bias is not None:
        out = out + bias.astype(accum_dtype)
    return out


def conv2d_ref_int8(x, w, bias=None, *, stride: int = 1,
                    padding: Padding = "VALID", groups: int = 1,
                    dilation: int = 1):
    """int8 × int8 → int32 accumulation (production 8-bit datapath).

    Zero padding is exact for the symmetric (zero-point-0) int8 scheme."""
    assert x.dtype == jnp.int8 and w.dtype == jnp.int8
    check_groups(x.shape[3], w.shape[3], groups)
    pad = normalize_padding(padding, w.shape[0], w.shape[1], stride,
                            x.shape[1], x.shape[2], dilation)
    out = jax.lax.conv_general_dilated(
        x.astype(jnp.int32), w.astype(jnp.int32),
        window_strides=(stride, stride), padding=pad,
        rhs_dilation=(dilation, dilation),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)
    if bias is not None:
        out = out + bias.astype(jnp.int32)
    return out


def maxpool2d_ref(x, size: int = 2, stride: int = None):
    """Max pool over [N,H,W,C]; trailing rows/cols that don't fill a window
    are dropped (floor semantics, matching the fused kernel epilogue)."""
    stride = size if stride is None else stride
    init = jnp.iinfo(x.dtype).min if jnp.issubdtype(x.dtype, jnp.integer) \
        else -jnp.inf
    return jax.lax.reduce_window(
        x, jnp.asarray(init, x.dtype), jax.lax.max,
        (1, size, size, 1), (1, stride, stride, 1), "VALID")


def avgpool2d_ref(x, size: int = 2, stride: int = None):
    """Average pool over [N,H,W,C] (floor semantics, like maxpool2d_ref).

    Integer inputs accumulate the window sum in int32 and round the mean
    back to the input dtype — the int8 feature-map grid is preserved
    (mean of same-scale values stays on the same scale), so the unfused
    int8 avg-pool layer needs no requantization."""
    stride = size if stride is None else stride
    if jnp.issubdtype(x.dtype, jnp.integer):
        s = jax.lax.reduce_window(
            x.astype(jnp.int32), jnp.int32(0), jax.lax.add,
            (1, size, size, 1), (1, stride, stride, 1), "VALID")
        mean = jnp.round(s.astype(jnp.float32) / (size * size))
        info = jnp.iinfo(x.dtype)
        return jnp.clip(mean, info.min, info.max).astype(x.dtype)
    s = jax.lax.reduce_window(
        x.astype(jnp.float32), jnp.float32(0), jax.lax.add,
        (1, size, size, 1), (1, stride, stride, 1), "VALID")
    return (s / (size * size)).astype(x.dtype)


def global_avgpool_ref(x):
    """Global average pool [N,H,W,C] → [N,C] (the classifier-head reduce).

    Integer inputs round the mean back onto the input dtype's grid, like
    ``avgpool2d_ref``."""
    if jnp.issubdtype(x.dtype, jnp.integer):
        s = jnp.sum(x.astype(jnp.int32), axis=(1, 2))
        mean = jnp.round(s.astype(jnp.float32) / (x.shape[1] * x.shape[2]))
        info = jnp.iinfo(x.dtype)
        return jnp.clip(mean, info.min, info.max).astype(x.dtype)
    return jnp.mean(x.astype(jnp.float32), axis=(1, 2)).astype(x.dtype)


def requantize_ref(acc, out_scale):
    """int32/f32 accumulator × scale → int8 (round-to-nearest, saturating).
    out_scale: scalar or per-channel [K] (broadcast over the last axis)."""
    scaled = jnp.round(acc.astype(jnp.float32) * out_scale)
    return jnp.clip(scaled, -128, 127).astype(jnp.int8)


def add_requant_ref(a, b, scale_a, scale_b, *, relu: bool = False):
    """Residual (skip-connection) merge on a shared int8 grid — the oracle
    for the network executor's ``add`` node.

    Each int8 operand re-expresses on the merge node's output grid through
    its branch requant scale (``s_branch / s_out``, round-to-nearest), the
    aligned values add, optional ReLU, saturate to int8.  When both
    branches already sit on the shared grid (branch scales == 1) the merge
    is exact int8 arithmetic — the FPGA output-BRAM-crossbar idiom: the
    skip path adds into the conv path's output BRAMs without ever leaving
    8 bits, no int32 accumulator round-trip."""
    assert a.dtype == jnp.int8 and b.dtype == jnp.int8, (a.dtype, b.dtype)
    ya = jnp.round(a.astype(jnp.float32) * jnp.asarray(scale_a, jnp.float32))
    yb = jnp.round(b.astype(jnp.float32) * jnp.asarray(scale_b, jnp.float32))
    y = ya + yb
    if relu:
        y = jnp.maximum(y, 0)
    return jnp.clip(y, -128, 127).astype(jnp.int8)


def conv2d_epilogue_ref(x, w, bias=None, *, stride: int = 1,
                        padding: Padding = "VALID", relu: bool = False,
                        pool: bool = False, out_scale=None,
                        groups: int = 1, dilation: int = 1):
    """Conv + the fused FPGA post-processing chain: ReLU → 2×2 max-pool →
    requantize, in accumulator precision (the oracle for the fused kernel
    epilogue).  ``groups``/``dilation`` select grouped/depthwise channel
    contraction and kernel dilation like ``conv2d_ref``."""
    if x.dtype == jnp.int8:
        acc = conv2d_ref_int8(x, w, bias, stride=stride, padding=padding,
                              groups=groups, dilation=dilation)
    else:
        acc = conv2d_ref(x, w, bias, stride=stride, padding=padding,
                         groups=groups, dilation=dilation)
    if relu:
        acc = jnp.maximum(acc, 0)
    if pool:
        acc = maxpool2d_ref(acc)
    if out_scale is not None:
        return requantize_ref(acc, out_scale)
    return acc


def conv2d_ref_wrap8(x, w, bias=None):
    """Paper-waveform mode: every accumulation wraps in 8 bits.

    Because int8 wrap-around addition is associative and the products enter
    mod-256 arithmetic independently, this equals the int32 result mod 256."""
    out = conv2d_ref_int8(x, w, bias)
    return out.astype(jnp.int8)


# ---------------------------------------------------------------------------
# Transposed-convolution oracles (the dense-prediction contract)
# ---------------------------------------------------------------------------


def grouped_swap_weights(w, groups: int = 1):
    """Per-group channel-axis swap [KH,KW,C/groups,K] → [KH,KW,K/groups,C]
    with the groups reassembled along the new output axis — NO spatial
    flip.  An involution (applying it twice is the identity), and the
    algebraic half of ``grouped_transpose_weights = flip ∘ swap``: it maps
    the weights of a ``conv2d_transpose`` to the weights of the ordinary
    strided conv that is its adjoint (and vice versa), which is how the
    transpose op's own VJP reuses the forward kernels."""
    kh, kw, cg, k = w.shape
    kg = k // groups
    if groups == 1:
        return w.swapaxes(2, 3)
    return (w.reshape(kh, kw, cg, groups, kg)
            .transpose(0, 1, 4, 3, 2).reshape(kh, kw, kg, groups * cg))


def conv_transpose_out_shape(h: int, w: int, kh: int, kw: int,
                             stride: int = 1, padding: Padding = "VALID",
                             dilation: int = 1) -> Tuple[int, int]:
    """Spatial output shape of ``conv2d_transpose_ref``: the padding names
    the FORWARD conv being inverted, so the output extent is the input
    extent that forward conv would have consumed — VALID grows to
    ``(h−1)·s + ek`` (ek the dilated kernel extent), SAME to exactly
    ``h·s``, explicit ((pt,pb),(pl,pr)) to ``(h−1)·s + ek − pt − pb``."""
    (oh, ow), _ = conv_transpose_eq_params(h, w, kh, kw, stride, padding,
                                           dilation)
    return oh, ow


def conv_transpose_eq_params(h: int, w: int, kh: int, kw: int,
                             stride: int = 1, padding: Padding = "VALID",
                             dilation: int = 1, out_spatial=None):
    """The shared geometry of a transposed conv as its equivalent stride-1
    conv: resolve the output extent (OH, OW) and the "full" padding the
    zero-inserted input needs — ``ek−1−pt`` on top, ``OH+pt−(h−1)·s−1`` on
    the bottom (negative when the forward padding exceeded the kernel
    extent: those rows must be sliced away, not padded).  One definition
    consumed by the oracle, the WS kernel path, and the planner, so they
    can never disagree on transpose geometry.

    ``out_spatial`` pins (OH, OW) directly — the input-gradient use, where
    the forward input extent is known and the stride remainder rows
    (``r = OH+pt+pb−ek−(h−1)·s ∈ [0, s)``) must be recovered exactly."""
    ekh, ekw = dilated_extent(kh, dilation), dilated_extent(kw, dilation)
    if out_spatial is not None:
        oh, ow = out_spatial
        (pt, pb), (pl_, pr) = normalize_padding(padding, kh, kw, stride,
                                                oh, ow, dilation)
    elif isinstance(padding, (int, tuple, list)):
        (pt, pb), (pl_, pr) = normalize_padding(padding, kh, kw, stride)
        oh = (h - 1) * stride + ekh - pt - pb
        ow = (w - 1) * stride + ekw - pl_ - pr
    elif padding == "VALID":
        (pt, pb), (pl_, pr) = (0, 0), (0, 0)
        oh, ow = (h - 1) * stride + ekh, (w - 1) * stride + ekw
    elif padding == "SAME":
        oh, ow = h * stride, w * stride
        (pt, pb), (pl_, pr) = normalize_padding(padding, kh, kw, stride,
                                                oh, ow, dilation)
    else:
        raise ValueError(f"unknown padding {padding!r}")
    for dim, o, p0, p1, ek in ((h, oh, pt, pb, ekh), (w, ow, pl_, pr, ekw)):
        r = o + p0 + p1 - ek - (dim - 1) * stride
        if o < 1 or not 0 <= r < max(stride, 1):
            raise ValueError(
                f"conv_transpose geometry is not invertible: input {dim} "
                f"with stride={stride}, kernel extent {ek}, padding "
                f"({p0},{p1}) cannot produce output extent {o}")
    eq_pads = ((ekh - 1 - pt, oh + pt - (h - 1) * stride - 1),
               (ekw - 1 - pl_, ow + pl_ - (w - 1) * stride - 1))
    return (oh, ow), eq_pads


def conv2d_transpose_ref(x, w, bias=None, *, stride: int = 1,
                         padding: Padding = "VALID", groups: int = 1,
                         dilation: int = 1, out_spatial=None,
                         accum_dtype=jnp.float32):
    """Transposed (fractionally-strided / upsampling) convolution oracle.
    x: [N,H,W,C]; w: [KH,KW,C/groups,K] → [N,OH,OW,K] — the FORWARD weight
    layout, so an encoder conv and its decoder transpose read the same
    shaped parameter.

    Stated directly as zero-insertion dilation + kernel flip (NOT via
    jax.vjp, so it is an independent contract for the WS kernel path): the
    input dilates by ``stride`` (lhs zero-insertion), the kernel flips
    spatially, and a stride-1 grouped correlation with the "full" padding
    of ``conv_transpose_eq_params`` produces the upsampled map.  Duality:
    ``conv2d_input_grad_ref`` is exactly this op applied to the cotangent
    with per-group channel-swapped weights (``grouped_swap_weights``)."""
    check_groups(x.shape[3], w.shape[3], groups)
    kh, kw = w.shape[0], w.shape[1]
    _, eq_pads = conv_transpose_eq_params(
        x.shape[1], x.shape[2], kh, kw, stride, padding, dilation,
        out_spatial)
    out = jax.lax.conv_general_dilated(
        x.astype(accum_dtype), jnp.flip(w, (0, 1)).astype(accum_dtype),
        (1, 1), eq_pads, lhs_dilation=(stride, stride),
        rhs_dilation=(dilation, dilation),
        feature_group_count=groups,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=accum_dtype)
    if bias is not None:
        out = out + bias.astype(accum_dtype)
    return out


def conv2d_transpose_ref_int8(x, w, bias=None, *, stride: int = 1,
                              padding: Padding = "VALID", groups: int = 1,
                              dilation: int = 1, out_spatial=None):
    """int8 × int8 → int32 transposed conv (production 8-bit datapath).
    Zero insertion is exact for the symmetric (zero-point-0) scheme — the
    inserted zeros ARE the quantized zero."""
    assert x.dtype == jnp.int8 and w.dtype == jnp.int8
    return conv2d_transpose_ref(x, w, bias, stride=stride, padding=padding,
                                groups=groups, dilation=dilation,
                                out_spatial=out_spatial,
                                accum_dtype=jnp.int32)


def conv2d_transpose_epilogue_ref(x, w, bias=None, *, stride: int = 1,
                                  padding: Padding = "VALID",
                                  relu: bool = False, pool: bool = False,
                                  out_scale=None, groups: int = 1,
                                  dilation: int = 1):
    """Transposed conv + the same fused post-processing chain as
    ``conv2d_epilogue_ref`` (ReLU → 2×2 max-pool → requantize) — the
    oracle for a first-class ``conv_transpose`` network layer."""
    if x.dtype == jnp.int8:
        acc = conv2d_transpose_ref_int8(x, w, bias, stride=stride,
                                        padding=padding, groups=groups,
                                        dilation=dilation)
    else:
        acc = conv2d_transpose_ref(x, w, bias, stride=stride,
                                   padding=padding, groups=groups,
                                   dilation=dilation)
    if relu:
        acc = jnp.maximum(acc, 0)
    if pool:
        acc = maxpool2d_ref(acc)
    if out_scale is not None:
        return requantize_ref(acc, out_scale)
    return acc


# ---------------------------------------------------------------------------
# Backward-pass oracles (the training contract)
# ---------------------------------------------------------------------------


def grouped_transpose_weights(w, groups: int = 1):
    """Forward weights [KH,KW,C/groups,K] → transposed-conv weights
    [KH,KW,K/groups,C]: spatial flip + per-group channel-axis swap
    (``grouped_swap_weights``), groups reassembled along the new output
    axis.  The single definition shared by the input-gradient oracle and
    the WS backward kernel — in the transposed conv the cotangent's K
    channels play the input role (K/g per group) and the forward input's
    C channels the output role."""
    return grouped_swap_weights(jnp.flip(w, (0, 1)), groups)


def conv2d_input_grad_ref(g, w, x_shape, *, stride: int = 1,
                          padding: Padding = "VALID", groups: int = 1,
                          dilation: int = 1):
    """dL/dx of ``conv2d_ref``: a special case of the first-class
    transposed conv — ``conv2d_transpose_ref`` applied to the cotangent
    with per-group channel-swapped weights ([KH,KW,C/g,K] → [KH,KW,K/g,C],
    ``grouped_swap_weights``; the transpose op supplies the spatial flip),
    with ``out_spatial`` pinned to the forward input extent so the stride
    remainder rows the strided forward never reached are recovered."""
    n, h, w_dim, c = x_shape
    kh, kw, cg, k = w.shape
    assert c == cg * groups, (c, cg, groups)
    return conv2d_transpose_ref(
        g.astype(jnp.float32),
        grouped_swap_weights(w, groups).astype(jnp.float32),
        stride=stride, padding=padding, groups=groups, dilation=dilation,
        out_spatial=(h, w_dim))


def conv2d_weight_grad_ref(x, g, kh: int, kw: int, *, stride: int = 1,
                           padding: Padding = "VALID", groups: int = 1,
                           dilation: int = 1):
    """dL/dw of ``conv2d_ref``: a batched correlation — tap (dy,dx) of the
    weight gradient contracts the stride-strided input window starting at
    (dy·dilation, dx·dilation) with the cotangent over (N,OH,OW):

        dW[dy,dx,c,k] = Σ_{n,i,j} x_pad[n, i·s+dy·d, j·s+dx·d, c] · g[n,i,j,k]

    With ``groups > 1`` the contraction stays within each group: output
    kernel k in group i only ever saw that group's C/g input channels, so
    the tap einsum carries a group axis and dW keeps the forward's
    [KH,KW,C/g,K] layout."""
    n, h, w_dim, c = x.shape
    oh, ow, k = g.shape[1], g.shape[2], g.shape[3]
    check_groups(c, k, groups)
    cg, kg = c // groups, k // groups
    (pt, pb), (pl_, pr) = normalize_padding(padding, kh, kw, stride, h,
                                            w_dim, dilation)
    xp = jnp.pad(x.astype(jnp.float32),
                 ((0, 0), (pt, pb), (pl_, pr), (0, 0)))
    gf = g.astype(jnp.float32)
    taps = []
    for dy in range(kh):
        for dx in range(kw):
            xs = jax.lax.slice(
                xp, (0, dy * dilation, dx * dilation, 0),
                (n, dy * dilation + (oh - 1) * stride + 1,
                 dx * dilation + (ow - 1) * stride + 1,
                 c), (1, stride, stride, 1))
            if groups == 1:
                taps.append(jnp.einsum("nijc,nijk->ck", xs, gf))
            else:
                tap = jnp.einsum(
                    "nijgc,nijgk->gck",
                    xs.reshape(n, oh, ow, groups, cg),
                    gf.reshape(n, oh, ow, groups, kg))
                taps.append(tap.transpose(1, 0, 2).reshape(cg, k))
    return jnp.stack(taps).reshape(kh, kw, cg, k)


def conv2d_bias_grad_ref(g):
    """dL/db of ``conv2d_ref``: the cotangent summed over (N,OH,OW), in
    f32 (low-precision cotangents must not round per-partial-sum)."""
    return jnp.sum(g.astype(jnp.float32), axis=(0, 1, 2))


def relu_mask_ref(acc):
    """The fused-epilogue ReLU backward mask: 1 where the accumulator was
    strictly positive (the subgradient-at-0 convention jax.grad uses)."""
    return acc > 0


def maxpool2x2_argmax_ref(y):
    """Per-window argmax of the 2×2/2 max-pool (row-major within the
    window, first max wins — jnp.argmax semantics).  Trailing odd rows /
    columns are dropped, matching the fused epilogue's floor semantics.
    Returns int8 [N, H//2, W//2, C] with values in 0..3 — the pool mask
    the training residuals carry."""
    n, h, w, c = y.shape
    h2, w2 = h // 2, w // 2
    win = y[:, :h2 * 2, :w2 * 2].reshape(n, h2, 2, w2, 2, c)
    win = win.transpose(0, 1, 3, 5, 2, 4).reshape(n, h2, w2, c, 4)
    return jnp.argmax(win, axis=-1).astype(jnp.int8)


def maxpool2x2_bwd_ref(idx, g, out_shape):
    """Backward of the 2×2/2 max-pool given its argmax mask: each window's
    cotangent routes to the position ``idx`` selected in the forward pass;
    dropped trailing odd rows/columns get zero.  ``out_shape`` is the
    pre-pool [N,H,W,C] shape."""
    n, h, w, c = out_shape
    h2, w2 = h // 2, w // 2
    onehot = jax.nn.one_hot(idx.astype(jnp.int32), 4,
                            dtype=jnp.float32)            # [N,H2,W2,C,4]
    dwin = g.astype(jnp.float32)[..., None] * onehot
    dy = dwin.reshape(n, h2, w2, c, 2, 2).transpose(0, 1, 4, 2, 5, 3)
    dy = dy.reshape(n, h2 * 2, w2 * 2, c)
    return jnp.pad(dy, ((0, 0), (0, h - h2 * 2), (0, w - w2 * 2), (0, 0)))


def matmul_ref(x, w, bias=None, *, accum_dtype=jnp.float32):
    """x: [M,K] @ w: [K,N] + bias."""
    out = jnp.dot(x.astype(accum_dtype), w.astype(accum_dtype),
                  preferred_element_type=accum_dtype)
    if bias is not None:
        out = out + bias.astype(accum_dtype)
    return out


def matmul_ref_int8(x, w, bias=None):
    assert x.dtype == jnp.int8 and w.dtype == jnp.int8
    out = jnp.dot(x.astype(jnp.int32), w.astype(jnp.int32))
    if bias is not None:
        out = out + bias.astype(jnp.int32)
    return out


def conv1d_depthwise_ref(x, w, bias=None):
    """Causal depthwise temporal conv (RecurrentGemma site).
    x: [B,S,W]; w: [K,W] → [B,S,W]."""
    K = w.shape[0]
    pad = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    S = x.shape[1]
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for j in range(K):
        out = out + xp[:, j:j + S].astype(jnp.float32) * w[j].astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)
