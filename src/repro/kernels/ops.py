"""Public jit'd kernel wrappers.

* auto-select interpret mode on CPU (the host platform cannot lower Mosaic;
  interpret=True executes the kernel body in Python — the validation mode
  this container uses; on TPU the same call compiles natively);
* ``matmul_ws`` carries a custom VJP so the paper-dataflow GEMM is usable
  inside training graphs (backward = two more WS-GEMMs);
* ``conv2d`` adds the requantization / wrap8 modes of the 8-bit datapath,
  and carries a custom VJP on the float accumulator path: the backward
  kernels (kernels/conv2d_ws_bwd.py) run the same weight-stationary
  dataflow, and the residuals store the fused-epilogue MASKS (ReLU sign
  bits, 2×2-pool argmax indices) instead of a second copy of the
  accumulator, so stride/padding/epilogue configs differentiate
  bit-consistently with the ref oracle.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import conv2d_ws as _conv_mod
from repro.kernels import conv2d_ws_bwd as _bwd_mod
from repro.kernels import conv2d_ws_pipe as _pipe_mod
from repro.kernels import conv2d_ws_trans as _trans_mod
from repro.kernels import matmul_ws as _mm_mod
from repro.kernels import ref as _ref


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# GEMM with custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def matmul_ws(x, w, bias=None):
    return _matmul_fwd_impl(x, w, bias)


def _matmul_fwd_impl(x, w, bias):
    out = _mm_mod.matmul_ws(x, w, bias, interpret=_interpret())
    if x.dtype == jnp.int8:
        return out
    return out.astype(x.dtype)


def _matmul_fwd(x, w, bias):
    return _matmul_fwd_impl(x, w, bias), (x, w, bias)


def _matmul_bwd(res, g):
    x, w, bias = res
    if not (jnp.issubdtype(x.dtype, jnp.floating)
            and jnp.issubdtype(w.dtype, jnp.floating)):
        raise TypeError(
            "matmul_ws VJP requires float operands: an int8 forward has no "
            "meaningful int8 gradient (casting the cotangent to int8 would "
            "silently truncate it) — differentiate the float path instead")
    # promote the cotangent to the accumulator dtype; the backward GEMMs run
    # in f32 and only the results cast back to the operand dtypes
    gf = g.astype(jnp.float32)
    dx = _mm_mod.matmul_ws(gf, w.T.astype(jnp.float32),
                           interpret=_interpret()).astype(x.dtype)
    dw = _mm_mod.matmul_ws(x.T.astype(jnp.float32), gf,
                           interpret=_interpret()).astype(w.dtype)
    # bias grad reduces in f32 and only the RESULT casts to the bias dtype:
    # summing the raw cotangent rounds every partial sum to the cotangent
    # dtype, and an f32 master bias fed bf16 cotangents would silently get
    # a bf16-rounded gradient
    db = (jnp.sum(gf, axis=0).astype(bias.dtype)
          if bias is not None else None)
    return dx, dw, db


matmul_ws.defvjp(_matmul_fwd, _matmul_bwd)


# ---------------------------------------------------------------------------
# Convolution (the IP core entry point)
# ---------------------------------------------------------------------------


class _ConvCfg(NamedTuple):
    """Hashable static config of one conv layer pass (the nondiff argument
    of the custom VJP; padding is pre-resolved to explicit form so SAME
    needs no shape context in the backward rules)."""
    stride: int
    padding: Tuple[Tuple[int, int], Tuple[int, int]]
    groups: int
    cin_banks: int
    kout_banks: int
    h_tile: int
    w_tile: int
    relu: bool
    pool: bool
    dilation: int = 1
    pipelined: bool = False


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _conv2d_float(cfg: _ConvCfg, x, w, bias):
    """Float-accumulator conv with the fused ReLU → 2×2-max-pool epilogue
    and a paper-dataflow backward (see _conv2d_float_bwd).  The primal
    honors ``cfg.pipelined`` (both kernel variants are bit-exact, so the
    VJP rules below may keep the sequential kernel for the residual
    recompute without any value drift)."""
    fwd = (_pipe_mod.conv2d_ws_pipe if cfg.pipelined
           else _conv_mod.conv2d_ws)
    return fwd(x, w, bias, None, stride=cfg.stride,
               padding=cfg.padding, groups=cfg.groups,
               cin_banks=cfg.cin_banks,
               kout_banks=cfg.kout_banks, h_tile=cfg.h_tile,
               w_tile=cfg.w_tile, relu=cfg.relu,
               pool=cfg.pool, dilation=cfg.dilation, interpret=_interpret())


def _conv2d_float_fwd(cfg: _ConvCfg, x, w, bias):
    """Run the kernel WITHOUT the epilogue to expose the f32 accumulator,
    then apply ReLU/pool at the jnp level — bit-identical to the fused
    epilogue (same maximum ops on the same accumulator values) — and keep
    only the epilogue MASKS as residuals: the ReLU sign bits and the pool
    argmax indices, 1 byte each per accumulator cell instead of 4."""
    acc = _conv_mod.conv2d_ws(x, w, bias, None, stride=cfg.stride,
                              padding=cfg.padding, groups=cfg.groups,
                              cin_banks=cfg.cin_banks,
                              kout_banks=cfg.kout_banks, h_tile=cfg.h_tile,
                              w_tile=cfg.w_tile, dilation=cfg.dilation,
                              interpret=_interpret())
    relu_mask = pool_idx = None
    y = acc
    if cfg.relu:
        relu_mask = _ref.relu_mask_ref(acc)
        y = jnp.maximum(y, 0)
    if cfg.pool:
        oh, ow = acc.shape[1], acc.shape[2]
        if oh < 2 or ow < 2:
            # the epilogue-disabled kernel call above skipped conv2d_ws's
            # own check — differentiation must fail exactly like the
            # primal, not train on an empty pooled map
            raise ValueError(
                f"2×2 pool needs a ≥2×2 conv output, got {oh}×{ow}")
        pool_idx = _ref.maxpool2x2_argmax_ref(y)
        y = _ref.maxpool2d_ref(y, 2)
    return y, (x, w, bias, relu_mask, pool_idx, acc.shape)


def _conv2d_float_bwd(cfg: _ConvCfg, res, g):
    x, w, bias, relu_mask, pool_idx, acc_shape = res
    # walk the epilogue backwards: pool argmax routing → ReLU mask → the
    # accumulator cotangent the WS backward kernels consume
    dacc = g.astype(jnp.float32)
    if cfg.pool:
        dacc = _ref.maxpool2x2_bwd_ref(pool_idx, dacc, acc_shape)
    if cfg.relu:
        dacc = dacc * relu_mask
    dx = _bwd_mod.conv2d_ws_input_grad(
        dacc, w, x.shape, stride=cfg.stride, padding=cfg.padding,
        groups=cfg.groups, cin_banks=cfg.cin_banks,
        kout_banks=cfg.kout_banks, h_tile=cfg.h_tile, w_tile=cfg.w_tile,
        dilation=cfg.dilation, interpret=_interpret()).astype(x.dtype)
    dw = _bwd_mod.conv2d_ws_weight_grad(
        x, dacc, w.shape[0], w.shape[1], stride=cfg.stride,
        padding=cfg.padding, groups=cfg.groups, dilation=cfg.dilation,
        interpret=_interpret()).astype(w.dtype)
    # like _matmul_bwd: reduce in f32, cast only the result to the bias dtype
    db = (jnp.sum(dacc, axis=(0, 1, 2)).astype(bias.dtype)
          if bias is not None else None)
    return dx, dw, db


_conv2d_float.defvjp(_conv2d_float_fwd, _conv2d_float_bwd)


def conv2d(x, w, bias=None, *, stride: int = 1, padding="VALID",
           groups: int = 1, cin_banks: int = 4, kout_banks: int = 4,
           h_tile: int = 0, w_tile: int = 0, relu: bool = False,
           pool: bool = False, wrap8: bool = False, out_scale=None,
           dilation: int = 1, pipelined: bool = False):
    """Paper-dataflow convolution (arbitrary stride / SAME|VALID|explicit
    padding, fused ReLU → 2×2 max-pool → requantize epilogue, halo-aware
    spatial tiling via h_tile/w_tile — 0 = whole map).

    ``groups`` selects grouped channel contraction (w: [KH,KW,C/groups,K];
    1 = dense, ``groups == C`` = depthwise — the MobileNet workload
    family).  The requested bank counts re-legalize through
    ``ref.grouped_banks`` (cin banks must divide the per-group slice,
    kout banks split along group boundaries, and each channel block is
    the full extent or a multiple of 128 lanes, as Mosaic requires).

    float in → f32 out; int8 in → int32 out.  ``out_scale`` requantizes
    in-kernel (acc × scale → int8) on EITHER accumulator path — int32 for
    int8 inputs (the production chained-layer path) and f32 for float
    inputs (matching RefBackend's epilogue contract) — so the output dtype
    is int8 whenever a scale is given.  ``wrap8=True`` (int8 inputs only)
    instead wraps the accumulator to int8, bit-matching the paper's Fig. 6
    waveform — the wrap path has no requantize stage, so combining it with
    ``out_scale`` is an error rather than a silent drop.

    The float accumulator path (float inputs, no out_scale/wrap8) is
    differentiable: a custom VJP runs the backward through the same
    weight-stationary dataflow (kernels/conv2d_ws_bwd.py), with residuals
    carrying the fused-epilogue masks — so any stride/padding/epilogue
    config used in a training graph differentiates consistently with the
    ref oracle.  The int8 and requantized paths stay non-differentiable
    (an int8 forward has no meaningful int8 gradient; QAT trains the
    float shadow with straight-through fake quantization instead —
    core/training.py).

    ``dilation`` dilates the kernel taps (effective extent
    ``dilation·(k−1)+1``) — the dense-prediction context-aggregation
    knob; it threads through padding/halo geometry, both kernel
    variants, and the custom VJP unchanged.

    ``pipelined=True`` routes the layer through ``conv2d_ws_pipe`` (the
    explicit double-buffered manual-DMA kernel) instead of ``conv2d_ws``
    — bit-exact on every path, so this is purely a performance choice;
    a layer's tile plan makes it (``TilePlan.pipelined``: more than one
    slab) and the backends forward it here.
    """
    if wrap8 and out_scale is not None:
        raise ValueError("wrap8 and out_scale are mutually exclusive: the "
                         "Fig. 6 wrap path has no requantize stage")
    # re-legalize the requested banking: divisors of the (per-group)
    # channel slices, kout banks on group boundaries, lane-legal blocks
    cin_banks, kout_banks = _ref.grouped_banks(
        x.shape[3], w.shape[3], groups, want_cin=cin_banks,
        want_kout=kout_banks)
    if (out_scale is None and not wrap8
            and jnp.issubdtype(jnp.result_type(x), jnp.floating)):
        pad = _ref.normalize_padding(padding, w.shape[0], w.shape[1],
                                     stride, x.shape[1], x.shape[2],
                                     dilation)
        cfg = _ConvCfg(stride=stride, padding=pad, groups=groups,
                       cin_banks=cin_banks, kout_banks=kout_banks,
                       h_tile=h_tile, w_tile=w_tile, relu=relu, pool=pool,
                       dilation=dilation, pipelined=pipelined)
        return _conv2d_float(cfg, x, w, bias)
    fwd = (_pipe_mod.conv2d_ws_pipe if pipelined else _conv_mod.conv2d_ws)
    out = fwd(x, w, bias, out_scale, stride=stride,
              padding=padding, groups=groups,
              cin_banks=cin_banks, kout_banks=kout_banks,
              h_tile=h_tile, w_tile=w_tile, relu=relu,
              pool=pool, dilation=dilation, interpret=_interpret())
    if x.dtype == jnp.int8 and wrap8:
        return out.astype(jnp.int8)
    return out


# ---------------------------------------------------------------------------
# Transposed convolution (the dense-prediction upsampling entry point)
# ---------------------------------------------------------------------------


class _ConvTransCfg(NamedTuple):
    """Hashable static config of one transposed-conv pass.  ``padding`` is
    pre-resolved to explicit form normalized against the OUTPUT spatial
    shape ``(out_h, out_w)`` — the forward-conv frame of the transpose
    duality — so the backward rules need no shape context."""
    stride: int
    padding: Tuple[Tuple[int, int], Tuple[int, int]]
    groups: int
    cin_banks: int
    kout_banks: int
    h_tile: int
    w_tile: int
    relu: bool
    pool: bool
    dilation: int
    out_h: int
    out_w: int
    pipelined: bool = False


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _conv2d_transpose_float(cfg: _ConvTransCfg, x, w, bias):
    return _trans_mod.conv2d_ws_transpose(
        x, w, bias, None, stride=cfg.stride, padding=cfg.padding,
        groups=cfg.groups, cin_banks=cfg.cin_banks,
        kout_banks=cfg.kout_banks, h_tile=cfg.h_tile, w_tile=cfg.w_tile,
        relu=cfg.relu, pool=cfg.pool, dilation=cfg.dilation,
        out_spatial=(cfg.out_h, cfg.out_w), pipelined=cfg.pipelined,
        interpret=_interpret())


def _conv2d_transpose_float_fwd(cfg: _ConvTransCfg, x, w, bias):
    """Epilogue-free transpose exposes the f32 accumulator; ReLU/pool at
    the jnp level are bit-identical to the fused epilogue and leave only
    their MASKS as residuals (same scheme as _conv2d_float_fwd)."""
    acc = _trans_mod.conv2d_ws_transpose(
        x, w, bias, None, stride=cfg.stride, padding=cfg.padding,
        groups=cfg.groups, cin_banks=cfg.cin_banks,
        kout_banks=cfg.kout_banks, h_tile=cfg.h_tile, w_tile=cfg.w_tile,
        dilation=cfg.dilation, out_spatial=(cfg.out_h, cfg.out_w),
        interpret=_interpret())
    relu_mask = pool_idx = None
    y = acc
    if cfg.relu:
        relu_mask = _ref.relu_mask_ref(acc)
        y = jnp.maximum(y, 0)
    if cfg.pool:
        oh, ow = acc.shape[1], acc.shape[2]
        if oh < 2 or ow < 2:
            raise ValueError(
                f"2×2 pool needs a ≥2×2 transpose output, got {oh}×{ow}")
        pool_idx = _ref.maxpool2x2_argmax_ref(y)
        y = _ref.maxpool2d_ref(y, 2)
    return y, (x, w, bias, relu_mask, pool_idx, acc.shape)


def _conv2d_transpose_float_bwd(cfg: _ConvTransCfg, res, g):
    """The transpose duality run in reverse — NO new kernel code:

    * dX = the ORDINARY strided forward conv of the cotangent with the
      channel-swapped weights (the transpose op is the adjoint of exactly
      that conv, so its VJP wrt the input is the conv itself);
    * dW = the channel-swap of the forward weight-grad GEMMs with the
      cotangent playing the conv INPUT and the primal input playing the
      conv cotangent (⟨g, Tᵂ x⟩ = ⟨F_w g, x⟩ differentiated in w);
    * db = the cotangent summed over (N, OH, OW).
    """
    x, w, bias, relu_mask, pool_idx, acc_shape = res
    dacc = g.astype(jnp.float32)
    if cfg.pool:
        dacc = _ref.maxpool2x2_bwd_ref(pool_idx, dacc, acc_shape)
    if cfg.relu:
        dacc = dacc * relu_mask
    wf = _ref.grouped_swap_weights(w, cfg.groups).astype(jnp.float32)
    # the dual conv contracts the transpose's K channels back to C, so
    # the bank requests re-legalize against (K, C)
    cb_n, kb_n = _ref.grouped_banks(
        w.shape[3], x.shape[3], cfg.groups, want_cin=cfg.cin_banks,
        want_kout=max(cfg.kout_banks, cfg.groups))
    dx = _conv_mod.conv2d_ws(
        dacc, wf, None, stride=cfg.stride, padding=cfg.padding,
        groups=cfg.groups, cin_banks=cb_n, kout_banks=kb_n,
        h_tile=cfg.h_tile, w_tile=cfg.w_tile, dilation=cfg.dilation,
        interpret=_interpret()).astype(x.dtype)
    dwf = _bwd_mod.conv2d_ws_weight_grad(
        dacc, x.astype(jnp.float32), w.shape[0], w.shape[1],
        stride=cfg.stride, padding=cfg.padding, groups=cfg.groups,
        dilation=cfg.dilation, interpret=_interpret())
    dw = _ref.grouped_swap_weights(dwf, cfg.groups).astype(w.dtype)
    db = (jnp.sum(dacc, axis=(0, 1, 2)).astype(bias.dtype)
          if bias is not None else None)
    return dx, dw, db


_conv2d_transpose_float.defvjp(_conv2d_transpose_float_fwd,
                               _conv2d_transpose_float_bwd)


def conv2d_transpose(x, w, bias=None, *, stride: int = 1, padding="VALID",
                     groups: int = 1, cin_banks: int = 4,
                     kout_banks: int = 4, h_tile: int = 0, w_tile: int = 0,
                     relu: bool = False, pool: bool = False, out_scale=None,
                     dilation: int = 1, out_spatial=None,
                     pipelined: bool = False):
    """Transposed convolution through the weight-stationary dataflow
    (kernels/conv2d_ws_trans.py): lhs zero-insertion by ``stride``,
    kernel flip, and the stride-1 forward kernel under the "full"-padding
    equivalence.  x: [N,H,W,C]; w: [KH,KW,C/groups,K] (forward layout) →
    [N,OH,OW,K] with SAME growing to exactly ``H·stride``, VALID to
    ``(H−1)·stride + dilation·(k−1)+1``, and ``out_spatial`` pinning the
    output shape (the gradient-duality form).

    The epilogue contract (``relu`` / 2×2 ``pool`` / ``out_scale``
    requantize — int8 chained-layer deployment), grouped banking,
    spatial tiling, and ``pipelined=`` kernel choice all match
    ``conv2d``.  The float path (no out_scale) is differentiable: the
    custom VJP is the transpose duality run in reverse — dX is an
    ordinary strided conv, dW the channel-swapped weight-grad GEMMs — so
    upsampling layers train through the same paper dataflow.
    """
    cin_banks, kout_banks = _ref.grouped_banks(
        x.shape[3], w.shape[3], groups, want_cin=cin_banks,
        want_kout=kout_banks)
    kh, kw = w.shape[0], w.shape[1]
    (oh, ow), _ = _ref.conv_transpose_eq_params(
        x.shape[1], x.shape[2], kh, kw, stride, padding, dilation,
        out_spatial)
    pad = _ref.normalize_padding(padding, kh, kw, stride, oh, ow, dilation)
    if (out_scale is None
            and jnp.issubdtype(jnp.result_type(x), jnp.floating)):
        cfg = _ConvTransCfg(stride=stride, padding=pad, groups=groups,
                            cin_banks=cin_banks, kout_banks=kout_banks,
                            h_tile=h_tile, w_tile=w_tile, relu=relu,
                            pool=pool, dilation=dilation, out_h=oh,
                            out_w=ow, pipelined=pipelined)
        return _conv2d_transpose_float(cfg, x, w, bias)
    return _trans_mod.conv2d_ws_transpose(
        x, w, bias, out_scale, stride=stride, padding=pad, groups=groups,
        cin_banks=cin_banks, kout_banks=kout_banks, h_tile=h_tile,
        w_tile=w_tile, relu=relu, pool=pool, dilation=dilation,
        out_spatial=(oh, ow), pipelined=pipelined, interpret=_interpret())


def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: int = 512, block_k: int = 512):
    """Pallas flash attention (beyond-paper kernel; see
    kernels/flash_attention.py).  On TPU this replaces the pure-JAX
    chunked attention for prefill/train (cfg.attn_impl == "flash");
    interpret mode validates it on CPU."""
    from repro.kernels.flash_attention import flash_attention as _fa
    return _fa(q, k, v, causal=causal, block_q=block_q, block_k=block_k,
               interpret=_interpret())


def conv1d_depthwise(x, w, bias=None):
    """Causal depthwise temporal conv through the grouped WS conv kernel.

    x: [B,S,W], w: [K,W] → [B,S,W] (in x's dtype).  The temporal conv is
    a width-grouped 1×K conv2d over a height-1 map: the sequence plays
    the spatial W axis, causality is left-padding of K−1, and
    ``groups == W`` makes every lane its own group — the depthwise case
    of the paper dataflow (one image BMG per lane, kernel-set banks on
    group boundaries).  Going through ``conv2d`` keeps the grouped
    custom VJP, so the temporal conv stays differentiable inside
    training graphs.  The old pass-through to the ref oracle is gone;
    ``ref.conv1d_depthwise_ref`` remains the correctness contract."""
    k, width = w.shape
    acc = conv2d(x[:, None], w[None, :, None, :], bias, stride=1,
                 padding=((0, 0), (k - 1, 0)), groups=width,
                 cin_banks=1, kout_banks=width)[:, 0]
    return acc.astype(x.dtype)
