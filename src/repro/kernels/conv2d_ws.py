"""The paper's IP core as a Pallas TPU kernel: weight-stationary, channel-
banked, bias-preloaded blocked convolution with a fused post-processing
epilogue — and spatially tiled, so feature maps larger than VMEM stream
through halo'd H/W blocks.

Mapping of the FPGA architecture (DESIGN.md §3):

* grid = (N, h_tiles, w_tiles, kout_banks, cin_banks) — co innermost:
  "PSUM values of each core get accumulated continually into the output
  BRAMs until the processing depth is finished" (§4.2), then the next
  kernel set (ko), then the next spatial tile.  Spatial tiles are the
  paper's fixed-size image BRAMs generalized: the FPGA streams a bounded
  window of the map through BRAM; here each grid step DMAs one halo'd
  window of the padded map into VMEM;
* the weight block (the Weight Loader contents) is VMEM-resident for the
  whole spatial sweep of a grid step — weight-stationary;
* the accumulator is a VMEM scratch block (the output BRAMs), revisited
  across the cin sweep and *initialized with the bias at cin step 0* —
  the paper's bias-preload trick (M5), so bias costs zero extra passes;
* the KH×KW window is computed as KH·KW shifted (HW×Cb)@(Cb×Kb) MXU
  matmuls — the systolic-array form of "9 MACs + adder tree" per PCORE;
  stride-s convolution reads the shifted slices with stride s;
* on the LAST cin step the fused epilogue runs in VMEM before writeback —
  ReLU → 2×2 max-pool → requantize(int8) — the FPGA "post-process in the
  output BRAMs before DMA-out" idiom, so a conv+relu+pool layer costs one
  HBM round-trip instead of three;
* Pallas's software pipeline double-buffers the HBM→VMEM block DMA against
  MXU compute across grid steps — the paper's two-stage load/compute
  pipeline (M4).

Tiling dataflow and halo math
-----------------------------
An output tile of ``h_tile × w_tile`` conv-output pixels at tile index
(ty, tx) consumes the padded-input window starting at element
``(ty·h_tile·s, tx·w_tile·s)`` with extent

    in_tile = (tile − 1)·s + k        (per spatial dim, s = stride)

so adjacent input windows overlap by a halo of ``k − s`` rows/columns
(k − 1 for the stride-1 case) — re-read from HBM per tile, exactly like
the FPGA re-DMAs the boundary rows of its image BRAM window.  The input
BlockSpec of a tiled layer is element-indexed (``pl.Element``) because
halo'd windows overlap: block strides (h_tile·s) differ from block
extents (in_tile).  An untiled spatial dim spans the whole padded map, so
a planner that tiles only H (banking.plan_tiles) keeps W, the sublane
dim, at the array's own extent — the form Mosaic accepts.  The padded map
is extended with extra zero rows/columns on the bottom/right so the LAST
tile's window is always in bounds; the correspondingly padded output rows
are sliced off after the call.

The fused epilogue is tile-local: with ``pool=True`` tile sizes must be
even (pool-aligned) so no 2×2 pool window straddles a tile edge — tile
boundaries then land on pool-window boundaries and tiled pooling equals
whole-map pooling.  core/banking.plan_tiles chooses (h_tile, cin_banks,
kout_banks) jointly so the VMEM working set (halo'd input block + weight
block + accumulator scratch + epilogue output block, with pipeline
double-buffering, plus the kernel body's values — all counted as Mosaic
lays them out) fits ``VMEM_LIMIT_BYTES``, the ``vmem_limit_bytes`` both
conv kernels pass to Mosaic.

Padding is materialized by zero-padding the feature map before the kernel
(the FPGA writes zero margins into the image BRAMs); zero padding is exact
for the symmetric zero-point-0 int8 scheme.

int8 mode: int8×int8 → int32 accumulation (the production reading of the
paper's 8-bit datapath).  With ``out_scale`` the epilogue requantizes to
int8 in-kernel, so chained layers never round-trip int32 through HBM.  The
bit-exact wrap-around-in-8-bit mode of the Fig. 6 waveform lives in
ops.conv2d (wrap8=True) on top of the int32 result.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import (LANES, VMEM_LIMIT_BYTES, check_groups,
                               conv_out_shape, dilated_extent, halo_window,
                               normalize_padding)

DMA_SUBLANES = 8    # second-minor alignment of an int8 map sliced by a DMA


class ConvGeom(NamedTuple):
    """Resolved static geometry of one conv layer pass — the single
    host-side derivation (banking legality, halo math, tile extents,
    zero-extension, epilogue dtypes) shared by the implicitly-pipelined
    kernel (``conv2d_ws``) and the manual-DMA double-buffered variant
    (``conv2d_ws_pipe``), so the two dataflows can never disagree on
    shapes — the precondition for their bit-exactness contract."""
    n: int
    kh: int
    kw: int
    k: int
    stride: int
    cin_banks: int
    kout_banks: int
    cb: int                   # channels per cin bank (within one group)
    kb: int                   # kernels per kout bank
    cgrp: int                 # channels per group (C // groups)
    bpg: int                  # kout banks per group
    th: int                   # conv-output tile extents (pre-pool)
    tw: int
    n_th: int
    n_tw: int
    in_th: int                # halo'd input window extents
    in_tw: int
    hp: int                   # padded (+zero-extended) map extents
    wp: int
    pth: int                  # epilogue output tile extents (post-pool)
    ptw: int
    poh: int                  # whole-map epilogue output extents
    pow_: int
    tiled: bool
    int_path: bool
    requant: bool
    dilation: int = 1


def setup_conv(x, w, *, stride: int = 1, padding="VALID", groups: int = 1,
               cin_banks: int = 4, kout_banks: int = 4, h_tile: int = 0,
               w_tile: int = 0, pool: bool = False, requant: bool = False,
               dilation: int = 1, manual_dma: bool = False):
    """Validate one conv layer pass and materialize its padded input.

    Returns ``(x_padded, w, geom)`` where ``x_padded`` carries the zero
    margins (padding + trailing-tile zero-extension — exact for the
    symmetric zero-point-0 int8 scheme) and ``geom`` is the resolved
    :class:`ConvGeom`.  Raises exactly the errors the kernels contract
    with the planner (banking invariant, group boundaries, sub-2×2
    pooled outputs, pool-aligned tiles).

    Where a DMA cuts windows out of the map in HBM (spatial tiles, or
    ``manual_dma`` — conv2d_ws_pipe's own copies), Mosaic needs the map's
    two minor dims tile-aligned: the width is zero-extended to a multiple
    of 8 and, for a dense layer run as one channel bank, the channels
    (with the weights' input channels) to a multiple of 128.  With
    ``manual_dma`` the weights' output channels of a single kout bank are
    zero-extended to a multiple of 128 as well (a VMEM slot of a narrower
    weight bank cannot be addressed); ``geom.k`` is then the extended
    count and the caller slices the extra output channels off.  Channel
    extension applies to int8 maps only: zeros add nothing to an int32
    accumulator, whereas a longer f32 contraction may round differently
    (so on the chip the f32 path needs lane-aligned channels).  The
    cost is the larger padded arrays in HBM."""
    n, h, w_dim, c = x.shape
    kh, kw, c2, k = w.shape
    check_groups(c, k, groups)
    cgrp = c // groups
    assert cgrp == c2, ("weights carry the per-group channel slice: "
                        "w.shape[2] must be C/groups", c, groups, c2)
    if groups > 1 and kout_banks % groups:
        raise ValueError(
            f"grouped conv needs kout banks that split along group "
            f"boundaries: kout_banks={kout_banks} is not a multiple "
            f"of groups={groups} (C={c}, K={k})")
    if cgrp % cin_banks or k % kout_banks:
        raise ValueError(
            f"paper banking invariant (§4.1): C/groups={cgrp} and K={k} "
            f"must divide by the bank counts ({cin_banks}, {kout_banks})")
    (pt, pb), (pl_, pr) = normalize_padding(padding, kh, kw, stride,
                                            h, w_dim, dilation)
    oh, ow = conv_out_shape(h, w_dim, kh, kw, stride, padding, dilation)
    if oh < 1 or ow < 1:
        # same error as banking.plan_tiles — planner and kernel agree
        raise ValueError(
            f"dilated kernel extent "
            f"{dilated_extent(kh, dilation)}×{dilated_extent(kw, dilation)} "
            f"(kernel {kh}×{kw}, dilation={dilation}) exceeds the padded "
            f"input {h + pt + pb}×{w_dim + pl_ + pr}")
    if pool:
        if oh < 2 or ow < 2:
            # same error as banking.plan_tiles — planner and kernel agree
            raise ValueError(
                f"2×2 pool needs a ≥2×2 conv output, got {oh}×{ow}")
        oh, ow = (oh // 2) * 2, (ow // 2) * 2     # floor semantics
    th = oh if h_tile in (0, None) else min(h_tile, oh)
    tw = ow if w_tile in (0, None) else min(w_tile, ow)
    if pool:
        assert th % 2 == 0 and tw % 2 == 0, (
            "pool-aligned tiles required: 2×2 windows must not straddle "
            "tile edges", th, tw)
    n_th, n_tw = -(-oh // th), -(-ow // tw)
    tiled = (th, tw) != (oh, ow)
    # halo'd input window per tile: (tile-1)·s + d·(k-1)+1, overlapping by
    # the dilated kernel extent minus the stride
    in_th = halo_window(th, stride, kh, dilation)
    in_tw = halo_window(tw, stride, kw, dilation)
    hp, wp = h + pt + pb, w_dim + pl_ + pr
    # extend the padded map so the LAST tile's window is in bounds; the
    # matching garbage output rows/cols are sliced off after the kernel
    extra_h = max(0, (n_th - 1) * th * stride + in_th - hp)
    extra_w = max(0, (n_tw - 1) * tw * stride + in_tw - wp)
    extra_c = extra_k = 0
    if tiled or manual_dma:                    # DMA-aligned HBM layout
        extra_w += -(wp + extra_w) % DMA_SUBLANES
        if x.dtype == jnp.int8 and groups == 1:
            extra_c = -c % LANES if cin_banks == 1 else 0
            extra_k = -k % LANES if kout_banks == 1 and manual_dma else 0
    if pt or pb or pl_ or pr or extra_h or extra_w or extra_c:
        # zero margins written into the image BRAMs (exact for zero-point-0)
        x = jnp.pad(x, ((0, 0), (pt, pb + extra_h), (pl_, pr + extra_w),
                        (0, extra_c)))
    if extra_c or extra_k:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, extra_c), (0, extra_k)))
        cgrp, k = cgrp + extra_c, k + extra_k
    hp, wp = hp + extra_h, wp + extra_w
    if pool:
        pth, ptw = th // 2, tw // 2
        poh, pow_ = oh // 2, ow // 2
    else:
        pth, ptw = th, tw
        poh, pow_ = oh, ow
    # per-bank blocks live inside ONE group: the cin sweep covers only the
    # C/groups channels a kout bank's kernel set reads (dense: the whole C)
    geom = ConvGeom(
        n=n, kh=kh, kw=kw, k=k, stride=stride,
        cin_banks=cin_banks, kout_banks=kout_banks,
        cb=cgrp // cin_banks, kb=k // kout_banks, cgrp=cgrp,
        bpg=kout_banks // groups,
        th=th, tw=tw, n_th=n_th, n_tw=n_tw, in_th=in_th, in_tw=in_tw,
        hp=hp, wp=wp, pth=pth, ptw=ptw, poh=poh, pow_=pow_,
        tiled=tiled, int_path=x.dtype == jnp.int8, requant=requant,
        dilation=dilation)
    return x, w, geom


def halo_block_spec(g: ConvGeom, index_map) -> pl.BlockSpec:
    """BlockSpec of one grid step's halo'd input window.

    ``index_map`` returns ``(batch, h_tile, w_tile, channel_block)``
    indices.  An untiled layer reads one full-map block per channel bank.
    A tiled layer's spec is element-indexed (``pl.Element``) in every
    dim, as Mosaic requires once one dim is: a tiled spatial dim starts
    at tile · tile_extent · stride and spans the halo'd extent, an
    untiled one spans the whole padded map."""
    def elem_map(*ids):
        b, ty, tx, c = index_map(*ids)
        return (b, ty * g.th * g.stride if g.n_th > 1 else 0,
                tx * g.tw * g.stride if g.n_tw > 1 else 0, c * g.cb)
    if not g.tiled:
        return pl.BlockSpec((1, g.hp, g.wp, g.cb), index_map)
    return pl.BlockSpec(
        (pl.Element(1), pl.Element(g.in_th if g.n_th > 1 else g.hp),
         pl.Element(g.in_tw if g.n_tw > 1 else g.wp), pl.Element(g.cb)),
        elem_map)


def _conv_kernel(x_ref, w_ref, b_ref, s_ref, o_ref, acc_ref, *, kh: int,
                 kw: int, stride: int, cin_banks: int, relu: bool,
                 pool: bool, requant: bool, acc_dtype, dilation: int = 1):
    co = pl.program_id(4)

    th, tw, kb = acc_ref.shape
    cb = x_ref.shape[3]

    # M5: bias preload — initialize the accumulator with the bias on the
    # first channel bank, exactly like preloading the output BRAMs.
    @pl.when(co == 0)
    def _init():
        acc_ref[...] = jnp.broadcast_to(
            b_ref[...].astype(acc_dtype), acc_ref.shape)

    acc = acc_ref[...]                                 # [TH, TW, KB]
    x = x_ref[0]                                       # [in_th, in_tw, CB]
    # KH×KW shifted matmuls — the 9-MAC adder tree on the MXU; stride-s
    # output pixels read every s-th input row/column of the shifted slab;
    # a dilated kernel's taps sit dilation pixels apart
    for dy in range(kh):
        for dx in range(kw):
            xs = jax.lax.slice(
                x, (dy * dilation, dx * dilation, 0),
                (dy * dilation + (th - 1) * stride + 1,
                 dx * dilation + (tw - 1) * stride + 1, cb),
                (stride, stride, 1)).reshape(th * tw, cb)
            wk = w_ref[dy, dx]                         # [CB, KB]
            acc = acc + jnp.dot(
                xs, wk, preferred_element_type=acc_dtype
            ).reshape(th, tw, kb)
    acc_ref[...] = acc

    # Fused epilogue on the last cin step: the FPGA post-processes the
    # output BRAMs (activation, pooling, requantization) before writeback.
    # Tile-local: pool-aligned tiles guarantee no 2×2 window straddles a
    # tile edge, so per-tile pooling == whole-map pooling.
    @pl.when(co == cin_banks - 1)
    def _epilogue():
        y = acc_ref[...]
        if relu:
            y = jnp.maximum(y, 0)
        if pool:
            y = jnp.max(y.reshape(th // 2, 2, tw // 2, 2, kb), axis=(1, 3))
        if requant:
            y = jnp.clip(jnp.round(y.astype(jnp.float32) * s_ref[...]),
                         -128, 127)
        o_ref[0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "stride", "padding", "groups", "cin_banks", "kout_banks", "h_tile",
    "w_tile", "relu", "pool", "dilation", "interpret"))
def conv2d_ws(x, w, bias=None, out_scale=None, *, stride: int = 1,
              padding="VALID", groups: int = 1, cin_banks: int = 4,
              kout_banks: int = 4, h_tile: int = 0, w_tile: int = 0,
              relu: bool = False, pool: bool = False, dilation: int = 1,
              interpret: bool = False):
    """Generalized paper-dataflow convolution with fused epilogue and
    halo-aware spatial tiling.

    x: [N,H,W,C]; w: [KH,KW,C/groups,K]; bias: [K] or None → [N,OH,OW,K]
    (f32 accumulate for float inputs, int32 for int8 inputs).

    stride / padding: any stride ≥ 1; "SAME" | "VALID" | int |
    ((top,bottom),(left,right)).  Epilogue (applied in-VMEM on the last
    cin step, in this order): ``relu``, ``pool`` (2×2/2 max-pool, floor
    semantics), ``out_scale`` (requantize to int8; scalar or per-channel
    [K]).

    groups: grouped channel contraction (1 = dense, ``groups == C`` =
    depthwise).  The grid shape is unchanged — kout banks are constrained
    to group boundaries (``kout_banks % groups == 0``, so every kout
    bank's kernel set lives inside ONE group) and the input BlockSpec's
    channel index gains the group offset: the cin sweep of kout bank
    ``ko`` walks only its group's C/groups-channel slice.  The per-bank
    weight block, the accumulator revisit pattern, and the halo'd H/W
    tiling are identical to the dense dataflow — a depthwise layer is
    simply the degenerate one-cin-bank sweep per kernel set, which is why
    its arithmetic intensity collapses onto the DMA roofline
    (core/perfmodel prices this).

    h_tile / w_tile: conv-output tile extents (pre-pool pixels).  0 means
    "whole map" (one spatial tile — the seed dataflow).  Tiles need not
    divide the output: the trailing tile is computed on zero-extended
    input and sliced off.  With ``pool=True`` tile sizes must be even so
    pool windows never straddle tile edges.  core/banking.plan_tiles
    picks sizes that fit the VMEM budget.

    cin_banks/kout_banks default to the paper's 4×4 banking; C/groups and
    K must divide by them (the paper's divisible-by-4 invariant, §4.1 —
    ``ref.grouped_banks`` degrades the defaults legally for grouped
    layers).  Mosaic further needs each channel block to be the full
    extent or a multiple of 128 lanes; the planner and the backends only
    pick such counts (``ref.divisor_banks``), while interpret mode runs
    any divisor.
    """
    x, w, g = setup_conv(x, w, stride=stride, padding=padding,
                         groups=groups, cin_banks=cin_banks,
                         kout_banks=kout_banks, h_tile=h_tile,
                         w_tile=w_tile, pool=pool,
                         requant=out_scale is not None, dilation=dilation)
    n, kh, kw, k = g.n, g.kh, g.kw, g.k
    th, tw, n_th, n_tw = g.th, g.tw, g.n_th, g.n_tw
    pth, ptw, poh, pow_ = g.pth, g.ptw, g.poh, g.pow_
    cb, kb, bpg = g.cb, g.kb, g.bpg

    acc_dtype = jnp.int32 if g.int_path else jnp.float32
    if bias is None:
        bias = jnp.zeros((k,), acc_dtype)
    bias = bias.astype(acc_dtype)
    requant = out_scale is not None
    out_dtype = jnp.int8 if requant else acc_dtype
    # scale broadcast to per-kout-bank blocks ([K] covers scalar + per-chan)
    scale = jnp.broadcast_to(
        jnp.asarray(1.0 if out_scale is None else out_scale, jnp.float32),
        (k,))

    # the channel index of the input block carries the GROUP offset: kout
    # bank ko belongs to group ko // bpg, whose cin slice starts at block
    # (ko // bpg) · cin_banks — the cin sweep (co) walks only that slice.
    # Dense convs have bpg == kout_banks, so the offset is always 0.
    kernel = functools.partial(
        _conv_kernel, kh=kh, kw=kw, stride=stride, cin_banks=cin_banks,
        relu=relu, pool=pool, requant=requant, acc_dtype=acc_dtype,
        dilation=dilation)
    out = pl.pallas_call(
        kernel,
        grid=(n, n_th, n_tw, kout_banks, cin_banks),
        in_specs=[
            halo_block_spec(g, lambda b, ty, tx, ko, co: (
                b, ty, tx, (ko // bpg) * cin_banks + co)),
            pl.BlockSpec((kh, kw, cb, kb),
                         lambda b, ty, tx, ko, co: (0, 0, co, ko)),
            pl.BlockSpec((1, kb), lambda b, ty, tx, ko, co: (0, ko)),
            pl.BlockSpec((1, kb), lambda b, ty, tx, ko, co: (0, ko)),
        ],
        out_specs=pl.BlockSpec((1, pth, ptw, kb),
                               lambda b, ty, tx, ko, co: (b, ty, tx, ko)),
        out_shape=jax.ShapeDtypeStruct(
            (n, n_th * pth, n_tw * ptw, k), out_dtype),
        scratch_shapes=[pltpu.VMEM((th, tw, kb), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(x, w, bias.reshape(1, k), scale.reshape(1, k))
    if (n_th * pth, n_tw * ptw) != (poh, pow_):
        out = out[:, :poh, :pow_]
    return out
