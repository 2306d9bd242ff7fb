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
* the accumulator is a 2-D (pixels × Kb) VMEM scratch block (the output
  BRAMs), revisited across the cin sweep and *initialized with the bias
  at cin step 0* — the paper's bias-preload trick (M5), so bias costs
  zero extra passes; it takes its (H, W, Kb) map shape only in the
  epilogue, so no int32 value changes layout inside the cin sweep;
* the KH×KW window is the systolic-array form of "9 MACs + adder tree"
  per PCORE (``conv_slab``, shared with conv2d_ws_pipe).  Where the tile
  width is off the 8-row sublane tile (VGG-16's 28² and 14² maps), the
  KH·KW taps are folded into one contraction: each tap is a contiguous
  row run of the window flattened over its full width, copied into a
  (rows × KH·KW·Cb) patch in VMEM, and one (KH·KW·Cb)-deep MXU matmul
  adds the cin slab into the accumulator.  Elsewhere each tap runs its
  own (HW×Cb)@(Cb×Kb) matmul on the shifted slice, the taps summed
  before one accumulator update per slab.  The body only ever sees a
  stride-1 layer: ``setup_conv`` lays a stride-s layer out as a stride-1
  conv over the s² space-to-depth phases of its padded map;
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
(ty, tx) consumes the (stride-1) input window starting at element
``(ty·h_tile, tx·w_tile)`` with extent

    in_tile = tile + k − 1            (per spatial dim)

so adjacent input windows overlap by a halo of ``k − 1`` rows/columns —
re-read from HBM per tile, exactly like the FPGA re-DMAs the boundary
rows of its image BRAM window.  The input BlockSpec of a tiled layer is
element-indexed (``pl.Element``) because halo'd windows overlap: block
strides (h_tile) differ from block extents (in_tile).  An untiled spatial
dim spans the whole padded map, so a planner that tiles only H
(banking.plan_tiles) keeps W, the sublane dim, at the array's own extent
— the form Mosaic accepts.  The padded map is extended with extra zero
rows/columns on the bottom/right so the LAST tile's window is always in
bounds; the correspondingly padded output rows are sliced off after the
call.

The fused epilogue is tile-local: with ``pool=True`` tile sizes must be
even (pool-aligned) so no 2×2 pool window straddles a tile edge — tile
boundaries then land on pool-window boundaries and tiled pooling equals
whole-map pooling.  core/banking.plan_tiles chooses (h_tile, cin_banks,
kout_banks) jointly so the VMEM working set (halo'd input block + weight
block + epilogue output block, with pipeline double-buffering, plus the
accumulator and tap-patch scratch and the kernel body's values — all
counted as Mosaic lays them out) fits ``VMEM_LIMIT_BYTES``, the
``vmem_limit_bytes`` both conv kernels pass to Mosaic.

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
                               conv_out_shape, dilated_extent, folds_taps,
                               halo_window, normalize_padding, phase_split)

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
    strided: bool = False     # laid out from a stride > 1 layer


def setup_conv(x, w, *, stride: int = 1, padding="VALID", groups: int = 1,
               cin_banks: int = 4, kout_banks: int = 4, h_tile: int = 0,
               w_tile: int = 0, pool: bool = False, requant: bool = False,
               dilation: int = 1, manual_dma: bool = False):
    """Validate one conv layer pass and materialize its padded input.

    Returns ``(x_padded, w, geom)`` where ``x_padded`` carries the zero
    margins (padding + trailing-tile zero-extension — exact for the
    symmetric zero-point-0 int8 scheme) and ``geom`` is the resolved
    :class:`ConvGeom` of a stride-1 layer.  Raises exactly the errors the
    kernels contract with the planner (banking invariant, group
    boundaries, sub-2×2 pooled outputs, pool-aligned tiles).

    A stride-s layer (s > 1) is laid out as the stride-1 conv it equals
    (``space_to_depth``): its padded map becomes the phases that hold a
    tap (``ref.phase_split``: s² of them, one for a 1×1 kernel) stacked
    along the channels, its weights a ⌈e/s⌉×⌈e/s⌉ kernel over those
    channels with zero taps where the kernel does not reach.  The
    kernels, their compute body and the planner's VMEM count then see an
    ordinary stride-1 layer with the same output, so no kernel slices
    with a stride (Mosaic has no strided vector slice); ``geom.strided``
    marks it for the kernel's name.

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
    if pool and (oh < 2 or ow < 2):
        # same error as banking.plan_tiles — planner and kernel agree
        raise ValueError(
            f"2×2 pool needs a ≥2×2 conv output, got {oh}×{ow}")
    if stride > 1:
        x, w = space_to_depth(x, w, stride=stride,
                              pads=((pt, pb), (pl_, pr)), out=(oh, ow),
                              groups=groups, dilation=dilation)
        x, w, geom = setup_conv(
            x, w, padding="VALID", groups=groups, cin_banks=cin_banks,
            kout_banks=kout_banks, h_tile=h_tile, w_tile=w_tile, pool=pool,
            requant=requant, manual_dma=manual_dma)
        return x, w, geom._replace(strided=True)
    if pool:
        oh, ow = (oh // 2) * 2, (ow // 2) * 2     # floor semantics
    th = oh if h_tile in (0, None) else min(h_tile, oh)
    tw = ow if w_tile in (0, None) else min(w_tile, ow)
    if pool:
        assert th % 2 == 0 and tw % 2 == 0, (
            "pool-aligned tiles required: 2×2 windows must not straddle "
            "tile edges", th, tw)
    n_th, n_tw = -(-oh // th), -(-ow // tw)
    tiled = (th, tw) != (oh, ow)
    # halo'd input window per tile: tile + d·(k-1), overlapping by the
    # dilated kernel extent minus one
    in_th = halo_window(th, 1, kh, dilation)
    in_tw = halo_window(tw, 1, kw, dilation)
    hp, wp = h + pt + pb, w_dim + pl_ + pr
    # extend the padded map so the LAST tile's window is in bounds; the
    # matching garbage output rows/cols are sliced off after the kernel
    extra_h = max(0, (n_th - 1) * th + in_th - hp)
    extra_w = max(0, (n_tw - 1) * tw + in_tw - wp)
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
        n=n, kh=kh, kw=kw, k=k,
        cin_banks=cin_banks, kout_banks=kout_banks,
        cb=cgrp // cin_banks, kb=k // kout_banks, cgrp=cgrp,
        bpg=kout_banks // groups,
        th=th, tw=tw, n_th=n_th, n_tw=n_tw, in_th=in_th, in_tw=in_tw,
        hp=hp, wp=wp, pth=pth, ptw=ptw, poh=poh, pow_=pow_,
        tiled=tiled, int_path=x.dtype == jnp.int8, requant=requant,
        dilation=dilation)
    return x, w, geom


def space_to_depth(x, w, *, stride: int, pads, out, groups: int = 1,
                   dilation: int = 1):
    """A stride-``stride`` conv of ``x`` [N,H,W,C] by ``w`` [KH,KW,C/g,K]
    under ``pads`` ((top, bottom), (left, right)) with output ``out`` =
    (OH, OW), as a VALID stride-1 conv: returns its input
    [N, OH+QH−1, OW+QW−1, g·PH·PW·C/g] and weights [QH, QW, PH·PW·C/g, K],
    (QH, PH) and (QW, PW) the taps and phases of ``ref.phase_split``.

    Channel (g, ry, rx, c) of input pixel (i, j) is padded-map pixel
    (s·i + ry, s·j + rx) of channel c of group g, so each group's channels
    stay one contiguous slice; weight tap (qy, qx) of channel (ry, rx, c)
    is the kernel's tap (s·qy + ry, s·qx + rx) — a dilated kernel's taps
    spread over its extent first — and zero past the kernel's edge.  The
    padded map is cut or zero-extended to s·(OH+QH−1) rows and
    s·(OW+QW−1) columns, which hold every row and column an output
    reads.  Run in XLA, under the calling node's scope."""
    n, h, wd, c = x.shape
    kh, kw, cg, k = w.shape
    (qh, ph), (qw, pw) = (phase_split(kh, stride, dilation),
                          phase_split(kw, stride, dilation))
    if dilation > 1:
        w = jnp.zeros((dilated_extent(kh, dilation),
                       dilated_extent(kw, dilation), cg, k), w.dtype
                      ).at[::dilation, ::dilation].set(w)
    hq, wq = out[0] + qh - 1, out[1] + qw - 1
    (pt, _), (pl_, _) = pads
    x = jnp.pad(x, ((0, 0), (pt, max(0, stride * hq - pt - h)),
                    (pl_, max(0, stride * wq - pl_ - wd)), (0, 0)))
    x = x[:, :stride * hq, :stride * wq].reshape(
        n, hq, stride, wq, stride, groups, cg)[:, :, :ph, :, :pw]
    x = x.transpose(0, 1, 3, 5, 2, 4, 6).reshape(n, hq, wq,
                                                 groups * ph * pw * cg)
    w = jnp.pad(w, ((0, stride * qh - w.shape[0]),
                    (0, stride * qw - w.shape[1]), (0, 0), (0, 0)))
    w = w.reshape(qh, stride, qw, stride, cg, k)[:, :ph, :, :pw]
    return x, w.transpose(0, 2, 1, 3, 4, 5).reshape(qh, qw, ph * pw * cg, k)


def kernel_name(base: str, g: ConvGeom):
    """The ``pallas_call`` name of a layer pass: ``<base>_strided`` for a
    layer laid out from a stride > 1 conv, so a profile keeps its time
    apart; None (the wrapping function's name, ``<base>``) otherwise."""
    return f"{base}_strided" if g.strided else None


def halo_block_spec(g: ConvGeom, index_map) -> pl.BlockSpec:
    """BlockSpec of one grid step's halo'd input window.

    ``index_map`` returns ``(batch, h_tile, w_tile, channel_block)``
    indices.  An untiled layer reads one full-map block per channel bank.
    A tiled layer's spec is element-indexed (``pl.Element``) in every
    dim, as Mosaic requires once one dim is: a tiled spatial dim starts
    at tile · tile_extent and spans the halo'd extent, an untiled one
    spans the whole padded map."""
    def elem_map(*ids):
        b, ty, tx, c = index_map(*ids)
        return (b, ty * g.th if g.n_th > 1 else 0,
                tx * g.tw if g.n_tw > 1 else 0, c * g.cb)
    if not g.tiled:
        return pl.BlockSpec((1, g.hp, g.wp, g.cb), index_map)
    return pl.BlockSpec(
        (pl.Element(1), pl.Element(g.in_th if g.n_th > 1 else g.hp),
         pl.Element(g.in_tw if g.n_tw > 1 else g.wp), pl.Element(g.cb)),
        elem_map)


def conv_slab(x, w, acc_ref, patch_ref, *, th: int, tw: int,
              dilation: int, acc_dtype):
    """Add one cin slab's convolution into the accumulator — the compute
    body both conv kernels share, so their results agree bit for bit.

    ``x`` is the slab's halo'd input window [in_th, W, CB], ``w`` its
    weight bank [KH, KW, CB, KB], ``acc_ref`` the 2-D accumulator.  The
    form follows the shape (``ref.folds_taps``; ``conv_scratch`` sizes
    the scratch to match):

    * taps folded into one contraction (``patch_ref`` given): the window
      is flattened to [in_th·W, CB] rows, so tap (dy, dx) is the
      contiguous run of M = (TH−1)·W + TW rows from dilation·(dy·W + dx)
      and output pixel (y, x) is accumulator row y·W + x; the columns
      x ≥ TW between the rows are computed and dropped by the epilogue.
      Each tap's rows are copied into column block t = dy·KW + dx of the
      [M, KH·KW·CB] patch, and one MXU dot against the bank reshaped to
      [KH·KW·CB, KB] (a merge of leading dims) adds the slab into the
      [TH·W, KB] accumulator: no value changes layout;
    * otherwise one dot per tap on its shifted [TH, TW] slice, the dots
      summed and added into the [TH·TW, KB] accumulator once per slab.  A
      1×1 kernel is one tap.

    Every layer reaches here at stride 1 (``setup_conv``)."""
    kh, kw, cb, kb = w.shape
    if patch_ref is not None:
        wide = x.shape[1]
        rows = patch_ref.shape[0]
        flat = x.reshape(x.shape[0] * wide, cb)
        for dy in range(kh):
            for dx in range(kw):
                t = dy * kw + dx
                off = dilation * (dy * wide + dx)
                patch_ref[:, t * cb:(t + 1) * cb] = jax.lax.slice(
                    flat, (off, 0), (off + rows, cb))
        acc_ref[:rows] += jnp.dot(patch_ref[...],
                                  w.reshape(kh * kw * cb, kb),
                                  preferred_element_type=acc_dtype)
        return
    part = None
    for dy in range(kh):
        for dx in range(kw):
            xs = jax.lax.slice(
                x, (dy * dilation, dx * dilation, 0),
                (dy * dilation + th, dx * dilation + tw, cb)
            ).reshape(th * tw, cb)
            d = jnp.dot(xs, w[dy, dx], preferred_element_type=acc_dtype)
            part = d if part is None else part + d
    acc_ref[...] += part


def conv_epilogue(acc_ref, s_ref, *, th: int, tw: int, relu: bool,
                  pool: bool, requant: bool):
    """The fused epilogue both conv kernels run on the finished
    accumulator: the FPGA post-processes the output BRAMs (activation,
    pooling, requantization) before writeback.  The 2-D accumulator takes
    its [TH, TW, KB] map shape here, once per grid step, dropping the
    columns a folded body computes past TW.  Tile-local: pool-aligned
    tiles guarantee no 2×2 window straddles a tile edge, so per-tile
    pooling == whole-map pooling."""
    kb = acc_ref.shape[1]
    wide = acc_ref.shape[0] // th
    y = acc_ref[...].reshape(th, wide, kb)
    if wide != tw:
        y = y[:, :tw]
    if relu:
        y = jnp.maximum(y, 0)
    if pool:
        y = jnp.max(y.reshape(th // 2, 2, tw // 2, 2, kb), axis=(1, 3))
    if requant:
        y = jnp.clip(jnp.round(y.astype(jnp.float32) * s_ref[...]),
                     -128, 127)
    return y


def conv_scratch(g: ConvGeom, dtype, acc_dtype) -> list:
    """VMEM scratch of the compute body (``conv_slab``): the 2-D
    accumulator and, where the body folds the taps, the tap patch; a
    folded accumulator spans the window's full width W."""
    if not folds_taps(g.tw, g.kh, g.kw):
        return [pltpu.VMEM((g.th * g.tw, g.kb), acc_dtype)]
    wide = g.in_tw if g.n_tw > 1 else g.wp
    return [pltpu.VMEM((g.th * wide, g.kb), acc_dtype),
            pltpu.VMEM(((g.th - 1) * wide + g.tw, g.kh * g.kw * g.cb), dtype)]


def _conv_kernel(x_ref, w_ref, b_ref, s_ref, o_ref, acc_ref, patch_ref=None,
                 *, th: int, tw: int, cin_banks: int,
                 relu: bool, pool: bool, requant: bool, acc_dtype,
                 dilation: int = 1):
    co = pl.program_id(4)

    # M5: bias preload — initialize the accumulator with the bias on the
    # first channel bank, exactly like preloading the output BRAMs.
    @pl.when(co == 0)
    def _init():
        acc_ref[...] = jnp.broadcast_to(
            b_ref[...].astype(acc_dtype), acc_ref.shape)

    conv_slab(x_ref[0], w_ref[...], acc_ref, patch_ref, th=th, tw=tw,
              dilation=dilation, acc_dtype=acc_dtype)

    @pl.when(co == cin_banks - 1)
    def _epilogue():
        o_ref[0] = conv_epilogue(
            acc_ref, s_ref, th=th, tw=tw, relu=relu, pool=pool,
            requant=requant).astype(o_ref.dtype)


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

    stride / padding: any stride ≥ 1 (a strided layer runs as the stride-1
    conv over its space-to-depth phases, ``setup_conv``, under the kernel
    name ``conv2d_ws_strided``); "SAME" | "VALID" | int |
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
        _conv_kernel, th=th, tw=tw, cin_banks=cin_banks,
        relu=relu, pool=pool, requant=requant, acc_dtype=acc_dtype,
        dilation=g.dilation)
    out = pl.pallas_call(
        kernel,
        name=kernel_name("conv2d_ws", g),
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
        scratch_shapes=conv_scratch(g, x.dtype, acc_dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(x, w, bias.reshape(1, k), scale.reshape(1, k))
    if (n_th * pth, n_tw * ptw) != (poh, pow_):
        out = out[:, :poh, :pow_]
    return out
