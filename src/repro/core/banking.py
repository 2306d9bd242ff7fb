"""BRAM-bank ↔ VMEM-block mapping math (paper §4.1 → TPU v5e), promoted to
a full spatial-tile planner.

The paper stores one quarter of the channels per BRAM (4 image BMGs) and a
4×4 grid of kernel BMGs; crucially its image BRAMs are *fixed-size* — maps
stream through a bounded window, they are never required to fit whole.  On
TPU the analogous resource is VMEM: a grid step's working set is

    2 × (halo'd image block + weight block + epilogue output block
         + bias block + scale block) + accumulator scratch
      + the kernel body's values (conv_vmem_bytes)

— the ×2 is Pallas's load/compute pipeline double-buffering (M4) of the
DMA'd blocks; the accumulator scratch is a single persistent VMEM buffer
revisited across the cin sweep, so it is *not* double-buffered, and the
epilogue output block is the post-pool block in the output dtype (int8
when the epilogue requantizes).  Every block is counted as Mosaic lays it
out (``laid_out_bytes``): the minor dim padded to 128 lanes and the
second-minor to the dtype's sublane tile — a 4-channel int8 map occupies
32× its data bytes.  Counting data bytes instead is how a planner comes
to promise layers that run out of VMEM on the chip.

``plan_tiles`` jointly chooses (h_tile, cin_banks, kout_banks): starting
from the requested banking and the whole map as one tile, it greedily
applies whichever legal move (halve the tile height, double a bank count)
shrinks the working set most, until the plan fits the VMEM budget or
nothing can shrink further.  Bank counts stay lane-legal (each channel
block the full extent or a multiple of 128 — ``ref.lane_legal_banks``),
so for narrow layers spatial tiling does all the shrinking.  Tiles span
the full map width: the width is the input block's sublane dim, and a
full-extent block there is what Mosaic accepts for any width.  Tile-height
halving keeps tiles pool-aligned (even extents when the 2×2 epilogue pool
is fused) so pool windows never straddle tile edges.

Halo math: an ``h_tile × w_tile`` conv-output tile at stride s consumes a
``((h_tile−1)·s + kh) × ((w_tile−1)·s + kw)`` halo'd input window;
adjacent windows overlap by ``k − s`` rows/columns, which are re-read
from HBM per tile (the FPGA re-DMAs its BRAM window boundaries the same
way).  core/perfmodel.tile_traffic prices that re-read.

Stride / padding awareness: the image window lives in the *padded* map
(the FPGA writes zero margins into the image BRAMs) and the accumulator
block is the *strided* conv output, so plans stay correct for SAME /
stride-2 / pooled layers.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional

from repro.kernels.ref import (LANES, VMEM_LIMIT_BYTES, check_groups,
                               conv_out_shape, dilated_extent, grouped_banks,
                               halo_window, lane_legal_banks,
                               normalize_padding)
from repro.kernels.ref import divisor_banks as _ref_divisor_banks

VMEM_BYTES_V5E = 128 * 1024 * 1024   # legacy generous budget (BankPlan)


def laid_out_bytes(shape, itemsize: int) -> int:
    """VMEM bytes of one block as Mosaic lays it out: the minor dim
    padded to 128 lanes, the second-minor to the sublane tile of the
    dtype (8 rows of 32-bit, 16 of 16-bit, 32 of 8-bit values)."""
    *lead, rows, lanes = (1, 1, *shape)[-max(2, len(shape)):]
    sub = 32 // itemsize
    n = itemsize * -(-rows // sub) * sub * -(-lanes // LANES) * LANES
    for d in lead:
        n *= d
    return n


@dataclass(frozen=True)
class BankPlan:
    cin_banks: int
    kout_banks: int
    image_block_bytes: int
    weight_block_bytes: int
    output_block_bytes: int           # epilogue output block (out dtype)
    stride: int = 1
    out_h: int = 0                    # conv output (pre-pool) spatial shape
    out_w: int = 0
    acc_block_bytes: int = 0          # accumulator scratch (acc dtype)
    budget: int = VMEM_BYTES_V5E      # the budget the plan was sized for

    @property
    def working_set_bytes(self) -> int:
        # ×2: Pallas double-buffers the DMA'd blocks (load/compute
        # pipeline, M4); the accumulator scratch is a single persistent
        # buffer — counted once, separately from the epilogue output.
        return (2 * (self.image_block_bytes + self.weight_block_bytes
                     + self.output_block_bytes) + self.acc_block_bytes)

    @property
    def fits_vmem(self) -> bool:
        return self.working_set_bytes <= self.budget


def plan_banks(h: int, w: int, c: int, k: int, kh: int = 3, kw: int = 3,
               in_bytes: int = 1, acc_bytes: int = 4,
               out_bytes: Optional[int] = None,
               cin_banks: int = 4, kout_banks: int = 4,
               stride: int = 1, padding="VALID",
               vmem_budget: int = VMEM_BYTES_V5E) -> BankPlan:
    """Channel-bank-only legacy planner: start from the paper's 4×4
    banking; double bank counts until the working set fits VMEM (each
    doubling halves the per-bank block).  ``plan_tiles`` supersedes this
    with joint spatial/channel planning."""
    assert c % cin_banks == 0 and k % kout_banks == 0, (
        "divisible-by-4 invariant (paper §4.1)")
    out_bytes = acc_bytes if out_bytes is None else out_bytes
    (pt, pb), (pl_, pr) = normalize_padding(padding, kh, kw, stride, h, w)
    hp, wp = h + pt + pb, w + pl_ + pr
    oh, ow = conv_out_shape(h, w, kh, kw, stride, padding)
    while True:
        cb, kb = c // cin_banks, k // kout_banks
        plan = BankPlan(
            cin_banks=cin_banks, kout_banks=kout_banks,
            image_block_bytes=hp * wp * cb * in_bytes,
            weight_block_bytes=kh * kw * cb * kb * in_bytes,
            output_block_bytes=oh * ow * kb * out_bytes,
            stride=stride, out_h=oh, out_w=ow,
            acc_block_bytes=oh * ow * kb * acc_bytes,
            budget=vmem_budget,
        )
        if plan.fits_vmem or (cb == 1 and kb == 1):
            return plan
        if plan.image_block_bytes >= plan.acc_block_bytes and cb > 1 \
                and c % (cin_banks * 2) == 0:
            cin_banks *= 2
        elif kb > 1 and k % (kout_banks * 2) == 0:
            kout_banks *= 2
        elif cb > 1 and c % (cin_banks * 2) == 0:
            cin_banks *= 2
        else:
            return plan


@dataclass(frozen=True)
class TilePlan:
    """A joint (spatial tile × channel bank) decomposition of one conv
    layer for the tiled conv2d_ws kernel.

    ``h_tile``/``w_tile`` are conv-output tile extents (pre-pool pixels);
    ``in_h_tile``/``in_w_tile`` the halo'd input windows they consume.
    The ``*_block_bytes`` fields are the DATA bytes of each per-grid-step
    block — what a DMA moves, priced by perfmodel.tile_traffic;
    ``working_set_bytes`` is the laid-out working set of the module
    docstring (``conv_vmem_bytes``), which is what must fit the VMEM
    budget.  Pallas's implicit pipeline double-buffers the DMA'd blocks
    and conv2d_ws_pipe materializes the same two slots as explicit VMEM
    scratch, so it is identical for both kernel variants and
    ``pipelined`` never changes whether a plan fits."""
    cin_banks: int
    kout_banks: int
    h_tile: int
    w_tile: int
    n_h_tiles: int
    n_w_tiles: int
    in_h_tile: int                    # (h_tile-1)·stride + dilation·(kh-1)+1
    in_w_tile: int
    image_block_bytes: int            # halo'd input window × cb × in_bytes
    weight_block_bytes: int
    acc_block_bytes: int              # accumulator scratch (acc dtype)
    output_block_bytes: int           # epilogue output block (out dtype)
    working_set_bytes: int            # laid-out VMEM working set
    stride: int = 1
    out_h: int = 0                    # whole-map conv output (pool-floored)
    out_w: int = 0
    pool: bool = False
    in_bytes: int = 1
    budget: int = VMEM_LIMIT_BYTES
    groups: int = 1                   # grouped conv: kout banks stay inside
                                      # group boundaries; image blocks are
                                      # the per-group C/groups slice
    pipelined: bool = False           # run this layer on conv2d_ws_pipe
                                      # (explicit ping-pong DMA) instead of
                                      # the implicitly pipelined conv2d_ws

    @property
    def fits_vmem(self) -> bool:
        return self.working_set_bytes <= self.budget

    @property
    def n_tiles(self) -> int:
        return self.n_h_tiles * self.n_w_tiles

    @property
    def tiled(self) -> bool:
        return self.n_tiles > 1

    @property
    def halo_read_factor(self) -> float:
        """Input bytes DMA'd with tiling ÷ the whole-map input bytes for
        one full kout sweep — ≥ 1; the excess is halo re-reads (plus the
        zero-extension of the trailing partial tiles)."""
        kh = self.in_h_tile - (self.h_tile - 1) * self.stride
        kw = self.in_w_tile - (self.w_tile - 1) * self.stride
        whole = (halo_window(self.out_h, self.stride, kh)
                 * halo_window(self.out_w, self.stride, kw))
        tiled = self.n_tiles * self.in_h_tile * self.in_w_tile
        return tiled / whole if whole else 1.0


def conv_vmem_bytes(in_h: int, in_w: int, cb: int, kh: int, kw: int,
                    kb: int, th: int, tw: int, pool: bool, in_bytes: int,
                    out_bytes: int, acc_bytes: int) -> int:
    """Laid-out VMEM working set of one conv grid step.

    Blocks: two buffers each of the (in_h × in_w × cb) input window, the
    (kh × kw × cb × kb) weight bank, the epilogue output block and the
    (1 × kb) bias and scale blocks, plus the (th × tw × kb) accumulator
    scratch once.  Kernel body: Mosaic keeps the body's values in VMEM
    too — the loaded input window, one tap's (th·tw × cb) slice, and up
    to four accumulator-sized values (the running sum, a tap's matmul
    result, their sum, the epilogue's f32 copy).  Against the scoped
    allocation Mosaic reported for a vgg_imagenet layer (112×112×32 → 64,
    both kernels, two tile heights) compiled for a v5e, this count ran
    11–23% high: a safe promise, not a tight one."""
    pth, ptw = (th // 2, tw // 2) if pool else (th, tw)
    acc = laid_out_bytes((th * tw, kb), acc_bytes)
    window = laid_out_bytes((in_h, in_w, cb), in_bytes)
    blocks = (2 * (window + laid_out_bytes((kh, kw, cb, kb), in_bytes)
                   + laid_out_bytes((pth, ptw, kb), out_bytes)
                   + 2 * laid_out_bytes((1, kb), 4))
              + laid_out_bytes((th, tw, kb), acc_bytes))
    body = window + laid_out_bytes((th * tw, cb), in_bytes) + 4 * acc
    return blocks + body


def tile_plan(h: int, w: int, c: int, k: int, kh: int, kw: int, th: int,
              tw: int, cin_banks: int, kout_banks: int, *, stride: int = 1,
              padding="VALID", pool: bool = False, groups: int = 1,
              dilation: int = 1, in_bytes: int = 1, acc_bytes: int = 4,
              out_bytes: Optional[int] = None,
              budget: int = VMEM_LIMIT_BYTES) -> TilePlan:
    """The TilePlan of one (h_tile, w_tile, cin_banks, kout_banks) state —
    the single geometry builder ``plan_tiles`` and the autotuner share.
    An untiled spatial dim is one full-extent block of the padded map (as
    conv2d_ws's BlockSpec has it), a tiled one the halo'd window."""
    out_bytes = acc_bytes if out_bytes is None else out_bytes
    (pt, pb), (pl_, pr) = normalize_padding(padding, kh, kw, stride, h, w,
                                            dilation)
    oh, ow = conv_out_shape(h, w, kh, kw, stride, padding, dilation)
    if pool:
        oh, ow = (oh // 2) * 2, (ow // 2) * 2
    cb, kb = c // groups // cin_banks, k // kout_banks
    n_th, n_tw = -(-oh // th), -(-ow // tw)
    in_th = halo_window(th, stride, kh, dilation)
    in_tw = halo_window(tw, stride, kw, dilation)
    blk_h = in_th if n_th > 1 else max(in_th, h + pt + pb)
    blk_w = in_tw if n_tw > 1 else max(in_tw, w + pl_ + pr)
    pth, ptw = (th // 2, tw // 2) if pool else (th, tw)
    return TilePlan(
        cin_banks=cin_banks, kout_banks=kout_banks, h_tile=th, w_tile=tw,
        n_h_tiles=n_th, n_w_tiles=n_tw, in_h_tile=in_th, in_w_tile=in_tw,
        image_block_bytes=in_th * in_tw * cb * in_bytes,
        weight_block_bytes=kh * kw * cb * kb * in_bytes,
        acc_block_bytes=th * tw * kb * acc_bytes,
        output_block_bytes=pth * ptw * kb * out_bytes,
        working_set_bytes=conv_vmem_bytes(
            blk_h, blk_w, cb, kh, kw, kb, th, tw, pool, in_bytes, out_bytes,
            acc_bytes),
        stride=stride, out_h=oh, out_w=ow, pool=pool, in_bytes=in_bytes,
        budget=budget, groups=groups)


def _align_tile(v: int, pool: bool) -> int:
    if pool:
        return max(2, -(-v // 2) * 2)
    return max(1, v)


def plan_tiles(h: int, w: int, c: int, k: int, kh: int = 3, kw: int = 3, *,
               stride: int = 1, padding="VALID", pool: bool = False,
               groups: int = 1, dilation: int = 1, in_bytes: int = 1,
               acc_bytes: int = 4, out_bytes: Optional[int] = None,
               cin_banks: int = 4, kout_banks: int = 4,
               vmem_budget: Optional[int] = VMEM_LIMIT_BYTES,
               kernel: str = "auto", calib=None) -> TilePlan:
    """Jointly choose (h_tile, cin_banks, kout_banks) so the laid-out
    per-grid-step working set fits ``vmem_budget``.

    Greedy descent from (whole map, the requested banks degraded to
    legal counts by ``grouped_banks``): each step applies
    the legal move — halve h_tile (kept pool-aligned), double cin_banks,
    double kout_banks, a doubling only where the channel blocks stay
    lane-legal — that shrinks the working set most; stops when the plan
    fits or no move shrinks it.  Tiles keep the full map width (see the
    module docstring).  With ``vmem_budget=None`` no fitting is attempted
    (whole-map single tile — the seed dataflow).

    ``groups`` plans the grouped/depthwise working set: image and weight
    blocks carry the per-group C/groups channel slice (a kout bank only
    ever DMAs its own group's channels), cin-bank doubling is bounded by
    that slice, and kout-bank doubling stays on group boundaries.
    Depthwise layers therefore bottom out at one-channel blocks whose
    working set is pure DMA — the planner's view of why their arithmetic
    intensity sits on the DMA roofline (perfmodel prices it).

    ``dilation`` widens the halo'd input windows to the dilated kernel
    extent ``dilation·(k−1)+1`` (weight blocks are unchanged — the taps
    spread, they do not multiply); a layer whose dilated extent exceeds
    the padded input raises the same shaped ``ValueError`` as the kernel
    itself, at plan time.

    ``out_bytes`` is the epilogue output element size (1 when the fused
    requantize writes int8; defaults to ``acc_bytes``).

    ``kernel`` selects the conv kernel variant the plan will run on:
    ``"sequential"`` (conv2d_ws), ``"pipelined"`` (conv2d_ws_pipe, the
    explicit ping-pong DMA kernel), or ``"auto"`` — consult
    ``perfmodel.pipeline_estimate`` and set ``TilePlan.pipelined`` only
    where the overlap model says it wins (tiny layers lose to the
    per-slab protocol overhead and stay sequential).  The choice never
    affects VMEM fitting: both variants hold the same two buffered
    copies of each block (see ``working_set_bytes``).

    ``calib`` (a ``core.calibration.CalibrationTable``) makes the
    ``kernel="auto"`` crossover consult the MEASUREMENT-calibrated model
    instead of the analytic one; the tile/bank descent itself is VMEM
    geometry and does not depend on it.  ``core/autotune.py`` supersedes
    this greedy descent with a full search of the candidate space — this
    function remains the fallback when no tuner/table is present."""
    if kernel not in ("auto", "pipelined", "sequential"):
        raise ValueError(f"kernel must be auto|pipelined|sequential, "
                         f"got {kernel!r}")
    cgrp = c // groups
    # the requested banking degrades to the largest legal counts: divisors
    # of the channel slices, kout banks on group boundaries, lane-legal
    # channel blocks (grouped_banks → ref.divisor_banks)
    cin_banks, kout_banks = grouped_banks(c, k, groups, want_cin=cin_banks,
                                          want_kout=kout_banks)
    (pt, pb), (pl_, pr) = normalize_padding(padding, kh, kw, stride, h, w,
                                            dilation)
    if (dilated_extent(kh, dilation) > h + pt + pb
            or dilated_extent(kw, dilation) > w + pl_ + pr):
        # same error (and wording) as conv2d_ws.setup_conv — an
        # over-dilated layer must fail at PLAN time with the geometry
        # spelled out, not produce an out-of-range halo'd BlockSpec
        raise ValueError(
            f"dilated kernel extent "
            f"{dilated_extent(kh, dilation)}×{dilated_extent(kw, dilation)} "
            f"(kernel {kh}×{kw}, dilation={dilation}) exceeds the padded "
            f"input {h + pt + pb}×{w + pl_ + pr}")
    oh, ow = conv_out_shape(h, w, kh, kw, stride, padding, dilation)
    if pool:
        # agree with the kernel: conv2d_ws rejects fused pooling of conv
        # outputs smaller than the 2×2 window, so the planner must not
        # invent a 2×2 map (and its phantom tile traffic) for such layers
        if oh < 2 or ow < 2:
            raise ValueError(
                f"2×2 pool needs a ≥2×2 conv output, got {oh}×{ow}")
        oh, ow = (oh // 2) * 2, (ow // 2) * 2
    budget = VMEM_LIMIT_BYTES if vmem_budget is None else vmem_budget

    def build(th: int, cbn: int, kbn: int) -> TilePlan:
        return tile_plan(h, w, c, k, kh, kw, th, ow, cbn, kbn,
                         stride=stride, padding=padding, pool=pool,
                         groups=groups, dilation=dilation,
                         in_bytes=in_bytes, acc_bytes=acc_bytes,
                         out_bytes=out_bytes, budget=budget)

    def choose_kernel(plan: TilePlan) -> TilePlan:
        if kernel == "sequential":
            return plan
        if kernel == "pipelined":
            return replace(plan, pipelined=True)
        from repro.core import perfmodel
        psums = perfmodel.psum_count(h, w, c, k, kh, kw, stride=stride,
                                     padding=padding, groups=groups,
                                     dilation=dilation)
        est = perfmodel.pipeline_estimate(plan, psums, calib=calib)
        return replace(plan, pipelined=est["profitable"])

    state = (oh, cin_banks, kout_banks)
    plan = build(*state)
    if vmem_budget is None:
        return choose_kernel(plan)
    min_tile = 2 if pool else 1
    while not plan.fits_vmem:
        th, cbn, kbn = state
        moves = []
        if _align_tile(-(-th // 2), pool) < th and th > min_tile:
            moves.append((_align_tile(-(-th // 2), pool), cbn, kbn))
        if cgrp % (cbn * 2) == 0 and lane_legal_banks(cgrp, cbn * 2):
            moves.append((th, cbn * 2, kbn))
        # kout doubling keeps banks on group boundaries automatically
        # (2·(m·groups) is still a multiple of groups)
        if k % (kbn * 2) == 0 and lane_legal_banks(k, kbn * 2):
            moves.append((th, cbn, kbn * 2))
        candidates = [(build(*m), m) for m in moves]
        candidates = [(p, m) for p, m in candidates
                      if p.working_set_bytes < plan.working_set_bytes]
        if not candidates:
            # nothing shrinks further: best effort
            return choose_kernel(plan)
        plan, state = min(candidates,
                          key=lambda pm: pm[0].working_set_bytes)
    return choose_kernel(plan)


def divisor_banks(dim: int, want: int) -> int:
    """Largest bank count ≤ ``want`` that divides ``dim`` — how the paper's
    divisible-by-4 invariant degrades for awkward channel counts (e.g. the
    C=1 input layer of a grayscale network runs on a single image BMG).
    Delegates to the shared definition in kernels/ref.py; ``grouped_banks``
    (re-exported here) is its grouped-conv generalization."""
    return _ref_divisor_banks(dim, want)
