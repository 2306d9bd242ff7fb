"""Manual-DMA double-buffered variant of the weight-stationary conv kernel:
the paper's two-stage load/compute pipeline (M4) made EXPLICIT.

``conv2d_ws`` leans on Pallas's implicit software pipeline: BlockSpecs
describe the blocks, Pallas double-buffers the HBM→VMEM DMAs behind the
MXU.  That is the right default, but BENCH_network.json shows where it is
not enough — depthwise/grouped layers whose arithmetic intensity collapses
onto the shared-DMA roofline (``dma_bound_board`` rows).  This kernel is
the canonical FPGA answer (ping-pong BRAM buffers overlapping
load/compute/store) written out by hand:

* inputs stay in HBM (``memory_space=ANY``); the kernel owns the motion;
* **ping-pong VMEM buffers** (2× halo'd input window, 2× weight bank):
  while slab ``g`` (one (tile, kout bank, cin bank) step) is computing on
  buffer ``g % 2``, the DMAs for slab ``g+1`` stream into buffer
  ``(g+1) % 2`` — ``pltpu.make_async_copy`` + per-slot DMA semaphores;
* the prefetch chain crosses grid steps: the LAST cin slab of one
  (tile, ko) grid step starts the FIRST slab of the next, so the pipe
  never drains between kernel sets or spatial tiles (scratch buffers and
  semaphores persist across the sequential TPU grid);
* the fused epilogue (ReLU → 2×2 max-pool → requantize) writes the
  output block, whose VMEM→HBM store Pallas overlaps with the next grid
  step (an output BlockSpec: the block may be as narrow as the map, which
  Mosaic accepts there and refuses in a hand-written DMA window).

Logical iteration space is IDENTICAL to ``conv2d_ws`` — the
(N, h_tiles, w_tiles, kout, cin) sweep with co innermost — except the cin
sweep runs as an in-kernel ``fori_loop`` instead of a grid dimension (the
accumulator lives in the same VMEM scratch either way).  The compute body
is ``conv2d_ws``'s own (``conv_slab``: the KH·KW taps folded into one
contraction per cin slab where the tile width is off the sublane tile,
one dot per tap elsewhere; then ``conv_epilogue``), run on the same
operand blocks in the same order, so results are **bit-exact** against
``conv2d_ws`` on both the int32 and the f32 accumulator paths (asserted
across the full stride × padding × epilogue × groups × tiling space in
tests/test_pipeline_kernel.py).

The input map is laid out for the DMA windows by ``setup_conv``
(``manual_dma=True``: width to a multiple of 8, a single channel bank to
a multiple of 128 channels, zero-filled).

VMEM working set: 2·input + 2·weight + 2·output blocks plus the compute
body's scratch (accumulator, and the tap patch where the taps fold) — the
laid-out bytes ``banking.TilePlan.working_set_bytes`` already budgets (the
implicit pipeline double-buffers the same blocks), so any plan that fits
the sequential kernel fits this one.  A layer runs here when its plan
sweeps more than one slab (``TilePlan.pipelined``: one slab has no next
slab to prefetch); the backend dispatches on that property.

Interpret-mode note: ``make_async_copy`` executes eagerly under
``interpret=True`` (the DMA completes at ``start()``), so CPU validation
checks the full descriptor/semaphore protocol but not the overlap itself;
on TPU the same code compiles to real async DMAs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.conv2d_ws import (conv_epilogue, conv_scratch, conv_slab,
                                     kernel_name, setup_conv)
from repro.kernels.ref import VMEM_LIMIT_BYTES


def _window(ref, dim: int, start, size: int):
    """Index of one DMA window along ``dim`` of ``ref``: the static full
    slice where the window spans the whole dim (an untiled spatial dim,
    a single channel bank), else a dynamic ``pl.ds``.  Mosaic accepts an
    unaligned extent on a tiled (second-minor or minor) dim only as the
    full extent."""
    return slice(None) if size == ref.shape[dim] else pl.ds(start, size)


def _pipe_kernel(x_hbm, w_hbm, b_ref, s_ref, o_ref, xb, wb, in_sem, w_sem,
                 acc_ref, patch_ref=None, *, cin_banks: int,
                 kout_banks: int, th: int, tw: int, cb: int, kb: int,
                 cgrp: int, bpg: int, relu: bool, pool: bool, requant: bool,
                 acc_dtype, dilation: int = 1):
    b, ty, tx, ko = (pl.program_id(i) for i in range(4))
    n_th, n_tw = pl.num_programs(1), pl.num_programs(2)
    n_steps = pl.num_programs(0) * n_th * n_tw * kout_banks
    # linear grid-step index (row-major, matching TPU's sequential grid)
    step = ((b * n_th + ty) * n_tw + tx) * kout_banks + ko
    total_slabs = n_steps * cin_banks

    def coords(s):
        """Decompose a linear step index back into (b, ty, tx, ko)."""
        sko = jax.lax.rem(s, kout_banks)
        s = jax.lax.div(s, kout_banks)
        stx = jax.lax.rem(s, n_tw)
        s = jax.lax.div(s, n_tw)
        return jax.lax.div(s, n_th), jax.lax.rem(s, n_th), stx, sko

    def slab_copies(sb, sty, stx, sko, sco, slot):
        """The two DMAs of one slab: the halo'd input window and the
        weight bank of (tile, kout bank, cin bank) — element offsets
        carry the group's channel base, exactly like the sequential
        kernel's BlockSpec index maps."""
        coff = (sko // bpg) * cgrp + sco * cb
        in_dma = pltpu.make_async_copy(
            x_hbm.at[sb, _window(x_hbm, 1, sty * th, xb.shape[1]),
                     _window(x_hbm, 2, stx * tw, xb.shape[2]),
                     _window(x_hbm, 3, coff, cb)],
            xb.at[slot], in_sem.at[slot])
        w_dma = pltpu.make_async_copy(
            w_hbm.at[:, :, _window(w_hbm, 2, sco * cb, cb),
                     _window(w_hbm, 3, sko * kb, kb)],
            wb.at[slot], w_sem.at[slot])
        return in_dma, w_dma

    # Warm-up: the very first grid step primes the pipe with slab 0;
    # every later slab is prefetched by its predecessor.
    @pl.when(step == 0)
    def _prime():
        for dma in slab_copies(b, ty, tx, ko, 0, 0):
            dma.start()

    # M5: bias preload — the accumulator starts as the bias, exactly like
    # preloading the output BRAMs (same init as conv2d_ws at co == 0).
    acc_ref[...] = jnp.broadcast_to(
        b_ref[...].astype(acc_dtype), acc_ref.shape)

    def cin_step(co, _):
        g = step * cin_banks + co                   # global slab index
        slot = jax.lax.rem(g, 2)
        # the DMAs for THIS slab were started by the previous slab (or the
        # warm-up); wait for them, then immediately stream the next slab
        # into the other buffer while the MXU works on this one
        for dma in slab_copies(b, ty, tx, ko, co, slot):
            dma.wait()

        @pl.when(g + 1 < total_slabs)
        def _prefetch():
            last_co = co + 1 == cin_banks
            ns = jnp.where(last_co, step + 1, step)
            nco = jnp.where(last_co, 0, co + 1)
            nb, nty, ntx, nko = coords(ns)
            for dma in slab_copies(nb, nty, ntx, nko, nco, 1 - slot):
                dma.start()

        # conv2d_ws's compute body on the same operand blocks in the same
        # order, hence bit-exact accumulation
        conv_slab(xb[slot], wb[slot], acc_ref, patch_ref, th=th, tw=tw,
                  dilation=dilation, acc_dtype=acc_dtype)
        return 0

    jax.lax.fori_loop(0, cin_banks, cin_step, 0)

    # Fused epilogue into the output block; Pallas stores it to HBM while
    # the next grid step computes.
    o_ref[0] = conv_epilogue(
        acc_ref, s_ref, th=th, tw=tw, relu=relu, pool=pool,
        requant=requant).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "stride", "padding", "groups", "cin_banks", "kout_banks", "h_tile",
    "w_tile", "relu", "pool", "dilation", "interpret"))
def conv2d_ws_pipe(x, w, bias=None, out_scale=None, *, stride: int = 1,
                   padding="VALID", groups: int = 1, cin_banks: int = 4,
                   kout_banks: int = 4, h_tile: int = 0, w_tile: int = 0,
                   relu: bool = False, pool: bool = False,
                   dilation: int = 1, interpret: bool = False):
    """Drop-in replacement for ``conv2d_ws`` with explicit double-buffered
    DMA (see the module docstring).  Same signature, same contracts, same
    results bit-for-bit; a layer's tile plan decides which variant a
    compiled network runs (``TilePlan.pipelined``)."""
    k = w.shape[3]
    x, w, g = setup_conv(x, w, stride=stride, padding=padding,
                         groups=groups, cin_banks=cin_banks,
                         kout_banks=kout_banks, h_tile=h_tile,
                         w_tile=w_tile, pool=pool,
                         requant=out_scale is not None, dilation=dilation,
                         manual_dma=True)
    acc_dtype = jnp.int32 if g.int_path else jnp.float32
    if bias is None:
        bias = jnp.zeros((k,), acc_dtype)
    bias = bias.astype(acc_dtype)
    out_dtype = jnp.int8 if g.requant else acc_dtype
    scale = jnp.broadcast_to(
        jnp.asarray(1.0 if out_scale is None else out_scale, jnp.float32),
        (k,))
    # setup_conv may have zero-extended the kernel sets (g.k ≥ k)
    bias = jnp.pad(bias, (0, g.k - k)).reshape(1, g.k)
    scale = jnp.pad(scale, (0, g.k - k)).reshape(1, g.k)

    kernel = functools.partial(
        _pipe_kernel, cin_banks=g.cin_banks, kout_banks=g.kout_banks,
        th=g.th, tw=g.tw, cb=g.cb, kb=g.kb, cgrp=g.cgrp, bpg=g.bpg,
        relu=relu, pool=pool, requant=g.requant, acc_dtype=acc_dtype,
        dilation=g.dilation)
    out = pl.pallas_call(
        kernel,
        name=kernel_name("conv2d_ws_pipe", g),
        grid=(g.n, g.n_th, g.n_tw, g.kout_banks),
        in_specs=[
            # feature map + weights stay in HBM: the kernel moves them
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            # bias/scale per-bank blocks are tiny: implicit pipeline
            pl.BlockSpec((1, g.kb), lambda b, ty, tx, ko: (0, ko)),
            pl.BlockSpec((1, g.kb), lambda b, ty, tx, ko: (0, ko)),
        ],
        out_specs=pl.BlockSpec((1, g.pth, g.ptw, g.kb),
                               lambda b, ty, tx, ko: (b, ty, tx, ko)),
        out_shape=jax.ShapeDtypeStruct(
            (g.n, g.n_th * g.pth, g.n_tw * g.ptw, g.k), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((2, g.in_th if g.n_th > 1 else g.hp,     # ping-pong in
                        g.in_tw if g.n_tw > 1 else g.wp, g.cb), x.dtype),
            pltpu.VMEM((2, g.kh, g.kw, g.cb, g.kb), w.dtype),   # ping-pong w
            pltpu.SemaphoreType.DMA((2,)),                      # input slabs
            pltpu.SemaphoreType.DMA((2,)),                      # weight slabs
        ] + conv_scratch(g, x.dtype, acc_dtype),                # acc, patch
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(x, w, bias, scale)
    if (g.n_th * g.pth, g.n_tw * g.ptw, g.k) != (g.poh, g.pow_, k):
        out = out[:, :g.poh, :g.pow_, :k]
    return out
