"""Analytic cycle/throughput model of the paper's IP core (§5.2).

Reproduces the paper's own numbers exactly:

* [224×224×8] ⊛ [8×3×3×8] → 3,154,176 psums (= 222·222·8·8),
* the 4-core system computes 16 psums / 8 cycles,
* at 112 MHz (Pynq Z2 synthesis, Table 1) → 0.01408 s,
* paper-GOPS (= psums/second): 0.224; 20 replicated IP cores: 4.48.

The paper counts one psum (a 3×3×1 weighted sum) as one "operation"; we
also report standard MAC-ops (1 psum = KH·KW MACs = 2·KH·KW flops) so the
numbers are comparable with TPU rooflines (DESIGN.md §3).

``pipeline_estimate`` and ``network_report`` price a layer pass under
a ``banking.TilePlan`` on the same FPGA terms (8 DMA bytes per cycle, a
16-cycle per-slab ping-pong cost).  They are the paper-anchor reference
and the model ``core/autotune.py`` prices candidates with; which TPU
kernel a layer runs is the plan's own property (``TilePlan.pipelined``),
not a verdict of this model.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import List, Optional, Sequence, Tuple

from repro.kernels.ref import conv_out_shape, conv_transpose_out_shape


@dataclass(frozen=True)
class IPCoreConfig:
    clock_hz: float = 112e6        # Pynq Z2 synthesis (Table 1)
    computing_cores: int = 4       # channel-parallel cores (M1)
    pcores_per_core: int = 4       # kernels in flight per core (M2)
    cycles_per_batch: int = 8      # "four psum values for each eight cycles"
    ip_cores: int = 1              # replicated IP cores on the fabric
    dma_bytes_per_cycle: float = 8.0   # 64-bit DDR/AXI interface (shared)


def psum_count(h: int, w: int, c: int, k: int, kh: int = 3, kw: int = 3,
               stride: int = 1, padding="VALID", groups: int = 1,
               dilation: int = 1) -> int:
    """One psum per (output pixel × kernel × input channel); stride/padding
    change only the output pixel count.  ``groups > 1`` contracts only the
    C/groups channels of each kernel's group — a depthwise layer
    (groups == C) computes a factor-C fewer psums than its dense
    counterpart while moving the SAME feature maps, which is exactly why
    its cycles floor at the shared DMA interface, not at compute
    (``network_report`` flags this per layer).  ``dilation`` spreads the
    taps without multiplying them — it changes the psum count only
    through the output pixel count."""
    oh, ow = conv_out_shape(h, w, kh, kw, stride, padding, dilation)
    return oh * ow * k * (c // groups)


def conv_transpose_psum_count(h: int, w: int, c: int, k: int, kh: int = 3,
                              kw: int = 3, stride: int = 1,
                              padding="VALID", groups: int = 1,
                              dilation: int = 1, skip_zeros: bool = True
                              ) -> int:
    """Psum count of a TRANSPOSED conv layer (lhs zero-insertion by
    ``stride``, then a stride-1 conv — kernels/conv2d_ws_trans.py).

    Two prices, both honest about different hardware:

    * **naive** (``skip_zeros=False``): the equivalent stride-1 conv
      sweeps the zero-inserted map as-is — one psum per (output pixel ×
      kernel × group channel), ``oh·ow·k·c/groups``.  This is what the
      unmodified IP core pays: its MAC array cannot tell an inserted
      zero from data.
    * **skip** (``skip_zeros=True``, the default): every psum whose
      image window lands entirely on inserted zeros is free, and only
      ~1/stride² of each window's taps carry data — the input-pixel
      accounting ``h·w·k·c/groups``: one psum per (INPUT pixel × kernel
      × group channel), since each real input pixel is touched by
      exactly KH·KW output taps.  A zero-skipping MAC controller (the
      standard deconv-accelerator trick the FPGA survey literature
      describes) achieves this bound.

    The ratio naive/skip ≈ stride² is the upsampling waste a
    zero-skipping datapath recovers; ``network_report`` rows for
    transposed layers are priced on the skip count with the naive count
    recorded alongside."""
    if skip_zeros:
        return h * w * k * (c // groups)
    oh, ow = conv_transpose_out_shape(h, w, kh, kw, stride, padding,
                                      dilation)
    return oh * ow * k * (c // groups)


def cycles(n_psums: int, cfg: IPCoreConfig = IPCoreConfig()) -> int:
    per_batch = cfg.computing_cores * cfg.pcores_per_core  # 16 psums
    batches = -(-n_psums // (per_batch * cfg.ip_cores))
    return batches * cfg.cycles_per_batch


def seconds(n_psums: int, cfg: IPCoreConfig = IPCoreConfig()) -> float:
    return cycles(n_psums, cfg) / cfg.clock_hz


def gops_paper(n_psums: int, cfg: IPCoreConfig = IPCoreConfig()) -> float:
    """The paper's accounting: psums per second / 1e9."""
    return n_psums / seconds(n_psums, cfg) / 1e9


def gops_macs(n_psums: int, kh: int = 3, kw: int = 3,
              cfg: IPCoreConfig = IPCoreConfig()) -> float:
    """Standard accounting: 1 psum = KH·KW MACs = 2·KH·KW ops."""
    return n_psums * 2 * kh * kw / seconds(n_psums, cfg) / 1e9


def paper_reference_numbers():
    """The exact §5.2 workload; asserted in tests/test_perfmodel.py."""
    n = psum_count(224, 224, 8, 8)
    one = IPCoreConfig()
    twenty = IPCoreConfig(ip_cores=20)
    return {
        "psums": n,
        "seconds_1core": seconds(n, one),
        "gops_1core": gops_paper(n, one),
        "gops_20cores": gops_paper(n, twenty),
        "gops_macs_1core": gops_macs(n, cfg=one),
    }


def network_cycles(layer_psums: Sequence[int],
                   cfg: IPCoreConfig = IPCoreConfig()) -> int:
    """Whole-network cycle estimate: the IP core processes one layer at a
    time (§4.2), so the network cost is the sum of per-layer passes (each
    layer rounds up to full psum batches separately — the pipeline drains
    between layer configurations).  This holds for DAG plans too: parallel
    branches of a residual graph still serialize on the single core, so a
    topological schedule's length is exactly this sum; merge nodes (add /
    concat) contribute zero psums — the output-BRAM crossbar absorbs
    them."""
    return sum(cycles(p, cfg) for p in layer_psums if p)


def tile_traffic(plan) -> dict:
    """DMA traffic of one layer pass under a ``banking.TilePlan``.

    Every kout bank revisits every spatial tile (the weight-stationary
    sweep re-DMAs the halo'd input window per kernel set), so

        input bytes  = n_tiles · cin_banks · image_block · kout_banks
        weight bytes = n_tiles · cin_banks · kout_banks · weight_block
        output bytes = n_tiles · kout_banks · output_block

    The halo_read_factor isolates the pure halo/zero-extension overhead
    vs a single whole-map read."""
    in_b = plan.n_tiles * plan.cin_banks * plan.image_block_bytes \
        * plan.kout_banks
    w_b = plan.n_tiles * plan.cin_banks * plan.kout_banks \
        * plan.weight_block_bytes
    out_b = plan.n_tiles * plan.kout_banks * plan.output_block_bytes
    return {"input_bytes": in_b, "weight_bytes": w_b,
            "output_bytes": out_b, "total_bytes": in_b + w_b + out_b,
            "halo_read_factor": plan.halo_read_factor,
            "kout_revisits": plan.kout_banks}


def dma_cycles(total_bytes: int,
               cfg: IPCoreConfig = IPCoreConfig()) -> int:
    """DMA cycles for ``total_bytes`` on the shared interface."""
    return math.ceil(total_bytes / max(cfg.dma_bytes_per_cycle, 1e-9))


# Per-slab cost of the explicit ping-pong protocol (descriptor setup,
# semaphore wait, buffer swap): when the overlappable work per slab is
# smaller than this bookkeeping, the steady-state overlap never
# amortizes it.
PIPELINE_OVERHEAD_CYCLES = 16


def pipeline_estimate(plan, psums: int,
                      cfg: IPCoreConfig = IPCoreConfig()) -> dict:
    """Sequential-vs-pipelined cost of one layer pass under ``plan``.

    * sequential (``conv2d_ws`` without overlap credit):
      every slab pays its DMA then its compute →  Σ(dma + compute) = D + C;
    * pipelined (``conv2d_ws_pipe`` ping-pong): the first slab's load
      fills the pipe, steady state hides the cheaper phase behind the
      costlier one, the last slab's compute drains →
      fill + (n−1)·max(d, c) + drain, plus per-slab protocol overhead,

    with n = ``plan.n_slabs`` and d = ⌈D/n⌉, c = ⌈C/n⌉ the per-slab
    shares.  Priced entirely on the §5.2 cycle model (``cycles``) and the
    ``tile_traffic`` / ``dma_cycles`` machinery — the paper anchors are
    untouched.  ``cycles`` is the price of the variant the plan runs
    (``TilePlan.pipelined``)."""
    n = max(plan.n_slabs, 1)
    dma = dma_cycles(tile_traffic(plan)["total_bytes"], cfg)
    compute = cycles(psums, cfg) if psums else 0
    d, c = -(-dma // n), -(-compute // n)
    sequential = dma + compute
    pipelined = d + (n - 1) * max(d, c) + c + n * PIPELINE_OVERHEAD_CYCLES
    return {
        "n_slabs": n,
        "dma_cycles": dma,
        "compute_cycles": compute,
        "sequential_cycles": sequential,
        "pipelined_cycles": pipelined,
        "speedup": sequential / pipelined if pipelined else 1.0,
        "cycles": pipelined if plan.pipelined else sequential,
    }


def network_report(layers: Sequence[Tuple[str, int]],
                   cfg: IPCoreConfig = IPCoreConfig(),
                   full_board_cores: int = 20,
                   tile_plans: Optional[Sequence] = None) -> dict:
    """Per-layer + total cycles/seconds/GOPS for a layer list
    [(name, psums_per_image), ...], for ``cfg`` and for the paper's
    full-board configuration (ip_cores=20, batch-sharded replication).

    ``tile_plans`` (one ``banking.TilePlan`` or None per layer, e.g. from
    ``NetworkPlan.tile_plans``) adds the spatial-tiling DMA cost: each
    layer is priced by ``pipeline_estimate`` for the kernel variant its
    plan carries (``TilePlan.pipelined``) — sequential pays DMA + compute
    per slab, pipelined overlaps them through the ping-pong buffers —
    with tile revisits and halo re-reads priced by ``tile_traffic``.
    Priced rows carry both variants (``cycles_sequential`` /
    ``cycles_pipelined`` / ``pipeline_speedup``) so the two variants
    compare per layer.  The DMA interface is SHARED across
    replicated IP cores, so full-board cycles floor at the same DMA time:
    that is what keeps the 20-core GOPS honest on large maps.  Each
    priced row carries ``dma_bound`` / ``dma_bound_board`` flags — on
    depthwise/grouped layers the psum count collapses by the group factor
    while the feature-map traffic stays put, so the shared-DMA floor, not
    compute, is what binds (visibly so on the full board, where compute
    divides by the core count and the DMA interface does not)."""
    board = replace(cfg, ip_cores=full_board_cores)
    if tile_plans is None:
        tile_plans = [None] * len(layers)
    per_layer: List[dict] = []
    total = total_board = 0
    for (name, p), tp in zip(layers, tile_plans):
        compute = cycles(p, cfg) if p else 0
        compute_board = cycles(p, board) if p else 0
        row = {"name": name, "psums": p, "cycles": compute}
        if tp is not None:
            traffic = tile_traffic(tp)
            dma = dma_cycles(traffic["total_bytes"], cfg)
            est = pipeline_estimate(tp, p, cfg)
            row.update(dma_bytes=traffic["total_bytes"], dma_cycles=dma,
                       halo_read_factor=traffic["halo_read_factor"],
                       n_tiles=tp.n_tiles,
                       cycles=est["cycles"] if p else dma,
                       pipelined=tp.pipelined,
                       cycles_sequential=est["sequential_cycles"],
                       cycles_pipelined=est["pipelined_cycles"],
                       pipeline_speedup=est["speedup"],
                       dma_bound=dma >= compute,
                       dma_bound_board=dma >= compute_board)
            total += row["cycles"]
            total_board += (pipeline_estimate(tp, p, board)["cycles"]
                            if p else dma)
        else:
            total += compute
            total_board += compute_board
        per_layer.append(row)
    total_psums = sum(p for _, p in layers)
    return {
        "layers": per_layer,
        # how many priced layers the SHARED DMA interface binds on the
        # full board — the depthwise/grouped arithmetic-intensity story
        "dma_bound_board_layers": sum(
            1 for r in per_layer if r.get("dma_bound_board")),
        # how many priced layers the planner routed to conv2d_ws_pipe
        "pipelined_layers": sum(
            1 for r in per_layer if r.get("pipelined")),
        "psums": total_psums,
        "cycles": total,
        "seconds": total / cfg.clock_hz,
        "gops_paper": total_psums / (total / cfg.clock_hz) / 1e9 if total
        else 0.0,
        "full_board": {
            "ip_cores": full_board_cores,
            "cycles": total_board,
            "seconds": total_board / board.clock_hz,
            "gops_paper": total_psums / (total_board / board.clock_hz) / 1e9
            if total_board else 0.0,
        },
    }


def train_report(layers: Sequence[Tuple[str, int]],
                 cfg: IPCoreConfig = IPCoreConfig(),
                 weight_bytes: Optional[Sequence[int]] = None,
                 full_board_cores: int = 20,
                 tile_plans: Optional[Sequence] = None) -> dict:
    """§5.2 cycle model of one TRAINING step over a layer list
    [(name, forward_psums_per_image), ...].

    Backward accounting on the weight-stationary dataflow
    (kernels/conv2d_ws_bwd.py):

    * the input gradient is a transposed conv with the SAME psum count as
      the forward pass — every forward psum has exactly one transposed
      counterpart (one cotangent pixel × kernel tap × channel);
    * the weight gradient is a batched correlation contracting the same
      (output pixel × kernel × channel) index set — again one psum per
      forward psum;

    so the backward pass costs ≈2× the forward psums, and a full step
    (forward + backward) ≈3× — the classic conv-training rule of thumb,
    here exact in the paper's psum accounting.  ``weight_bytes`` (per
    layer, e.g. 4·|W| for f32 gradients; None entries for parameter-free
    nodes) adds the weight-GRADIENT writeback traffic on the shared DMA
    interface — unlike inference, every layer pass must ship dW back to
    the host optimizer, and for fat dense layers that traffic, not
    compute, bounds the backward pass.  Per-layer backward cycles are
    max(compute, dW DMA), the M4 overlap argument applied to the
    gradient stream.

    ``tile_plans`` prices the forward exactly like ``network_report``
    (tile revisits + halo re-reads); the backward input/weight streams
    revisit the same tiles, which the 2× psum accounting already covers
    at compute level."""
    fwd = network_report(layers, cfg, full_board_cores=full_board_cores,
                         tile_plans=tile_plans)
    board = replace(cfg, ip_cores=full_board_cores)
    if weight_bytes is None:
        weight_bytes = [None] * len(layers)
    bwd_rows: List[dict] = []
    bwd_total = bwd_board = 0
    for (name, p), wb in zip(layers, weight_bytes):
        compute = cycles(2 * p, cfg) if p else 0
        compute_board = cycles(2 * p, board) if p else 0
        row = {"name": name, "psums_bwd": 2 * p, "cycles": compute}
        if wb:
            dma = dma_cycles(wb, cfg)
            row.update(dw_bytes=wb, dw_dma_cycles=dma,
                       cycles=max(compute, dma))
            bwd_total += row["cycles"]
            bwd_board += max(compute_board, dma)   # shared DMA interface
        else:
            bwd_total += compute
            bwd_board += compute_board
        bwd_rows.append(row)
    total = fwd["cycles"] + bwd_total
    total_board = fwd["full_board"]["cycles"] + bwd_board
    step_psums = 3 * fwd["psums"]
    return {
        "forward": fwd,
        "backward": {"layers": bwd_rows, "psums": 2 * fwd["psums"],
                     "cycles": bwd_total,
                     "seconds": bwd_total / cfg.clock_hz},
        "psums": step_psums,
        "cycles": total,
        "seconds": total / cfg.clock_hz,
        "gops_paper": step_psums / (total / cfg.clock_hz) / 1e9 if total
        else 0.0,
        "full_board": {
            "ip_cores": full_board_cores,
            "cycles": total_board,
            "seconds": total_board / board.clock_hz,
            "gops_paper": step_psums / (total_board / board.clock_hz) / 1e9
            if total_board else 0.0,
        },
    }


def tpu_conv_roofline(h: int, w: int, c: int, k: int, kh: int = 3,
                      kw: int = 3, in_bytes: int = 1,
                      peak_flops: float = 197e12 / 2,  # int8 ≈ bf16 on v5e MXU
                      hbm_bw: float = 819e9):
    """Roofline terms for the same layer on one v5e core (conv2d_ws kernel):
    used for the paper-vs-TPU comparison table in benchmarks."""
    oh, ow = h - kh + 1, w - kw + 1
    flops = 2.0 * oh * ow * k * c * kh * kw
    bytes_moved = (h * w * c + kh * kw * c * k) * in_bytes + oh * ow * k * 4
    t = max(flops / peak_flops, bytes_moved / hbm_bw)
    return {"flops": flops, "bytes": bytes_moved,
            "t_compute": flops / peak_flops, "t_memory": bytes_moved / hbm_bw,
            "seconds": t, "gops_macs": flops / t / 1e9,
            "gops_paper": (oh * ow * k * c) / t / 1e9}
