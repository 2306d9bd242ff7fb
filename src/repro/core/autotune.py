"""Plan autotuner: search (TilePlan × scheduler mode × core count)
against the §5.2 cost model.

``banking.plan_tiles`` is a greedy descent: from the paper's 4×4 banking
it applies whichever single move shrinks the working set most until the
plan fits VMEM.  That finds *a* legal plan, not the cheapest one — the
descent stops at the first fit and never revisits bank counts that
trade VMEM headroom for DMA traffic (input bytes scale with
``kout_banks`` revisits!).  This module explores that space:

* :func:`autotune_layer` — enumerate the LEGAL candidate space for one
  conv layer (pool-aligned tile halving chains × divisor bank sets,
  pruned by ``fits_vmem`` and group alignment), price every candidate
  for the kernel variant its plan carries (``TilePlan.pipelined``) with
  ``perfmodel.pipeline_estimate``, and return the cheapest
  (deterministic tie-break).  The greedy ``plan_tiles`` plan is always
  seeded into the candidate set, so the tuned plan is never worse than
  the fallback *by construction*.
* :func:`autotune_network` — run the layer search over a
  ``NetworkPlan`` and then search (scheduler mode × core count) for the
  whole network, returning a :class:`NetworkTunePlan` whose
  ``tile_plans`` list threads through ``NetworkPlan.tile_plans`` /
  ``make_int8_program`` / ``MultiCoreScheduler`` unchanged at the call
  sites.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import List, Optional, Sequence, Tuple

from repro import obs
from repro.core import banking, perfmodel
from repro.core.banking import TilePlan
from repro.kernels.ref import check_groups, conv_out_shape, lane_legal_banks

SCHEDULER_MODES = ("batch", "kout", "spatial")
CORE_COUNTS = (1, 2, 4, 8, 16, 20)


# ---------------------------------------------------------------------------
# Per-layer candidate enumeration
# ---------------------------------------------------------------------------


def _tile_chain(full: int, pool: bool) -> List[int]:
    """The pool-aligned halving chain ``plan_tiles`` descends — full map
    first, then successive (aligned) halvings down to the minimum tile.
    Enumerating exactly this chain keeps every candidate a tile extent
    the kernels' BlockSpecs already handle and makes the greedy plan a
    guaranteed member of the search space."""
    vals, v = [], max(full, 2 if pool else 1)
    while True:
        vals.append(v)
        nv = banking._align_tile(-(-v // 2), pool)
        if nv >= v or v <= (2 if pool else 1):
            return vals
        v = nv


def _divisors(n: int) -> List[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def candidate_states(oh: int, ow: int, cgrp: int, k: int, groups: int,
                     pool: bool) -> List[Tuple[int, int, int, int]]:
    """All legal (h_tile, w_tile, cin_banks, kout_banks) states for one
    layer: tile heights from the pool-aligned halving chain over the full
    map width (the tiling ``plan_tiles`` descends), cin banks any
    lane-legal divisor of the per-group channel slice, kout banks any
    group-aligned lane-legal divisor of K (``kout_banks = groups · m``
    with ``m`` dividing ``K/groups`` — a bank never straddles a group
    boundary)."""
    kouts = [groups * m for m in _divisors(k // groups)
             if groups * m == groups or lane_legal_banks(k, groups * m)]
    cins = [b for b in _divisors(cgrp) if lane_legal_banks(cgrp, b)]
    return [(th, ow, cb, kb)
            for th in _tile_chain(oh, pool)
            for cb in cins
            for kb in kouts]


@dataclass(frozen=True)
class LayerTune:
    """The tuner's verdict for one node: the chosen plan, its cycle
    count, and the greedy fallback it beat (or matched).  ``source`` is
    "autotuned" when the chosen plan differs from the greedy
    ``plan_tiles`` plan, "greedy" when the search confirmed the fallback
    was already optimal."""
    name: str
    plan: Optional[TilePlan]
    cycles: int
    greedy_plan: Optional[TilePlan] = None
    greedy_cycles: int = 0
    psums: int = 0
    k: int = 0                       # conv layers: kernel count (for the
    groups: int = 1                  # kout-shard legality rule)

    @property
    def source(self) -> str:
        if self.plan is None:
            return "greedy"
        return "greedy" if self.plan == self.greedy_plan else "autotuned"


def plan_cost(plan: TilePlan, psums: int,
              cfg: perfmodel.IPCoreConfig = perfmodel.IPCoreConfig()) -> int:
    """Cycle count of one layer pass under ``plan``, priced for the
    kernel variant the plan carries (``TilePlan.pipelined``) — the
    single cost definition the tuner, its tests, and the benchmark
    reports share."""
    return perfmodel.pipeline_estimate(plan, psums, cfg)["cycles"]


def autotune_layer(h: int, w: int, c: int, k: int, kh: int = 3, kw: int = 3,
                   *, stride: int = 1, padding="VALID", pool: bool = False,
                   groups: int = 1, dilation: int = 1, in_bytes: int = 1,
                   acc_bytes: int = 4,
                   out_bytes: Optional[int] = None,
                   cin_banks: int = 4, kout_banks: int = 4,
                   vmem_budget: Optional[int] = banking.VMEM_LIMIT_BYTES,
                   cfg: perfmodel.IPCoreConfig = perfmodel.IPCoreConfig(),
                   name: str = "conv",
                   psums: Optional[int] = None) -> LayerTune:
    """Exhaustive TilePlan search for one conv layer.

    Every candidate is built through ``banking.plan_tiles``'s own
    ``build`` geometry (same halo math, same byte accounting), pruned by
    ``fits_vmem``, and priced by ``plan_cost`` for the kernel variant it
    carries; the cheapest (cost, then a fixed structural tie-break)
    wins, so the result is deterministic.  The greedy ``plan_tiles``
    plan for the same arguments is seeded into the candidate set: the
    tuned plan can only ever match or beat it under the same model.

    ``psums`` overrides the compute price (transposed layers pass their
    zero-skipping count — the eq stride-1 conv geometry this function
    sees would otherwise price the ~stride²× naive sweep)."""
    check_groups(c, k, groups)
    cgrp = c // groups
    if psums is None:
        psums = perfmodel.psum_count(h, w, c, k, kh, kw, stride=stride,
                                     padding=padding, groups=groups,
                                     dilation=dilation)
    greedy = banking.plan_tiles(
        h, w, c, k, kh, kw, stride=stride, padding=padding, pool=pool,
        groups=groups, dilation=dilation, in_bytes=in_bytes,
        acc_bytes=acc_bytes,
        out_bytes=out_bytes, cin_banks=cin_banks, kout_banks=kout_banks,
        vmem_budget=vmem_budget)
    greedy_cost = plan_cost(greedy, psums, cfg)

    oh, ow = conv_out_shape(h, w, kh, kw, stride, padding, dilation)
    if pool:
        oh, ow = (oh // 2) * 2, (ow // 2) * 2
    budget = banking.VMEM_LIMIT_BYTES if vmem_budget is None else vmem_budget

    def build(th: int, tw: int, cbn: int, kbn: int) -> TilePlan:
        return banking.tile_plan(
            h, w, c, k, kh, kw, th, tw, cbn, kbn, stride=stride,
            padding=padding, pool=pool, groups=groups, dilation=dilation,
            in_bytes=in_bytes, acc_bytes=acc_bytes, out_bytes=out_bytes,
            budget=budget)

    # (cost, structural tie-break, plan): the tie-break prefers fewer
    # tiles, then coarser banking — a fixed total order, so equal-cost
    # candidate sets always resolve the same way
    def key(plan: TilePlan, cost: int):
        return (cost, plan.n_tiles, plan.kout_banks, plan.cin_banks,
                plan.h_tile, plan.w_tile)

    best_plan, best_key = greedy, key(greedy, greedy_cost)
    n_cands = 0
    with obs.span("autotune.layer", layer=name, psums=psums):
        for th, tw, cbn, kbn in candidate_states(oh, ow, cgrp, k, groups,
                                                 pool):
            # per-candidate evaluation span: pruned candidates never get
            # one (they were never priced) — gated so the disabled path
            # costs one branch per candidate
            cand = build(th, tw, cbn, kbn)
            if vmem_budget is not None and not cand.fits_vmem:
                continue
            n_cands += 1
            with obs.span("autotune.candidate", layer=name, h_tile=th,
                          w_tile=tw, cin_banks=cbn, kout_banks=kbn):
                k_ = key(cand, plan_cost(cand, psums, cfg))
            if k_ < best_key:
                best_plan, best_key = cand, k_
    obs.metrics.counter("autotune.candidates").inc(n_cands)
    return LayerTune(name=name, plan=best_plan, cycles=best_key[0],
                     greedy_plan=greedy, greedy_cycles=greedy_cost,
                     psums=psums, k=k, groups=groups)


# ---------------------------------------------------------------------------
# Whole-network tuning: layers, then (scheduler mode × core count)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class NetworkTunePlan:
    """A tuned execution recipe for one network: per-layer plans (the
    ``tile_plans`` property is a drop-in for ``NetworkPlan.tile_plans``
    output — pass it to ``make_int8_program(..., tile_plans=...)``), the
    winning scheduler (mode, core count), and the modelled totals for
    both the tuned and the greedy-fallback plan sets."""
    network: str
    layers: Tuple[LayerTune, ...]
    scheduler_mode: str = "batch"
    n_cores: int = 1
    cycles: int = 0                 # tuned total, 1 core
    greedy_cycles: int = 0          # greedy-fallback total, 1 core
    schedule_cycles_: int = 0       # tuned total at (mode, n_cores)

    @property
    def tile_plans(self) -> List[Optional[TilePlan]]:
        return [lt.plan for lt in self.layers]

    @property
    def greedy_tile_plans(self) -> List[Optional[TilePlan]]:
        return [lt.greedy_plan for lt in self.layers]

    @property
    def layers_differ(self) -> int:
        """How many conv layers the search moved off the greedy plan."""
        return sum(1 for lt in self.layers if lt.source == "autotuned")

    @property
    def speedup(self) -> float:
        return self.greedy_cycles / self.cycles if self.cycles else 1.0

    def scheduler_config(self):
        """The winning mode/cores as a ``SchedulerConfig`` — feed it to
        ``MultiCoreScheduler`` unchanged."""
        from repro.core.scheduler import SchedulerConfig
        return SchedulerConfig(n_cores=self.n_cores,
                               mode=self.scheduler_mode)

    def layer_rows(self) -> List[dict]:
        """Per-layer report rows (plan_source + both cycle counts) for
        the benchmark JSON."""
        return [{"name": lt.name, "plan_source": lt.source,
                 "cycles_autotuned": lt.cycles,
                 "cycles_greedy": lt.greedy_cycles,
                 "pipelined": bool(lt.plan.pipelined) if lt.plan else None}
                for lt in self.layers]


def _kout_shards(k: int, groups: int, cores: int) -> int:
    """Largest core count ≤ ``cores`` whose contiguous K/n kernel-set
    slices stay group-aligned — the same legality rule
    ``scheduler.KoutShardedBackend`` enforces at run time."""
    kg = k // groups
    for n in range(min(cores, k), 0, -1):
        if k % n:
            continue
        s = k // n
        if s % kg == 0 or kg % s == 0:
            return n
    return 1


def _spatial_shards(tp: TilePlan, cores: int) -> int:
    unit = 2 if tp.pool else 1
    return max(1, min(cores, tp.out_h // unit))


def _spatial_halo_plan(tp: TilePlan, bands: int) -> TilePlan:
    """Charge the spatial mode's halo re-read: each extra band re-reads
    ``kh − stride`` input rows, exactly the overlap
    ``SpatialShardedBackend`` materializes.  Expressed as an inflated
    per-step image block so ``pipeline_estimate`` prices it unchanged."""
    if bands <= 1:
        return tp
    kh = tp.in_h_tile - (tp.h_tile - 1) * tp.stride
    in_h = banking.halo_window(tp.out_h, tp.stride, kh)
    factor = 1.0 + (bands - 1) * max(kh - tp.stride, 0) / max(in_h, 1)
    return replace(tp,
                   image_block_bytes=math.ceil(tp.image_block_bytes * factor))


def schedule_cycles(layers: Sequence[LayerTune], mode: str, cores: int,
                    cfg: perfmodel.IPCoreConfig = perfmodel.IPCoreConfig()
                    ) -> int:
    """Whole-network cycles under one (scheduler mode, core
    count) point:

    * batch — throughput pricing: compute divides by the core count,
      the SHARED DMA interface does not (the ``network_report``
      full-board rule);
    * kout — per-layer compute divides by the largest group-aligned
      kernel-set split ≤ cores; the input map is broadcast over the
      fabric crossbar, so DMA traffic is unchanged;
    * spatial — per-layer compute divides by the row-band count and the
      bands' ``kh − stride`` halo re-reads are charged to DMA.

    Layers without a plan (dense GEMMs, merge nodes) price on compute
    cycles with the same per-mode division."""
    total = 0
    for lt in layers:
        tp, p = lt.plan, lt.psums
        if tp is None:
            if not p:
                continue
            eff = cores if mode in ("batch", "kout") else 1
            total += perfmodel.cycles(p, replace(cfg, ip_cores=eff))
            continue
        if mode == "batch":
            eff, priced = cores, tp
        elif mode == "kout":
            eff = _kout_shards(lt.k, lt.groups, cores)
            priced = tp
        else:
            eff = _spatial_shards(tp, cores)
            priced = _spatial_halo_plan(tp, eff)
        total += plan_cost(priced, p, replace(cfg, ip_cores=eff))
    return total


def route_batch(layers: Sequence[LayerTune], batch: int, n_cores: int,
                cfg: perfmodel.IPCoreConfig = perfmodel.IPCoreConfig(),
                modes: Sequence[str] = SCHEDULER_MODES
                ) -> Tuple[str, int, int]:
    """Pick the scheduler mode the cost model predicts fastest for
    ONE formed batch of ``batch`` images on an ``n_cores`` budget.
    Returns ``(mode, cores, predicted_cycles)``.

    The autotuner's ``schedule_cycles`` prices steady-state throughput
    for a fixed batch size; a continuous-batching engine instead sees
    whatever size the deadline handed it, and the best verdict flips
    with that size: a deadline-launched single image wants the cores
    INSIDE the program (kout/spatial sharded backends — batch sharding
    can't split one image), while a full batch usually wants batch
    sharding (compute divides by every core with no halo/broadcast tax).
    Pricing: batch mode processes the formed batch across
    ``min(batch, n_cores)`` cores; kout/spatial run the sharded program
    once per image on all ``n_cores``.  First mode in ``modes`` wins
    ties (strict improvement to switch), matching ``autotune_network``'s
    never-worse-than-greedy convention."""
    if batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    if n_cores < 1:
        raise ValueError(f"n_cores must be >= 1, got {n_cores}")
    best = None
    for mode in modes:
        cores = min(batch, n_cores) if mode == "batch" else n_cores
        cycles = batch * schedule_cycles(layers, mode, cores, cfg)
        if best is None or cycles < best[2]:
            best = (mode, cores, cycles)
    return best


def autotune_network(plan, cin_banks: int = 4, kout_banks: int = 4,
                     in_bytes: int = 1,
                     vmem_budget: Optional[int] = banking.VMEM_LIMIT_BYTES,
                     cfg: perfmodel.IPCoreConfig = perfmodel.IPCoreConfig(),
                     modes: Sequence[str] = SCHEDULER_MODES,
                     core_counts: Sequence[int] = CORE_COUNTS
                     ) -> NetworkTunePlan:
    """Tune every conv layer of a ``NetworkPlan`` (same walk and bank
    legalization as ``NetworkPlan.tile_plans``, so the tuned list is a
    drop-in replacement), then search (scheduler mode × core count) for
    the whole network under the cost model.  Deterministic: modes
    are scanned in the given order and core counts ascending, with
    strict improvement required to move — ties resolve to the earliest
    (fewest-cores) point."""
    from repro.core.network import PARAM_KINDS, conv_geometry
    from repro.kernels.conv2d_ws_trans import transpose_eq_conv_geometry
    last_param = max((i for i, sp in enumerate(plan.layers)
                      if sp.kind in PARAM_KINDS), default=-1)
    names = plan.node_names()
    ins = plan.resolved_inputs()
    acts = plan.activation_shapes()
    psum_rows = dict(plan.psum_table())
    tunes: List[LayerTune] = []
    for i, sp in enumerate(plan.layers):
        if sp.kind not in ("conv", "conv_transpose"):
            p = psum_rows[names[i]]
            cyc = perfmodel.cycles(p, cfg) if p else 0
            tunes.append(LayerTune(name=names[i], plan=None, cycles=cyc,
                                   greedy_cycles=cyc, psums=p))
            continue
        h, w, c = plan.input_shape if ins[i][0] < 0 else acts[ins[i][0]]
        kh, kw = sp.kernel
        k_, g_ = conv_geometry(sp, c)
        stride, pad = sp.stride, sp.padding
        if sp.kind == "conv_transpose":
            # tune on the eq stride-1 conv geometry (what the kernel
            # lowering launches) but price compute on the zero-skipping
            # psum count the psum_table carries
            h, w, pad = transpose_eq_conv_geometry(
                h, w, kh, kw, sp.stride, sp.padding, sp.dilation)
            stride = 1
        tunes.append(autotune_layer(
            h, w, c, k_, kh, kw, stride=stride, padding=pad,
            pool=sp.pool, groups=g_, dilation=sp.dilation,
            in_bytes=in_bytes,
            out_bytes=4 if i == last_param else in_bytes,
            cin_banks=cin_banks, kout_banks=kout_banks,
            vmem_budget=vmem_budget,
            cfg=cfg, name=names[i],
            psums=psum_rows[names[i]]))
    total = sum(lt.cycles for lt in tunes)
    greedy_total = sum(lt.greedy_cycles for lt in tunes)
    best = ("batch", 1, schedule_cycles(tunes, "batch", 1, cfg))
    with obs.span("autotune.schedule_sweep", network=plan.name):
        for mode in modes:
            for cores in sorted(core_counts):
                with obs.span("autotune.schedule", mode=mode, cores=cores):
                    cyc = schedule_cycles(tunes, mode, cores, cfg)
                if cyc < best[2]:
                    best = (mode, cores, cyc)
    return NetworkTunePlan(
        network=plan.name, layers=tuple(tunes),
        scheduler_mode=best[0], n_cores=best[1],
        cycles=total, greedy_cycles=greedy_total,
        schedule_cycles_=best[2])
