"""Per-layer profiler + model-drift detection.

* :func:`profile_network` runs a quantized ``NetworkPlan`` program
  layer-at-a-time through the SAME int8 node semantics the compiled
  program executes (``network.int8_forward`` with a node hook — the
  paper's single IP core processes "a convolutional layer at a time"
  (§4.2), so the walk is the hardware schedule, not an approximation),
  wall-clocking each node with monotonic clocks and emitting one
  :class:`LayerProfile` per node: wall µs, psums, achieved GOPS (the
  paper's psums/second accounting), and the §5.2 cost model's predicted
  µs for the layer's plan and kernel variant.

* :class:`DriftDetector` flags layers whose measured/predicted ratio
  leaves a configurable band.  The prediction is the analytic FPGA
  time, so the ratio says how far a machine sits from the modelled IP
  core, layer by layer.  Events also land in ``obs.metrics`` (counter
  ``obs.drift.events``) and as instant marks in the trace, so a
  Perfetto view shows *where* the model lost the machine.

Profiling imports jax lazily and is only ever called explicitly, offline:
an eager layer walk compiles op by op, so it never runs on a serving
thread.  The obs package itself stays dependency-free.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from repro import obs

# measured/predicted inside [lo, hi] is "the model holds"; outside is
# drift.  The default band is generous: 2× each way.
DEFAULT_DRIFT_BAND = (0.5, 2.0)


@dataclass(frozen=True)
class LayerProfile:
    """One node's profile record: measurement, workload, prediction."""
    index: int
    name: str
    kind: str
    wall_us: float
    psums: int                         # per image (the paper accounting)
    batch: int
    gops: float                        # achieved, psums·batch / wall / 1e9
    predicted_us: Optional[float]      # None: the model prices it free
    pipelined: Optional[bool]          # conv nodes: kernel variant

    @property
    def ratio(self) -> Optional[float]:
        """measured / predicted — the drift signal (None when the model
        prices the node free: merges, pools, flatten)."""
        if not self.predicted_us:
            return None
        return self.wall_us / self.predicted_us

    def to_dict(self) -> Dict[str, Any]:
        return {"index": self.index, "name": self.name, "kind": self.kind,
                "wall_us": self.wall_us, "psums": self.psums,
                "batch": self.batch, "gops": self.gops,
                "predicted_us": self.predicted_us, "ratio": self.ratio,
                "pipelined": self.pipelined}


@dataclass(frozen=True)
class DriftEvent:
    """One flagged layer: its measured/predicted ratio left the band."""
    name: str
    wall_us: float
    predicted_us: float
    ratio: float
    band: Tuple[float, float]

    def to_dict(self) -> Dict[str, Any]:
        return {"name": self.name, "wall_us": self.wall_us,
                "predicted_us": self.predicted_us, "ratio": self.ratio,
                "band": list(self.band)}


@dataclass(frozen=True)
class NetworkProfile:
    """The per-layer profile of one forward pass."""
    network: str
    batch: int
    records: Tuple[LayerProfile, ...]
    drift: Tuple[DriftEvent, ...] = ()

    @property
    def layer_names(self) -> List[str]:
        return [r.name for r in self.records]

    @property
    def total_wall_us(self) -> float:
        return sum(r.wall_us for r in self.records)

    def to_dict(self) -> Dict[str, Any]:
        return {"network": self.network, "batch": self.batch,
                "total_wall_us": self.total_wall_us,
                "layers": [r.to_dict() for r in self.records],
                "drift": [d.to_dict() for d in self.drift]}


class DriftDetector:
    """Flag layers whose measured/predicted wall-time ratio leaves
    ``band`` — live model-drift detection over profile records.

    ``min_wall_us`` suppresses noise-floor layers: a 2 µs pool node
    doubling its time is clock jitter, not drift.  Each flagged layer
    increments ``obs.metrics`` counter ``obs.drift.events`` and drops an
    instant mark into the trace (when obs is enabled), so drift is
    visible both in aggregate and on the timeline."""

    def __init__(self, band: Tuple[float, float] = DEFAULT_DRIFT_BAND,
                 min_wall_us: float = 0.0):
        lo, hi = band
        if not (0.0 < lo < hi):
            raise ValueError(f"drift band wants 0 < lo < hi, got {band}")
        self.band = (float(lo), float(hi))
        self.min_wall_us = float(min_wall_us)

    def check(self, records: Sequence[LayerProfile]) -> List[DriftEvent]:
        lo, hi = self.band
        events: List[DriftEvent] = []
        for r in records:
            ratio = r.ratio
            if ratio is None or r.wall_us < self.min_wall_us:
                continue
            if lo <= ratio <= hi:
                continue
            ev = DriftEvent(name=r.name, wall_us=r.wall_us,
                            predicted_us=float(r.predicted_us),
                            ratio=ratio, band=self.band)
            events.append(ev)
            obs.metrics.counter("obs.drift.events").inc()
            obs.instant("drift", layer=r.name, ratio=round(ratio, 3),
                        band=list(self.band))
        return events


def _predicted_us(psums: int, tile_plan, cfg) -> Optional[float]:
    """The cost model's wall-time prediction for one node, priced exactly
    the way ``network_report`` prices it (``perfmodel.pipeline_estimate``
    for the kernel variant a planned conv runs, compute cycles for
    GEMMs); None for nodes the model prices free (merges, pools, flatten
    — the fused epilogue / output-BRAM crossbar absorb them)."""
    from repro.core import perfmodel
    if tile_plan is not None:
        cyc = perfmodel.pipeline_estimate(tile_plan, psums, cfg)["cycles"]
    elif psums:
        cyc = perfmodel.cycles(psums, cfg)
    else:
        return None
    return cyc / cfg.clock_hz * 1e6


def profile_network(qnet, x, *, core_config=None,
                    tile_plans: Optional[Sequence] = None,
                    warmup: int = 1,
                    drift: Optional[DriftDetector] = None,
                    perf_cfg=None) -> NetworkProfile:
    """Profile one int8 forward pass layer-at-a-time.

    Runs ``network.int8_forward`` EAGERLY (no jit) with a node hook that
    blocks on each node's output and wall-clocks it — the per-node walk
    is the same topological schedule the single layer-at-a-time IP core
    executes, so the layer set matches ``NetworkPlan`` topology exactly
    (one record per node, asserted in tests).  Each node gets a
    ``layer:<name>`` span in the trace when obs is enabled.

    The predicted column is the analytic §5.2 FPGA time at
    ``perf_cfg``'s clock (a cross-platform reference, not a prediction
    of interpret-mode wall time).  ``warmup`` extra passes absorb
    first-call compilation.

    Eager per-node dispatch is slower than the fused jitted program —
    profiling is a diagnostic mode, never the serving path."""
    import jax

    from repro.core import network, perfmodel
    from repro.core.convcore import ConvCoreConfig, get_backend

    if core_config is None:
        core_config = ConvCoreConfig(int8=True)
    plan = qnet.plan
    if tile_plans is None:
        tile_plans = network.program_tile_plans(plan, core_config)
    cfg = perf_cfg if perf_cfg is not None else perfmodel.IPCoreConfig()
    backend = get_backend(core_config.backend)
    batch = int(x.shape[0]) if getattr(x, "ndim", 4) == 4 else 1
    psum_rows = dict(plan.psum_table())
    names = plan.node_names()

    for _ in range(max(warmup, 0)):
        jax.block_until_ready(network.int8_forward(
            qnet, x, backend=backend, tile_plans=tile_plans))

    intervals: List[Tuple[int, int]] = []    # per-node (t0_ns, t1_ns)
    t_prev = [time.perf_counter_ns()]

    def hook(i, name, sp, h):
        jax.block_until_ready(h)
        t1 = time.perf_counter_ns()
        intervals.append((t_prev[0], t1))
        t_prev[0] = time.perf_counter_ns()   # exclude the hook's own cost

    with obs.span("profile", network=plan.name, batch=batch):
        t_prev[0] = time.perf_counter_ns()
        out = network.int8_forward(qnet, x, backend=backend,
                                   tile_plans=tile_plans, node_hook=hook)
        jax.block_until_ready(out)

    records: List[LayerProfile] = []
    hist = obs.metrics.histogram(f"profile.layer_us.{plan.name}")
    for i, sp in enumerate(plan.layers):
        psums = psum_rows[names[i]]
        t0, t1 = intervals[i]
        wall = (t1 - t0) / 1e3
        pred = _predicted_us(psums, tile_plans[i], cfg)
        rec = LayerProfile(
            index=i, name=names[i], kind=sp.kind, wall_us=wall,
            psums=psums, batch=batch,
            gops=(psums * batch) / (wall * 1e-6) / 1e9 if wall > 0 else 0.0,
            predicted_us=pred,
            pipelined=(bool(tile_plans[i].pipelined)
                       if tile_plans[i] is not None else None))
        records.append(rec)
        if obs.enabled():
            # the measured walk as trace events with their REAL intervals
            # (timing happened inside the hook, so the spans are emitted
            # retroactively — ts/dur are what Perfetto nests on)
            obs.tracer._record(
                f"layer:{names[i]}", t0, t1,
                {"kind": sp.kind, "psums": psums,
                 "predicted_us": None if pred is None else round(pred, 2)})
        hist.observe(wall)

    events: Tuple[DriftEvent, ...] = ()
    if drift is not None:
        events = tuple(drift.check(records))
    return NetworkProfile(network=plan.name, batch=batch,
                          records=tuple(records), drift=events)
