"""Multi-core network scheduler — the paper's replicated-IP-core mode.

§5.2: one IP core reaches 0.224 GOPS; "when the board is fully utilized"
~20 replicated cores reach 4.48 GOPS.  Replication on the FPGA takes two
forms, and both have exact TPU analogues:

* **batch sharding** ("each IP core processes its own image"): the input
  batch is split across cores.  On a multi-device TPU slice this is data
  parallelism — one device per IP core via a NamedSharding over the batch
  axis, GSPMD partitions the jitted program.  On one device the cores are
  *virtual*: a vmap over batch shards (the compiler interleaves them the
  way the fabric interleaves replicated cores).

* **kout sharding** ("the kernel sets are divided among the cores", the
  single-image latency mode): every layer's K output channels are split
  across cores, each core convolves the SAME feature map with its kernel
  slice, and the slices concatenate into the next layer's input — the
  inter-layer concat is the fabric's output-BRAM crossbar (on a real mesh,
  an all-gather).  Implemented as a ``Backend`` decorator so any network
  program compiles against it unchanged.

* **spatial sharding** (this PR's third axis): every conv layer's output
  ROWS are split across cores; each core receives a halo'd horizontal
  band of the input map (halo = kernel extent − stride, the same overlap
  math as the tiled kernel's BlockSpecs) and convolves it with the FULL
  kernel set — the paper's fixed-size image BRAMs replicated across the
  fabric, each holding one band of a map too large for any single core.
  Bands are pool-aligned so the fused 2×2 epilogue never straddles a
  band edge; single-image latency mode, like kout.

``perfmodel.network_report`` prices them: cycles scale ~1/n_cores until a
layer's psum count no longer fills all cores, and tile/halo re-reads are
charged against the DMA interface.
"""

from __future__ import annotations

import weakref
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.banking import divisor_banks
from repro.core.convcore import Backend, get_backend
from repro.kernels.ref import conv_out_shape, halo_window, normalize_padding


@dataclass(frozen=True)
class SchedulerConfig:
    n_cores: int = 1
    mode: str = "batch"                 # "batch" | "kout" | "spatial"

    @classmethod
    def for_tune(cls, tune) -> "SchedulerConfig":
        """Config matching an autotuned plan's (mode × cores) verdict —
        accepts anything with ``scheduler_mode`` / ``n_cores`` attributes
        (core/autotune.NetworkTunePlan), so autotune stays an optional
        upper layer this module never imports."""
        return cls(n_cores=int(tune.n_cores), mode=str(tune.scheduler_mode))


class KoutShardedBackend:
    """Backend decorator: split every conv/matmul's output channels across
    ``n_cores`` virtual IP cores and concatenate (paper kernel-set
    division).  Each shard sees the full input map — weight-stationary per
    core, exactly the replicated-core dataflow.

    Grouped convs shard along GROUP boundaries: a core's contiguous
    kernel-set slice must either tile one group (a dense conv over that
    group's cin slice) or cover whole groups (a narrower grouped conv
    over their cin slices) — each core then DMAs only the input channels
    its kernel sets actually read, the grouped reading of "each core
    convolves the same feature map with its kernel slice".  A core count
    that would cut through a group mid-slice raises a ``ValueError`` with
    the offending shapes instead of silently degrading the core count
    the way dense convs do (``_shards``): silently running a depthwise
    layer on fewer cores than configured would misreport the fabric."""

    def __init__(self, inner: Backend, n_cores: int):
        self.inner = inner
        self.n_cores = n_cores
        self.name = f"{inner.name}@kout{n_cores}"

    def _shards(self, k: int) -> int:
        n = min(self.n_cores, k)
        while k % n:
            n -= 1
        return n

    def conv(self, x, w, bias=None, *, groups=1, out_scale=None, plan=None,
             **kw):
        return self._sharded(self.inner.conv, x, w, bias, groups=groups,
                             out_scale=out_scale, plan=plan, **kw)

    def conv_transpose(self, x, w, bias=None, *, groups=1, out_scale=None,
                       plan=None, **kw):
        """Kernel-set division of a TRANSPOSED conv: identical sharding
        law — the transpose's output channels are its K kernel sets, each
        core upsamples the same input map with its slice, and the slices
        concatenate on the channel axis (the output-BRAM crossbar)."""
        return self._sharded(self.inner.conv_transpose, x, w, bias,
                             groups=groups, out_scale=out_scale, plan=plan,
                             **kw)

    def _sharded(self, op, x, w, bias, *, groups, out_scale, plan, **kw):
        k = w.shape[-1]
        if groups > 1:
            return self._conv_grouped(op, x, w, bias, groups=groups,
                                      out_scale=out_scale, plan=plan, **kw)
        n = self._shards(k)
        if n == 1:
            return op(x, w, bias, out_scale=out_scale, plan=plan, **kw)
        if plan is not None:
            # re-bank for the per-core kernel slice (K/n output channels)
            plan = replace(plan, kout_banks=divisor_banks(
                k // n, plan.kout_banks))
        outs = []
        for i in range(n):                 # one iteration per fabric core
            sl = slice(i * (k // n), (i + 1) * (k // n))
            outs.append(op(
                x, w[..., sl], None if bias is None else bias[sl],
                out_scale=(out_scale if out_scale is None
                           or jnp.ndim(out_scale) == 0 else out_scale[sl]),
                plan=plan, **kw))
        return jnp.concatenate(outs, axis=-1)

    def _conv_grouped(self, op, x, w, bias, *, groups, out_scale, plan,
                      **kw):
        """Kernel-set division of a grouped conv: each core's contiguous
        K/n slice stays group-aligned (tiles one group, or covers whole
        groups) and reads only the matching cin slice."""
        k = w.shape[-1]
        kg = k // groups                     # kernels per group
        cgrp = x.shape[-1] // groups         # cin channels per group
        n = min(self.n_cores, k)
        if n == 1:
            return op(x, w, bias, groups=groups,
                      out_scale=out_scale, plan=plan, **kw)
        s = k // n                           # kernel sets per core
        if k % n or (kg % s and s % kg):
            raise ValueError(
                f"kout sharding cannot split K={k} kernels "
                f"(groups={groups}, {kg} kernels/group) across "
                f"{self.n_cores} cores: each core's slice of {k}/{n} "
                f"kernel sets must tile a group or cover whole groups")
        outs = []
        for i in range(n):                   # one iteration per fabric core
            sl = slice(i * s, (i + 1) * s)
            gi0, gi1 = (i * s) // kg, ((i + 1) * s - 1) // kg + 1
            g_s = gi1 - gi0 if s >= kg else 1    # shard's group count
            shard_plan = plan
            if plan is not None:
                if s >= kg:                  # whole groups: keep banks/group
                    kb_n = g_s * max(1, plan.kout_banks // groups)
                else:                        # within one group: dense shard
                    kb_n = divisor_banks(s, plan.kout_banks)
                shard_plan = replace(plan, kout_banks=kb_n, groups=g_s)
            outs.append(op(
                x[..., gi0 * cgrp:gi1 * cgrp], w[..., sl],
                None if bias is None else bias[sl], groups=g_s,
                out_scale=(out_scale if out_scale is None
                           or jnp.ndim(out_scale) == 0 else out_scale[sl]),
                plan=shard_plan, **kw))
        return jnp.concatenate(outs, axis=-1)

    def matmul(self, x, w, bias=None):
        k = w.shape[-1]
        n = self._shards(k)
        if n == 1:
            return self.inner.matmul(x, w, bias)
        outs = [self.inner.matmul(
            x, w[:, i * (k // n):(i + 1) * (k // n)],
            None if bias is None else bias[i * (k // n):(i + 1) * (k // n)])
            for i in range(n)]
        return jnp.concatenate(outs, axis=-1)


class SpatialShardedBackend:
    """Backend decorator: split every conv's output rows into ``n_cores``
    halo'd horizontal bands, one per virtual IP core, and concatenate.

    Band i computing conv-output rows [oy0, oy1) reads padded-input rows
    [oy0·s, (oy1−1)·s + kh) — adjacent bands overlap by the same
    ``kh − s`` halo the tiled kernel's BlockSpecs re-read.  The overlap
    is materialized by slicing the unpadded map and converting the
    residual margins to per-band explicit padding, so each band is an
    ordinary conv the inner backend (and its own TilePlan) handles.
    Bands are pool-aligned: with the fused 2×2 epilogue, band boundaries
    sit on even output rows so no pool window straddles cores."""

    def __init__(self, inner: Backend, n_cores: int):
        self.inner = inner
        self.n_cores = n_cores
        self.name = f"{inner.name}@spatial{n_cores}"

    def conv(self, x, w, bias=None, *, stride=1, padding="VALID",
             dilation=1, pool=False, plan=None, **kw):
        n, h, w_dim, c = x.shape
        kh, kw_ = w.shape[:2]
        (pt, pb), (pl_, pr) = normalize_padding(padding, kh, kw_, stride,
                                                h, w_dim, dilation)
        oh, _ = conv_out_shape(h, w_dim, kh, kw_, stride, padding, dilation)
        if pool:
            oh = (oh // 2) * 2           # floor semantics, like the kernel
        unit = 2 if pool else 1          # pool-aligned band boundaries
        rows = oh // unit
        shards = min(self.n_cores, rows)
        if shards <= 1:
            return self.inner.conv(x, w, bias, stride=stride,
                                   padding=padding, dilation=dilation,
                                   pool=pool, plan=plan, **kw)
        # balanced unit split: the first (rows % shards) bands get one more
        base, rem = divmod(rows, shards)
        outs, oy0 = [], 0
        for i in range(shards):
            oy1 = oy0 + (base + (1 if i < rem else 0)) * unit
            a = oy0 * stride - pt        # input rows, unpadded coordinates
            # the band halo is the DILATED kernel extent minus stride —
            # dilation widens every band's overlap exactly like the tiled
            # kernel's BlockSpecs
            b_ = a + halo_window(oy1 - oy0, stride, kh, dilation)
            lo, hi = max(a, 0), min(b_, h)
            outs.append(self.inner.conv(
                x[:, lo:hi], w, bias, stride=stride,
                padding=((lo - a, b_ - hi), (pl_, pr)), dilation=dilation,
                pool=pool, plan=plan, **kw))
            oy0 = oy1
        return jnp.concatenate(outs, axis=1)

    def conv_transpose(self, x, w, bias=None, *, stride=1, padding="VALID",
                       dilation=1, **kw):
        """Row-band a TRANSPOSED conv by lowering it to its equivalent
        stride-1 conv first (kernels/conv2d_ws_trans.transpose_eq_conv_
        inputs: zero-inserted map + flipped kernel + "full" padding) and
        banding THAT through ``self.conv`` — each core sweeps a halo'd
        band of the upsampled map, which is exactly what replicated
        fixed-size image BRAMs holding one band each would do.  Bit-exact
        with the unsharded kernel because the lowering is the SAME one
        conv2d_ws_transpose performs before launching."""
        from repro.kernels.conv2d_ws_trans import transpose_eq_conv_inputs
        xd, eq_pads = transpose_eq_conv_inputs(
            x, w.shape[0], w.shape[1], stride=stride, padding=padding,
            dilation=dilation)
        return self.conv(xd, jnp.flip(w, (0, 1)), bias, stride=1,
                         padding=eq_pads, dilation=dilation, **kw)

    def matmul(self, x, w, bias=None):
        return self.inner.matmul(x, w, bias)


class MultiCoreScheduler:
    """Run a compiled network program as if on ``n_cores`` replicated IP
    cores."""

    def __init__(self, config: SchedulerConfig = SchedulerConfig()):
        assert config.mode in ("batch", "kout", "spatial"), config.mode
        self.config = config
        # program → its shard_map over the devices; an entry lives as long
        # as its program, which whoever compiled it owns (the serving
        # engine's bounded LRU)
        self._per_device = weakref.WeakKeyDictionary()

    @classmethod
    def from_tune(cls, tune) -> "MultiCoreScheduler":
        """Scheduler for an autotuned network plan: the (scheduler mode ×
        core count) the search priced cheapest under the calibrated
        model (see core/autotune.autotune_network)."""
        return cls(SchedulerConfig.for_tune(tune))

    def shard_backend(self, backend_name: str) -> Backend:
        """kout / spatial modes: a Backend whose every conv layer is
        kernel-set- or row-band-sharded across the virtual cores."""
        inner = get_backend(backend_name)
        if self.config.mode == "spatial":
            return SpatialShardedBackend(inner, self.config.n_cores)
        return KoutShardedBackend(inner, self.config.n_cores)

    def run(self, program, x: jax.Array) -> jax.Array:
        """batch mode: split the batch over cores.  kout / spatial modes:
        pass through — the cores divide kernels or row bands inside the
        program (compile it against ``shard_backend``), not the batch.

        Ragged batches (n not a multiple of the core count) zero-pad up to
        the next multiple and slice the padding back off — some cores
        process a blank image on the last step instead of the host
        crashing (the fabric doesn't care what's in an idle core's BRAMs).

        With enough local devices, one device per IP core: the program
        runs under ``jax.shard_map`` on each device's slice of the batch
        (XLA will not partition a Mosaic kernel itself, so the split is
        explicit);
        otherwise vmapped virtual cores on one device.  The counters
        ``sched.batch.device`` / ``sched.batch.virtual`` count which of
        the two served each batch-mode run.

        Each run is an ``sched.run`` trace span (mode, cores, batch,
        virtual-vs-device) when obs is enabled — the per-core/mode
        breakdown the full-board utilization story needs."""
        cores = self.config.n_cores
        n = x.shape[0]
        if cores == 1 or self.config.mode in ("kout", "spatial"):
            # kout/spatial: the cores live INSIDE the program (sharded
            # backend); the span still attributes the pass to the mode
            with obs.span("sched.run", mode=self.config.mode, cores=cores,
                          batch=n):
                return program(x)
        pad = -n % cores
        if pad:
            x = jnp.concatenate(
                [x, jnp.zeros((pad, *x.shape[1:]), x.dtype)])
        if jax.device_count() >= cores:
            obs.metrics.counter("sched.batch.device").inc()
            with obs.span("sched.run", mode="batch", cores=cores, batch=n,
                          padded=pad, virtual=False):
                return self._on_devices(program)(x)[:n]
        obs.metrics.counter("sched.batch.virtual").inc()
        with obs.span("sched.run", mode="batch", cores=cores, batch=n,
                      padded=pad, virtual=True):
            xs = x.reshape(cores, (n + pad) // cores, *x.shape[1:])
            ys = jax.vmap(program)(xs)
            return ys.reshape(n + pad, *ys.shape[2:])[:n]

    def _on_devices(self, program):
        """``program`` data-parallel over the first ``n_cores`` devices:
        each device runs it on its own batch shard (jitted once per
        program)."""
        fn = self._per_device.get(program)
        if fn is None:
            from jax.sharding import PartitionSpec as P
            cores = self.config.n_cores
            mesh = jax.make_mesh((cores,), ("cores",),
                                 devices=jax.devices()[:cores])
            # a weak reference, so the wrapper (the entry's value) never
            # keeps its key alive once the program is evicted
            prog = weakref.ref(program)
            fn = self._per_device[program] = jax.jit(jax.shard_map(
                lambda x: prog()(x), mesh=mesh, in_specs=P("cores"),
                out_specs=P("cores"), check_vma=False))
        return fn
