"""ConvCore — the paper's IP core as a composable JAX module.

Semantics follow the paper exactly (§3–4): the core processes **one
convolutional layer at a time**; it accepts a C-channel feature-map stack and
K C-channel kernels, and produces a K-channel feature map.  Bias is
*preloaded* into the output accumulator (M5).  Generalized beyond the
paper's stride-1 VALID demo: any stride, SAME/VALID/explicit padding, and
the fused post-processing epilogue (ReLU → 2×2 max-pool → requantize)
executed before writeback.  Bank counts degrade gracefully for channel
counts that break the divisible-by-4 invariant (a C=1 grayscale input
layer runs on one image BMG), and ``ConvCore.plan`` returns a joint
``banking.TilePlan`` — feature maps whose whole-map working set exceeds
the VMEM budget stream through halo'd spatial tiles.

Backends implement the ``Backend`` protocol and live in a registry, so
``apply_layer`` is a pure dispatch (no per-dtype if/else ladder):

* "pallas"  — kernels/conv2d_ws.py, the TPU-native dataflow (interpret mode
  on CPU);
* "ref"     — pure-jnp oracle (lax.conv), used for training graphs/vjp.

The int8 path mirrors the paper's 8-bit datapath: int8 features/weights →
int32 psum accumulation → requantize (or wrap8 for waveform fidelity).
Layer-at-a-time networks are built on top by core/network.py; replicated
IP cores map to core/scheduler.py.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Protocol

import jax
import jax.numpy as jnp

from repro.core import banking
from repro.core.quantize import Quantized, quantize_symmetric
from repro.kernels import ops, ref


class Backend(Protocol):
    """One implementation of the IP-core ops (conv + transposed conv +
    the dense GEMM).

    ``plan`` is a banking.TilePlan: the joint spatial-tile × channel-bank
    decomposition the conv should run under (None → whole map, paper 4×4
    banking).  ``conv_transpose`` is the dense-prediction upsampling
    layer — its plan is sized on the EQUIVALENT stride-1 conv geometry
    (the zero-inserted map the kernel actually sweeps)."""

    name: str

    def conv(self, x: jax.Array, w: jax.Array,
             bias: Optional[jax.Array] = None, *, stride: int = 1,
             padding="VALID", groups: int = 1, dilation: int = 1,
             relu: bool = False, pool: bool = False, out_scale=None,
             wrap8: bool = False,
             plan: Optional[banking.TilePlan] = None) -> jax.Array:
        ...

    def conv_transpose(self, x: jax.Array, w: jax.Array,
                       bias: Optional[jax.Array] = None, *,
                       stride: int = 1, padding="VALID", groups: int = 1,
                       dilation: int = 1, relu: bool = False,
                       pool: bool = False, out_scale=None,
                       plan: Optional[banking.TilePlan] = None
                       ) -> jax.Array:
        ...

    def matmul(self, x: jax.Array, w: jax.Array,
               bias: Optional[jax.Array] = None) -> jax.Array:
        ...


class RefBackend:
    """Pure-jnp oracle (lax.conv) — differentiable, the correctness
    contract for the Pallas dataflow."""

    name = "ref"

    def conv(self, x, w, bias=None, *, stride=1, padding="VALID",
             groups=1, dilation=1, relu=False, pool=False, out_scale=None,
             wrap8=False, plan=None):
        if wrap8:
            # epilogue runs on the int32 accumulator, THEN the result wraps
            # to 8 bits — matching the Pallas path (epilogue in the kernel,
            # wrap in ops.conv2d); like ops.conv2d, wrap8 + out_scale is a
            # contract violation, not a silent drop
            if out_scale is not None:
                raise ValueError("wrap8 and out_scale are mutually "
                                 "exclusive: the Fig. 6 wrap path has no "
                                 "requantize stage")
            assert x.dtype == jnp.int8
            acc = ref.conv2d_epilogue_ref(x, w, bias, stride=stride,
                                          padding=padding, relu=relu,
                                          pool=pool, groups=groups,
                                          dilation=dilation)
            return acc.astype(jnp.int8)
        return ref.conv2d_epilogue_ref(x, w, bias, stride=stride,
                                       padding=padding, relu=relu,
                                       pool=pool, out_scale=out_scale,
                                       groups=groups, dilation=dilation)

    def conv_transpose(self, x, w, bias=None, *, stride=1, padding="VALID",
                       groups=1, dilation=1, relu=False, pool=False,
                       out_scale=None, plan=None):
        return ref.conv2d_transpose_epilogue_ref(
            x, w, bias, stride=stride, padding=padding, relu=relu,
            pool=pool, out_scale=out_scale, groups=groups,
            dilation=dilation)

    def matmul(self, x, w, bias=None):
        if x.dtype == jnp.int8:
            return ref.matmul_ref_int8(x, w, bias)
        return ref.matmul_ref(x, w, bias)


class PallasBackend:
    """The TPU-native weight-stationary dataflow (kernels/conv2d_ws.py)."""

    name = "pallas"

    def conv(self, x, w, bias=None, *, stride=1, padding="VALID",
             groups=1, dilation=1, relu=False, pool=False, out_scale=None,
             wrap8=False, plan=None):
        if plan is not None:
            cin_banks, kout_banks = plan.cin_banks, plan.kout_banks
        else:
            # no plan → whole map under the paper's 4×4 banking, degraded
            # to the largest legal counts (narrow kernel-set shards and
            # grouped layers would otherwise trip the divisibility assert,
            # sub-128-channel banks the chip's lane tiling)
            cin_banks, kout_banks = ref.grouped_banks(
                x.shape[-1], w.shape[-1], groups)
        # tile extents are conv-output pixels; the kernel clamps them to
        # the actual map (shard slices may be smaller than the plan's map)
        h_tile = plan.h_tile if plan else 0
        w_tile = plan.w_tile if plan else 0
        return ops.conv2d(x, w, bias, stride=stride, padding=padding,
                          groups=groups, cin_banks=cin_banks,
                          kout_banks=kout_banks, h_tile=h_tile,
                          w_tile=w_tile, relu=relu, pool=pool, wrap8=wrap8,
                          out_scale=out_scale, dilation=dilation,
                          pipelined=plan.pipelined if plan else False)

    def conv_transpose(self, x, w, bias=None, *, stride=1, padding="VALID",
                       groups=1, dilation=1, relu=False, pool=False,
                       out_scale=None, plan=None):
        if plan is not None:
            cin_banks, kout_banks = plan.cin_banks, plan.kout_banks
        else:
            cin_banks, kout_banks = ref.grouped_banks(
                x.shape[-1], w.shape[-1], groups)
        h_tile = plan.h_tile if plan else 0
        w_tile = plan.w_tile if plan else 0
        return ops.conv2d_transpose(
            x, w, bias, stride=stride, padding=padding, groups=groups,
            cin_banks=cin_banks, kout_banks=kout_banks, h_tile=h_tile,
            w_tile=w_tile, relu=relu, pool=pool, out_scale=out_scale,
            dilation=dilation,
            pipelined=plan.pipelined if plan else False)

    def matmul(self, x, w, bias=None):
        return ops.matmul_ws(x, w, bias)


BACKENDS: Dict[str, Backend] = {"ref": RefBackend(), "pallas": PallasBackend()}


def get_backend(name: str) -> Backend:
    try:
        return BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; have {sorted(BACKENDS)}") from None


def register_backend(backend: Backend) -> None:
    BACKENDS[backend.name] = backend


def unregister_backend(name: str) -> None:
    """Remove a registered backend (no-op if absent).  Tests that register
    sharded backends must clean up so the global registry doesn't leak
    across tests — tests/conftest.py snapshots/restores it as well."""
    BACKENDS.pop(name, None)


@dataclass(frozen=True)
class ConvCoreConfig:
    cin_banks: int = 4            # paper: 4 image BMGs / computing cores (M1)
    kout_banks: int = 4           # paper: 4 PCOREs per core (M2)
    backend: str = "pallas"       # a BACKENDS registry key
    int8: bool = False            # the paper's 8-bit datapath
    wrap8: bool = False           # bit-faithful 8-bit psum wrap (Fig. 6)
    auto_bank: bool = True        # fit spatial tiles + banks to VMEM
    vmem_budget: int = banking.VMEM_LIMIT_BYTES   # per-core VMEM target
    kernel: str = "auto"          # conv variant per layer: "auto" lets the
                                  # perfmodel crossover predictor choose
                                  # conv2d_ws_pipe vs conv2d_ws;
                                  # "pipelined"/"sequential" force one
    calib: Optional[object] = None  # core.calibration.CalibrationTable:
                                  # measured model terms for the planner's
                                  # crossover; None → analytic §5.2 model


class ConvCore:
    """One paper IP core.  Use ``apply_layer`` per convolutional layer."""

    def __init__(self, config: ConvCoreConfig = ConvCoreConfig()):
        self.config = config

    def plan(self, x_shape, w_shape, stride: int = 1, padding="VALID",
             *, pool: bool = False, groups: int = 1,
             out_bytes: Optional[int] = None) -> banking.TilePlan:
        """Joint spatial-tile × channel-bank plan for one layer.  With
        ``auto_bank`` the planner shrinks tiles / grows banks until the
        working set fits ``vmem_budget``; otherwise the whole map runs as
        one tile under the configured banking (the seed dataflow).
        ``groups`` plans the grouped/depthwise working set (per-group
        channel slices, kout banks on group boundaries)."""
        n, h, w_, c = x_shape
        kh, kw, _, k = w_shape
        cfg = self.config
        in_bytes = 1 if cfg.int8 else 4
        # plan_tiles degrades the configured banks to the largest legal
        # counts (C=1 input layers, per-group slices, lane-legal blocks)
        return banking.plan_tiles(
            h, w_, c, k, kh, kw, stride=stride, padding=padding, pool=pool,
            groups=groups, in_bytes=in_bytes, acc_bytes=4,
            out_bytes=out_bytes, cin_banks=cfg.cin_banks,
            kout_banks=cfg.kout_banks,
            vmem_budget=cfg.vmem_budget if cfg.auto_bank else None,
            kernel=cfg.kernel, calib=cfg.calib)

    def apply_layer(self, x: jax.Array, w: jax.Array,
                    bias: Optional[jax.Array] = None,
                    out_scale: Optional[jax.Array] = None, *,
                    stride: int = 1, padding="VALID", groups: int = 1,
                    relu: bool = False, pool: bool = False) -> jax.Array:
        """x: [N,H,W,C] ⊛ w: [KH,KW,C/groups,K] (+bias [K]) → [N,OH,OW,K].

        Fused epilogue order: ReLU → 2×2 max-pool → requantize(out_scale).
        """
        cfg = self.config
        plan = self.plan(x.shape, w.shape, stride, padding, pool=pool,
                         groups=groups,
                         out_bytes=1 if out_scale is not None else None)
        if cfg.int8:
            assert x.dtype == jnp.int8 and w.dtype == jnp.int8
        backend = get_backend(cfg.backend)
        return backend.conv(x, w, bias, stride=stride, padding=padding,
                            groups=groups, relu=relu, pool=pool,
                            out_scale=out_scale, wrap8=cfg.wrap8, plan=plan)

    def apply_quantized_layer(self, x_f32: jax.Array, w_f32: jax.Array,
                              bias_f32: Optional[jax.Array] = None, *,
                              stride: int = 1, padding="VALID",
                              relu: bool = False, pool: bool = False):
        """Float-in/float-out convenience: symmetric int8 quantization of
        activations + weights, int32 accumulate, dequantize (the edge-AI
        deployment path the paper targets)."""
        xq = quantize_symmetric(x_f32)
        wq = quantize_symmetric(w_f32)
        bias_i32 = None
        if bias_f32 is not None:
            bias_i32 = jnp.round(
                bias_f32.astype(jnp.float32) / (xq.scale * wq.scale)
            ).astype(jnp.int32)
        core = ConvCore(ConvCoreConfig(
            cin_banks=self.config.cin_banks,
            kout_banks=self.config.kout_banks,
            backend=self.config.backend, int8=True))
        acc = core.apply_layer(xq.values, wq.values, bias_i32,
                               stride=stride, padding=padding, relu=relu,
                               pool=pool)
        return acc.astype(jnp.float32) * (xq.scale * wq.scale)


def paper_workload():
    """The exact §5.2 simulation workload shapes."""
    return {"x": (1, 224, 224, 8), "w": (3, 3, 8, 8), "bias": (8,)}
