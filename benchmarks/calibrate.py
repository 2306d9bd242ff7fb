"""Calibration sweep: time the REAL conv kernels over a factorial grid of
(tile shape × cin/kout banks × groups × dilation/transpose × epilogue ×
pipelined) and fit the per-term corrections of
``core/calibration.CalibrationTable`` onto the §5.2 analytic model — the
measured counterpart of the exemplar repo's ``overhead_factor = 3.89``.
The dense-prediction grid points (PR 8) cover a dilated 3×3 and a
stride-2 transposed conv (timed through the shared ``conv2d_ws_trans``
eq-conv lowering, with the zero-skipping psum count as the analytic
compute term).

Each grid point runs ``conv2d_ws`` (sequential) or ``conv2d_ws_pipe``
(explicit double-buffered DMA) with a concrete ``banking.TilePlan``; its
analytic terms (compute cycles, DMA bytes incl. tile revisits/halos,
pipeline slab count) come from the same perfmodel walk the planner uses,
so the fitted table corrects exactly the expression the planner descends
against.  ``bench_util.time_fn`` returns the full stats record; samples
whose IQR exceeds half their median are rejected before the fit.

On a TPU host the kernels compile natively and the table calibrates the
real datapath; on this CPU container they run in interpret mode and the
table calibrates the emulation — either way predictions and measurements
land on one scale, which is what turns ``measured_vs_predicted`` error
into a trackable number (BENCH_network.json).

Usage::

    python benchmarks/calibrate.py [--smoke] [--out CALIBRATION.json]

``--smoke`` runs a reduced grid with minimal iterations (the CI lane);
the fitted table is written to ``--out`` (default ``CALIBRATION.json``,
or the ``CALIBRATION_JSON`` env var) with provenance + fit diagnostics.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.bench_util import emit, time_fn
from repro.core import perfmodel
from repro.core.banking import grouped_banks, plan_tiles
from repro.core.calibration import (NOISE_IQR_FRACTION, fit_calibration,
                                    sample_from_plan)
from repro.kernels.conv2d_ws import conv2d_ws
from repro.kernels.conv2d_ws_pipe import conv2d_ws_pipe
from repro.kernels.conv2d_ws_trans import (conv2d_ws_transpose,
                                           transpose_eq_conv_geometry)

OUT_PATH = os.environ.get("CALIBRATION_JSON", "CALIBRATION.json")


def _provenance(smoke: bool) -> dict:
    """Same toolchain pin as BENCH_network.json, plus the execution mode
    (interpret on CPU vs native Mosaic on TPU) — a table fitted on the
    emulation must never be mistaken for silicon numbers."""
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            timeout=10).stdout.strip()
    except (OSError, subprocess.SubprocessError):
        sha = ""
    dev = jax.devices()[0]
    return {"jax_version": jax.__version__,
            "backend": jax.default_backend(),
            "device_kind": getattr(dev, "device_kind", str(dev)),
            "git_sha": sha or "unknown",
            "mode": "native" if jax.default_backend() == "tpu"
                    else "interpret",
            "smoke": smoke}


# factorial axes: (name, H, W, C, K, KH, groups, padding, dilation, op)
# × bank pairs × epilogues × {sequential, pipelined}.  The shapes span
# the zoo's workload classes: dense 3×3, pointwise 1×1, grouped,
# depthwise, a spatially-tiled map (many slabs — the axis that
# constrains the per-slab overhead term), and the dense-prediction pair
# (PR 8): a dilated (atrous) kernel with its widened halo, and a
# stride-2 transposed-conv upsampler through the shared
# ``conv2d_ws_trans`` eq-conv lowering.  ``op`` is "conv" (stride 1) or
# "transpose" (stride-2 upsampling, the unet_small deconv shape class).
_SHAPES = [
    ("dense3x3",    16, 16, 16, 16, 3, 1,  "SAME",  1, "conv"),
    ("dense3x3big", 32, 32, 16, 16, 3, 1,  "SAME",  1, "conv"),
    ("pointwise",   16, 16, 32, 32, 1, 1,  "VALID", 1, "conv"),
    ("grouped",     16, 16, 32, 32, 3, 4,  "SAME",  1, "conv"),
    ("depthwise",   16, 16, 32, 32, 3, 32, "SAME",  1, "conv"),
    ("tiledmap",    64, 64, 16, 16, 3, 1,  "SAME",  1, "conv"),
    ("dilated2",    16, 16, 16, 16, 3, 1,  "SAME",  2, "conv"),
    ("transpose2x",  8,  8, 16, 16, 2, 1,  "VALID", 1, "transpose"),
]
_BANKS = [(4, 4), (8, 8)]
# the stride the transposed shapes upsample by (the zoo's 2× deconv)
_TRANSPOSE_STRIDE = 2
# epilogue grid: bare, ReLU, ReLU+pool, fused requantize
_EPILOGUES = [
    ("bare",    dict()),
    ("relu",    dict(relu=True)),
    ("relupool", dict(relu=True, pool=True)),
    ("requant", dict(out_scale=0.03125)),
]

_SMOKE_SHAPES = [_SHAPES[0], _SHAPES[2], _SHAPES[4], _SHAPES[5],
                 _SHAPES[6], _SHAPES[7]]
_SMOKE_EPILOGUES = [_EPILOGUES[1], _EPILOGUES[3]]


def sweep(smoke: bool = False, iters: int = 0) -> list:
    """Run the factorial microbenchmark grid; one CalibrationSample per
    (shape × banks × epilogue × kernel variant) point."""
    interpret = jax.default_backend() != "tpu"
    shapes = _SMOKE_SHAPES if smoke else _SHAPES
    banks = _BANKS[:1] if smoke else _BANKS
    epilogues = _SMOKE_EPILOGUES if smoke else _EPILOGUES
    iters = iters or (2 if smoke else 5)
    rng = np.random.default_rng(7)
    samples = []
    for name, h, w, c, k, kh, groups, pad, dil, op in shapes:
        x = jnp.asarray(rng.integers(-128, 128, (1, h, w, c)), jnp.int8)
        wt = jnp.asarray(
            rng.integers(-128, 128, (kh, kh, c // groups, k)), jnp.int8)
        if op == "transpose":
            # zero-skipping MACs — the count the planner prices transposed
            # rows with; the plan geometry is the eq stride-1 conv the
            # lowering actually launches
            psums = perfmodel.conv_transpose_psum_count(
                h, w, c, k, kh, kh, stride=_TRANSPOSE_STRIDE, padding=pad,
                groups=groups, dilation=dil)
            ph, pw, ppad = transpose_eq_conv_geometry(
                h, w, kh, kh, _TRANSPOSE_STRIDE, pad, dil)
        else:
            psums = perfmodel.psum_count(h, w, c, k, kh, kh, padding=pad,
                                         groups=groups, dilation=dil)
            ph, pw, ppad = h, w, pad
        # spatial tiles only where the shape calls for them: the tiled
        # map's tight budget forces plan_tiles into halo'd H/W tiles —
        # the many-slab axis that constrains the per-slab overhead term
        budget = 96 * 1024 if name == "tiledmap" else None
        for cb, kb in banks:
            cb_n, kb_n = grouped_banks(c, k, groups, want_cin=cb,
                                       want_kout=kb)
            for ep_name, ep in epilogues:
                out_scale = ep.get("out_scale")
                for variant, fn, pipelined in (
                        ("seq", conv2d_ws, False),
                        ("pipe", conv2d_ws_pipe, True)):
                    plan = plan_tiles(
                        ph, pw, c, k, kh, kh, padding=ppad, groups=groups,
                        dilation=dil,
                        pool=ep.get("pool", False), in_bytes=1,
                        out_bytes=1 if out_scale is not None else 4,
                        cin_banks=cb_n, kout_banks=kb_n,
                        vmem_budget=budget,
                        kernel="pipelined" if pipelined else "sequential")
                    # the kernel runs the PLAN's geometry (banks + tiles),
                    # so the analytic terms describe exactly what was
                    # measured
                    kw = dict(stride=1, padding=pad, groups=groups,
                              dilation=dil,
                              cin_banks=plan.cin_banks,
                              kout_banks=plan.kout_banks,
                              h_tile=plan.h_tile if plan.tiled else 0,
                              w_tile=plan.w_tile if plan.tiled else 0,
                              relu=ep.get("relu", False),
                              pool=ep.get("pool", False))
                    scale = (jnp.float32(out_scale)
                             if out_scale is not None else None)
                    if op == "transpose":
                        # both variants go through the shared lowering —
                        # it dispatches the eq conv on ``pipelined``
                        fn = conv2d_ws_transpose
                        kw.update(stride=_TRANSPOSE_STRIDE,
                                  pipelined=pipelined)
                    t = time_fn(
                        lambda fn=fn, kw=kw, scale=scale: fn(
                            x, wt, None, scale, interpret=interpret, **kw),
                        iters=iters, warmup=1)
                    label = (f"{name}/b{plan.cin_banks}x{plan.kout_banks}"
                             f"/{ep_name}/{variant}")
                    s = sample_from_plan(
                        label, plan, psums, t.median_us, t.iqr_us,
                        pipelined=pipelined, shape=[h, w, c, k, kh],
                        groups=groups, epilogue=ep_name)
                    samples.append(s)
                    emit(f"calibrate/{label}", t,
                         f"compute_cycles={s.compute_cycles};"
                         f"dma_bytes={s.dma_bytes};n_slabs={s.n_slabs};"
                         f"noisy={int(s.noisy)}")
    return samples


def run(smoke: bool = False, out_path: str = OUT_PATH):
    samples = sweep(smoke=smoke)
    table = fit_calibration(samples, provenance=_provenance(smoke))
    table.save(out_path)
    fit = table.fit
    emit("calibrate/fit", 0.0,
         f"path={out_path};compute_factor={table.compute_factor:.3f};"
         f"dma_bpc={table.dma_bytes_per_cycle};"
         f"pipe_overhead={table.pipeline_overhead_cycles:.1f};"
         f"n_fit={fit['n_fit']}/{fit['n_samples']};"
         f"mean_err_pct={fit['mean_abs_error_pct']:.1f}")
    return table


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    out = OUT_PATH
    if "--out" in sys.argv:
        out = sys.argv[sys.argv.index("--out") + 1]
    print("name,us_per_call,derived")
    run(smoke="--smoke" in sys.argv, out_path=out)
