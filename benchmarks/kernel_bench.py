"""Kernel microbenchmarks: banked conv + WS-GEMM variants (functional CPU
timings + analytic VMEM working sets from banking.py), plus the
sequential-vs-pipelined conv head-to-head over the DMA-bound shapes from
the zoo, with the §5.2 model's price of each variant and the plan's own
kernel choice alongside the measurement.  Interpret-mode caveat for the
head-to-head: the manual DMAs execute eagerly in Python on CPU, so
measured_us there reflects emulation overhead, not overlap; on a TPU
host the same rows time native Mosaic."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.bench_util import emit, time_fn
from repro.core import perfmodel
from repro.core.banking import plan_banks, plan_tiles
from repro.kernels import ref
from repro.kernels.conv2d_ws import conv2d_ws
from repro.kernels.conv2d_ws_pipe import conv2d_ws_pipe
from repro.kernels.matmul_ws import matmul_ws


def run():
    rng = np.random.default_rng(1)

    # --- conv banking variants (paper M1/M2 sweep) -----------------------
    x = jnp.asarray(rng.normal(size=(1, 64, 64, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 3, 16, 16)), jnp.float32)
    for cb, kb in [(1, 1), (4, 4), (8, 8)]:
        plan = plan_banks(64, 64, 16, 16, in_bytes=4,
                          cin_banks=cb, kout_banks=kb)
        us = time_fn(lambda cb=cb, kb=kb: conv2d_ws(
            x, w, cin_banks=cb, kout_banks=kb, interpret=True), iters=3)
        emit(f"conv2d_ws/banks_{cb}x{kb}", us,
             f"vmem_ws_bytes={plan.working_set_bytes}")

    # --- int8 vs f32 datapath --------------------------------------------
    xi = jnp.asarray(rng.integers(-128, 128, (1, 64, 64, 16)), jnp.int8)
    wi = jnp.asarray(rng.integers(-128, 128, (3, 3, 16, 16)), jnp.int8)
    us = time_fn(lambda: conv2d_ws(xi, wi, interpret=True), iters=3)
    emit("conv2d_ws/int8", us, "accum=int32")

    # --- WS-GEMM block sweep ----------------------------------------------
    a = jnp.asarray(rng.normal(size=(512, 1024)), jnp.float32)
    bmat = jnp.asarray(rng.normal(size=(1024, 512)), jnp.float32)
    for bm, bk, bn in [(128, 256, 128), (256, 512, 256)]:
        us = time_fn(lambda bm=bm, bk=bk, bn=bn: matmul_ws(
            a, bmat, bm=bm, bk=bk, bn=bn, interpret=True), iters=3)
        flops = 2 * 512 * 1024 * 512
        emit(f"matmul_ws/b{bm}x{bk}x{bn}", us, f"flops={flops}")

    # --- oracle baseline ---------------------------------------------------
    us = time_fn(lambda: ref.matmul_ref(a, bmat), iters=3)
    emit("matmul_ref/xla_cpu", us, "")

    # --- sequential vs pipelined head-to-head (DMA-bound zoo shapes) ------
    # depthwise 3×3 (the dma_bound_board family), 1×1 pointwise, and a
    # large-map tiled layer: one row per (shape, kernel variant) with the
    # plan's kernel choice alongside the measurement
    cases = [
        ("depthwise3x3", dict(h=16, w=16, c=32, k=32, kh=3, kw=3,
                              groups=32, pad="SAME", h_tile=0, w_tile=0)),
        ("pointwise1x1", dict(h=16, w=16, c=32, k=64, kh=1, kw=1,
                              groups=1, pad="VALID", h_tile=0, w_tile=0)),
        ("largemap_tiled", dict(h=64, w=64, c=16, k=16, kh=3, kw=3,
                                groups=1, pad="SAME", h_tile=16,
                                w_tile=16)),
    ]
    for name, c_ in cases:
        cb, kb = ref.grouped_banks(c_["c"], c_["k"], c_["groups"])
        xi8 = jnp.asarray(
            rng.integers(-128, 128,
                         (1, c_["h"], c_["w"], c_["c"])), jnp.int8)
        wi8 = jnp.asarray(
            rng.integers(-128, 128,
                         (c_["kh"], c_["kw"], c_["c"] // c_["groups"],
                          c_["k"])), jnp.int8)
        plan = plan_tiles(c_["h"], c_["w"], c_["c"], c_["k"], c_["kh"],
                          c_["kw"], padding=c_["pad"], groups=c_["groups"],
                          in_bytes=1, out_bytes=1, cin_banks=cb,
                          kout_banks=kb)
        psums = perfmodel.psum_count(c_["h"], c_["w"], c_["c"], c_["k"],
                                     c_["kh"], c_["kw"], padding=c_["pad"],
                                     groups=c_["groups"])
        est = perfmodel.pipeline_estimate(plan, psums)
        model = (f"model_seq_cycles={est['sequential_cycles']};"
                 f"model_pipe_cycles={est['pipelined_cycles']};"
                 f"model_speedup={est['speedup']:.3f};"
                 f"plan_pipelined={int(plan.pipelined)}")
        for variant, fn in (("seq", conv2d_ws), ("pipe", conv2d_ws_pipe)):
            us = time_fn(lambda fn=fn: fn(
                xi8, wi8, padding=c_["pad"], groups=c_["groups"],
                cin_banks=cb, kout_banks=kb, h_tile=c_["h_tile"],
                w_tile=c_["w_tile"], interpret=True), iters=2)
            emit(f"conv_pipe/{name}/{variant}", us, model)
