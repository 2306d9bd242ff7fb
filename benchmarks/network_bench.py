"""Whole-network benchmark: LeNet / VGG-small / ResNet-small / MobileNet /
segmentation (unet_small, dilated_context) / large-map int8 NetworkPlans
through the Pallas backend (interpret on CPU — functional timing
reference), with the §5.2 cycle model's whole-network prediction alongside
the measurement.

The segmentation rows exercise the dense-prediction contract (PR 8):
``unet_small`` compiles transposed-conv upsampling through the shared
``conv2d_ws_trans`` eq-conv lowering (its model rows price psums with the
zero-skipping MAC count, not the naive upsampled sweep) and
``dilated_context`` runs dilated (atrous) kernels with their widened
halos; both also land in ``measured_vs_predicted`` when a calibration
table is loaded.

The resnet row exercises the residual-graph (DAG) compiler: skip
connections with shared-grid int8 merge adds and 1×1 projection
shortcuts.  The mobilenet rows exercise the grouped-conv contract
(depthwise-separable and inverted-residual blocks); their model rows
carry the grouped perfmodel pricing — ``grouped_layers`` and
``dma_bound_board_layers`` record how many layers the shared DMA
interface binds on the full board (depthwise layers compute a factor-C
fewer psums over the same maps, so DMA, not compute, is their floor).

The large-map network's first layer exceeds the whole-map VMEM budget —
it only runs because the spatially-tiled conv pipeline streams it through
halo'd H/W blocks; its model row also carries the tile-revisit / halo
DMA pricing (perfmodel.tile_traffic).

Emits ``BENCH_network.json`` so the perf trajectory of the network executor
is tracked across PRs: per-network images/s, layers/s, measured µs/batch,
the model-predicted FPGA times (1 IP core and the 20-core full board),
and per-plan tiling stats.  A ``provenance`` block (jax version, device
kind, git sha) pins each run to its toolchain, each network row carries
``pipelined_layers`` (how many convs the planner routed to the explicit
DMA pipeline, kernels/conv2d_ws_pipe), and a ``pipeline`` section prices
every network both ways (kernel="sequential" vs "auto") with per-layer
crossover rows — the model columns there are the cross-PR throughput
signal; interpret-mode measurements of the pipelined kernel time Python
DMA emulation, not overlap.

``--smoke`` (or run(smoke=True)) times LeNet, the resnet residual graph,
the mobilenet grouped-conv compiler, and the two segmentation nets with
minimal iterations — the CI fast path.  The large-map row is
measured with iters=1/warmup=0 (interpret mode is slow), so treat its
measured_us as indicative — the modelled FPGA times are the stable
cross-PR signal.

Calibration & autotuning rows: with a fitted CalibrationTable present
(``CALIBRATION.json`` or the ``CALIBRATION_JSON`` env var —
benchmarks/calibrate.py writes it), each network row's ``autotune`` block
prices the full (TilePlan × kernel × scheduler mode × core count) search
against the calibrated model (``cycles_autotuned ≤ cycles_greedy`` is
asserted — the greedy plan is in the search space), every row carries
``plan_source``, and a ``measured_vs_predicted`` section reports the
calibrated model's per-layer wall-time error (mean |error| % + worst
layer per network) — the model-accuracy regression signal.  Without a
table the autotune block prices on the analytic model and the
measured_vs_predicted section is omitted (no shared scale to predict on).

Train-step rows: one jitted ``training.make_train_step`` step (forward
through the WS kernels + backward through the transposed-conv /
weight-grad kernels + AdamW), measured per batch and priced by
``perfmodel.train_report`` (≈3× forward psums + dW traffic).  The full
run ALWAYS writes them into the ``train`` section of
``BENCH_network.json`` (so a flagless run can never silently drop the
tracked training trajectory); ``--train`` opts the fast ``--smoke`` path
into one train-step row as well.

Serving rows (``--serving``, benchmarks/serving_load.py): sustained
requests/s under open-loop load through the continuous-batching engine —
sync-baseline vs saturating throughput (the ≥1.5× acceptance gate, with
mean batch fill and zero-drop/zero-dup accounting asserted), an
offered-load sweep at λ ∈ {0.5, 1, 2}× capacity with p50/p90/p99
*including queue wait* and the deadline-launch fraction, and the
multi-model LRU cache segment.  Lands as the schema-additive ``serving``
section (smoke: lenet + multi-model; full: the zoo minus large_map).
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
from repro import obs
from repro.core import autotune, network, training
from repro.core.calibration import load_table, sample_from_plan
from repro.core.convcore import ConvCoreConfig
from repro.kernels.conv2d_ws import conv2d_ws
from repro.kernels.conv2d_ws_pipe import conv2d_ws_pipe
from repro.kernels.conv2d_ws_trans import conv2d_ws_transpose

BATCH = 4
OUT_PATH = os.environ.get("BENCH_NETWORK_JSON", "BENCH_network.json")
# fitted CalibrationTable (benchmarks/calibrate.py output); None → the
# analytic model, autotune rows priced uncalibrated, no
# measured_vs_predicted section (there is no measured scale to predict on)
CALIB = load_table(os.environ.get("CALIBRATION_JSON", "CALIBRATION.json"))


def _provenance() -> dict:
    """Pin the run to its toolchain so rows are comparable across PRs
    (the existing top-level keys stay untouched; this is additive)."""
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
            "git_sha": sha or "unknown"}


def _bench_plan(plan: network.NetworkPlan, rng, batch: int = BATCH,
                iters: int = 3, warmup: int = 1) -> dict:
    params = plan.init_params(rng)
    x = jnp.asarray(
        rng.normal(size=(batch, *plan.input_shape)), jnp.float32)
    qnet = network.quantize_network(plan, params, x)
    cfg = ConvCoreConfig(backend="pallas", int8=True)
    # the very plans the compiled program executes — reported stats can't
    # drift from the measured run
    tile_plans = network.program_tile_plans(plan, cfg)
    program = network.make_int8_program(qnet, cfg, tile_plans=tile_plans)
    with obs.span("bench.network", network=plan.name, batch=batch,
                  iters=iters):
        us = time_fn(lambda: program(x), iters=iters, warmup=warmup)
    if obs.enabled():
        us.to_histogram(f"bench.network_us.{plan.name}")

    n_layers = len(plan.layers)
    rep = plan.perf_report(tile_plans=tile_plans)
    fb = rep["full_board"]
    tiled_layers = sum(1 for tp in tile_plans if tp is not None and tp.tiled)
    halo_max = max((tp.halo_read_factor for tp in tile_plans
                    if tp is not None), default=1.0)
    # grouped-conv rows: how many layers are grouped/depthwise, and how
    # many priced layers the shared DMA interface binds on the full board
    # (the depthwise arithmetic-intensity signal the model must show)
    grouped_layers = plan.grouped_layer_count()
    dma_bound = rep["dma_bound_board_layers"]
    # kernel-variant split: how many conv layers the planner routed to
    # the explicit DMA pipeline (conv2d_ws_pipe) in the measured program
    pipelined_layers = rep["pipelined_layers"]
    images_s = batch / (us * 1e-6)
    layers_s = batch * n_layers / (us * 1e-6)
    # autotuner verdict under the loaded (or analytic) model: the tuned
    # plan may only ever match or beat greedy — assert the acceptance
    # contract right where the tracked numbers are produced
    tune = autotune.autotune_network(plan, calib=CALIB)
    assert tune.cycles <= tune.greedy_cycles, (
        f"{plan.name}: autotuned {tune.cycles} > greedy "
        f"{tune.greedy_cycles} cycles — the greedy plan is in the search "
        "space, this must be impossible")
    emit(f"network/{plan.name}", us,
         f"images_s={images_s:.1f};layers_s={layers_s:.1f};"
         f"model_ms={rep['seconds']*1e3:.3f};"
         f"model_ms_20core={fb['seconds']*1e3:.3f};"
         f"tiled_layers={tiled_layers};halo_factor={halo_max:.3f};"
         f"grouped_layers={grouped_layers};dma_bound_board={dma_bound};"
         f"pipelined_layers={pipelined_layers};"
         f"tune_speedup={tune.speedup:.4f};"
         f"tune_differ={tune.layers_differ};"
         f"tune_sched={tune.scheduler_mode}x{tune.n_cores}")
    return {
        "name": plan.name,
        "batch": batch,
        "layers": n_layers,
        # the measured program above ran the greedy program_tile_plans
        # (the serving default); the autotune block reports what the
        # tuner would run and how much the calibrated model says it saves
        "plan_source": "greedy",
        "autotune": {
            "calibrated": tune.calibrated,
            "cycles_autotuned": tune.cycles,
            "cycles_greedy": tune.greedy_cycles,
            "model_speedup": tune.speedup,
            "layers_differ": tune.layers_differ,
            "scheduler_mode": tune.scheduler_mode,
            "n_cores": tune.n_cores,
            "schedule_cycles": tune.schedule_cycles_,
            "layers": tune.layer_rows(),
        },
        "measured_us_per_batch": us,
        "images_per_s": images_s,
        "layers_per_s": layers_s,
        "model_psums": rep["psums"],
        "model_seconds_1core": rep["seconds"],
        "model_gops_1core": rep["gops_paper"],
        "model_seconds_20core": fb["seconds"],
        "model_gops_20core": fb["gops_paper"],
        "tiled_layers": tiled_layers,
        "max_halo_read_factor": halo_max,
        "grouped_layers": grouped_layers,
        "dma_bound_board_layers": dma_bound,
        "pipelined_layers": pipelined_layers,
        # exact percentiles over the raw timing samples (additive; the
        # top-level latency_percentiles section aggregates these per net)
        "latency_percentiles": us.percentiles(),
    }


def _bench_pipeline(plan: network.NetworkPlan, rng, batch: int = 2,
                    iters: int = 1, measure: bool = True) -> dict:
    """Sequential-vs-pipelined head-to-head for one network: the same
    quantized program compiled with kernel="sequential" (every conv on
    conv2d_ws) and kernel="auto" (the planner routes DMA-bound layers to
    conv2d_ws_pipe), with the §5.2 model pricing both ways and per-layer
    crossover rows for the layers the planner pipelined.  The model
    columns are the cross-PR throughput signal; interpret-mode
    measurements time Python DMA emulation, so on CPU they bound
    correctness cost, not overlap (the docstring caveat above)."""
    params = plan.init_params(rng)
    x = jnp.asarray(
        rng.normal(size=(batch, *plan.input_shape)), jnp.float32)
    qnet = network.quantize_network(plan, params, x)
    reports, measured = {}, {}
    for kernel in ("sequential", "auto"):
        cfg = ConvCoreConfig(backend="pallas", int8=True, kernel=kernel)
        tps = network.program_tile_plans(plan, cfg)
        reports[kernel] = plan.perf_report(tile_plans=tps)
        if measure:
            program = network.make_int8_program(qnet, cfg, tile_plans=tps)
            measured[kernel] = time_fn(lambda p=program: p(x),
                                       iters=iters, warmup=1)
    seq, auto = reports["sequential"], reports["auto"]
    speedup = seq["seconds"] / auto["seconds"] if auto["seconds"] else 1.0
    layer_rows = [
        {"name": r["name"], "pipelined": r["pipelined"],
         "cycles_sequential": r["cycles_sequential"],
         "cycles_pipelined": r["cycles_pipelined"],
         "speedup": r["pipeline_speedup"],
         "dma_bound_board": r["dma_bound_board"]}
        for r in auto["layers"] if r.get("pipelined") is not None]
    emit(f"pipeline/{plan.name}", measured.get("auto", 0.0),
         f"pipelined_layers={auto['pipelined_layers']};"
         f"model_speedup={speedup:.3f};"
         f"model_ms_seq={seq['seconds']*1e3:.3f};"
         f"model_ms_auto={auto['seconds']*1e3:.3f}")
    row = {
        "name": plan.name,
        "pipelined_layers": auto["pipelined_layers"],
        "model_seconds_sequential": seq["seconds"],
        "model_seconds_auto": auto["seconds"],
        "model_speedup": speedup,
        "model_gops_sequential": seq["gops_paper"],
        "model_gops_auto": auto["gops_paper"],
        "layers": layer_rows,
    }
    if measure:
        row["measured_us_sequential"] = measured["sequential"]
        row["measured_us_auto"] = measured["auto"]
    return row


def _measured_vs_predicted(plan: network.NetworkPlan, rng,
                           iters: int = 2) -> dict:
    """Per-layer model-accuracy row for one network: time every conv /
    conv_transpose layer's actual kernel call (the variant + plan
    geometry the compiled program runs — transposed layers go through the
    shared ``conv2d_ws_trans`` lowering, so what's timed is the eq
    stride-1 conv their TilePlan was planned on) and compare against the
    calibrated model's predicted wall time — mean |error| % across layers
    plus the worst layer, the regression-tested number that says how much
    to trust the planner's cost model.  Requires a loaded
    CalibrationTable: predictions and measurements only share a scale
    through the fitted ``clock_hz``."""
    assert CALIB is not None
    interpret = jax.default_backend() != "tpu"
    cfg = ConvCoreConfig(backend="pallas", int8=True, calib=CALIB)
    tile_plans = network.program_tile_plans(plan, cfg)
    names = plan.node_names()
    ins = plan.resolved_inputs()
    acts = plan.activation_shapes()
    psum_rows = dict(plan.psum_table())
    rows = []
    for i, sp in enumerate(plan.layers):
        tp = tile_plans[i]
        if sp.kind not in ("conv", "conv_transpose") or tp is None:
            continue
        h, w, c = plan.input_shape if ins[i][0] < 0 else acts[ins[i][0]]
        k, g_ = network.conv_geometry(sp, c)
        kh, kw_ = sp.kernel
        x = jnp.asarray(rng.integers(-128, 128, (1, h, w, c)), jnp.int8)
        wt = jnp.asarray(
            rng.integers(-128, 128, (kh, kw_, c // g_, k)), jnp.int8)
        scale = jnp.float32(0.03125)
        if sp.kind == "conv_transpose":
            # the lowering re-legalizes banks and dispatches the eq conv
            # (sequential or pipelined) off the plan verdict itself
            def call(fn=conv2d_ws_transpose, tp=tp, sp=sp, x=x, wt=wt,
                     g_=g_):
                return fn(
                    x, wt, None, scale, stride=sp.stride,
                    padding=sp.padding, groups=g_, cin_banks=tp.cin_banks,
                    kout_banks=tp.kout_banks,
                    h_tile=tp.h_tile if tp.tiled else 0,
                    w_tile=tp.w_tile if tp.tiled else 0,
                    relu=sp.relu, pool=sp.pool, dilation=sp.dilation,
                    pipelined=tp.pipelined, interpret=interpret)
        else:
            def call(fn=conv2d_ws_pipe if tp.pipelined else conv2d_ws,
                     tp=tp, sp=sp, x=x, wt=wt, g_=g_):
                return fn(
                    x, wt, None, scale, stride=sp.stride,
                    padding=sp.padding, groups=g_, cin_banks=tp.cin_banks,
                    kout_banks=tp.kout_banks,
                    h_tile=tp.h_tile if tp.tiled else 0,
                    w_tile=tp.w_tile if tp.tiled else 0,
                    relu=sp.relu, pool=sp.pool, dilation=sp.dilation,
                    interpret=interpret)
        t = time_fn(call, iters=iters, warmup=1)
        s = sample_from_plan(names[i], tp, psum_rows[names[i]],
                             t.median_us, t.iqr_us)
        pred = CALIB.predicted_us(s.compute_cycles, s.dma_bytes,
                                  s.n_slabs, s.pipelined)
        err = abs(pred - t.median_us) / max(t.median_us, 1e-9) * 100.0
        rows.append({"name": names[i], "measured_us": t.median_us,
                     "predicted_us": pred, "abs_error_pct": err,
                     "pipelined": tp.pipelined})
    if not rows:
        return {"name": plan.name, "layers": []}
    worst = max(rows, key=lambda r: r["abs_error_pct"])
    mean_err = sum(r["abs_error_pct"] for r in rows) / len(rows)
    emit(f"mvp/{plan.name}", 0.0,
         f"mean_abs_error_pct={mean_err:.1f};"
         f"worst_layer={worst['name']};"
         f"worst_abs_error_pct={worst['abs_error_pct']:.1f}")
    return {"name": plan.name,
            "mean_abs_error_pct": mean_err,
            "worst_layer": worst["name"],
            "worst_abs_error_pct": worst["abs_error_pct"],
            "layers": rows}


def _bench_train(plan: network.NetworkPlan, rng, batch: int = BATCH,
                 iters: int = 3, warmup: int = 1, qat: bool = True) -> dict:
    """Time one jitted QAT train step (fwd WS kernels + bwd WS kernels +
    AdamW) and put the §5.2 train-step model alongside it."""
    x, y = training.synthetic_digits(
        rng, max(batch * 2, 16), input_shape=plan.input_shape,
        classes=plan.activation_shapes()[-1][-1])
    cfg = training.TrainConfig(qat=qat)
    step = training.make_train_step(plan, cfg)
    state = training.init_train_state(plan, rng)

    def one_step():
        nonlocal state
        state, m = step(state, x[:batch], y[:batch])
        return m["loss"]

    us = time_fn(one_step, iters=iters, warmup=warmup)
    rep = plan.train_report()
    fb = rep["full_board"]
    steps_s = 1e6 / us
    emit(f"train/{plan.name}", us,
         f"steps_s={steps_s:.2f};qat={int(qat)};"
         f"model_ms={rep['seconds']*1e3:.3f};"
         f"model_ms_20core={fb['seconds']*1e3:.3f};"
         f"bwd_frac={rep['backward']['cycles']/max(rep['cycles'],1):.3f}")
    return {
        "name": plan.name,
        "batch": batch,
        "qat": qat,
        "measured_us_per_step": us,
        "steps_per_s": steps_s,
        "model_psums_step": rep["psums"],
        "model_seconds_1core": rep["seconds"],
        "model_gops_1core": rep["gops_paper"],
        "model_seconds_20core": fb["seconds"],
        "model_gops_20core": fb["gops_paper"],
        "backward_cycle_fraction":
            rep["backward"]["cycles"] / max(rep["cycles"], 1),
    }


def _latency_section(results) -> dict:
    """Top-level p50/p90/p99 per zoo network (schema-additive)."""
    return {r["name"]: r["latency_percentiles"] for r in results}


def _dump_obs():
    """With REPRO_OBS=1 (or obs.enable()), write the Chrome trace +
    metrics JSONL next to the bench output (REPRO_OBS_DIR overrides)."""
    if not obs.enabled():
        return
    paths = obs.dump(os.environ.get("REPRO_OBS_DIR", "."), prefix="bench")
    if paths:
        emit("obs/trace", 0.0, f"path={paths['trace']}")
        emit("obs/metrics", 0.0, f"path={paths['metrics']}")


def run(smoke: bool = False, train: bool = False, serving: bool = False):
    rng = np.random.default_rng(3)
    serving_rows = None
    if serving:
        from benchmarks.serving_load import serving_section
        serving_rows = serving_section(np.random.default_rng(11),
                                       smoke=smoke)
    if smoke:
        # CI fast path: LeNet + the residual-graph compiler (resnet) +
        # the grouped-conv compiler (mobilenet) with minimal iterations;
        # do NOT touch the tracked BENCH_network.json by default — that
        # file records the cross-PR trajectory of the full run.  With
        # BENCH_NETWORK_JSON pointed elsewhere (the CI calibration lane),
        # the smoke payload IS written there so the calibration +
        # measured_vs_predicted sections land in the uploaded artifact.
        results = [
            _bench_plan(network.lenet(), rng, batch=2, iters=1, warmup=1),
            _bench_plan(network.resnet_small(), rng, batch=2, iters=1,
                        warmup=1),
            _bench_plan(network.mobilenet_small(), rng, batch=2, iters=1,
                        warmup=1),
            # dense prediction: the transposed-conv (unet) and dilated
            # (atrous-context) compilers ride the CI fast path too
            _bench_plan(network.unet_small(), rng, batch=2, iters=1,
                        warmup=1),
            _bench_plan(network.dilated_context(), rng, batch=2, iters=1,
                        warmup=1)]
        # sequential-vs-pipelined compile path (model columns + one
        # measured pass each way)
        pipe_rows = [_bench_pipeline(network.mobilenet_small(), rng)]
        mvp = []
        if CALIB is not None:
            mvp = [_measured_vs_predicted(network.lenet(), rng, iters=1),
                   _measured_vs_predicted(network.mobilenet_small(), rng,
                                          iters=1),
                   # exercises the conv_transpose timing branch
                   _measured_vs_predicted(network.unet_small(), rng,
                                          iters=1)]
        if train:
            _bench_train(network.lenet(input_shape=(12, 12, 1)), rng,
                         batch=2, iters=1, warmup=1)
        if os.environ.get("BENCH_NETWORK_JSON"):
            payload = {"backend": jax.default_backend(),
                       "interpret": jax.default_backend() != "tpu",
                       "smoke": True,
                       "provenance": _provenance(),
                       "calibration": (CALIB.to_dict()
                                       if CALIB is not None else None),
                       "networks": results,
                       "latency_percentiles": _latency_section(results),
                       "pipeline": pipe_rows,
                       "measured_vs_predicted": mvp}
            if serving_rows is not None:
                payload["serving"] = serving_rows
            with open(OUT_PATH, "w") as f:
                json.dump(payload, f, indent=2)
            emit("network/json", 0.0, f"path={OUT_PATH}")
        _dump_obs()
        return
    results = [_bench_plan(network.lenet(), rng),
               _bench_plan(network.vgg_small(), rng),
               # residual graphs: skip adds + projection shortcuts
               _bench_plan(network.resnet_small(), rng),
               # grouped/depthwise convs: the MobileNet edge family, with
               # grouped perfmodel rows (DMA-bound depthwise layers)
               _bench_plan(network.mobilenet_small(), rng),
               _bench_plan(network.mobilenet_v2ish(), rng),
               # dense-prediction (segmentation) workloads: transposed-
               # conv upsampling with skip concats (unet) and dilated
               # context aggregation — the rows carry the zero-skipping
               # transpose psum pricing
               _bench_plan(network.unet_small(), rng),
               _bench_plan(network.dilated_context(), rng),
               # the tiled-pipeline workload: exceeds whole-map VMEM
               _bench_plan(network.large_map(), rng, batch=2,
                           iters=1, warmup=0)]
    payload = {"backend": jax.default_backend(),
               "interpret": jax.default_backend() != "tpu",
               "provenance": _provenance(),
               # the table the autotune rows were priced under — None
               # means the analytic model (run benchmarks/calibrate.py
               # first, or set CALIBRATION_JSON, for calibrated rows)
               "calibration": (CALIB.to_dict() if CALIB is not None
                               else None),
               "networks": results,
               "latency_percentiles": _latency_section(results)}
    # model-accuracy tracking: per-layer measured vs calibrated-predicted
    # wall time.  large_map is deliberately skipped — interpret-mode
    # timing of its tiled layers is minutes per row; its model columns in
    # the network section remain the tracked signal.
    if CALIB is not None:
        payload["measured_vs_predicted"] = [
            _measured_vs_predicted(network.lenet(), rng),
            _measured_vs_predicted(network.vgg_small(), rng),
            _measured_vs_predicted(network.resnet_small(), rng),
            _measured_vs_predicted(network.mobilenet_small(), rng),
            _measured_vs_predicted(network.mobilenet_v2ish(), rng),
            _measured_vs_predicted(network.unet_small(), rng),
            _measured_vs_predicted(network.dilated_context(), rng),
        ]
        payload["measured_vs_predicted_skipped"] = [
            {"name": "large_map",
             "reason": "interpret-mode per-layer timing is minutes per "
                       "row; model columns in 'networks' are the signal"}]
    # sequential-vs-pipelined head-to-head: measured on the DMA-bound
    # MobileNet family, model-only for the big tiled map (interpret-mode
    # timing of large_map is already minutes per run)
    payload["pipeline"] = [
        _bench_pipeline(network.mobilenet_small(), rng),
        _bench_pipeline(network.mobilenet_v2ish(), rng),
        _bench_pipeline(network.large_map(), rng, measure=False),
    ]
    # train-step rows: the QAT trainer through the backward WS kernels.
    # Always part of the full run — the tracked JSON must not lose its
    # training trajectory just because a flag was omitted.
    payload["train"] = [
        _bench_train(network.lenet(input_shape=(12, 12, 1)), rng),
        _bench_train(network.resnet_small(input_shape=(16, 16, 4)),
                     rng, batch=2, iters=2),
        # the grouped backward pass: depthwise transposed convs +
        # per-group weight-grad GEMMs through the QAT step
        _bench_train(network.mobilenet_small(input_shape=(12, 12, 1)),
                     rng, batch=2, iters=2),
    ]
    # serving trajectory: sustained requests/s through the continuous-
    # batching queue (only with --serving — the open-loop sweeps add
    # minutes of interpret-mode wall time to a flagless run)
    if serving_rows is not None:
        payload["serving"] = serving_rows
    with open(OUT_PATH, "w") as f:
        json.dump(payload, f, indent=2)
    emit("network/json", 0.0, f"path={OUT_PATH}")
    _dump_obs()


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    run(smoke="--smoke" in sys.argv, train="--train" in sys.argv,
        serving="--serving" in sys.argv)
