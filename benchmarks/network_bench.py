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
halos.

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
``pipelined_layers`` (how many convs run on the explicit DMA pipeline,
kernels/conv2d_ws_pipe: the layers whose plan sweeps more than one
slab).

``--smoke`` (or run(smoke=True)) times LeNet, the resnet residual graph,
the mobilenet grouped-conv compiler, and the two segmentation nets with
minimal iterations — the CI fast path.  The large-map row is
measured with iters=1/warmup=0 (interpret mode is slow), so treat its
measured_us as indicative — the modelled FPGA times are the stable
cross-PR signal.

Autotuning rows: each network row's ``autotune`` block prices the full
(TilePlan × scheduler mode × core count) search on the §5.2 model
(``cycles_autotuned ≤ cycles_greedy`` is asserted — the greedy plan is
in the search space), and every row carries ``plan_source``.

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
from repro.core.convcore import ConvCoreConfig

BATCH = 4
OUT_PATH = os.environ.get("BENCH_NETWORK_JSON", "BENCH_network.json")


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
    # kernel-variant split: how many conv layers ran on the explicit DMA
    # pipeline (conv2d_ws_pipe) in the measured program
    pipelined_layers = rep["pipelined_layers"]
    images_s = batch / (us * 1e-6)
    layers_s = batch * n_layers / (us * 1e-6)
    # autotuner verdict: the tuned plan may only ever match or beat
    # greedy — assert the acceptance contract right where the tracked
    # numbers are produced
    tune = autotune.autotune_network(plan)
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
        # tuner would run and how much the model says it saves
        "plan_source": "greedy",
        "autotune": {
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
        # BENCH_NETWORK_JSON pointed elsewhere, the smoke payload IS
        # written there.
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
        if train:
            _bench_train(network.lenet(input_shape=(12, 12, 1)), rng,
                         batch=2, iters=1, warmup=1)
        if os.environ.get("BENCH_NETWORK_JSON"):
            payload = {"backend": jax.default_backend(),
                       "interpret": jax.default_backend() != "tpu",
                       "smoke": True,
                       "provenance": _provenance(),
                       "networks": results,
                       "latency_percentiles": _latency_section(results)}
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
               "networks": results,
               "latency_percentiles": _latency_section(results)}
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
