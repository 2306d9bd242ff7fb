"""One run of one cell: set-up, the measured window, the check.

Set-up makes the weights and images from the seed, quantizes, builds the
engine, compiles (or loads from the persistent cache) the one program
shape the cell's traffic uses, and warms the engine up with a second of
the same traffic.  The window then offers the cell's traffic for
``seconds``.  After it the device's peak memory is read, the engine and
its program are freed, and the answers the window kept (each pool
image's first and a seeded share of the rest) are compared with the
configuration's float32 reference on the same weights, regenerated from
the seed.
"""

from __future__ import annotations

import os
import resource
import shutil
import sys
import time
import types
from typing import Callable, Dict

import numpy as np

from perfbench.harness import check, counts, device, manifest, model
from perfbench.harness import trace as trace_mod
from perfbench.harness import traffic

TRACE_DIR = ".bench_trace"           # the traced run's profile
WARMUP_S = 1.0


def enable_cache() -> str:
    """The program's persistent compilation cache (where
    JAX_COMPILATION_CACHE_DIR says, else ``<checkout>/.jax_cache``),
    keeping every program however fast it compiled."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return enable_compile_cache()


def _log(msg: str) -> None:
    print(f"[perfbench] {msg}", flush=True)


def _e2e(name: str, rec: traffic.Record, mix: dict, seconds: float,
         setup_s: float) -> float:
    if name == "setup_s":
        return setup_s
    if name == "images_per_s":
        return rec.completed_in_window() / seconds
    if name in ("latency_p50_ms", "latency_p95_ms"):
        p = 50.0 if name.endswith("p50_ms") else 95.0
        return traffic.percentile(
            traffic.latencies_ms(rec, float(mix["grace_s"])), p)
    raise KeyError(f"no end-to-end metric {name!r} in the harness")


def setup(cell: manifest.Cell, seed: int, *, t_start: float,
          root: str = manifest.ROOT,
          require: Callable = device.require,
          quantize: Callable = model.quantize) -> types.SimpleNamespace:
    """Everything before the window: the engine serving the cell's model,
    compiled and warmed up, and what the check needs afterwards."""
    import jax
    import jax.numpy as jnp

    from repro.serving.batching import ContinuousBatchingEngine

    marks: Dict[str, float] = {}

    def mark(what: str) -> None:
        marks[what] = time.perf_counter() - t_start

    devices = require(cell.chips)
    enable_cache()
    mark("JAX and devices")
    cfg, mix = cell.config, cell.traffic
    peak = device.peaks(devices[0].device_kind)
    ref_mod = manifest.reference(cell.config_file)
    plan = model.build_plan(cfg)
    weights = model.make_weights(ref_mod, cfg, seed)
    params = model.program_params(plan, weights)
    calib = model.make_images(cfg, seed, model.CALIBRATION,
                              cfg["calibration_images"])
    pool = model.make_images(cfg, seed, model.POOL, mix["pool"])
    mark("weights and images")
    qnet = quantize(plan, params, jnp.asarray(calib))
    jax.block_until_ready(qnet.weights)
    del params, weights
    mark("quantize")

    layer_counts = counts.layer_counts(cfg)
    engine_args = dict(mix["engine"])
    batch = engine_args["batch"]
    per_device = batch // engine_args.get("n_cores", 1)
    for line in model.plan_lines(plan, layer_counts, per_device, peak):
        _log(line)

    engine = ContinuousBatchingEngine(backend="pallas", **engine_args)
    engine.add_model(qnet)
    engine.submit(pool[:batch])
    mark("compile and first batch")
    traffic.run(engine, pool, mix, seed + 1, WARMUP_S, trace=False)
    for name in ("queue_wait_us", "batch_fill"):
        engine.metrics.get(name).reset()
    mark("warm-up traffic")
    _log("set-up: " + ", ".join(f"{k} at {v:.2f} s"
                                for k, v in marks.items()))
    return types.SimpleNamespace(
        cell=cell, cfg=cfg, mix=mix, devices=devices, peak=peak,
        ref_mod=ref_mod, calib=calib, pool=pool, engine=engine,
        counts=layer_counts, per_device=per_device,
        setup_s=time.perf_counter() - t_start,
        trace_dir=os.path.join(root, TRACE_DIR, cell.name))


def measure(st, seed: int, seconds: float, trace: bool) -> traffic.Record:
    """The window: the cell's traffic for ``seconds``, every answer
    collected; then the peak memory is read and the engine is closed."""
    import jax
    if trace:
        shutil.rmtree(st.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1    # annotations, not runtime internals
        jax.profiler.start_trace(st.trace_dir, profiler_options=options)
    try:
        rec = traffic.run(st.engine, st.pool, st.mix, seed, seconds,
                          trace=trace)
    finally:
        if trace:
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            _log(f"trace written in {time.perf_counter() - t_stop:.2f} s")
    st.memory_peak = device.memory_peak(st.devices)
    st.engine_hists = {n: st.engine.metrics.get(n)
                       for n in ("queue_wait_us", "batch_fill")}
    st.engine.close()
    st.engine = None
    _log(f"window: {rec.attempted} requests, {rec.failed} failed, "
         f"{rec.completed_in_window()} answered in the window, "
         f"{len(rec.answers)} answers kept for the check; host peak RSS "
         f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20:.2f}"
         " GiB")
    if st.mix["loop"] == "open":
        grace = float(st.mix["grace_s"])
        _log(f"open loop: {traffic.open_summary(rec, grace)}")
    return rec


def references(st, rec: traffic.Record, seed: int) -> Dict[int, np.ndarray]:
    """The float32 reference's answer for each pool image the run kept an
    answer for, on weights regenerated from the seed."""
    used = np.unique(rec.pool_idx[sorted(rec.answers)])
    return dict(zip(used.tolist(), check.reference_answers(
        st.ref_mod, model.make_weights(st.ref_mod, st.cfg, seed),
        st.pool[used])))


def compare(st, rec: traffic.Record, seed: int) -> Dict[str, float]:
    """``check.errors`` of the kept answers against the float32 reference."""
    t_ref = time.perf_counter()
    err = check.errors(rec.answers, rec.pool_idx, references(st, rec, seed))
    _log(f"reference check of {len(rec.answers)} answers took "
         f"{time.perf_counter() - t_ref:.2f} s")
    return err


def run(cell: manifest.Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, root: str = manifest.ROOT,
        require: Callable = device.require) -> dict:
    """The result object of one run (its last line of standard output)."""
    st = setup(cell, seed, t_start=t_start, root=root, require=require)
    rec = measure(st, seed, seconds, trace)
    limits = st.cfg["limits"]
    checks = {name: {"value": value, "limit": limits[name]}
              for name, value in compare(st, rec, seed).items()
              if name in limits}
    checks["unanswered"] = {"value": rec.failed, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    result = {"correct": bool(correct), "attempted": rec.attempted,
              "failed": rec.failed, "metrics": {}, "device": {
                  **device.describe(st.devices),
                  "memory_peak_bytes": st.memory_peak}}
    if trace:
        t_read = time.perf_counter()
        red = trace_mod.reduce(trace_mod.load(st.trace_dir))
        _log(f"trace read and reduced in {time.perf_counter() - t_read:.2f} s")
        result["device"]["busy_s"] = red.busy_s
        result["device"]["window_s"] = red.window_s
        ctx = types.SimpleNamespace(
            trace=red, record=rec, mix=st.mix,
            counts=st.counts, batch_per_device=st.per_device,
            peak=st.peak, chips=cell.chips, engine=st.engine_hists,
            ops_per_image=counts.useful_ops_per_image(st.cfg))
        for m in cell.per_layer:
            value = manifest.reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": float(value),
                                                "unit": m["unit"]}
        result["breakdown"] = {"device_ops": red.device_ops,
                               "idle_gaps": red.idle_gaps}
    else:
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {
                "value": float(_e2e(m["name"], rec, st.mix, seconds,
                                    st.setup_s)),
                "unit": m["unit"]}
    result["check"] = checks
    return result


def report(result: dict, out=sys.stdout, err=sys.stderr) -> None:
    import json
    for line in check.lines(result["check"]):
        print(line, file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)
