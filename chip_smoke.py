"""Smoke run of the int8 CNN serving path on a TPU.

Drives the user path once at full width: ``network.vgg_imagenet()`` at its
defaults (224×224×4 input, 32–256 channels, 1000 classes) with seeded
random weights → ``quantize_network`` on seeded images →
``make_int8_program`` behind ``ContinuousBatchingEngine`` (batch 8,
``backend="pallas"``) → about 16 served requests.  The served logits must
equal, exactly, those of the same ``QuantizedNetwork`` compiled with the
ref backend on the same chip: every layer accumulates in int32, so any
difference is a bug to explain, not noise.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # MultiCoreScheduler batch mode over
                                      # four real devices vs one chip

One process holds the chip(s) for the whole run and starts no others.  It
fails, printing no result, unless JAX's first device is a TPU.  Timings
printed on the way are smoke numbers (one short run, host wall clock),
not benchmark numbers.  The last line of standard output is the JSON
object ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

BATCH = 8


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"[chip_smoke] FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def build(seed: int):
    """vgg_imagenet with seeded weights, quantized on seeded images; the
    requests to serve."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import network
    plan = network.vgg_imagenet()
    rng = np.random.default_rng(seed)
    params = plan.init_params(rng)
    calib = jnp.asarray(rng.normal(size=(BATCH, *plan.input_shape)),
                        jnp.float32)
    t0 = time.perf_counter()
    qnet = network.quantize_network(plan, params, calib)
    log(f"quantized {plan.name} {plan.input_shape} → "
        f"{plan.activation_shapes()[-1][-1]} classes in "
        f"{time.perf_counter() - t0:.2f} s")
    images = rng.normal(size=(2 * BATCH, *plan.input_shape)).astype(
        np.float32)
    return qnet, images


def serve(qnet, images, n_cores: int):
    """Serve ``images`` through a fresh engine; returns the logits, the
    warm-up wall (trace + compile + one batch) and the per-batch wall of
    the served requests."""
    import numpy as np

    from repro.serving.batching import ContinuousBatchingEngine
    with ContinuousBatchingEngine(batch=BATCH, n_cores=n_cores,
                                  backend="pallas") as engine:
        engine.add_model(qnet)
        t0 = time.perf_counter()
        engine.submit(images[:BATCH])
        warm = time.perf_counter() - t0
        t0 = time.perf_counter()
        futures = engine.submit_async(images)
        logits = np.stack([f.result() for f in futures])
        per_batch = (time.perf_counter() - t0) / (len(images) // BATCH)
        stats = engine.stats
    if stats["requests"] != BATCH + len(images):
        fail(f"engine counted {stats['requests']} requests")
    return logits, warm, per_batch


def check_finite(name: str, logits, classes: int) -> None:
    import numpy as np
    if logits.shape != (2 * BATCH, classes):
        fail(f"{name} logits have shape {logits.shape}")
    if not np.all(np.isfinite(logits)):
        fail(f"{name} logits are not all finite")


def one_chip(qnet, images) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core.convcore import ConvCoreConfig
    from repro.core.network import make_int8_program, program_tile_plans
    plans = program_tile_plans(qnet.plan, ConvCoreConfig(int8=True))
    names = qnet.plan.node_names()
    for name, tp in zip(names, plans):
        if tp is not None:
            log(f"layer {name}: {'tiled' if tp.tiled else 'whole map'} "
                f"(h_tile {tp.h_tile} × {tp.n_h_tiles}), banks "
                f"{tp.cin_banks}×{tp.kout_banks}, "
                f"{'conv2d_ws_pipe' if tp.pipelined else 'conv2d_ws'}, "
                f"VMEM working set {tp.working_set_bytes / 2**20:.2f} MiB")
    classes = qnet.plan.activation_shapes()[-1][-1]
    logits, warm, per_batch = serve(qnet, images, n_cores=1)
    log(f"smoke: warm-up batch (trace + compile + run) {warm:.2f} s; "
        f"{per_batch * 1e3:.2f} ms per batch of {BATCH} after warm-up "
        f"(host wall clock, not a benchmark number)")
    check_finite("served", logits, classes)

    ref = make_int8_program(qnet, ConvCoreConfig(int8=True, backend="ref"))
    want = np.concatenate([np.asarray(ref(jnp.asarray(images[i:i + BATCH])))
                           for i in range(0, len(images), BATCH)])
    check_finite("ref", want, classes)
    delta = float(np.max(np.abs(logits - want)))
    log(f"served vs ref backend: max |Δ| = {delta} over {logits.size} "
        f"logits")
    if delta != 0.0:
        fail(f"pallas and ref int8 programs disagree (max |Δ| = {delta})")


def four_chips(qnet, images) -> None:
    import numpy as np

    from repro import obs
    one, _, _ = serve(qnet, images, n_cores=1)
    before = obs.metrics.counter("sched.batch.device").value
    four, warm, per_batch = serve(qnet, images, n_cores=4)
    device_runs = obs.metrics.counter("sched.batch.device").value - before
    virtual_runs = obs.metrics.counter("sched.batch.virtual").value
    log(f"smoke: 4 devices, warm-up batch {warm:.2f} s; "
        f"{per_batch * 1e3:.2f} ms per batch of {BATCH} after warm-up "
        f"(host wall clock, not a benchmark number)")
    log(f"batch-mode runs on real devices: {device_runs}, on vmapped "
        f"virtual cores: {virtual_runs}")
    if virtual_runs or device_runs == 0:
        fail("n_cores=4 did not run on the four devices")
    check_finite("4-device", four, one.shape[1])
    delta = float(np.max(np.abs(four - one)))
    log(f"4 devices vs 1 chip: max |Δ| = {delta}")
    if delta != 0.0:
        fail(f"4-device logits differ from one chip (max |Δ| = {delta})")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX's first device is {devices[0].platform!r}")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} but JAX sees {len(devices)} devices")
    cache = enable_compile_cache()
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"compile cache: {cache} ({entries} entries at start: "
        f"{'warm' if entries else 'cold'})")
    log(f"devices: JAX sees {len(devices)} × {devices[0].device_kind}; "
        f"this run uses {args.chips}")

    qnet, images = build(args.seed)
    if args.chips == 4:
        four_chips(qnet, images)
    else:
        one_chip(qnet, images)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": args.chips}}), flush=True)


if __name__ == "__main__":
    main()
