"""Training subsystem: the float shadow of a ``NetworkPlan``, trained
through the paper-dataflow kernels, with optional quantization-aware
training that drops straight into the int8 deployment pipeline.

The paper's IP core is inference-only, but the int8 weights it consumes
have to come from somewhere: the standard route (Jiang et al. 2025; Guo
et al. 2017 — PAPERS.md) is to train a float "shadow" of the deployed
network, fake-quantizing weights and activations during training so the
float model learns values that survive the 8-bit datapath, then lower the
trained weights with the existing calibration pipeline.  This module is
that route, end-to-end through this repo's stack:

* ``float_forward`` — a differentiable forward of ANY NetworkPlan DAG
  (skip adds, concats, projection shortcuts included) that runs convs and
  GEMMs through the weight-stationary kernels (``ops.conv2d`` /
  ``ops.matmul_ws``), so the backward pass executes the transposed-conv /
  batched-correlation WS kernels of kernels/conv2d_ws_bwd.py — training
  exercises the same dataflow the deployment runs;
* QAT mode — straight-through fake quantization (quantize.fake_quantize)
  of weights (per-tensor or per-output-channel, matching what
  ``quantize_network`` will emit) and of every activation grid point the
  int8 program has (the network input, every non-final conv/dense output
  AFTER its fused epilogue, every merge node);
* ``make_train_step`` — one jitted AdamW step (optim/adamw.py) over the
  plan's parameter list; ``fit`` the minibatch loop on top;
* the round trip: a QAT-trained ``state.params`` feeds directly into
  ``quantize_network`` → ``make_int8_program`` — the acceptance contract
  is that the deployed int8 accuracy stays within a couple points of the
  float shadow.

core/perfmodel.train_report prices the backward pass of all of this on
the §5.2 cycle model (≈2× the forward psums + weight-gradient traffic).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import banking
from repro.core.network import PARAM_KINDS, NetworkPlan
from repro.core.quantize import fake_quant_act, fake_quant_weight
from repro.kernels import ops, ref
from repro.optim.adamw import AdamWConfig, adamw_update


@dataclass(frozen=True)
class TrainConfig:
    """Hyperparameters of one training run.

    ``qat=True`` turns on straight-through fake quantization of weights
    and activations (the deployment-grid shadow); ``per_channel`` must
    match the ``quantize_network(per_channel=...)`` call that will lower
    the trained weights, so training and deployment see the same weight
    grids."""
    adamw: AdamWConfig = field(default_factory=lambda: AdamWConfig(
        peak_lr=3e-3, warmup_steps=20, total_steps=400, weight_decay=1e-4,
        grad_clip_norm=1.0))
    qat: bool = False
    per_channel: bool = False


class TrainState(NamedTuple):
    params: List[Optional[dict]]       # plan.init_params layout
    opt_state: Dict[str, List]         # AdamW m/v mirroring params
    step: jax.Array                    # int32 scalar


def init_train_state(plan: NetworkPlan,
                     rng: np.random.Generator) -> TrainState:
    """He-initialized float parameters + zeroed AdamW moments (m/v mirror
    the parameter tree, ZeRO-style — here simply zeros_like)."""
    params = plan.init_params(rng)
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)  # noqa: E731
    return TrainState(params=params,
                      opt_state={"m": zeros(), "v": zeros()},
                      step=jnp.zeros((), jnp.int32))


def float_forward(plan: NetworkPlan, params: Sequence[Optional[dict]],
                  x: jax.Array, *, qat: bool = False,
                  per_channel: bool = False) -> jax.Array:
    """Differentiable forward of the plan's float shadow through the
    weight-stationary kernels.

    Node semantics mirror ``NetworkPlan.forward_activations`` (the float
    oracle) exactly; the difference is the execution substrate — convs and
    dense GEMMs run ``ops.conv2d`` / ``ops.matmul_ws``, whose custom VJPs
    execute the backward through the same WS dataflow.  In QAT mode every
    int8 grid point of the deployed program is shadowed with a
    straight-through fake-quantize: the input, each non-final parametric
    output (after its fused ReLU/pool epilogue — where the deployed
    requantize sits), and each merge node's shared grid.  The final
    parametric layer stays unquantized on its output, like the deployed
    program's dequantized logits."""
    ins = plan.resolved_inputs()
    geoms = plan.conv_geometries()
    last_param = max((i for i, sp in enumerate(plan.layers)
                      if sp.kind in PARAM_KINDS), default=-1)
    x0 = fake_quant_act(x) if qat else x
    acts: List[jax.Array] = []
    for i, sp in enumerate(plan.layers):
        p = params[i]
        src = [x0 if j < 0 else acts[j] for j in ins[i]]
        h = src[0]
        if sp.kind in ("conv", "conv_transpose"):
            k_, g_ = geoms[i]
            w = fake_quant_weight(p["w"], per_channel) if qat else p["w"]
            cb_n, kb_n = banking.grouped_banks(h.shape[-1], k_, g_)
            op = (ops.conv2d_transpose if sp.kind == "conv_transpose"
                  else ops.conv2d)
            h = op(
                h, w, p["b"], stride=sp.stride, padding=sp.padding,
                groups=g_, dilation=sp.dilation, cin_banks=cb_n,
                kout_banks=kb_n, relu=sp.relu, pool=sp.pool)
            if qat and i != last_param:
                h = fake_quant_act(h)
        elif sp.kind == "pool":
            h = ref.maxpool2d_ref(h, sp.size, sp.stride, sp.padding)
        elif sp.kind == "avgpool":
            h = ref.avgpool2d_ref(h, sp.size, sp.stride, sp.padding)
        elif sp.kind == "globalpool":
            h = ref.global_avgpool_ref(h)
        elif sp.kind == "flatten":
            h = h.reshape(h.shape[0], -1)
        elif sp.kind == "dense":
            w = fake_quant_weight(p["w"], per_channel) if qat else p["w"]
            h = ops.matmul_ws(h, w, p["b"])
            if sp.relu:
                h = jnp.maximum(h, 0)
            if qat and i != last_param:
                h = fake_quant_act(h)
        elif sp.kind == "add":
            h = src[0] + src[1]
            if sp.relu:
                h = jnp.maximum(h, 0)
            if qat:                       # the merge node's shared grid
                h = fake_quant_act(h)
        elif sp.kind == "concat":
            h = jnp.concatenate(src, axis=-1)
            if qat:
                h = fake_quant_act(h)
        else:
            raise ValueError(f"unknown layer kind {sp.kind!r}")
        acts.append(h)
    return acts[-1]


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean cross-entropy of integer labels over the LAST (class) axis,
    computed in f32.  Leading dims are arbitrary: classifier heads pass
    [N, classes] + [N] labels, dense-prediction heads pass per-pixel
    [N, H, W, classes] + [N, H, W] label maps — every pixel is one term
    of the mean."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(
        jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32),
                            axis=-1))


def accuracy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Fraction of correct argmax predictions over the last axis —
    per-sample for classifiers, per-pixel for segmentation maps."""
    return jnp.mean((jnp.argmax(logits, axis=-1) == labels)
                    .astype(jnp.float32))


def make_train_step(plan: NetworkPlan, cfg: TrainConfig = TrainConfig()):
    """One jitted training step: float-shadow forward (QAT-aware) →
    backward through the WS kernels' custom VJPs → AdamW update.

    Returns ``step(state, x, y) -> (state, metrics)`` with metrics
    {loss, accuracy, lr, grad_norm}."""

    def loss_fn(params, x, y):
        logits = float_forward(plan, params, x, qat=cfg.qat,
                               per_channel=cfg.per_channel)
        return softmax_cross_entropy(logits, y), accuracy(logits, y)

    @jax.jit
    def step(state: TrainState, x: jax.Array, y: jax.Array):
        (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, x, y)
        params, opt_state, metrics = adamw_update(
            state.params, grads, state.opt_state, state.step, cfg.adamw)
        new_state = TrainState(params=params, opt_state=opt_state,
                               step=state.step + 1)
        return new_state, {"loss": loss, "accuracy": acc, **metrics}

    return step


def make_eval_step(plan: NetworkPlan, cfg: TrainConfig = TrainConfig()):
    """Jitted (loss, accuracy) of the float shadow (QAT-aware, so eval
    sees the same fake-quantized forward training optimizes)."""

    @jax.jit
    def evaluate(params, x, y):
        logits = float_forward(plan, params, x, qat=cfg.qat,
                               per_channel=cfg.per_channel)
        return softmax_cross_entropy(logits, y), accuracy(logits, y)

    return evaluate


def fit(plan: NetworkPlan, x: jax.Array, y: jax.Array, *, steps: int,
        batch: int = 32, cfg: TrainConfig = TrainConfig(), seed: int = 0,
        state: Optional[TrainState] = None
        ) -> Tuple[TrainState, List[dict]]:
    """Minibatch training loop (uniform sampling with replacement).
    Returns the final state and the per-step metric history."""
    import time

    from repro import obs
    rng = np.random.default_rng(seed)
    if state is None:
        state = init_train_state(plan, rng)
    step_fn = make_train_step(plan, cfg)
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    history: List[dict] = []
    n = x.shape[0]
    # telemetry: monotonic per-step wall time into the shared histogram
    # type (p50/p90/p99), images/sec as a gauge — observation is cheap
    # enough to keep on unconditionally; spans only when obs is enabled
    step_us = obs.metrics.histogram(f"train.step_us.{plan.name}")
    ips = obs.metrics.gauge(f"train.images_per_s.{plan.name}")
    with obs.span("train.fit", network=plan.name, steps=steps, batch=batch,
                  qat=cfg.qat):
        for i in range(steps):
            idx = rng.integers(0, n, size=batch)
            with obs.span("train.step", step=i):
                t0 = time.perf_counter()
                state, metrics = step_fn(state, x[idx], y[idx])
                metrics = {k: float(v) for k, v in metrics.items()}
                dt = time.perf_counter() - t0
            step_us.observe(dt * 1e6)
            if dt > 0:
                ips.set(batch / dt)
            history.append(metrics)
    return state, history


def synthetic_digits(rng: np.random.Generator, n: int,
                     input_shape: Tuple[int, int, int] = (12, 12, 1),
                     classes: int = 10, noise: float = 0.35,
                     template_seed: int = 0
                     ) -> Tuple[jax.Array, jax.Array]:
    """A synthetic "digits" classification set: one low-frequency template
    per class (a coarse random pattern upsampled 3×) plus per-sample
    noise.  Linearly separable but not trivially so — a tiny LeNet fits
    it in a few dozen steps, which is exactly what the training smoke
    tests and the QAT round-trip acceptance need.

    The class templates come from ``template_seed`` (NOT from ``rng``), so
    successive calls with the same seed draw train/eval sets from the
    same task; ``rng`` drives only the labels and the per-sample noise."""
    h, w, c = input_shape
    trng = np.random.default_rng(template_seed)
    base = trng.normal(size=(classes, max(1, -(-h // 3)),
                             max(1, -(-w // 3)), c))
    templates = np.repeat(np.repeat(base, 3, axis=1), 3, axis=2)[:, :h, :w]
    y = rng.integers(0, classes, size=n)
    x = templates[y] + noise * rng.normal(size=(n, h, w, c))
    return (jnp.asarray(x, jnp.float32),
            jnp.asarray(y, jnp.int32))


def synthetic_segmentation(rng: np.random.Generator, n: int,
                           input_shape: Tuple[int, int, int] = (16, 16, 4),
                           classes: int = 3, noise: float = 0.3,
                           template_seed: int = 0
                           ) -> Tuple[jax.Array, jax.Array]:
    """A synthetic dense-prediction set: each image is a per-pixel class
    map (coarse random label blobs upsampled 4×, so regions are several
    pixels wide) rendered through one channel signature per class, plus
    noise.  The label is the [H, W] class map itself — what the
    ``unet_small`` / ``dilated_context`` heads must reproduce per pixel.
    A few conv layers separate it easily, which is what the segmentation
    training smokes and the QAT round-trip acceptance need.

    Like :func:`synthetic_digits`, the class signatures and blob layout
    statistics come from ``template_seed`` so train/eval calls draw from
    the same task; ``rng`` drives the per-sample blobs and noise."""
    h, w, c = input_shape
    trng = np.random.default_rng(template_seed)
    sig = trng.normal(size=(classes, c))              # channel signature
    coarse = rng.integers(0, classes,
                          size=(n, max(1, -(-h // 4)), max(1, -(-w // 4))))
    y = np.repeat(np.repeat(coarse, 4, axis=1), 4, axis=2)[:, :h, :w]
    x = sig[y] + noise * rng.normal(size=(n, h, w, c))
    return (jnp.asarray(x, jnp.float32),
            jnp.asarray(y, jnp.int32))
