"""Dense-prediction (segmentation) acceptance: the PR-8 contract.

The dilated & transposed conv knobs threaded through the stack must hold
the repo's established guarantees on the new workload class:

* ``unet_small`` (encoder–decoder, conv_transpose upsampling + skip
  concats) and ``dilated_context`` (atrous context module) compile via
  ``make_int8_program`` and are BIT-EXACT ref↔pallas under all three
  scheduler modes, with both the sequential and the pipelined kernel;
* QAT round trip (train the float shadow → quantize_network →
  make_int8_program) holds per-pixel accuracy within the established 2%;
* the §5.2 paper anchors (0.224 / 4.48 GOPS, 3,154,176 psums) remain
  exact — dense prediction is additive, not a drift;
* the transposed-conv psum pricing exposes both the naive (~stride²×)
  and the zero-skipping MAC counts;
* over-dilated layers fail loudly in ``plan_tiles`` (the satellite
  shaped-error contract) instead of emitting an out-of-range BlockSpec.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import banking, network, perfmodel, scheduler, training
from repro.core.convcore import ConvCoreConfig, register_backend
from repro.kernels import ref

RNG = np.random.default_rng(5)

ZOO = [network.unet_small, network.dilated_context]


def _setup(make, batch: int = 2):
    plan = make()
    rng = np.random.default_rng(3)
    params = plan.init_params(rng)
    xf = jnp.asarray(rng.normal(size=(batch,) + plan.input_shape),
                     jnp.float32)
    qnet = network.quantize_network(plan, params, xf)
    return plan, params, xf, qnet


# ---------------------------------------------------------------------------
# Compile + numeric parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("make", ZOO)
def test_zoo_compiles_and_tracks_float_oracle(make):
    """Both segmentation nets compile to full-resolution logit maps; the
    int8 program tracks the float oracle within quantization error and is
    bit-exact ref↔pallas."""
    plan, params, xf, qnet = _setup(make)
    a = network.make_int8_program(
        qnet, ConvCoreConfig(backend="pallas", int8=True))(xf)
    b = network.make_int8_program(
        qnet, ConvCoreConfig(backend="ref", int8=True))(xf)
    h, w, _ = plan.input_shape
    assert a.shape[1:3] == (h, w), "dense prediction keeps resolution"
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    want = plan.apply_ref(params, xf)
    rel = float(jnp.linalg.norm(a - want) / jnp.linalg.norm(want))
    assert rel < 0.15, rel


@pytest.mark.parametrize("mode", ["batch", "kout", "spatial"])
@pytest.mark.parametrize("make", ZOO)
def test_zoo_bit_exact_all_scheduler_modes(make, mode):
    """Acceptance: ref↔pallas bit-exact under every scheduler mode — the
    kout shards divide transposed kernels like forward ones, and spatial
    row bands widen their halos for dilation / lower the transpose onto
    the banded eq conv."""
    plan, params, xf, qnet = _setup(make)
    outs = []
    for backend in ("ref", "pallas"):
        sched = scheduler.MultiCoreScheduler(
            scheduler.SchedulerConfig(n_cores=2, mode=mode))
        name = backend
        if mode != "batch":
            sb = sched.shard_backend(backend)
            register_backend(sb)
            name = sb.name
        program = network.make_int8_program(
            qnet, ConvCoreConfig(backend=name, int8=True))
        outs.append(sched.run(program, xf))
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(outs[1]))


@pytest.mark.parametrize("mode", ["batch", "kout", "spatial"])
@pytest.mark.parametrize("make", ZOO)
def test_zoo_pipelined_kernel_bit_exact(make, mode, monkeypatch):
    """The forced-pipelined compile (every conv, transposed ones via the
    eq-conv lowering included, on conv2d_ws_pipe) is bit-identical to the
    forced-sequential compile under every scheduler mode."""
    plan, params, xf, qnet = _setup(make)
    outs = []
    for forced in (False, True):
        monkeypatch.setattr(banking.TilePlan, "pipelined",
                            property(lambda self, v=forced: v))
        sched = scheduler.MultiCoreScheduler(
            scheduler.SchedulerConfig(n_cores=2, mode=mode))
        name = "pallas"
        if mode != "batch":
            sb = sched.shard_backend("pallas")
            register_backend(sb)
            name = sb.name
        program = network.make_int8_program(
            qnet, ConvCoreConfig(backend=name, int8=True))
        outs.append(sched.run(program, xf))
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(outs[1]))


# ---------------------------------------------------------------------------
# QAT round trip on the segmentation task
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("make", ZOO)
def test_segmentation_qat_roundtrip_within_2pct(make):
    """Acceptance: train the float shadow (through the transposed/dilated
    WS-kernel VJPs) with QAT on the synthetic segmentation task, lower
    with quantize_network, deploy with make_int8_program — per-PIXEL
    accuracy of the int8 program within 2% of the float shadow."""
    plan = make(input_shape=(8, 8, 2), classes=3)
    rng = np.random.default_rng(7)
    x, y = training.synthetic_segmentation(rng, 256, (8, 8, 2), classes=3)
    xe, ye = training.synthetic_segmentation(rng, 128, (8, 8, 2), classes=3)
    cfg = training.TrainConfig(qat=True, per_channel=True)
    state, _ = training.fit(plan, x, y, steps=60, batch=32, cfg=cfg, seed=8)

    float_logits = training.float_forward(plan, state.params, xe)
    float_acc = float(training.accuracy(float_logits, ye))
    assert float_acc >= 0.9, f"shadow model failed to learn: {float_acc}"

    qnet = network.quantize_network(plan, state.params, x[:128],
                                    per_channel=True)
    program = network.make_int8_program(
        qnet, ConvCoreConfig(backend="pallas", int8=True))
    int8_acc = float(training.accuracy(program(xe), ye))
    assert abs(float_acc - int8_acc) <= 0.02, (float_acc, int8_acc)


# ---------------------------------------------------------------------------
# Perf model: anchors untouched, transpose psum pricing
# ---------------------------------------------------------------------------


def test_paper_anchors_exact_with_calib_none():
    """The dense-prediction layer is additive: §5.2 anchors stay exact."""
    refnum = perfmodel.paper_reference_numbers()
    assert refnum["psums"] == 3_154_176
    assert refnum["gops_1core"] == pytest.approx(0.224, rel=1e-3)
    assert refnum["gops_20cores"] == pytest.approx(4.48, rel=1e-2)


def test_transpose_psum_skip_vs_naive():
    """Zero-skipping prices one psum per INPUT pixel × tap; the naive
    sweep prices the upsampled output — ~stride²× more for stride-s
    upsampling (exactly stride² when the kernel tiles the stride)."""
    h = w = 8
    c, k, kh, s = 4, 8, 2, 2
    skip = perfmodel.conv_transpose_psum_count(h, w, c, k, kh, kh, stride=s)
    naive = perfmodel.conv_transpose_psum_count(h, w, c, k, kh, kh,
                                               stride=s, skip_zeros=False)
    assert skip == h * w * k * c
    oh, ow = ref.conv_transpose_out_shape(h, w, kh, kh, s)
    assert naive == oh * ow * k * c
    assert naive == s * s * skip
    # the network walk prices transposed rows on the skip count
    plan = network.unet_small()
    rows = dict(plan.psum_table())
    acts = plan.activation_shapes()
    ins = plan.resolved_inputs()
    for i, sp in enumerate(plan.layers):
        if sp.kind != "conv_transpose":
            continue
        hh, ww, cc = plan.input_shape if ins[i][0] < 0 else acts[ins[i][0]]
        assert rows[plan.node_names()[i]] == hh * ww * sp.features * cc


def test_plan_tiles_rejects_over_dilated_kernel():
    """Satellite: a dilated kernel extent wider than the padded input is
    a shaped ValueError from the planner, not an out-of-range BlockSpec
    from the kernel launch."""
    with pytest.raises(ValueError, match="dilated kernel extent"):
        banking.plan_tiles(12, 12, 4, 4, 3, 3, padding="VALID", dilation=50)


def test_tile_plans_transpose_planned_on_eq_geometry():
    """Transposed layers plan on the stride-1 eq conv: the plan's output
    extent is the transpose output and its input tile carries the eq
    stride-1 halo."""
    plan = network.unet_small()
    plans = plan.tile_plans()
    acts = plan.activation_shapes()
    for i, sp in enumerate(plan.layers):
        if sp.kind != "conv_transpose":
            continue
        tp = plans[i]
        assert (tp.out_h, tp.out_w) == acts[i][:2]
        assert tp.stride == 1
        kh = sp.kernel[0]
        assert tp.in_h_tile == (tp.h_tile - 1) + ref.dilated_extent(
            kh, sp.dilation)


def test_autotuned_engine_serves_segmentation():
    """Satellite: a NetworkTunePlan routes end-to-end through
    ConvNetEngine — tuned tile plans into the compiled program, the
    winning scheduler verdict into the serving loop — and stays
    bit-exact with the greedy engine."""
    from repro.core.autotune import autotune_network
    from repro.serving.engine import ConvNetEngine
    plan, params, xf, qnet = _setup(network.dilated_context, batch=3)
    tune = autotune_network(plan)
    base = ConvNetEngine(qnet, batch=2, backend="pallas")
    tuned = ConvNetEngine(qnet, batch=2, backend="pallas", tune=tune)
    a = base.submit(np.asarray(xf))
    b = tuned.submit(np.asarray(xf))
    np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError, match="tune plan is for network"):
        ConvNetEngine(_setup(network.unet_small)[3], tune=tune)
