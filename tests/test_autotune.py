"""Calibration layer + plan autotuner.

Calibration: CalibrationTable JSON round-trip, synthetic fit recovery
(known ground-truth factors come back out of fit_calibration), IQR noise
rejection, and the bit-exactness contract — with no table loaded every
perfmodel output (including the §5.2 paper anchors 0.224 / 4.48 GOPS) is
identical to the uncalibrated model.

Autotuner: hypothesis invariants over random layer shapes — the chosen
plan always fits VMEM, respects group-aligned banks, is never worse than
the greedy ``plan_tiles(kernel="auto")`` plan under the same model, and
is deterministic given a fixed CalibrationTable; plus the crossover
verdict flip a fitted overhead makes (the README worked example) and the
execution contract (tuned plans produce bit-identical network outputs —
they change WHERE tiles fall, never WHAT is computed).

``bench_util``'s Timing stats record (the even-iters median fix) is
covered here too — tier-1 runs with PYTHONPATH=src, so the benchmarks
package is added to sys.path explicitly.
"""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import banking, network, perfmodel, scheduler
from repro.core.autotune import (NetworkTunePlan, autotune_network,
                                 candidate_states, schedule_cycles)
from repro.core.calibration import (CalibrationSample, CalibrationTable,
                                    fit_calibration, load_table,
                                    sample_from_plan)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

RNG = np.random.default_rng(11)


# ---------------------------------------------------------------------------
# CalibrationTable: round-trip, defaults, prediction
# ---------------------------------------------------------------------------


def test_table_json_round_trip(tmp_path):
    t = CalibrationTable(compute_factor=3.89, dma_bytes_per_cycle=2.5,
                         pipeline_overhead_cycles=40.0,
                         fit={"n_fit": 12}, provenance={"mode": "test"})
    assert CalibrationTable.from_json(t.to_json()) == t
    p = tmp_path / "calib.json"
    t.save(str(p))
    assert CalibrationTable.load(str(p)) == t
    assert load_table(str(p)) == t
    assert load_table(str(tmp_path / "missing.json")) is None
    assert load_table(None) is None


def test_table_defaults_are_analytic():
    t = CalibrationTable()
    assert t.compute_factor == 1.0
    assert t.dma_bytes_per_cycle is None
    assert t.pipeline_overhead_cycles == perfmodel.PIPELINE_OVERHEAD_CYCLES


def test_pipeline_overhead_is_table_field_default_16():
    # the satellite contract: the module constant is the no-table value
    # and stays pinned; a table carries the fitted value
    assert perfmodel.PIPELINE_OVERHEAD_CYCLES == 16
    assert perfmodel.pipeline_overhead_cycles(None) == 16
    t = CalibrationTable(pipeline_overhead_cycles=64.0)
    assert perfmodel.pipeline_overhead_cycles(t) == 64.0


# ---------------------------------------------------------------------------
# No table loaded → bit-identical perfmodel (the CI-asserted anchor)
# ---------------------------------------------------------------------------


def test_no_table_is_bit_exact():
    ref_nums = perfmodel.paper_reference_numbers()
    assert ref_nums["gops_1core"] == 0.224
    assert round(ref_nums["gops_20cores"], 2) == 4.48
    assert ref_nums["psums"] == 3_154_176
    plan = banking.plan_tiles(28, 28, 8, 16, in_bytes=1)
    psums = perfmodel.psum_count(28, 28, 8, 16)
    est0 = perfmodel.pipeline_estimate(plan, psums)
    est1 = perfmodel.pipeline_estimate(plan, psums, calib=None)
    assert est0 == est1
    assert perfmodel.calibrated_cycles(psums) == perfmodel.cycles(psums)
    net = network.lenet()
    tps = net.tile_plans()
    assert tps == net.tile_plans(calib=None)
    assert net.perf_report(tile_plans=tps) == \
        net.perf_report(tile_plans=tps, calib=None)
    assert net.train_report(tile_plans=tps) == \
        net.train_report(tile_plans=tps, calib=None)


# ---------------------------------------------------------------------------
# Fit: synthetic recovery + noise rejection
# ---------------------------------------------------------------------------

_TRUTH = dict(cf=3.89, bpc=2.5, ov=40.0)


def _synthetic_samples(noise_sd=0.002):
    cfg = perfmodel.IPCoreConfig()
    rng = np.random.default_rng(0)
    cases = [  # (compute_cycles, dma_bytes, n_slabs, pipelined)
        (2_000_000, 1_000_000, 8, True), (1_500_000, 4_000_000, 16, True),
        (500_000, 8_000_000, 32, True), (3_000_000, 200_000, 4, True),
        (800_000, 2_500_000, 64, True), (50_000, 100_000, 128, True),
        (20_000, 50_000, 256, True), (1_000_000, 1_000_000, 1, False),
        (2_500_000, 500_000, 1, False), (100_000, 6_000_000, 1, False),
    ]
    out = []
    for i, (cc, db, ns, pl) in enumerate(cases):
        true_cycles = (_TRUTH["cf"] * cc + db / _TRUTH["bpc"]
                       + (_TRUTH["ov"] * ns if pl else 0))
        us = true_cycles / cfg.clock_hz * 1e6 \
            * (1.0 + rng.normal(0, noise_sd))
        out.append(CalibrationSample(
            name=f"s{i}", compute_cycles=cc, dma_bytes=db, n_slabs=ns,
            pipelined=pl, measured_us=us, iqr_us=us * 0.001))
    return out


def test_fit_recovers_ground_truth():
    table = fit_calibration(_synthetic_samples(),
                            provenance={"mode": "synthetic"})
    assert abs(table.compute_factor - _TRUTH["cf"]) < 0.05
    assert abs(table.dma_bytes_per_cycle - _TRUTH["bpc"]) < 0.1
    assert abs(table.pipeline_overhead_cycles - _TRUTH["ov"]) < 8
    assert table.fit["n_rejected_noisy"] == 0
    assert table.fit["mean_abs_error_pct"] < 2.0
    assert table.provenance["mode"] == "synthetic"


def test_fit_rejects_noisy_samples():
    samples = _synthetic_samples()
    wild = CalibrationSample(name="wild", compute_cycles=1_000_000,
                             dma_bytes=1_000_000, n_slabs=4, pipelined=True,
                             measured_us=1e6, iqr_us=9e5)   # IQR ≈ median
    assert wild.noisy
    table = fit_calibration(samples + [wild])
    assert table.fit["n_rejected_noisy"] == 1
    assert abs(table.compute_factor - _TRUTH["cf"]) < 0.05
    with pytest.raises(ValueError):
        fit_calibration([wild])          # nothing usable left


def test_fit_without_pipelined_samples_keeps_default_overhead():
    seq_only = [s for s in _synthetic_samples() if not s.pipelined]
    table = fit_calibration(seq_only)
    assert table.pipeline_overhead_cycles == \
        perfmodel.PIPELINE_OVERHEAD_CYCLES
    assert "pipeline_overhead_cycles" not in table.fit["terms_fit"]


def test_sample_from_plan_terms_match_perfmodel():
    plan = banking.plan_tiles(28, 28, 8, 16, in_bytes=1)
    psums = perfmodel.psum_count(28, 28, 8, 16)
    s = sample_from_plan("l0", plan, psums, measured_us=123.0, iqr_us=1.0)
    assert s.compute_cycles == perfmodel.cycles(psums)
    assert s.dma_bytes == perfmodel.tile_traffic(plan)["total_bytes"]
    assert s.n_slabs == perfmodel.pipeline_slabs(plan)
    assert s.pipelined == plan.pipelined


# ---------------------------------------------------------------------------
# Autotuner (deterministic; hypothesis invariants live in
# tests/test_autotune_property.py, skipped where hypothesis is absent)
# ---------------------------------------------------------------------------

_CALIB = CalibrationTable(compute_factor=2.0, dma_bytes_per_cycle=4.0,
                          pipeline_overhead_cycles=32.0)


def test_greedy_plan_in_candidate_space():
    # the "never worse" guarantee rests on the greedy tile/bank state
    # being enumerable: its tile extents come from the same halving
    # chain, its banks are divisors
    greedy = banking.plan_tiles(64, 64, 16, 16, in_bytes=1,
                                vmem_budget=96 * 1024)
    states = candidate_states(greedy.out_h, greedy.out_w, 16, 16, 1, False)
    assert (greedy.h_tile, greedy.w_tile, greedy.cin_banks,
            greedy.kout_banks) in states


def test_network_tune_plan_contract():
    plan = network.mobilenet_v2ish()
    tune = autotune_network(plan, calib=_CALIB)
    assert isinstance(tune, NetworkTunePlan)
    assert len(tune.tile_plans) == len(plan.layers)
    assert tune.cycles <= tune.greedy_cycles
    assert tune.calibrated
    # per-layer rows carry the plan_source contract
    rows = tune.layer_rows()
    assert all(r["plan_source"] in ("greedy", "autotuned") for r in rows)
    assert sum(r["plan_source"] == "autotuned" for r in rows) == \
        tune.layers_differ
    # the scheduler glue: mode/cores thread into SchedulerConfig
    cfg = tune.scheduler_config()
    assert cfg == scheduler.SchedulerConfig.for_tune(tune)
    assert scheduler.MultiCoreScheduler.from_tune(tune).config == cfg
    # the schedule point the tuner reports is reproducible
    assert tune.schedule_cycles_ == schedule_cycles(
        tune.layers, tune.scheduler_mode, tune.n_cores, calib=_CALIB)


def test_zoo_networks_tune_leq_greedy_and_one_differs():
    # the PR acceptance criterion, as a regression test: on every zoo
    # network tuned ≤ greedy, and at least one network actually moves
    zoo = [network.lenet(), network.vgg_small(), network.resnet_small(),
           network.mobilenet_small(), network.mobilenet_v2ish()]
    differ = 0
    for plan in zoo:
        tune = autotune_network(plan)
        assert tune.cycles <= tune.greedy_cycles, plan.name
        differ += tune.layers_differ
    assert differ > 0


def test_crossover_verdict_flips_with_fitted_overhead():
    # a tiny DMA-bound layer (6×6×8 ⊛ 8 streamed through 4 row tiles —
    # one lane-legal bank each way) that the analytic 16-cycle overhead
    # routes to the pipelined kernel flips back to sequential once a
    # fitted table says slabs cost 128 cycles each
    plan16 = banking.plan_tiles(6, 6, 8, 8, in_bytes=1, kernel="auto",
                                vmem_budget=120 * 1024)
    assert plan16.n_tiles == 4 and plan16.pipelined
    plan128 = banking.plan_tiles(
        6, 6, 8, 8, in_bytes=1, kernel="auto", vmem_budget=120 * 1024,
        calib=CalibrationTable(pipeline_overhead_cycles=128.0))
    assert not plan128.pipelined


def test_tuned_plans_execute_bit_exact():
    # tile plans change WHERE tiles fall, never WHAT is computed: the
    # compiled int8 program under tuned plans must produce bit-identical
    # outputs to the greedy-planned program
    plan = network.lenet(input_shape=(12, 12, 1))
    rng = np.random.default_rng(5)
    params = plan.init_params(rng)
    x = jnp.asarray(rng.normal(size=(2, *plan.input_shape)), jnp.float32)
    qnet = network.quantize_network(plan, params, x)
    from repro.core.convcore import ConvCoreConfig
    cfg = ConvCoreConfig(backend="pallas", int8=True)
    greedy_prog = network.make_int8_program(
        qnet, cfg, tile_plans=network.program_tile_plans(plan, cfg))
    tune = autotune_network(plan, calib=_CALIB)
    tuned_prog = network.make_int8_program(
        qnet, cfg, tile_plans=tune.tile_plans)
    np.testing.assert_array_equal(np.asarray(greedy_prog(x)),
                                  np.asarray(tuned_prog(x)))


# ---------------------------------------------------------------------------
# ConvCoreConfig.calib threads into the compile-time planner
# ---------------------------------------------------------------------------


def test_convcore_config_threads_calib():
    from repro.core.convcore import ConvCoreConfig
    plan = network.lenet()
    cfg = ConvCoreConfig(int8=True, calib=_CALIB)
    tps = network.program_tile_plans(plan, cfg)
    assert tps == plan.tile_plans(calib=_CALIB)
    cfg0 = ConvCoreConfig(int8=True)
    assert network.program_tile_plans(plan, cfg0) == plan.tile_plans()


# ---------------------------------------------------------------------------
# bench_util.Timing (stats record + even-iters median fix)
# ---------------------------------------------------------------------------


def test_timing_even_median_and_stats():
    from benchmarks.bench_util import Timing
    t = Timing([4.0, 1.0, 3.0, 2.0])
    assert t == 2.5                       # was 3.0 (upper-middle) before
    assert isinstance(t, float)
    assert t.min_us == 1.0 and t.median_us == 2.5
    assert t.samples_us == (1.0, 2.0, 3.0, 4.0)
    assert t.iqr_us > 0
    assert Timing([5.0, 1.0, 3.0, 2.0, 4.0]) == 3.0
    s = t.stats()
    assert set(s) == {"median_us", "min_us", "iqr_us", "samples_us"}
    with pytest.raises(ValueError):
        Timing([])


def test_time_fn_returns_timing():
    from benchmarks.bench_util import Timing, time_fn
    r = time_fn(lambda: jnp.zeros(()), iters=4, warmup=1)
    assert isinstance(r, Timing)
    assert len(r.samples_us) == 4
    assert r.min_us <= r.median_us <= max(r.samples_us)
