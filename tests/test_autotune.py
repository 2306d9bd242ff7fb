"""Plan autotuner.

Hypothesis invariants over random layer shapes live in
tests/test_autotune_property.py (the chosen plan always fits VMEM,
respects group-aligned banks, is never worse than the greedy
``plan_tiles`` plan under the same model, and is deterministic).  Here:
the greedy plan is a member of the search space, the network-level
contract, and execution (tuned plans produce bit-identical network
outputs — they change WHERE tiles fall, never WHAT is computed).

``bench_util``'s Timing stats record (the even-iters median fix) is
covered here too — tier-1 runs with PYTHONPATH=src, so the benchmarks
package is added to sys.path explicitly.
"""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import banking, network, scheduler
from repro.core.autotune import (NetworkTunePlan, autotune_network,
                                 candidate_states, schedule_cycles)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

RNG = np.random.default_rng(11)


# ---------------------------------------------------------------------------
# Autotuner (deterministic; hypothesis invariants live in
# tests/test_autotune_property.py, skipped where hypothesis is absent)
# ---------------------------------------------------------------------------

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
    tune = autotune_network(plan)
    assert isinstance(tune, NetworkTunePlan)
    assert len(tune.tile_plans) == len(plan.layers)
    assert tune.cycles <= tune.greedy_cycles
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
        tune.layers, tune.scheduler_mode, tune.n_cores)


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
    tune = autotune_network(plan)
    tuned_prog = network.make_int8_program(
        qnet, cfg, tile_plans=tune.tile_plans)
    np.testing.assert_array_equal(np.asarray(greedy_prog(x)),
                                  np.asarray(tuned_prog(x)))


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
