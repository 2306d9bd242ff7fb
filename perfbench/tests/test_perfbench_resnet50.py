"""ResNet-50 v1.5 as the benchmark runs it: its configuration file against
the published anchor, its plan against the program's shape walk, and a
copy at test size through a whole run on the CPU, correct against the
plain reference while the int4 control and swapped answers are not."""

import json
import os
import sys
import time

import numpy as np
import pytest

from perfbench.harness import counts, manifest, model, runner
from perfbench.tests.test_perfbench_counts import resnet50_v15
from perfbench.tests.test_perfbench_run import on_cpu  # noqa: F401

CONFIG = os.path.join(manifest.BENCH_DIR, "configs", "resnet50.json")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
WORKLOAD = "resnet50.offline-b32"


def _cfg():
    with open(CONFIG) as f:
        return json.load(f)


def test_config_is_the_anchor_layer_for_layer():
    cfg, anchor = _cfg(), resnet50_v15()
    assert cfg["input_shape"] == anchor["input_shape"]
    assert cfg["layers"] == json.loads(json.dumps(anchor["layers"]))
    assert cfg["reduced"] == []
    lc = counts.layer_counts(cfg)
    assert sum(c.macs for c in lc) == 4_089_184_256
    assert [c.name for c in lc] == [c.name for c in
                                    counts.layer_counts(anchor)]


def test_reference_parameters_match_plan_and_anchor():
    cfg = _cfg()
    ref = manifest.reference(CONFIG)
    shapes = ref.param_shapes(tuple(cfg["input_shape"]))
    assert sum(int(np.prod(w)) + int(np.prod(b)) for w, b in shapes) \
        == 25_530_472
    plan = model.build_plan(cfg)
    fake = [(np.zeros(w, np.float32), np.zeros(b, np.float32))
            for w, b in shapes]
    model.program_params(plan, fake)         # raises on any mismatch


def test_plan_shapes_match_counts_at_every_node():
    cfg = _cfg()
    plan = model.build_plan(cfg)
    walked = counts.walk(cfg)
    assert [n for n, _, _ in walked] == plan.node_names()
    assert [s for _, s, _ in walked] == plan.activation_shapes()
    pool1 = plan.layers[plan.node_names().index("pool1")]
    assert (pool1.kind, pool1.size, pool1.stride, pool1.padding) == (
        "pool", 3, 2, "SAME")
    strided = [n for n, sp in zip(plan.node_names(), plan.layers)
               if sp.kind == "conv" and sp.stride == 2]
    assert strided == ["conv1", "res3a_2", "res3a_proj", "res4a_2",
                       "res4a_proj", "res5a_2", "res5a_proj"]


def _tiny_cell():
    bench = manifest.load_benchmark()
    bench["configs"] = [{"name": "resnet50",
                         "file": os.path.join(DATA, "resnet50_tiny.json")}]
    return manifest.cell(bench, WORKLOAD)


def test_tiny_copy_runs_correct(on_cpu):  # noqa: F811
    result = runner.run(_tiny_cell(), 2**40 + 7, 1.0, False,
                        t_start=time.perf_counter(), **on_cpu)
    assert result["correct"], result["check"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"]["images_per_s"]["value"] > 0


def test_tiny_copy_int4_control_and_swapped_answers_fail(on_cpu):  # noqa: F811
    sys.path.insert(0, manifest.BENCH_DIR)
    try:
        import control
    finally:
        sys.path.remove(manifest.BENCH_DIR)
    cell = _tiny_cell()
    r = control.readings(cell, 2**33 + 5, 0.5, **on_cpu)
    limits = cell.config["limits"]
    for name, limit in limits.items():
        assert r["program"][name] <= limit
    assert any(r["control_int4"][n] > lim for n, lim in limits.items())
    assert r["fault_swapped"]["max_centred_err"] > limits["max_centred_err"]


@pytest.mark.parametrize("seed", [3, 2**35 + 11])
def test_tiny_copy_answers_match_the_program_walk(seed):
    """The test copy's reference and the program's float oracle
    (``NetworkPlan.apply_ref``) are one network: the same logits from the
    same weights."""
    import jax.numpy as jnp
    with open(os.path.join(DATA, "resnet50_tiny.json")) as f:
        cfg = json.load(f)
    ref = manifest.reference(os.path.join(DATA, "resnet50_tiny.json"))
    weights = model.make_weights(ref, cfg, seed)
    plan = model.build_plan(cfg)
    x = jnp.asarray(model.make_images(cfg, seed, model.POOL, 2))
    got = plan.apply_ref(model.program_params(plan, weights), x)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.forward(weights, x)),
                               rtol=2e-4, atol=2e-4)
