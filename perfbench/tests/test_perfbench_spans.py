"""The program's own names in a traced run (``harness/spans.py``): the
engine worker's spans and compile marks in a real profiler trace on the
CPU, node scopes in the compiled program, the readers on hand-made
traces, and the harness's existing reduction unchanged on a recorded chip
trace."""

import gzip
import json
import os
import re
import types

import numpy as np
import pytest

from perfbench.harness import manifest, spans
from perfbench.harness import trace as trace_mod
from repro import obs

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000


@pytest.fixture(autouse=True)
def _obs_clean():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _qnet(input_shape):
    from repro.core import network
    rng = np.random.default_rng(0)
    plan = network.lenet(input_shape=input_shape)
    x = np.asarray(rng.normal(size=(2, *plan.input_shape)), np.float32)
    return network.quantize_network(plan, plan.init_params(rng), x), x


def _traced(tmp_path, body):
    """Run ``body`` inside a ``bench.window`` span under the profiler, as
    the harness's traced window does; return the compact form of the
    trace."""
    import jax
    out = tmp_path / ".bench_trace" / "cell"
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(out), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW):
            body()
    finally:
        jax.profiler.stop_trace()
    return spans.load(spans.newest_trace(str(tmp_path)))


def test_worker_spans_reach_the_profiler_trace(tmp_path):
    """With obs on, every worker state of a small engine is an event on
    the profiler's host plane, with its per-batch args, on one thread."""
    from repro.serving.batching import ContinuousBatchingEngine
    qnet, _ = _qnet((12, 12, 1))
    eng = ContinuousBatchingEngine(batch=2, backend="pallas")
    eng.add_model(qnet)
    imgs = np.random.default_rng(1).normal(
        size=(3, *qnet.plan.input_shape)).astype(np.float32)
    eng.submit(imgs)                                  # compile outside
    obs.enable()
    tr = _traced(tmp_path, lambda: (eng.submit(imgs), eng.close()))
    assert tr["window"] is not None
    worker = spans._worker(tr)
    assert {ev[0] for ev in worker} == set(spans.WORKER)
    stages = [ev[3] for ev in worker if ev[0] == "engine.stage"]
    assert [(a["n"], a["fill"]) for a in stages] == [(2, 1.0), (1, 0.5)]
    assert stages[0]["reason"] == "full"
    for a, b in zip(worker, worker[1:]):
        assert a[1] + a[2] <= b[1]                     # no overlap
    assert len(spans.host_path_ms(tr)) == 2
    assert spans.compiles_in_window(tr) == 0


def test_program_scopes_name_every_node():
    """Each op of the compiled program that carries metadata maps to the
    input, a node of the plan, or the output."""
    from repro.core import network
    from repro.core.convcore import ConvCoreConfig
    qnet, x = _qnet((12, 12, 1))
    program = network.make_int8_program(qnet, ConvCoreConfig(int8=True))
    hlo = program.lower(x).compile().as_text()
    paths = re.findall(r'op_name="([^"]+)"', hlo)
    nodes = {spans.node_of(p) for p in paths if spans.node_of(p)}
    names = set(qnet.plan.node_names())
    assert nodes <= names | {"input", "output"}
    assert {"input", "output", "conv0", "dense5"} <= nodes


def test_second_input_shape_in_window_counts_a_compile(tmp_path,
                                                       monkeypatch):
    """A shape first seen in the window traces anew: ``compiles.served``
    reads at least 1; a window of warm shapes reads 0."""
    from repro.serving.batching import ContinuousBatchingEngine
    small, _ = _qnet((12, 12, 1))
    large, _ = _qnet((16, 16, 1))
    eng = ContinuousBatchingEngine(batch=2, backend="pallas")
    a = eng.add_model(small, name="small")
    b = eng.add_model(large, name="large")
    rng = np.random.default_rng(2)
    img_a = rng.normal(size=(2, 12, 12, 1)).astype(np.float32)
    img_b = rng.normal(size=(2, 16, 16, 1)).astype(np.float32)
    eng.submit(img_a, model=a)
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    read = manifest.reader("compiles.served")

    def run():
        return types.SimpleNamespace(trace=object())

    _traced(tmp_path, lambda: eng.submit(img_a, model=a))
    assert read(run()) == 0
    _traced(tmp_path, lambda: eng.submit(img_b, model=b))
    assert read(run()) >= 1
    assert obs.metrics.counter(obs.COMPILES).value >= 1
    eng.close()
    assert read(types.SimpleNamespace(trace=None)) is None


def _hand_trace():
    """A 100 ms window on one device.  Ops: [0, 10) under ``input``,
    [10, 40) conv1, [60, 80) output.  Worker: wait [0, 40), stage
    [40, 45), put [45, 50), sched.run [50, 52), wait [52, 55), ready
    [55, 80), fetch [80, 82), resolve [82, 85); then stage [90, 96) and
    sched.run [96, 99) of a second batch; nothing [85, 90) and [99, 100).
    Compile marks at 30 ms and, outside the window, 120 ms."""
    ops = [["clamp_convert_fusion", 0, 10 * MS, "input"],
           ["conv2d_ws.1 tpu_custom_call", 10 * MS, 30 * MS, "conv1"],
           ["fusion.4", 60 * MS, 20 * MS, "output"]]
    worker = [["engine.wait", 0, 40 * MS, {}],
              ["engine.stage", 40 * MS, 5 * MS, {"n": 8}],
              ["engine.put", 45 * MS, 5 * MS, {}],
              ["sched.run", 50 * MS, 2 * MS, {}],
              ["engine.wait", 52 * MS, 3 * MS, {}],
              ["engine.ready", 55 * MS, 25 * MS, {}],
              ["engine.fetch", 80 * MS, 2 * MS, {}],
              ["engine.resolve", 82 * MS, 3 * MS, {}],
              ["engine.stage", 90 * MS, 6 * MS, {}],
              ["sched.run", 96 * MS, 3 * MS, {}]]
    return {"window": [0, 100 * MS],
            "devices": [{"name": "/device:TPU:0", "ops": ops}],
            "threads": [{"name": "gen", "events": [
                            [spans.COMPILE_MARK, 30 * MS, 0, {}],
                            [spans.COMPILE_MARK, 120 * MS, 0, {}]]},
                        {"name": "worker", "events": worker}]}


def test_hand_made_trace_readers():
    tr = _hand_trace()
    # busy [0, 40) + [60, 80) = 60 ms, of it 10 under ``input``
    assert spans.input_share_percent(tr) == pytest.approx(100 * 10 / 60)
    # idle [40, 60) and [80, 100): stage 5, put 5, run 2, wait 3, ready 5;
    # fetch 2, resolve 3, none 5, stage 6, run 3, none 1
    split = spans.idle_by_state(tr)
    assert split["engine.stage"] == pytest.approx(0.011)
    assert split["engine.ready"] == pytest.approx(0.005)
    assert split["engine.wait"] == pytest.approx(0.003)
    assert split[spans.NO_SPAN] == pytest.approx(0.006)
    assert sum(split.values()) == pytest.approx(0.040)
    assert spans.idle_host_percent(tr) == pytest.approx(31.0)
    # batch 1: stage 40 → run end 52 = 12 ms; batch 2: 90 → 99 = 9 ms
    assert spans.host_path_ms(tr) == pytest.approx([12.0, 9.0])
    assert spans.host_path_p50_ms(tr) == pytest.approx(10.5)
    assert spans.compiles_in_window(tr) == 1
    # idle [40, 60): stage, put, run, wait, ready; [80, 100): fetch,
    # resolve, none, stage, run, none — each labelled by its longest
    gaps = spans.idle_gaps(tr)
    assert [(label, at) for label, at, _ in gaps] == [
        ("engine.stage", 40.0), ("engine.stage", 80.0)]
    assert [g for _, _, g in gaps] == pytest.approx([0.02, 0.02])
    lines = spans.summary_lines(tr)
    assert lines[0].startswith("device time by node: conv1 50.00%")
    assert "host-path idle 31.000%" in lines[1]
    assert lines[3].startswith("longest idle gaps: engine.stage at +40.0")


def test_hand_made_trace_without_the_program_names():
    """A trace of a program without spans, scopes or marks: the readers
    find nothing to read, except a count of marks, which reads 0."""
    tr = _hand_trace()
    tr["devices"][0]["ops"] = [[n, s, d, None]
                               for n, s, d, _ in tr["devices"][0]["ops"]]
    tr["threads"] = [{"name": "gen", "events": [
        ["bench.sleep", 45 * MS, 50 * MS, {}]]}]
    assert spans.idle_gaps(tr, top=1) == [("bench.sleep", 40.0,
                                           pytest.approx(0.02))]
    tr["threads"] = []
    assert spans.idle_gaps(tr, top=1)[0][0] == "no host span"
    assert spans.input_share_percent(tr) is None
    assert spans.node_seconds(tr) is None
    assert spans.idle_by_state(tr) is None
    assert spans.idle_host_percent(tr) is None
    assert spans.host_path_p50_ms(tr) is None
    assert spans.compiles_in_window(tr) == 0
    assert spans.summary_lines(tr) == []
    for read in (spans.input_share_percent, spans.idle_host_percent,
                 spans.host_path_p50_ms, spans.compiles_in_window):
        assert read(None) is None


def test_inserted_copies_take_their_consumers_node():
    """A relayout copy without metadata belongs to the node that reads
    it (the input's quantize), else to the node it reads."""
    hlo = {"copy.6": "%copy.6 = f32[8,224,224,3]{3,2,1,0} copy(f32[8,224,"
                     "224,3]{0,3,2,1} %Arg_0.1)",
           "clamp_convert_fusion": "%clamp_convert_fusion = s8[8,224,224,3]"
                                   " fusion(f32[8,224,224,3] %copy.6)",
           "fusion.4": "%fusion.4 = f32[8,10] fusion(s32[8,10] %dot.2)",
           "copy.54": "%copy.54 = f32[8,10]{0,1} copy(f32[8,10] %fusion.4)"}
    assert spans._operands(hlo["copy.6"]) == ["Arg_0.1"]
    nodes = spans._inherit(
        {"copy.6": None, "clamp_convert_fusion": "input",
         "fusion.4": "output", "copy.54": None},
        {k: spans._operands(v) for k, v in hlo.items()})
    assert nodes == {"copy.6": "input", "clamp_convert_fusion": "input",
                     "fusion.4": "output", "copy.54": "output"}


@pytest.mark.parametrize("path, node", [
    ("jit(program)/conv1_2/jit(conv2d_ws)/dot_general", "conv1_2"),
    ("jit(program)/input/convert_element_type", "input"),
    ("jit(program)/output/mul", "output"),
    ("jit(program)/concatenate", None),
    ("jit(program)/jit(conv2d_ws)/while/body/dot_general", None),
    ("while/body/cond/reduce_max", None),
    (None, None)])
def test_node_of_op_name_paths(path, node):
    assert spans.node_of(path) == node


def test_existing_reduction_reads_the_same():
    """The harness's reduction of the recorded chip trace, pinned: this
    module adds readers beside it and changes none of its numbers."""
    with gzip.open(os.path.join(DATA, "trace_vgg16_offline.json.gz"),
                   "rt") as f:
        r = trace_mod.reduce(json.load(f))
    assert (r.window_s, r.devices, r.runs) == (0.075, 1, 2)
    assert r.busy_s == pytest.approx(0.074974166, abs=1e-9)
    assert r.kernel_s == pytest.approx(0.061808083, abs=1e-9)
    assert r.run_kernel_s == pytest.approx(0.036684715, abs=1e-9)
