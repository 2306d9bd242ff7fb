"""Tests for the obs telemetry subsystem (trace spans, metrics,
per-layer profiling, drift detection).

Every test that enables obs restores the disabled default and resets the
global sinks (the autouse fixture) — the tier-1 suite must never see
leaked spans or metric counts.
"""

import json
import time

import numpy as np
import pytest

from repro import obs
from repro.obs.metrics import Histogram, MetricsRegistry, default_buckets
from repro.obs.profile import DriftDetector, LayerProfile, profile_network
from repro.obs.trace import NOOP_SPAN, Tracer


@pytest.fixture(autouse=True)
def _obs_clean():
    """Disabled-by-default in, disabled-and-empty out."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


# -- disabled-by-default no-op contract -------------------------------------

def test_disabled_by_default_span_is_shared_noop():
    assert not obs.enabled()
    s1 = obs.span("anything", key="val")
    s2 = obs.span("else")
    assert s1 is NOOP_SPAN and s2 is NOOP_SPAN   # no per-call allocation
    with s1:
        with s2:
            pass
    obs.instant("mark", x=1)
    assert len(obs.tracer) == 0                  # nothing recorded


def test_enable_disable_roundtrip():
    obs.enable()
    with obs.span("on"):
        pass
    assert len(obs.tracer) == 1
    obs.disable()
    with obs.span("off"):
        pass
    assert len(obs.tracer) == 1                  # disabled path records 0


# -- span nesting + exception safety ----------------------------------------

def test_span_nesting_records_parentage():
    obs.enable()
    with obs.span("outer"):
        with obs.span("inner"):
            pass
    evs = {e["name"]: e for e in obs.tracer.events()}
    assert set(evs) == {"outer", "inner"}
    assert evs["inner"]["args"]["parent"] == "outer"
    assert "args" not in evs["outer"] or "parent" not in evs["outer"]["args"]
    # inner is contained in outer on the timeline
    assert evs["inner"]["ts"] >= evs["outer"]["ts"]
    assert (evs["inner"]["ts"] + evs["inner"]["dur"]
            <= evs["outer"]["ts"] + evs["outer"]["dur"] + 1e-6)


def test_span_exception_recorded_and_propagated():
    obs.enable()
    with pytest.raises(ValueError):
        with obs.span("outer"):
            with obs.span("boom"):
                raise ValueError("expected")
    evs = {e["name"]: e for e in obs.tracer.events()}
    # every span the exception propagated through carries the error tag
    assert evs["boom"]["args"]["error"] == "ValueError"
    assert evs["outer"]["args"]["error"] == "ValueError"
    # the stack unwound fully: a new span nests at top level again
    with obs.span("after"):
        pass
    after = [e for e in obs.tracer.events() if e["name"] == "after"][0]
    assert "parent" not in after.get("args", {})


def test_chrome_trace_export_is_loadable(tmp_path):
    obs.enable()
    with obs.span("compile", network="lenet"):
        with obs.span("layer:conv1", psums=123):
            pass
    obs.instant("drift", layer="conv1")
    path = obs.tracer.export(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    assert len(doc["traceEvents"]) == 3
    for ev in doc["traceEvents"]:
        assert ev["ph"] in ("X", "i")
        assert isinstance(ev["ts"], float) and ev["ts"] >= 0
        assert {"name", "pid", "tid"} <= set(ev)
        if ev["ph"] == "X":
            assert ev["dur"] >= 0


def test_tracer_threads_nest_independently():
    import threading
    tr = Tracer()

    def worker(tag):
        with tr.span(f"outer:{tag}"):
            with tr.span(f"inner:{tag}"):
                pass

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    evs = tr.events()
    assert len(evs) == 8
    for e in evs:
        if e["name"].startswith("inner:"):
            tag = e["name"].split(":")[1]
            assert e["args"]["parent"] == f"outer:{tag}"


# -- metrics ----------------------------------------------------------------

def test_counter_gauge_reset_contract():
    reg = MetricsRegistry()
    c = reg.counter("req")
    c.inc()
    c.inc(5)
    assert c.value == 6
    g = reg.gauge("fill")
    g.set(0.75)
    assert g.value == 0.75
    assert reg.counter("req") is c               # get-or-create idempotent
    with pytest.raises(TypeError):
        reg.gauge("req")                         # type-checked
    reg.reset()
    assert c.value == 0 and g.value is None
    assert reg.get("req") is c                   # reset keeps registration


def test_histogram_percentiles_vs_numpy():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=5.0, sigma=1.5, size=5000)
    h = Histogram("lat_us")
    h.observe_many(samples)
    assert h.count == len(samples)
    assert h.sum == pytest.approx(samples.sum(), rel=1e-9)
    for p in (50, 90, 99):
        exact = float(np.percentile(samples, p))
        est = h.percentile(p)
        # interpolated fixed-bucket estimate: error bounded by the bucket
        # ratio (~12% at 20 buckets/decade), tested with headroom
        assert abs(est - exact) / exact < 0.15, (p, est, exact)
    s = h.summary()
    assert s["min"] == pytest.approx(samples.min())
    assert s["max"] == pytest.approx(samples.max())


def test_histogram_edge_cases():
    h = Histogram("h")
    assert h.percentile(50) == 0.0               # empty
    h.observe(42.0)
    assert h.percentile(0) == pytest.approx(42.0)
    assert h.percentile(100) == pytest.approx(42.0)
    big = Histogram("big", bounds=[1.0, 2.0])
    big.observe(1e9)                             # overflow bucket
    assert big.percentile(99) == pytest.approx(1e9)  # clamped to max
    with pytest.raises(ValueError):
        Histogram("bad", bounds=[2.0, 1.0])
    with pytest.raises(ValueError):
        h.percentile(101)


def test_default_buckets_cover_and_ascend():
    b = default_buckets()
    assert b[0] == pytest.approx(1.0)
    assert b[-1] >= 1e8
    assert all(y > x for x, y in zip(b, b[1:]))


def test_registry_jsonl_export(tmp_path):
    reg = MetricsRegistry()
    reg.counter("a").inc(3)
    reg.histogram("b").observe(10.0)
    path = reg.export_jsonl(str(tmp_path / "m.jsonl"))
    lines = [json.loads(ln) for ln in open(path)]
    assert [d["name"] for d in lines] == ["a", "b"]
    assert lines[0]["value"] == 3
    assert lines[1]["type"] == "histogram" and lines[1]["count"] == 1
    assert all("exported_at" in d for d in lines)


# -- profiler + drift --------------------------------------------------------

def _lenet_qnet():
    from repro.core import network
    rng = np.random.default_rng(0)
    plan = network.lenet(input_shape=(12, 12, 1))
    params = plan.init_params(rng)
    x = np.asarray(rng.normal(size=(1, *plan.input_shape)), np.float32)
    return network.quantize_network(plan, params, x), x


def test_profile_layer_set_matches_plan_topology():
    qnet, x = _lenet_qnet()
    prof = profile_network(qnet, x, warmup=0)
    plan = qnet.plan
    assert len(prof.records) == len(plan.layers)
    assert prof.layer_names == list(plan.node_names())
    for i, r in enumerate(prof.records):
        assert r.index == i
        assert r.wall_us > 0
        assert r.kind == plan.layers[i].kind
    # conv layers carry a prediction and achieved GOPS
    convs = [r for r in prof.records if r.kind in ("conv", "conv_transpose")]
    assert convs and all(r.predicted_us and r.predicted_us > 0
                         and r.gops > 0 for r in convs)


def test_profile_emits_layer_spans_when_enabled():
    qnet, x = _lenet_qnet()
    obs.enable()
    prof = profile_network(qnet, x, warmup=0)
    names = {e["name"] for e in obs.tracer.events()}
    assert "profile" in names
    for ln in prof.layer_names:
        assert f"layer:{ln}" in names
    # per-layer wall times landed in the profile histogram too
    h = obs.metrics.get(f"profile.layer_us.{qnet.plan.name}")
    assert h is not None and h.count == len(prof.records)


def test_drift_detector_fires_on_miscalibrated_table():
    qnet, x = _lenet_qnet()
    # a band no layer reaches (wall time a billion times the analytic
    # FPGA time), so every priced layer drifts below it
    band = (1e9, 2e9)
    det = DriftDetector(band=band)
    prof = profile_network(qnet, x, warmup=0, drift=det)
    priced = [r for r in prof.records if r.predicted_us]
    assert priced
    assert len(prof.drift) == len(priced)
    for ev in prof.drift:
        assert ev.ratio < band[0]
        assert ev.band == band
    assert obs.metrics.counter("obs.drift.events").value == len(prof.drift)


def test_drift_detector_band_and_floor():
    rec = LayerProfile(index=0, name="c1", kind="conv", wall_us=100.0,
                       psums=1000, batch=1, gops=0.01, predicted_us=110.0,
                       pipelined=False)
    assert DriftDetector().check([rec]) == []        # ratio ~0.9: in band
    fast = LayerProfile(index=1, name="c2", kind="conv", wall_us=10.0,
                        psums=1000, batch=1, gops=0.1, predicted_us=110.0,
                        pipelined=False)
    assert len(DriftDetector().check([fast])) == 1   # ratio ~0.09: drift
    # the noise floor suppresses tiny layers
    assert DriftDetector(min_wall_us=50.0).check([fast]) == []
    free = LayerProfile(index=2, name="pool", kind="maxpool", wall_us=5.0,
                        psums=0, batch=1, gops=0.0, predicted_us=None,
                        pipelined=None)
    assert DriftDetector().check([free]) == []       # unpriced: no signal
    with pytest.raises(ValueError):
        DriftDetector(band=(2.0, 0.5))


# -- engine integration ------------------------------------------------------

def test_engine_stats_and_percentiles():
    from repro.serving.engine import ConvNetEngine
    qnet, _ = _lenet_qnet()
    eng = ConvNetEngine(qnet, batch=2, backend="pallas")
    rng = np.random.default_rng(1)
    imgs = rng.normal(size=(3, *qnet.plan.input_shape)).astype(np.float32)
    eng.submit(imgs)
    assert eng.stats == {"requests": 3, "batches": 2, "padded": 1}
    pct = eng.latency_percentiles()
    assert pct["count"] == 3
    assert 0 < pct["p50"] <= pct["p90"] <= pct["p99"]
    # obs disabled: no spans recorded
    assert len(obs.tracer) == 0


WORKER_SPANS = ("engine.wait", "engine.stage", "engine.put", "sched.run",
                "engine.ready", "engine.fetch", "engine.resolve")


def test_engine_obs_enabled_profiles_first_batch():
    """With obs on, the worker thread's states are spans that do not
    overlap, each with per-batch args; the eager layer walk is gone."""
    from repro.serving.engine import ConvNetEngine
    qnet, _ = _lenet_qnet()
    obs.enable()
    eng = ConvNetEngine(qnet, batch=2, backend="pallas")
    rng = np.random.default_rng(1)
    imgs = rng.normal(size=(3, *qnet.plan.input_shape)).astype(np.float32)
    eng.submit(imgs)
    deadline = time.monotonic() + 60      # the last resolve span may still
    while sum(e["name"] == "engine.resolve"            # be closing
              for e in obs.tracer.events()) < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    events = obs.tracer.events()
    names = [e["name"] for e in events]
    assert "engine.compile" in names and "engine.batch" not in names
    assert not hasattr(eng, "layer_profile")
    assert not any(n.startswith("layer:") for n in names)
    worker = sorted((e for e in events if e["name"] in WORKER_SPANS),
                    key=lambda e: e["ts"])
    assert {e["name"] for e in worker} == set(WORKER_SPANS)
    assert len({e["tid"] for e in worker}) == 1       # the worker thread
    for a, b in zip(worker, worker[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1e-3    # no overlap (µs)
    for name in WORKER_SPANS[1:]:
        per_batch = [e["args"] for e in worker if e["name"] == name]
        if name == "sched.run":                 # the padded launch
            assert [a["batch"] for a in per_batch] == [2, 2]
            continue
        assert [(a["n"], a["fill"]) for a in per_batch] == [(2, 1.0),
                                                           (1, 0.5)]
        assert per_batch[0]["reason"] == "full"
        assert per_batch[1]["reason"] in ("drain", "deadline")
    # obs off → same engine records nothing more (once the wait span that
    # was open when it went off has ended)
    obs.disable()
    eng.submit(imgs)
    n = len(obs.tracer)
    eng.submit(imgs)
    assert len(obs.tracer) == n


def test_span_opens_profiler_annotation_only_once_jax_is_loaded(
        monkeypatch):
    """A live span opens a jax.profiler.TraceAnnotation with its name and
    args; before jax is imported it opens none (obs stays importable
    without jax)."""
    import sys

    import jax
    from repro.obs import trace as trace_mod
    opened = []

    class Annotation:
        def __init__(self, name, **args):
            opened.append((name, args))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    obs.enable()
    with obs.span("engine.stage", reason="full", n=8, shape=(8, 3)):
        pass
    assert opened == [("engine.stage",
                       {"reason": "full", "n": 8, "shape": "(8, 3)"})]
    monkeypatch.delitem(sys.modules, "jax")
    assert trace_mod._profiler_annotation("x", {}) is None
    with obs.span("no_jax"):
        pass
    assert len(opened) == 1
    assert [e["name"] for e in obs.tracer.events()] == ["engine.stage",
                                                        "no_jax"]


def test_watch_compiles_counts_each_new_trace_once():
    """One listener however often it is asked for; each jit cache miss
    (a new shape) counts one program, its nested jits none, and a cached
    call none, with obs off too."""
    import jax
    import jax.numpy as jnp
    obs.watch_compiles()
    obs.watch_compiles()
    f = jax.jit(lambda x: x * 2 + 1)
    x3, x5 = jnp.ones(3), jnp.ones(5)
    counter = obs.metrics.counter(obs.COMPILES)
    before = counter.value
    f(x3).block_until_ready()
    after_one = counter.value
    f(x3).block_until_ready()
    assert counter.value == after_one
    f(x5).block_until_ready()
    assert after_one - before == 1 and counter.value - after_one == 1
    assert len(obs.tracer) == 0


def test_obs_dump_writes_both_artifacts(tmp_path):
    assert obs.dump(str(tmp_path)) is None       # disabled → nothing
    obs.enable()
    with obs.span("s"):
        pass
    obs.metrics.counter("c").inc()
    paths = obs.dump(str(tmp_path), prefix="t")
    trace = json.load(open(paths["trace"]))
    assert trace["traceEvents"]
    lines = [json.loads(ln) for ln in open(paths["metrics"])]
    assert any(d["name"] == "c" for d in lines)
