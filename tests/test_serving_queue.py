"""Continuous-batching serving: queue semantics, LRU program cache,
batch routing, and end-to-end engine consistency.

Formation semantics (full / deadline / drain, priority lanes, aging)
are tested against :class:`RequestQueue` directly with an injected fake
clock — pure functions of (queue contents, time), no threads, no
sleeps.  The engine integration tests then exercise the real worker
thread: deadline launches without a drain waiter, LRU evict → recompile
→ bit-exact logits, and ≥4 concurrent submitters with zero dropped or
duplicated responses."""

import threading
from concurrent.futures import Future

import numpy as np
import pytest

from repro import obs
from repro.core import network
from repro.serving.batching import (ContinuousBatchingEngine, ProgramCache,
                                    RequestQueue, ServeRequest)

MS = 1_000_000                           # ns per ms


def _registry():
    return obs.MetricsRegistry()


def _queue(clock, **kw):
    kw.setdefault("deadline_ms", 5.0)
    kw.setdefault("bulk_aging_ms", 50.0)
    return RequestQueue(_registry(), clock=clock, **kw)


class FakeClock:
    def __init__(self):
        self.t = 0

    def __call__(self):
        return self.t


def _req(uid, model="m", priority="interactive", enq=0,
         deadline_ns=5 * MS):
    return ServeRequest(uid=uid, model=model,
                        image=np.zeros((2, 2, 1), np.float32),
                        priority=priority, enqueue_ns=enq,
                        deadline_ns=enq + deadline_ns, future=Future())


# -- formation: full / deadline / drain --------------------------------------

def test_deadline_fires_on_lone_request():
    clk = FakeClock()
    q = _queue(clk)
    q.push_many([_req(0)])
    assert q.form(8) is None                   # young: no launch
    clk.t = 5 * MS - 1
    assert q.form(8) is None                   # still inside deadline
    clk.t = 5 * MS
    fb = q.form(8)
    assert fb is not None and fb.reason == "deadline"
    assert [r.uid for r in fb.requests] == [0]
    assert len(q) == 0


def test_full_batch_fires_before_deadline():
    clk = FakeClock()
    q = _queue(clk)
    q.push_many([_req(i) for i in range(4)])
    fb = q.form(4)                             # t=0: way inside deadline
    assert fb.reason == "full"
    assert [r.uid for r in fb.requests] == [0, 1, 2, 3]


def test_drain_launches_partial_batch():
    clk = FakeClock()
    q = _queue(clk)
    q.push_many([_req(0), _req(1)])
    assert q.form(4) is None                   # not full, not due
    fb = q.form(4, drain=True)
    assert fb.reason == "drain"
    assert [r.uid for r in fb.requests] == [0, 1]


def test_full_model_wins_over_drain_and_takes_only_its_own():
    clk = FakeClock()
    q = _queue(clk)
    q.push_many([_req(0, model="a"), _req(1, model="b"),
                 _req(2, model="b")])
    fb = q.form(2, drain=True)
    assert fb.reason == "full" and fb.model == "b"
    assert [r.uid for r in fb.requests] == [1, 2]
    # model a's request stays queued, FIFO intact
    fb2 = q.form(2, drain=True)
    assert fb2.reason == "drain" and fb2.model == "a"
    assert [r.uid for r in fb2.requests] == [0]


def test_deadline_launches_oldest_requests_model():
    clk = FakeClock()
    q = _queue(clk)
    q.push_many([_req(0, model="a")])
    clk.t = 2 * MS
    q.push_many([_req(1, model="b", enq=clk.t)])
    clk.t = 5 * MS                             # a is due, b is not
    fb = q.form(8)
    assert fb.reason == "deadline" and fb.model == "a"


# -- priority lanes + aging --------------------------------------------------

def test_interactive_preempts_fresh_bulk():
    clk = FakeClock()
    q = _queue(clk)
    q.push_many([_req(0, priority="bulk"), _req(1, priority="bulk")])
    clk.t = 1 * MS
    q.push_many([_req(2, enq=clk.t), _req(3, enq=clk.t)])
    fb = q.form(2, drain=True)
    assert [r.uid for r in fb.requests] == [2, 3]   # interactive first
    fb2 = q.form(2, drain=True)
    assert [r.uid for r in fb2.requests] == [0, 1]  # bulk not dropped


def test_aged_bulk_outranks_newer_interactive():
    """Starvation-free: bulk older than the aging window merges into the
    interactive ordering by ORIGINAL enqueue time, so a steady
    interactive flood cannot hold it off forever."""
    clk = FakeClock()
    q = _queue(clk, bulk_aging_ms=50.0)
    q.push_many([_req(0, priority="bulk")])
    clk.t = 60 * MS                            # bulk is past aging
    q.push_many([_req(1, enq=clk.t), _req(2, enq=clk.t)])
    fb = q.form(2, drain=True)
    assert [r.uid for r in fb.requests] == [0, 1]   # aged bulk leads
    # under the window the same bulk request would have waited
    clk2 = FakeClock()
    q2 = _queue(clk2, bulk_aging_ms=50.0)
    q2.push_many([_req(0, priority="bulk")])
    clk2.t = 10 * MS
    q2.push_many([_req(1, enq=clk2.t), _req(2, enq=clk2.t)])
    fb2 = q2.form(2, drain=True)
    assert [r.uid for r in fb2.requests] == [1, 2]


def test_queue_depth_gauge_and_validation():
    clk = FakeClock()
    reg = _registry()
    q = RequestQueue(reg, deadline_ms=5.0, clock=clk)
    q.push_many([_req(i) for i in range(3)])
    assert reg.gauge("queue.depth").value == 3
    assert reg.gauge("queue.depth.peak").value == 3
    q.form(2, drain=True)
    assert reg.gauge("queue.depth").value == 1
    assert reg.gauge("queue.depth.peak").value == 3   # peak sticks
    with pytest.raises(ValueError):
        q.push_many([_req(9, priority="nope")])
    with pytest.raises(ValueError):
        RequestQueue(_registry(), deadline_ms=0.0, clock=clk)


# -- LRU program cache -------------------------------------------------------

def test_program_cache_lru_eviction_and_counters():
    reg = _registry()
    cache = ProgramCache(2, reg)
    built = []

    def mk(k):
        def build():
            built.append(k)
            return f"prog-{k}"
        return build

    assert cache.get("a", mk("a")) == "prog-a"
    assert cache.get("b", mk("b")) == "prog-b"
    assert cache.get("a", mk("a")) == "prog-a"       # hit refreshes a
    assert cache.get("c", mk("c")) == "prog-c"       # evicts b (LRU)
    assert cache.keys() == ["a", "c"]
    assert cache.get("b", mk("b")) == "prog-b"       # rebuild b
    assert built == ["a", "b", "c", "b"]
    assert reg.counter("cache.hits").value == 1
    assert reg.counter("cache.misses").value == 4
    assert reg.counter("cache.evictions").value == 2
    assert len(cache) == 2
    with pytest.raises(ValueError):
        ProgramCache(0, _registry())


# -- per-batch scheduler routing --------------------------------------------

def test_route_batch_flips_with_formed_size():
    from repro.core.autotune import route_batch
    tune = _TUNES["small"]
    mode1, cores1, cyc1 = route_batch(tune.layers, 1, 8)
    mode8, cores8, cyc8 = route_batch(tune.layers, 8, 8)
    # one image can't batch-shard: the cores must go inside the program
    assert mode1 in ("kout", "spatial")
    assert cores1 == 8
    # a full batch divides compute across every core with no halo tax
    assert mode8 == "batch" and cores8 == 8
    assert cyc8 >= cyc1                        # more images, more cycles
    # the verdict is never worse than forcing either extreme
    from repro.core.autotune import schedule_cycles
    assert cyc1 <= 1 * schedule_cycles(tune.layers, "batch", 1)
    assert cyc8 <= 8 * schedule_cycles(tune.layers, "batch", 8)
    with pytest.raises(ValueError):
        route_batch(tune.layers, 0, 8)
    with pytest.raises(ValueError):
        route_batch(tune.layers, 1, 0)


# -- engine integration ------------------------------------------------------

_QNETS = {}
_TUNES = {}


def _qnet(shape=(12, 12, 1)):
    if shape not in _QNETS:
        rng = np.random.default_rng(0)
        plan = network.lenet(input_shape=shape)
        params = plan.init_params(rng)
        x = np.asarray(rng.normal(size=(1, *shape)), np.float32)
        _QNETS[shape] = network.quantize_network(plan, params, x)
    return _QNETS[shape]


def setup_module(_m):
    from repro.core.autotune import autotune_network
    _TUNES["small"] = autotune_network(network.lenet(input_shape=(12, 12, 1)))


def test_deadline_launch_without_drain_waiter():
    """A lone async request must come back without anyone draining —
    the worker's deadline timeout is what launches it."""
    eng = ContinuousBatchingEngine(batch=8, backend="pallas",
                                   deadline_ms=25.0)
    try:
        eng.add_model(_qnet())
        fut = eng.submit_async(np.zeros((12, 12, 1), np.float32))
        logits = fut.result(timeout=300)
        assert logits.shape == (10,)
        counts = eng.formation_counts()
        assert counts["deadline"] == 1 and counts["full"] == 0
        assert eng.stats == {"requests": 1, "batches": 1, "padded": 7}
        assert eng.metrics.histogram("queue_wait_us").summary()["count"] == 1
    finally:
        eng.close()


def test_lru_evict_recompile_bit_exact():
    """capacity=1 multi-model serving: adding model b evicts a's
    program; the recompile on a's next batch must be observable
    (eviction/miss counters) and bit-exact with a fresh engine."""
    qa, qb = _qnet((12, 12, 1)), _qnet((10, 10, 1))
    rng = np.random.default_rng(7)
    imgs = rng.normal(size=(3, 12, 12, 1)).astype(np.float32)
    eng = ContinuousBatchingEngine(batch=2, backend="pallas",
                                   cache_capacity=1)
    try:
        eng.add_model(qa, name="a")
        eng.add_model(qb, name="b")            # evicts a's program
        assert eng.cache_stats()["evictions"] == 1
        got = eng.submit(imgs, model="a")      # recompile (miss)
        stats = eng.cache_stats()
        assert stats["misses"] == 3 and stats["evictions"] == 2
        assert stats["size"] == 1 and stats["capacity"] == 1
        # admission by unique input shape still finds model b
        out_b = eng.submit(rng.normal(size=(1, 10, 10, 1))
                           .astype(np.float32))
        assert out_b.shape == (1, 10)
    finally:
        eng.close()
    fresh = ContinuousBatchingEngine(batch=2, backend="pallas")
    try:
        fresh.add_model(qa, name="a")
        want = fresh.submit(imgs, model="a")
    finally:
        fresh.close()
    np.testing.assert_array_equal(got, want)


def test_concurrent_submitters_consistent():
    """≥4 threads share one engine; every thread must get exactly its
    own logits back (zero dropped, zero duplicated, zero cross-wired),
    bit-exact with the reference program run row-by-row."""
    import jax.numpy as jnp

    from repro.core.convcore import ConvCoreConfig
    from repro.core.network import make_int8_program
    qnet = _qnet()
    prog = make_int8_program(qnet, ConvCoreConfig(backend="pallas",
                                                  int8=True))
    eng = ContinuousBatchingEngine(batch=4, backend="pallas",
                                   deadline_ms=50.0)
    n_threads, per = 4, 6
    rng = np.random.default_rng(3)
    # distinct images per thread so a cross-wired response is detectable
    images = [rng.normal(size=(per, 12, 12, 1)).astype(np.float32)
              for _ in range(n_threads)]
    results = [None] * n_threads
    errors = []

    def work(t):
        try:
            results[t] = eng.submit(images[t])
        except BaseException as e:             # pragma: no cover
            errors.append((t, e))

    try:
        eng.add_model(qnet)
        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=300)
        assert not errors, errors
        assert all(r is not None for r in results)
        for t in range(n_threads):
            assert results[t].shape == (per, 10)
            for i in range(per):
                want = np.asarray(prog(jnp.asarray(images[t][i][None])))[0]
                np.testing.assert_array_equal(results[t][i], want)
        s = eng.stats
        assert s["requests"] == n_threads * per
        # continuous batching mixes threads' requests into shared
        # batches: fewer launches than the per-thread sync floor
        assert s["batches"] <= n_threads * per
        assert eng.latency_percentiles()["count"] == n_threads * per
    finally:
        eng.close()


def test_engine_validation_and_admission_errors():
    eng = ContinuousBatchingEngine(batch=2, backend="pallas")
    try:
        with pytest.raises(ValueError, match="no models"):
            eng.submit_async(np.zeros((12, 12, 1), np.float32))
        eng.add_model(_qnet(), name="m")
        with pytest.raises(ValueError, match="already registered"):
            eng.add_model(_qnet(), name="m")
        with pytest.raises(ValueError, match="unknown model"):
            eng.submit_async(np.zeros((12, 12, 1), np.float32),
                             model="nope")
        with pytest.raises(ValueError, match="input shape"):
            eng.submit_async(np.zeros((9, 9, 1), np.float32), model="m")
        with pytest.raises(ValueError, match="unknown priority"):
            eng.submit_async(np.zeros((12, 12, 1), np.float32),
                             priority="urgent")
        assert eng.models() == ["m"]
    finally:
        eng.close()
    with pytest.raises(ValueError):
        ContinuousBatchingEngine(batch=0)
    with pytest.raises(ValueError):
        ContinuousBatchingEngine(max_inflight=0)


def test_close_drains_queued_work():
    eng = ContinuousBatchingEngine(batch=4, backend="pallas",
                                   deadline_ms=10_000.0)
    eng.add_model(_qnet())
    futs = eng.submit_async(np.zeros((2, 12, 12, 1), np.float32))
    eng.close()                                # must not strand the futures
    for f in futs:
        assert f.result(timeout=60).shape == (10,)
    with pytest.raises(RuntimeError):
        eng.submit_async(np.zeros((12, 12, 1), np.float32))


def test_batch_mode_runs_on_real_devices():
    """n_cores=4 with four devices present: MultiCoreScheduler's batch
    mode splits each formed batch across the devices (shard_map), never
    the vmapped virtual cores, and serves logits bit-identical to one
    device."""
    _run_on_four_devices("""
        out = {}
        for cores in (1, 4):
            with ContinuousBatchingEngine(batch=4, n_cores=cores,
                                          backend="pallas") as eng:
                eng.add_model(qnet)
                out[cores] = eng.submit(x)
        np.testing.assert_array_equal(out[1], out[4])
        assert obs.metrics.counter("sched.batch.device").value == 2
        assert obs.metrics.counter("sched.batch.virtual").value == 0
    """)


def test_device_programs_live_as_long_as_the_lru_entry():
    """With n_cores=4 on four devices the scheduler keeps one shard_map
    wrapper per program, and only while the engine's LRU keeps that
    program: evict and recompile, and the scheduler holds one entry, not
    one per compile.  The recompiled program serves the same logits."""
    _run_on_four_devices("""
        import gc
        other = network.lenet(input_shape=(16, 16, 1))
        y = rng.normal(size=(4, 16, 16, 1)).astype(np.float32)
        qother = network.quantize_network(other, other.init_params(rng), y)
        with ContinuousBatchingEngine(batch=4, n_cores=4, backend="pallas",
                                      cache_capacity=1) as eng:
            a = eng.add_model(qnet, name="a")
            eng.add_model(qother, name="b")          # evicts a
            sched = eng._models[a].sched
            first = eng.submit(x, model=a)           # recompiles a
            eng.submit(y, model="b")                 # evicts a again
            second = eng.submit(x, model=a)          # and recompiles it
            gc.collect()
            assert len(sched._per_device) == 1, len(sched._per_device)
            assert eng.metrics.counter("cache.evictions").value >= 3
        np.testing.assert_array_equal(first, second)
    """)


def _run_on_four_devices(body: str) -> None:
    """Run ``body`` in a subprocess that sees four CPU devices, after a
    prelude that builds a quantized 12×12 lenet (``qnet``, images ``x``,
    ``rng``), so the four-device flag never leaks into this process."""
    import os
    import subprocess
    import sys
    import textwrap
    prelude = """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, numpy as np
        from repro import obs
        from repro.core import network
        from repro.serving.batching import ContinuousBatchingEngine
        assert jax.device_count() == 4
        plan = network.lenet(input_shape=(12, 12, 1))
        rng = np.random.default_rng(0)
        params = plan.init_params(rng)
        x = rng.normal(size=(8, 12, 12, 1)).astype(np.float32)
        qnet = network.quantize_network(plan, params, x)
    """
    script = (textwrap.dedent(prelude) + textwrap.dedent(body)
              + 'print("DEVICES_OK")\n')
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, cwd=repo, timeout=560)
    assert "DEVICES_OK" in r.stdout, r.stdout[-1500:] + r.stderr[-4000:]
