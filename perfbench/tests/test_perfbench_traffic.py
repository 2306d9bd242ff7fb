"""The load generator against a stand-in engine: which answers a run keeps
for the check, and how it counts requests that fail or never come back."""

import concurrent.futures as cf
import sys

import numpy as np
import pytest

from perfbench.harness import traffic

POOL = np.arange(16, dtype=np.float32).reshape(16, 1, 1, 1)


class StandIn:
    """Answers an image with twice its value on one worker thread.  Images
    in ``fail`` raise; images in ``lose`` are never answered."""

    def __init__(self, batch, fail=(), lose=()):
        self.batch = batch
        self.queue = []
        self.fail, self.lose = set(fail), set(lose)
        self.worker = cf.ThreadPoolExecutor(1)

    def _one(self, x):
        f = cf.Future()
        k = int(np.asarray(x).ravel()[0])
        if k in self.lose:
            return f

        def answer():
            if k in self.fail:
                f.set_exception(RuntimeError(f"image {k}"))
            else:
                f.set_result(np.asarray(x).ravel() * 2.0)
        self.worker.submit(answer)
        return f

    def submit_async(self, images, priority):
        if images.ndim == 3:
            return self._one(images)
        return [self._one(x) for x in images]


def _run(engine, mix, seconds=0.3, seed=3):
    try:
        return traffic.run(engine, POOL, {"grace_s": 0.5, "priority": "bulk",
                                          **mix}, seed, seconds, trace=False)
    finally:
        engine.worker.shutdown(wait=True)


@pytest.mark.parametrize("mix", [
    {"loop": "closed", "outstanding": 2},
    {"loop": "open", "rate_per_s": 2000.0}])
def test_keeps_each_images_first_answer_and_a_sample(mix):
    # answers resolve on the engine's thread while requests go in: switch
    # threads often, so that an update lost between them would show
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        rec = _run(StandIn(batch=4), mix)
    finally:
        sys.setswitchinterval(interval)
    assert rec.failed == 0 and rec.attempted > 200
    kept = sorted(rec.answers)
    first = {}
    for i, k in enumerate(rec.pool_idx):
        first.setdefault(int(k), i)
    assert set(first.values()) <= set(kept)
    assert len(first) < len(kept) < rec.attempted // 4
    for i in kept:
        np.testing.assert_array_equal(rec.answers[i],
                                      [2.0 * rec.pool_idx[i]])
    assert 0 < rec.completed_in_window() <= rec.attempted


def test_failed_and_unanswered_requests_count_as_failed():
    grace = 0.5
    rec = _run(StandIn(batch=4, fail={3}, lose={5}),
               {"loop": "open", "rate_per_s": 500.0, "grace_s": grace})
    bad = np.isin(rec.pool_idx, [3, 5])
    assert bad.any()
    assert rec.failed == int(bad.sum())
    assert not set(np.flatnonzero(bad)) & set(rec.answers)
    lat = traffic.latencies_ms(rec, grace)
    np.testing.assert_allclose(
        lat[bad], (rec.t1_ns + grace * 1e9 - rec.due_ns[bad]) / 1e6)
    assert (lat[~bad] < grace * 1e3).all()


def test_open_loop_tail_reader_and_summary():
    import types

    from perfbench.harness import readers
    grace = 0.5
    mix = {"loop": "open", "rate_per_s": 500.0, "grace_s": grace}
    rec = _run(StandIn(batch=4, lose={5}), mix)
    lat = traffic.latencies_ms(rec, grace)
    p95 = readers.latency_p95_ms(types.SimpleNamespace(record=rec, mix=mix))
    assert p95 == traffic.percentile(lat, 95.0)
    s = traffic.open_summary(rec, grace)
    assert s["latency_ms_p50_p95_p99_max"][1] == round(p95, 3)
    assert s["latency_ms_p50_p95_p99_max"][3] == round(float(lat.max()), 3)
    assert s["over_100_ms"] == int((lat > 100.0).sum()) > 0
    assert len(s["p95_ms_by_fifth"]) == 5
    closed = types.SimpleNamespace(record=rec, mix={"loop": "closed"})
    assert readers.latency_p95_ms(closed) is None
