"""One general load generator, driven by a traffic mix's parameters.

Two loops, as the mix's ``loop`` says:

* ``closed``: callers that each wait for their answers.  ``outstanding``
  groups of ``engine.batch`` requests stay submitted; when the oldest
  group's answers are all back, the next group goes in.  Groups are
  contiguous slices of the image pool, taken in a seeded order.
* ``open``: independent users.  ``round(rate_per_s * seconds)`` single
  requests arrive at seeded uniform times over the window (a Poisson
  process given its count, so every seed offers the same number) and are
  sent when due whatever the system is doing.

Each request is timed from when it was due (the open loop's schedule;
the moment it was sent in the closed loop) to when its answer is on the
host, read by a callback on its future.  A request that fails, or is not
answered ``grace_s`` after the window closes, counts as failed.

Only the answers that the check compares are kept: the first answer for
each pool image, and a share ``CHECK_SHARE`` of the others drawn from the
seed.  The callback copies those and drops every other answer with its
future, so the host holds a few hundred answers, not the window's all.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import threading
import time
from collections import deque
from concurrent.futures import TimeoutError as FutureTimeout
from typing import Callable, Dict, Optional

import numpy as np

CHECK_SHARE = 1 / 64     # of the answers after each pool image's first


@dataclasses.dataclass
class Record:
    t0_ns: int                   # window start
    t1_ns: int                   # window end
    pool_idx: np.ndarray         # per request: which pool image
    due_ns: np.ndarray
    sent_ns: np.ndarray
    done_ns: np.ndarray          # 0 = no answer
    failed_mask: np.ndarray      # the request raised or was never answered
    answers: Dict[int, np.ndarray]   # request -> answer, for those kept
    queue_depth_start: Optional[int] = None
    queue_depth_end: Optional[int] = None

    @property
    def attempted(self) -> int:
        return len(self.pool_idx)

    @property
    def failed(self) -> int:
        return int(self.failed_mask.sum())

    def completed_in_window(self) -> int:
        d = self.done_ns
        return int(np.sum((d > 0) & (d <= self.t1_ns) & ~self.failed_mask))


class _Collector:
    """Per request: when its answer came, whether it failed, and the
    answer itself where the check will compare it.  ``done`` runs on the
    engine's thread as each future resolves."""

    def __init__(self, cap: int, seed: int):
        self.idx = np.zeros(cap, np.int64)
        self.due = np.zeros(cap, np.int64)
        self.sent = np.zeros(cap, np.int64)
        self.done_ns = np.zeros(cap, np.int64)
        self.failed = np.zeros(cap, bool)
        self.keep = np.zeros(cap, bool)
        self.answers: Dict[int, np.ndarray] = {}
        self.n = 0
        self._first: set = set()
        self._rng = np.random.default_rng([seed, 11])
        self._lock = threading.Lock()
        self._resolved = 0
        self._closed = False
        self._all = threading.Event()

    def add(self, f, k: int, due: int, sent: int) -> None:
        i = self.n
        self.n += 1
        self.idx[i], self.due[i], self.sent[i] = k, due, sent
        first = k not in self._first
        self._first.add(k)
        self.keep[i] = first or self._rng.random() < CHECK_SHARE
        f.add_done_callback(lambda fut: self.done(i, fut))

    def done(self, i: int, fut) -> None:
        self.done_ns[i] = time.perf_counter_ns()
        if fut.exception() is not None:
            self.failed[i] = True
        elif self.keep[i]:
            self.answers[i] = np.array(fut.result())
        with self._lock:
            self._resolved += 1
            if self._closed and self._resolved == self.n:
                self._all.set()

    def close(self, timeout: float) -> None:
        """No more requests: wait up to ``timeout`` s for every answer."""
        with self._lock:
            self._closed = True
            if self._resolved == self.n:
                self._all.set()
        self._all.wait(timeout)

    def record(self, t0: int, t1: int, **depths) -> Record:
        n = self.n
        with self._lock:
            unanswered = self.done_ns[:n] == 0
            failed = self.failed[:n] | unanswered
            answers = {i: a for i, a in self.answers.items()
                       if not failed[i]}
        return Record(t0, t1, self.idx[:n].copy(), self.due[:n].copy(),
                      self.sent[:n].copy(), np.where(unanswered, 0,
                                                     self.done_ns[:n]),
                      failed, answers, **depths)


def _annotate(trace: bool) -> Callable:
    if trace:
        import jax
        return jax.profiler.TraceAnnotation
    return lambda name: contextlib.nullcontext()


def run(engine, pool: np.ndarray, mix: dict, seed: int, seconds: float,
        trace: bool) -> Record:
    """Offer ``mix`` to ``engine`` for ``seconds``; wait for every answer
    (up to ``mix["grace_s"]`` past the window's end)."""
    rng = np.random.default_rng([seed, 7])
    loop = {"closed": _closed, "open": _open}[mix["loop"]]
    # A full pass of the cyclic collector stops every thread for as long as
    # it scans, at random points of the window; so the collector is off
    # until the answers are in, as ``timeit`` turns it off.
    gc.collect()
    gc.disable()
    try:
        return loop(engine, pool, mix, rng, seconds, _annotate(trace),
                    _Collector(_cap(mix, seconds), seed))
    finally:
        gc.enable()


def _cap(mix: dict, seconds: float) -> int:
    if mix["loop"] == "open":
        return int(round(mix["rate_per_s"] * seconds))
    return 1 << 20


def _closed(engine, pool, mix, rng, seconds, ann, col) -> Record:
    b = engine.batch
    groups = pool.shape[0] // b
    if groups < 1:
        raise ValueError(f"pool of {pool.shape[0]} holds no batch of {b}")
    outstanding: deque = deque()
    order = rng.permutation(groups)
    n_groups = 0
    t0 = time.perf_counter_ns()
    t1 = t0 + int(seconds * 1e9)
    with ann("bench.window"):
        while True:
            while (len(outstanding) < mix["outstanding"]
                   and time.perf_counter_ns() < t1):
                g = int(order[n_groups % groups])
                n_groups += 1
                with ann("bench.submit"):
                    now = time.perf_counter_ns()
                    fs = engine.submit_async(pool[g * b:(g + 1) * b],
                                             priority=mix["priority"])
                for j, f in enumerate(fs):
                    col.add(f, g * b + j, now, now)
                outstanding.append(fs)
            if not outstanding or time.perf_counter_ns() >= t1:
                break
            with ann("bench.wait_result"):
                try:
                    for f in outstanding.popleft():
                        f.exception(timeout=float(mix["grace_s"]))
                except FutureTimeout:
                    break
            if time.perf_counter_ns() >= t1:
                break
        wait = t1 - time.perf_counter_ns()
        if wait > 0:
            time.sleep(wait / 1e9)
    outstanding.clear()
    col.close(float(mix["grace_s"]))
    return col.record(t0, t1)


def _open(engine, pool, mix, rng, seconds, ann, col) -> Record:
    n = int(round(mix["rate_per_s"] * seconds))
    offsets = np.sort(rng.uniform(0.0, seconds, n))
    idx = rng.integers(0, pool.shape[0], n)
    depth = engine.queue.__len__
    t0 = time.perf_counter_ns()
    t1 = t0 + int(seconds * 1e9)
    due = t0 + (offsets * 1e9).astype(np.int64)
    start_depth = None
    with ann("bench.window"):
        for i in range(n):
            delay = due[i] - time.perf_counter_ns()
            if delay > 0:
                with ann("bench.sleep"):
                    time.sleep(delay / 1e9)
            if start_depth is None and due[i] - t0 >= 1e9:
                start_depth = depth()
            with ann("bench.submit"):
                sent = time.perf_counter_ns()
                f = engine.submit_async(pool[idx[i]],
                                        priority=mix["priority"])
            col.add(f, int(idx[i]), int(due[i]), sent)
        end_depth = depth()
        wait = t1 - time.perf_counter_ns()
        if wait > 0:
            time.sleep(wait / 1e9)
    col.close(float(mix["grace_s"]))
    return col.record(t0, t1, queue_depth_start=start_depth,
                      queue_depth_end=end_depth)


def percentile(values, p: float) -> float:
    """Linear interpolation between closest ranks over the raw samples
    (the arithmetic of benchmarks/bench_util.Timing.percentiles)."""
    xs = sorted(values)
    n = len(xs)
    if n == 0:
        raise ValueError("no samples")
    if n == 1:
        return float(xs[0])
    x = (p / 100.0) * (n - 1)
    lo = int(x)
    hi = min(lo + 1, n - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (x - lo))


def open_summary(rec: Record, grace_s: float) -> dict:
    """Where an open-loop window lost its time, for the run log: the
    latency tail, how late the generator sent, the engine's queue depth,
    the p95 of each fifth of the window by due time, and the longest gaps
    between two answers (a host or device stall shows as one)."""
    lat = latencies_ms(rec, grace_s)
    late = (rec.sent_ns - rec.due_ns) / 1e6
    fifth = np.minimum((rec.due_ns - rec.t0_ns) * 5
                       // max(rec.t1_ns - rec.t0_ns, 1), 4)
    done = np.sort(rec.done_ns[rec.done_ns > 0])
    gaps = np.sort(np.diff(done))[::-1] / 1e6 if done.size > 1 else []
    return {
        "latency_ms_p50_p95_p99_max": [
            round(percentile(lat, p), 3) for p in (50, 95, 99, 100)],
        "over_100_ms": int(np.sum(lat > 100.0)),
        "late_ms_p95_max": [round(percentile(late, p), 3) for p in (95, 100)],
        "queue_depth_1s_end": [rec.queue_depth_start, rec.queue_depth_end],
        "p95_ms_by_fifth": [round(percentile(lat[fifth == k], 95), 3)
                            for k in range(5) if np.any(fifth == k)],
        "answer_gaps_ms_top5": [round(float(g), 3) for g in gaps[:5]],
        "answer_gaps_over_30_ms": int(np.sum(np.asarray(gaps) > 30.0)),
    }


def latencies_ms(rec: Record, grace_s: float) -> np.ndarray:
    """Per request, due to answered in ms; a failed request counts as
    waited on until the grace period ran out."""
    end = np.where(rec.failed_mask, rec.t1_ns + int(grace_s * 1e9),
                   rec.done_ns)
    return (end - rec.due_ns) / 1e6
