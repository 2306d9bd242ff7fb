"""Timing helpers for the benchmark harness (CPU host; kernel numbers on
this container are functional references — the TPU numbers come from the
roofline analysis of the compiled dry-run)."""

from __future__ import annotations

import time

import jax


class Timing(float):
    """Median wall-time per call in microseconds, carrying the full stats
    record: ``min`` / ``median`` / ``iqr`` and the raw sample list.  A
    float subclass, so every ``time_fn`` call site reads it as the
    median."""

    def __new__(cls, samples_us):
        times = sorted(samples_us)
        n = len(times)
        if n == 0:
            raise ValueError("Timing needs at least one sample")
        # proper median: mean of the two middle elements when n is even
        # (the old harness took the upper-middle one)
        mid = n // 2
        median = times[mid] if n % 2 else (times[mid - 1] + times[mid]) / 2.0
        self = super().__new__(cls, median)
        self.samples_us = tuple(times)
        self.median_us = median
        self.min_us = times[0]
        q1 = times[max(0, (n - 1) // 4)]
        q3 = times[min(n - 1, (3 * (n - 1) + 2) // 4)]
        self.iqr_us = q3 - q1
        return self

    def stats(self) -> dict:
        return {"median_us": self.median_us, "min_us": self.min_us,
                "iqr_us": self.iqr_us, "samples_us": list(self.samples_us)}

    def to_histogram(self, name: str):
        """Feed the samples into an obs histogram (global registry) and
        return it — the bridge from one-shot bench timings to the
        percentile machinery serving uses."""
        from repro import obs
        h = obs.metrics.histogram(name)
        for s in self.samples_us:
            h.observe(s)
        return h

    def percentiles(self) -> dict:
        """Exact p50/p90/p99 over the raw samples (no bucketing — bench
        runs hold every sample, unlike the serving histograms)."""
        times = self.samples_us
        n = len(times)

        def pct(p):
            if n == 1:
                return times[0]
            # linear interpolation between closest ranks
            x = (p / 100.0) * (n - 1)
            lo = int(x)
            hi = min(lo + 1, n - 1)
            return times[lo] + (times[hi] - times[lo]) * (x - lo)

        return {"count": n, "p50": pct(50), "p90": pct(90),
                "p99": pct(99), "min": times[0], "max": times[-1]}


def time_fn(fn, *args, iters: int = 5, warmup: int = 2) -> Timing:
    """Median wall-time per call in microseconds (blocks on results).
    Returns a :class:`Timing` — a float (the median) that also carries
    min / IQR / the sample list."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e6)
    return Timing(times)


def emit(name: str, us: float, derived: str = ""):
    print(f"{name},{us:.1f},{derived}")
    from repro import obs
    if obs.enabled():
        h = obs.metrics.histogram(f"bench.{name}")
        if isinstance(us, Timing):
            for s in us.samples_us:
                h.observe(s)
        else:
            h.observe(float(us))
