"""What the per-layer metrics read from a traced run.

Each metric under ``metrics/`` is a small file whose ``read(run)`` is one
of these.  ``run`` carries the trace's reduction (``run.trace``), the
traffic's record, the engine's histograms, the layer counts and the
chip's peaks.  A reader that finds nothing to read returns None, and the
metric is left out of the result line; a share of a peak or a roofline is
never reported as 0 for want of data.
"""

from __future__ import annotations

from typing import Optional

from perfbench.harness import traffic


def idle_percent(run) -> Optional[float]:
    """Share of the window in which no operation ran on the device."""
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * run.trace.idle_share


def kernel_roofline_percent(run) -> Optional[float]:
    """Least time of the program's layers over the device time of its
    Pallas kernels, summed over the program runs wholly in the window."""
    t = run.trace
    if t is None or t.runs == 0 or t.run_kernel_s <= 0:
        return None
    least = sum(lc.least_time(run.batch_per_device,
                              run.peak["int8_ops_per_s"],
                              run.peak["hbm_bytes_per_s"])[0]
                for lc in run.counts)
    return 100.0 * t.runs * least / t.run_kernel_s


def mfu_percent(run) -> Optional[float]:
    """Useful int8 operations of the images answered in the window, over
    what the chips' peak would do in the window."""
    t = run.trace
    done = run.record.completed_in_window()
    if t is None or t.window_s <= 0 or done == 0:
        return None
    return 100.0 * run.ops_per_image * done / (
        t.window_s * run.chips * run.peak["int8_ops_per_s"])


def queue_wait_p95_ms(run) -> Optional[float]:
    """The engine's own queue-wait histogram (log buckets, interpolated)."""
    h = run.engine["queue_wait_us"]
    return h.percentile(95.0) / 1e3 if h.count else None


def batch_fill_percent(run) -> Optional[float]:
    """Mean fill of the batches the engine formed in the window."""
    h = run.engine["batch_fill"]
    return 100.0 * h.mean if h.count else None


def gen_late_p95_ms(run) -> Optional[float]:
    """95th percentile of how late the open-loop generator sent a request
    after it was due."""
    rec = run.record
    if run.mix["loop"] != "open" or rec.attempted == 0:
        return None
    return traffic.percentile((rec.sent_ns - rec.due_ns) / 1e6, 95.0)


def latency_p95_ms(run) -> Optional[float]:
    """95th percentile of the open loop's latency, due to answered, over
    every request of the window (a failed one waited on to the grace)."""
    rec = run.record
    if run.mix["loop"] != "open" or rec.attempted == 0:
        return None
    return traffic.percentile(
        traffic.latencies_ms(rec, float(run.mix["grace_s"])), 95.0)
