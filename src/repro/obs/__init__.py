"""obs — the telemetry subsystem: spans, metrics, per-layer profiles,
drift detection.

One import surface for everything observable in the runtime:

* ``obs.span("compile")`` / ``obs.span("layer:conv1", psums=...)`` —
  nestable trace spans (obs/trace.py) exported as Chrome
  ``chrome://tracing`` JSON that Perfetto loads directly, and, once jax
  is imported, also opened as ``jax.profiler.TraceAnnotation``s, so a
  ``jax.profiler`` trace shows them on its host plane beside the
  device's operations;
* ``obs.metrics`` — the process-global :class:`MetricsRegistry`
  (obs/metrics.py): counters, gauges, p50/p90/p99 histograms, JSONL
  export, ``reset()`` for tests;
* ``obs.watch_compiles()`` — counts every jit cache miss in
  ``obs.metrics``' ``jax.compiles`` counter;
* ``obs.profile.profile_network`` — per-layer wall time / psums /
  achieved GOPS / §5.2 cost-model prediction over any compiled
  ``NetworkPlan`` program, plus the drift detector (obs/profile.py),
  both called explicitly, offline.

**Disabled by default, zero overhead when disabled.**  ``obs.span``
checks one module flag and returns a shared no-op context manager; the
tier-1 numerical tests and the §5.2 anchors run with the subsystem off
and cannot observe it.  Enable with ``obs.enable()`` or by exporting
``REPRO_OBS=1`` before import.  ``obs.metrics`` is live regardless of
the flag — incrementing a counter is nanoseconds and serving code
(``ConvNetEngine.stats``) depends on its counts — but nothing *records
spans* unless enabled.

``obs.dump(dir)`` writes the trace (``obs_trace.json``) and the metrics
(``obs_metrics.jsonl``) — the CI ``obs-smoke`` lane uploads both.

Dependency-free (stdlib only): importable before jax, usable in every
process the runtime runs in.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Optional

from repro.obs.metrics import (Counter, Gauge, Histogram,  # noqa: F401
                               MetricsRegistry, default_buckets)
from repro.obs.trace import NOOP_SPAN, Span, Tracer  # noqa: F401

# -- global state -----------------------------------------------------------

_enabled = False
tracer = Tracer()
metrics = MetricsRegistry()


def enable() -> None:
    """Turn span recording / profiling on (idempotent)."""
    global _enabled
    _enabled = True


def disable() -> None:
    """Back to the zero-overhead no-op sink (idempotent).  Collected
    events/metrics stay until ``reset()``."""
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


def reset() -> None:
    """Clear the trace buffer and zero every metric — the test contract:
    enable → exercise → assert → reset leaves nothing behind."""
    tracer.reset()
    metrics.reset()


def span(name: str, **args: Any):
    """A trace span when enabled, the shared no-op otherwise.  The
    disabled path is one global load + one branch — no allocation, no
    clock read."""
    if not _enabled:
        return NOOP_SPAN
    return tracer.span(name, **args)


def instant(name: str, **args: Any) -> None:
    """A zero-duration trace mark (drift warnings etc.); no-op when
    disabled."""
    if _enabled:
        tracer.instant(name, **args)


COMPILES = "jax.compiles"
COMPILE_MARK = "jax.compile"
# once per program a jit cache miss lowers for the backend (nested jits
# inline into it; the backend then compiles it or the persistent cache
# serves it)
_LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_compiles_lock = threading.Lock()
_compiles_watched = False


def _on_jax_duration(event: str, duration_secs: float, **kwargs) -> None:
    if event != _LOWERING_EVENT:
        return
    metrics.counter(COMPILES).inc()
    import jax
    with jax.profiler.TraceAnnotation(COMPILE_MARK,
                                      fun=str(kwargs.get("fun_name", ""))):
        pass


def watch_compiles() -> None:
    """Count every jit cache miss, each new program lowered whether the
    persistent cache or XLA then serves it, in ``metrics``'
    ``jax.compiles`` counter (live regardless of the flag; an eager op on
    a new shape is a program too), and mark each one as a zero-length
    ``jax.compile`` event in whatever ``jax.profiler`` trace is running,
    so a trace shows when it happened.  Imports jax; registers its
    ``jax.monitoring`` listener once per process."""
    global _compiles_watched
    with _compiles_lock:
        if _compiles_watched:
            return
        from jax import monitoring
        metrics.counter(COMPILES)
        monitoring.register_event_duration_secs_listener(_on_jax_duration)
        _compiles_watched = True


def dump(out_dir: str = ".", prefix: str = "obs") -> Optional[dict]:
    """Export the Chrome trace + metrics JSONL into ``out_dir``;
    returns the written paths (None when disabled — nothing was
    collected)."""
    if not _enabled:
        return None
    os.makedirs(out_dir, exist_ok=True)
    return {
        "trace": tracer.export(
            os.path.join(out_dir, f"{prefix}_trace.json")),
        "metrics": metrics.export_jsonl(
            os.path.join(out_dir, f"{prefix}_metrics.jsonl")),
    }


# REPRO_OBS=1 (or any non-empty value except "0") enables at import — the
# env-var path CI's obs-smoke lane and ad-hoc benchmark runs use.
if os.environ.get("REPRO_OBS", "0") not in ("", "0"):
    enable()
