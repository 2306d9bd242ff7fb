"""queue_wait_p95_ms.served (ms): the engine's queue-wait histogram, 95th percentile."""

from perfbench.harness.readers import queue_wait_p95_ms as read  # noqa: F401
