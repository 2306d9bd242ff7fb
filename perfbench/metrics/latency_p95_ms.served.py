"""latency_p95_ms.served (ms): the open loop's latency, 95th percentile, due to answered."""

from perfbench.harness.readers import latency_p95_ms as read  # noqa: F401
