"""gen_late_p95_ms.served (ms): how late the generator sent requests, 95th percentile."""

from perfbench.harness.readers import gen_late_p95_ms as read  # noqa: F401
