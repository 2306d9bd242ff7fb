"""device_idle.served (%): device idle share of the served window, from the trace."""

from perfbench.harness.readers import idle_percent as read  # noqa: F401
