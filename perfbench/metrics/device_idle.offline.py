"""device_idle.offline (%): device idle share of the offline window, from the trace."""

from perfbench.harness.readers import idle_percent as read  # noqa: F401
