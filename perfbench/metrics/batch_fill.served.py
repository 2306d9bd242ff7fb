"""batch_fill.served (%): mean fill of the batches the engine formed."""

from perfbench.harness.readers import batch_fill_percent as read  # noqa: F401
