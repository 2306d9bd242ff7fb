"""int8_mfu.offline (%): useful int8 ops answered in the window over the chips' peak."""

from perfbench.harness.readers import mfu_percent as read  # noqa: F401
