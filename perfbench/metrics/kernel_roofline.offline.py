"""kernel_roofline.offline (%): the program's least time over its Pallas kernel time."""

from perfbench.harness.readers import kernel_roofline_percent as read  # noqa: F401
