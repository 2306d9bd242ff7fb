"""compiles.served (count): jit cache misses in the served window (``jax.compiles``)."""

from perfbench.harness.spans import compiles as read  # noqa: F401
