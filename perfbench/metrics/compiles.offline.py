"""compiles.offline (count): jit cache misses in the offline window (``jax.compiles``)."""

from perfbench.harness.spans import compiles as read  # noqa: F401
