"""GPipe-style pipeline parallelism over a mesh axis (shard_map + ppermute).

Completes the parallelism matrix (DP/FSDP/TP/EP/SP + **PP**): the layer
stack is split into ``n_stages`` groups laid out along a mesh axis (on the
production mesh this is the "pod" axis — cross-pod DCN carries only the
[microbatch, S, D] activation handoff per tick, the communication pattern
that makes pipelining attractive across pods).

Schedule: classic GPipe.  ``n_micro`` microbatches flow through
``n_stages + n_micro - 1`` ticks; at tick t, stage s computes microbatch
``t - s`` if it is in range, then ppermutes its activation to stage s+1.
Bubble fraction = (n_stages-1)/(n_stages+n_micro-1), reported by
``bubble_fraction`` so launchers can budget microbatches.

The schedule runs inside shard_map over the stage axis with a lax.scan of
ticks; everything is differentiable (ppermute/scan transpose cleanly), and
``tests/test_pipeline.py`` checks pipeline == sequential to float
tolerance, forward and backward, on a debug mesh.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_stages + n_micro - 1)


def pipeline_apply(stage_fn: Callable, stage_params, x, *, mesh: Mesh,
                   axis: str, n_micro: int):
    """Run ``stage_fn`` as a pipeline over ``axis``.

    stage_fn(params_one_stage, x_mb) → y_mb  (same shape as x_mb)
    stage_params: pytree with a leading stage axis == mesh.shape[axis]
    x: [B, ...] with B divisible by n_micro.
    Returns y: [B, ...] (the last stage's outputs, gathered).
    """
    n_stages = mesh.shape[axis]
    B = x.shape[0]
    assert B % n_micro == 0, (B, n_micro)
    mb = B // n_micro
    x_mb = x.reshape(n_micro, mb, *x.shape[1:])

    def local(params_loc, x_loc):
        # params_loc: [1, ...] this stage's params; x_loc: the full
        # microbatched input (replicated — only stage 0 consumes it)
        params_one = jax.tree.map(lambda t: t[0], params_loc)
        s = jax.lax.axis_index(axis)
        ticks = n_micro + n_stages - 1
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            buf, outs = carry          # buf: activation arriving this tick
            mb_idx = t - s
            active = jnp.logical_and(mb_idx >= 0, mb_idx < n_micro)
            # stage 0 reads from the input stream; others from the wire
            inp0 = jax.lax.dynamic_index_in_dim(
                x_loc, jnp.clip(t, 0, n_micro - 1), axis=0, keepdims=False)
            inp = jnp.where(s == 0, inp0, buf)
            y = stage_fn(params_one, inp)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage collects; everyone else forwards
            outs = jax.lax.cond(
                jnp.logical_and(s == n_stages - 1, active),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(mb_idx, 0, n_micro - 1), axis=0),
                lambda o: o, outs)
            buf = jax.lax.ppermute(y, axis, fwd_perm)
            return (buf, outs), None

        buf0 = jnp.zeros_like(x_loc[0])
        outs0 = jnp.zeros_like(x_loc)
        (buf, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                      jnp.arange(ticks))
        # outputs live on the last stage; psum broadcasts them (others hold 0)
        return jax.lax.psum(outs, axis)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(P(axis), P()),
                       out_specs=P(), check_vma=False)
    y_mb = fn(stage_params, x_mb)
    return y_mb.reshape(B, *x.shape[1:])
