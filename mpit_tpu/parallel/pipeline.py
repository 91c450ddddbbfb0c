"""Pipeline parallelism: GPipe-style microbatch pipeline over a ``pp``
mesh axis.

Not in the reference (SURVEY §2 parallelism table: PP absent) — added as
the TPU-native expression: each device owns one stage's parameters
(stacked pytree leaves sharded on their leading axis), microbatched
activations flow stage-to-stage over ``ppermute`` (one ICI neighbor hop
per tick), and the schedule is a ``lax.scan`` of ``m + n - 1`` ticks
(the GPipe fill+drain bubble).  Differentiable end-to-end: scan,
ppermute and psum all have transpose rules, so pipelined training steps
backprop through the same ring.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipeline(
    mesh: Mesh,
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    axis: str = "pp",
):
    """Build ``fn(stacked_params, microbatches) -> outputs``.

    - ``stacked_params``: pytree whose leaves have a leading stage axis
      of size ``n = mesh.shape[axis]`` (stage i's slice lives on device
      i); under jit they are sharded ``P(axis)`` so each device holds
      only its stage.
    - ``microbatches``: ``(m, B, ...)`` — m microbatches, replicated.
    - ``stage_fn(params_i, x) -> y`` with ``y.shape == x.shape`` (equal
      inter-stage width, the GPipe contract).

    Returns ``(m, B, ...)`` outputs (replicated; the last stage's results
    are broadcast with one masked psum).
    """
    n = mesh.shape[axis]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def _local(stacked, xs):
        params = jax.tree_util.tree_map(lambda a: a[0], stacked)  # my stage
        idx = jax.lax.axis_index(axis)
        m = xs.shape[0]
        is_first = idx == 0
        is_last = idx == n - 1

        def tick(carry, t):
            # Stage 0 feeds microbatch t (clamped past the end during
            # drain); everyone else consumes what arrived on the ring.
            x0 = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, m - 1), 0, keepdims=False
            )
            x_in = jnp.where(is_first, x0, carry)
            y = stage_fn(params, x_in)
            # The microbatch leaving the last stage this tick.
            out_t = t - (n - 1)
            emit = jnp.where(is_last & (out_t >= 0), y, jnp.zeros_like(y))
            carry_next = jax.lax.ppermute(y, axis, perm)
            return carry_next, (emit, out_t)

        carry0 = jnp.zeros_like(xs[0])
        _, (emits, out_ts) = jax.lax.scan(
            tick, carry0, jnp.arange(m + n - 1)
        )
        # Scatter ticks back to microbatch order: tick t emitted
        # microbatch t-(n-1); ticks before the pipe filled emitted zeros
        # with out_t < 0, which the clip parks on row 0 — add them there
        # first, they are zero.
        outs = jnp.zeros_like(xs)
        outs = outs.at[jnp.clip(out_ts, 0, m - 1)].add(emits)
        # Broadcast from the last stage to every device.
        return jax.lax.psum(outs, axis)

    return shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )


def stack_stage_params(params_per_stage) -> Any:
    """Stack a list of per-stage pytrees into the stacked layout
    ``pipeline`` expects (leading stage axis on every leaf)."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *params_per_stage
    )
