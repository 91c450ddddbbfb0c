"""Tensor parallelism: Megatron-style column/row sharded matmul pairs.

The reference has no TP (SURVEY §2 parallelism table — model *compute* is
never sharded, only server state); this module adds it the TPU way:
``shard_map`` programs over a ``tp`` mesh axis where weights are sharded
by output (column) or input (row) dimension, and exactly one ``psum``
per sharded block pays the ICI cost:

- **column-parallel**: ``W1`` split over its output dim — each device
  computes a slice of the hidden activations, no communication;
- **row-parallel**: ``W2`` split over its input dim — each device
  contributes a partial product, combined with one ``psum``;
- the pair (column → elementwise → row) is the canonical TP MLP; the
  same layout over attention heads gives head-parallel attention (heads
  are embarrassingly parallel until the output projection).

All fns are differentiable (shard_map + psum have transpose rules) and
callable from inside jit on global arrays.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def tp_mlp(
    mesh: Mesh,
    axis: str = "tp",
    activation: Callable[[jnp.ndarray], jnp.ndarray] = jax.nn.gelu,
):
    """Two-layer MLP with hidden dim sharded over ``axis``.

    ``fn(x, w1, b1, w2, b2)``: ``x (..., d)``, ``w1 (d, h)``,
    ``b1 (h,)``, ``w2 (h, d)``, ``b2 (d,)``, hidden ``h`` divisible by
    the axis size.  One psum on the way out; activations between the two
    matmuls never materialize unsharded.
    """

    def _local(x, w1, b1, w2, b2):
        h = activation(
            jnp.einsum("...d,dh->...h", x, w1) + b1
        )  # local hidden slice
        partial = jnp.einsum("...h,hd->...d", h, w2)
        out = jax.lax.psum(partial, axis)
        return out + b2  # bias after the reduce (replicated)

    return shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(), P(None, axis), P(axis), P(axis, None), P()),
        out_specs=P(),
        check_vma=False,
    )


def tp_self_attention(
    mesh: Mesh,
    axis: str = "tp",
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
):
    """Head-parallel self-attention: heads sharded over ``axis``.

    ``fn(x, wqkv, wo)``: ``x (B, L, d)``, ``wqkv (d, 3, H, Dh)``,
    ``wo (H, Dh, d)``; ``H`` divisible by the axis size.  QKV projection
    and per-head attention are local; the output projection is
    row-parallel with one psum.
    """

    def _local(x, wqkv, wo):
        from mpit_tpu.ops.flash_attention import attention_reference

        qkv = jnp.einsum("bld,dthk->btlhk", x, wqkv)  # t in {q,k,v}
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]  # (B, L, Hl, Dh)
        heads = attention_reference(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal, sm_scale=sm_scale,
        ).transpose(0, 2, 1, 3)  # (B, L, Hl, Dh)
        partial = jnp.einsum("blhk,hkd->bld", heads, wo)
        return jax.lax.psum(partial, axis)

    return shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(), P(None, None, axis, None), P(axis, None, None)),
        out_specs=P(),
        check_vma=False,
    )
