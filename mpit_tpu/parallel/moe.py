"""Expert parallelism: a Switch-style top-1 MoE layer over an ``ep``
mesh axis.

Not in the reference (SURVEY §2: EP absent).  TPU-native shape:

- experts' MLP weights are stacked on a leading expert axis and sharded
  over ``ep`` — each device owns ``E/n`` experts in HBM;
- routing is **dense dispatch**: every device runs all tokens through
  its local experts and masks by the router's one-hot choice, combining
  across devices with one ``psum``.  No sort/ragged all-to-all — for
  small expert counts this trades redundant FLOPs for a fully static,
  fusable program (the usual small-scale TPU MoE trade);
- top-1 routing with the Switch combine (chosen expert scaled by its
  softmax probability) keeps the router differentiable.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def ep_moe(
    mesh: Mesh,
    axis: str = "ep",
    activation: Callable[[jnp.ndarray], jnp.ndarray] = jax.nn.gelu,
):
    """Build ``fn(x, gate_w, w1, b1, w2, b2) -> y``.

    ``x (..., d)``; ``gate_w (d, E)``; expert weights stacked:
    ``w1 (E, d, h)``, ``b1 (E, h)``, ``w2 (E, h, d)``, ``b2 (E, d)``,
    with ``E`` divisible by the axis size.  Output matches ``x``.
    """

    def _local(x, gate_w, w1, b1, w2, b2):
        e_local = w1.shape[0]
        idx = jax.lax.axis_index(axis)
        scores = jnp.einsum("...d,de->...e", x, gate_w)  # global experts
        probs = jax.nn.softmax(scores, axis=-1)
        choice = jnp.argmax(probs, axis=-1)  # (...,) global expert id
        # Switch combine weight: the chosen expert's probability.
        combine = jnp.take_along_axis(probs, choice[..., None], axis=-1)[..., 0]
        # Mask for MY experts: local one-hot over e_local slots.
        local_ids = idx * e_local + jnp.arange(e_local)
        dispatch = (choice[..., None] == local_ids).astype(x.dtype)  # (..., El)

        h = activation(jnp.einsum("...d,edh->e...h", x, w1)
                       + jnp.expand_dims(b1, tuple(range(1, x.ndim))))
        y_exp = jnp.einsum("e...h,ehd->e...d", h, w2) + jnp.expand_dims(
            b2, tuple(range(1, x.ndim))
        )
        y_local = jnp.einsum("...e,e...d->...d", dispatch, y_exp)
        y = jax.lax.psum(y_local, axis)
        return y * combine[..., None]

    return shard_map(
        _local,
        mesh=mesh,
        in_specs=(
            P(), P(),
            P(axis, None, None), P(axis, None),
            P(axis, None, None), P(axis, None),
        ),
        out_specs=P(),
        check_vma=False,
    )


def moe_reference(x, gate_w, w1, b1, w2, b2, activation=jax.nn.gelu):
    """Unsharded top-1 MoE with the same routing — the test oracle."""
    scores = jnp.einsum("...d,de->...e", x, gate_w)
    probs = jax.nn.softmax(scores, axis=-1)
    choice = jnp.argmax(probs, axis=-1)
    combine = jnp.take_along_axis(probs, choice[..., None], axis=-1)[..., 0]
    h = activation(jnp.einsum("...d,edh->e...h", x, w1)
                   + jnp.expand_dims(b1, tuple(range(1, x.ndim))))
    y_exp = jnp.einsum("e...h,ehd->e...d", h, w2) + jnp.expand_dims(
        b2, tuple(range(1, x.ndim))
    )
    onehot = jax.nn.one_hot(choice, w1.shape[0], dtype=x.dtype)
    y = jnp.einsum("...e,e...d->...d", onehot, y_exp)
    return y * combine[..., None]
