"""Flat-parameter view over a Flax module (the getParameters() analog).

The reference trains on a single flat tensor aliasing all model weights
(reference goot.lua:33-36); the PS protocol shards that vector by offset
(reference pclient.lua:111-129).  JAX arrays are immutable, so instead of
aliasing we carry the ``unravel`` closure from ``ravel_pytree`` and
re-materialize the pytree inside jit — XLA fuses the reshapes away, so the
flat view costs nothing at runtime.
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree


def sliced_unravel(params: Any) -> Callable[[jnp.ndarray], Any]:
    """``ravel_pytree``'s ``unravel`` for ``params`` (same leaf order,
    same offsets; one dtype throughout) with each leaf's 1-D slice
    behind an ``optimization_barrier`` before its reshape.  Without the
    barrier XLA's TPU compiler turns "slice, then reshape" into "reshape
    the whole vector into the leaf's tiled 2-D layout, then slice": a
    copy of the *whole* vector for each distinct trailing width, forward
    and again for the backward pass (at OLMoE's one layer, a 2.5 GB
    vector: 9.64 GB of temporaries without, 4.16 GB with; the compile
    for a described v5e, PERF.md section 6, PR 26).  The barrier is the
    identity and differentiates as one."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    shapes = [np.shape(leaf) for leaf in leaves]
    sizes = [int(np.prod(shape)) for shape in shapes]
    offsets = np.concatenate([[0], np.cumsum(sizes)])

    def unravel(w: jnp.ndarray) -> Any:
        pieces = [
            jax.lax.optimization_barrier(
                w[int(offsets[i]):int(offsets[i + 1])]).reshape(shape)
            for i, shape in enumerate(shapes)]
        return jax.tree_util.tree_unflatten(treedef, pieces)

    return unravel


# From this many elements on (2 GiB of float32) the leaves are cut behind
# the barrier.  Each whole-vector copy it saves costs a vector of memory,
# and the barrier costs time: on the v5e it makes the 598 MB vector's
# local step 22% slower (171.4 -> 209.3 ms) and the 598 MB and 1.6 GB
# vectors' PS rounds 5.0% and 4.2% slower, while the 2.5 GB vector's step
# fits the chip only with it (PERF.md section 6, PR 26).
BARRIER_FROM = 1 << 29


class FlatModel:
    """A Flax module + flat-parameter calling convention.  A vector of
    :data:`BARRIER_FROM` elements or more takes :func:`sliced_unravel`
    in place of ``ravel_pytree``'s (the same function of ``w``, another
    program)."""

    def __init__(self, module: Any, params: Any):
        self.module = module
        flat, unravel = ravel_pytree(params)
        self.w0 = flat
        self.size = int(flat.shape[0])
        self.unravel = (sliced_unravel(params) if self.size >= BARRIER_FROM
                        else unravel)

    def apply_flat(self, w: jnp.ndarray, *args: Any, **kwargs: Any):
        return self.module.apply({"params": self.unravel(w)}, *args, **kwargs)


def flatten_module(module: Any, rng: jax.Array, sample_input: Any) -> FlatModel:
    params = module.init(rng, sample_input)["params"]
    return FlatModel(module, params)
