"""Flat-parameter view over a Flax module (the getParameters() analog).

The reference trains on a single flat tensor aliasing all model weights
(reference goot.lua:33-36); the PS protocol shards that vector by offset
(reference pclient.lua:111-129).  JAX arrays are immutable, so instead of
aliasing we cut the vector into the module's leaves inside jit
(:func:`leaf_unravel`) and assemble the gradient from the leaves'.  That
is not free on the chip: the gradient's concatenation is a sweep of the
vector (11.9 ms of a 190 ms step at 486M elements) and, until PR 41, the
TPU compiler re-laid the *whole* vector as ``[N/64, 64]`` three times a
step for ten leaves 64 wide (27.6 ms of what was a 243 ms step; PERF.md
section 5, ``lfm2-l5e8-local``).  Outside the model a local msgd step
now sweeps the vector twice: that concatenation, and the optimizer's one
kernel (14.9 ms there), which since PR 47 writes the next step's
lookahead with the commit (``optim/msgd.py``; the separate lookahead
pass was a third sweep, 11.8 ms).  A commit that reads the leaves'
gradients where they lie would leave one.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from mpit_tpu.dplane.partition import flat_segments


def leaf_unravel(params: Any) -> Callable[[jnp.ndarray], Any]:
    """``ravel_pytree``'s ``unravel`` for ``params`` (same leaf order,
    same offsets; one dtype throughout) as a ``jax.custom_vjp``.

    Forward: every leaf is split off the vector and its 1-D piece goes
    through an ``optimization_barrier`` before its reshape.  Without the
    barrier XLA's TPU compiler turns "slice, then reshape" into "reshape
    the whole vector into the leaf's tiled 2-D layout, then slice": a
    copy of the whole vector for each distinct trailing width, and for a
    width under the chip's 128 lanes one that costs twice a wide one's.
    The rule reads nothing of the tree: no leaf's shape, no vector's
    size.

    Backward: the leaves' cotangents concatenated, each element written
    once.  It is what ``ravel_pytree``'s transpose simplifies to; a
    barrier's own transpose would keep one whole-vector ``pad`` a leaf
    apart instead (what every leaf behind a barrier cost the 598 MB
    vector's local step at PR 26: 171.4 -> 209.3 ms; with this backward
    the same step is 157.2 ms against 159.9 with no barrier at all;
    PERF.md section 6, PR 41)."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    dtypes = {str(jnp.result_type(leaf)) for leaf in leaves}
    if len(dtypes) > 1:
        raise TypeError(f"leaf_unravel needs one dtype, got {sorted(dtypes)}")
    shapes = [np.shape(leaf) for leaf in leaves]
    sizes = [int(np.prod(shape)) for shape in shapes]

    @jax.custom_vjp
    def unravel(w: jnp.ndarray) -> Any:
        return jax.tree_util.tree_unflatten(treedef, [
            jax.lax.optimization_barrier(piece).reshape(shape)
            for piece, shape in zip(jax.lax.split(w, sizes), shapes)])

    def forward(w):
        return unravel(w), None

    def backward(_, ct):
        return (jnp.concatenate(
            [leaf.reshape(-1) for leaf in jax.tree_util.tree_leaves(ct)]),)

    unravel.defvjp(forward, backward)
    return unravel


#: The leaves that move by a rule of their own: a router's selection
#: bias under the balancing rule (``parallel/moe.py`` ``balance_step``).
PLAIN_LEAVES = r"(^|/)router_bias$"
#: ``(start, stop)`` extents of the flat vector, ascending and disjoint
Ranges = Tuple[Tuple[int, int], ...]


def plain_ranges(params: Any, leaves: str = PLAIN_LEAVES) -> Ranges:
    """The *plain ranges* of ``params``' raveled vector: the extents of
    the leaves whose path matches ``leaves``, in the vector's order.

    The system's premise is one flat vector on which every element
    carries the same rule.  A plain range is the exception: its slot of
    the flat gradient holds a step that the model has already worked out
    (minus it, written as a gradient of rate 1), and whoever updates the
    vector moves those elements by exactly minus what it finds there: no
    learning rate, no momentum, no decay, no optimizer slot.  This is
    the one place that says where they lie; the local step
    (``optim/msgd.py``), the shells and, through the client's
    announcement, the servers' rule (``optim/rules.py`` ``apply_at``)
    are all handed what this returns (``lm/model.py`` ``build`` lays it
    on the step it hands out, ``optim/rules.py`` ``plain_of`` reads it
    there)."""
    match = re.compile(leaves).search
    return tuple((segment.offset, segment.end)
                 for segment in flat_segments(params) if match(segment.name))


class FlatModel:
    """A Flax module + flat-parameter calling convention.  Every vector
    is cut by :func:`leaf_unravel`."""

    def __init__(self, module: Any, params: Any):
        self.module = module
        self.w0 = ravel_pytree(params)[0]
        self.size = int(self.w0.shape[0])
        self.unravel = leaf_unravel(params)
        #: the plain ranges of the vector (:func:`plain_ranges`); none
        #: unless whoever builds the model says its block has a rule of
        #: its own (``lm/model.py`` ``build``)
        self.plain: Ranges = ()

    def apply_flat(self, w: jnp.ndarray, *args: Any, **kwargs: Any):
        return self.module.apply({"params": self.unravel(w)}, *args, **kwargs)


def flatten_module(module: Any, rng: jax.Array, sample_input: Any) -> FlatModel:
    params = module.init(rng, sample_input)["params"]
    return FlatModel(module, params)
