"""ICI collective primitives — the on-mesh analog of the PS wire protocol.

The reference moves parameter/gradient shards between ranks with tagged
MPI Isend/Irecv driven by coroutines (reference init.lua:40-102,
mpifuncs.c:1488-1532).  On a TPU mesh the same traffic pattern is three
XLA collectives, all riding ICI:

- **pull** (client fetches full params from all servers, reference
  pclient.lua:72-82) = ``all_gather`` over the shard axis;
- **push** (clients ship grads, each server applies its shard's sum,
  reference pserver.lua:75-90) = ``psum_scatter`` (reduce-scatter) over
  the shard axis;
- **ring transfer** (point-to-point neighbor exchange; also the building
  block for ring attention, §5 of SURVEY.md) = ``ppermute``.

These run inside ``shard_map`` so the collective schedule is explicit;
the higher-level trainers in :mod:`mpit_tpu.parallel` instead use jit +
sharding annotations and let XLA insert the identical collectives.
"""

from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def ps_pull(mesh: Mesh, axis: str = "shard") -> Callable[[jnp.ndarray], jnp.ndarray]:
    """Full-param fetch: every mesh cell receives the concatenation of all
    shards (reference pclient.lua:72-82's recv of every server's slice)."""

    def _pull(shard_slice):
        return jax.lax.all_gather(shard_slice, axis, tiled=True)

    return shard_map(
        _pull, mesh=mesh, in_specs=P(axis), out_specs=P(), check_vma=False
    )


def ps_push(
    mesh: Mesh, axis: str = "shard", reduce_axis: str | None = None
) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """Grad push: deliver to each shard owner the gradient slice it owns
    (the collective form of clients streaming grads to servers, reference
    pclient.lua:48-58 / pserver.lua:75-90).

    Without ``reduce_axis`` the input grad is replicated over ``axis``, so
    ownership transfer is a local slice, not a collective — XLA keeps it a
    zero-cost view.  With ``reduce_axis`` (the worker axis) the input is a
    ``(n_workers, plong)`` stack of per-worker grads, summed with ``psum``
    over that axis first — the server-side per-client ``p:add(g)``
    accumulation collapsed into one reduce (pserver.lua:83)."""

    def _push(full_grad):
        if reduce_axis is not None:
            full_grad = jax.lax.psum(full_grad, reduce_axis)[0]
        n = mesh.shape[axis]
        idx = jax.lax.axis_index(axis)
        size = full_grad.shape[0] // n
        return jax.lax.dynamic_slice_in_dim(full_grad, idx * size, size)

    in_spec = P(reduce_axis, None) if reduce_axis is not None else P()
    return shard_map(
        _push, mesh=mesh, in_specs=in_spec, out_specs=P(axis), check_vma=False
    )


def ps_pushpull(
    mesh: Mesh, apply_fn: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray],
    axis: str = "shard",
) -> Callable[[jnp.ndarray, jnp.ndarray], Tuple[jnp.ndarray, jnp.ndarray]]:
    """One full PS round on-mesh: push grads (reduce-scatter), apply the
    server rule on each shard, pull updated params (all-gather).

    ``apply_fn(p_shard, g_shard) -> p_shard`` is the jitted shard rule —
    plain add in the reference's pserver hot loop (pserver.lua:83).
    Returns ``(new_full_params, new_param_shard)``.
    """

    def _round(p_shard, full_grad):
        n = mesh.shape[axis]
        idx = jax.lax.axis_index(axis)
        size = full_grad.shape[0] // n
        g_shard = jax.lax.dynamic_slice_in_dim(full_grad, idx * size, size)
        p_shard = apply_fn(p_shard, g_shard)
        full = jax.lax.all_gather(p_shard, axis, tiled=True)
        return full, p_shard

    return shard_map(
        _round, mesh=mesh, in_specs=(P(axis), P()), out_specs=(P(), P(axis)),
        check_vma=False,
    )


def ring_shift(mesh: Mesh, axis: str, *, reverse: bool = False):
    """Neighbor exchange over ``axis``: each cell hands its block to the
    next cell on the ring (``ppermute``).  The mesh analog of a tagged
    point-to-point Isend/Irecv pair; also the step primitive of ring
    attention."""
    n = mesh.shape[axis]
    step = -1 if reverse else 1
    perm = [(i, (i + step) % n) for i in range(n)]

    def _shift(block):
        return jax.lax.ppermute(block, axis, perm)

    return shard_map(
        _shift, mesh=mesh, in_specs=P(axis), out_specs=P(axis), check_vma=False
    )


def allreduce_mean(mesh: Mesh, axis: str = "dp"):
    """Mean over the worker axis — the sync-DP gradient combine
    (the trained-in analog of the reference's Allreduce smoke tests,
    reference test/testreduceall.lua:31-33)."""

    def _mean(x):
        return jax.lax.pmean(x, axis)

    return shard_map(
        _mean, mesh=mesh, in_specs=P(axis), out_specs=P(axis), check_vma=False
    )


def measure_ps_pushpull(mb: float, rounds: int = 20) -> dict:
    """Measured PS push/pull bandwidth over the mesh ``shard`` axis — the
    one shared implementation of the asyncsgd/ptest.lua:58-67 measurement
    (``2*T*ssize*4/elapsed`` MB/s), used by both ``benchmarks/ptest.py``
    and the repo-root ``bench.py`` so the formula and payload sizing
    cannot drift apart.  Timing is the latency-cancelled fetch-fenced
    recipe of :mod:`mpit_tpu.utils.timing`."""
    from mpit_tpu.parallel.mesh import make_mesh, param_sharding
    from mpit_tpu.utils.platform import default_devices
    from mpit_tpu.utils.timing import timed_per_call

    devs = default_devices()
    mesh = make_mesh(devs, dp=1)  # all devices on the shard axis
    n = mesh.shape["shard"]
    size = int(mb * (1 << 20) / 4 // n * n)

    roundtrip = jax.jit(ps_pushpull(mesh, lambda p, g: p + g))
    p_shard = jax.device_put(
        jnp.zeros((size,), jnp.float32), param_sharding(mesh)
    )
    grad = jnp.ones((size,), jnp.float32)
    # auto_scale + min_ratio: a ms-scale round needs the iteration count
    # grown until the differenced legs clear 8x the observed jitter (this
    # number is published — the default stop rule permits ~100% relative
    # error).
    per_round = timed_per_call(roundtrip, p_shard, grad, iters=rounds,
                               auto_scale=True, min_ratio=8.0)
    mbs = 2 * size * 4 / per_round / 2**20  # reference formula, per round
    return {
        "mbs": mbs, "per_chip": mbs / n, "devices": n,
        "payload_mb": size * 4 / 2**20, "ms_per_round": per_round * 1e3,
    }
