"""Sequence-parallel ring attention over an ICI ring.

The reference has no long-context machinery at all (short per-example QA
sentences, SURVEY.md §5) — this module is the TPU-native long-context
capability built on the same collective-permute primitive the PS mesh
layer uses (:func:`mpit_tpu.parallel.collective.ring_shift`):

- the sequence axis of ``(B, L, H, D)`` activations is sharded over a
  mesh axis (``sp``): every device holds one contiguous chunk of the
  sequence and ALL heads — attention memory per device is
  O(B·(L/n)·H·D) regardless of L;
- each of the n ring steps computes blockwise attention of the local Q
  chunk against the KV chunk currently in hand — masked by **global**
  positions via the q/kv offsets of
  :func:`mpit_tpu.ops.flash_attention.block_attention_partial` — then
  passes the KV chunk to the next device with ``ppermute`` (one ICI
  neighbor hop; XLA overlaps the transfer with the block compute);
- per-step unnormalized partials ``(acc, m, l)`` are merged with the
  online-softmax combine (:func:`merge_partials`), so the result is
  *exactly* full attention, not an approximation.

Two block implementations: ``jnp`` (differentiable end-to-end; XLA fuses
the blockwise math) and ``pallas`` (the flash kernel emitting partials;
forward wrapped in a custom VJP whose backward is a second ring over the
pallas flash-backward pair kernels — (dk, dv) accumulators ride the KV
rotation, P is re-derived blockwise from the saved row log-sum-exp, so
backward memory is O(block) scratch per pair, never an (L, L) or even
per-chunk (C, C) score matrix).

Causal ring attention has two layouts: ``contiguous`` (every device
computes all n steps, most of them fully masked on low-rank devices) and
``zigzag`` (each device owns an early + late half-chunk, balancing the
causal work — see :func:`_ring_chunks_zigzag`).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from mpit_tpu.ops.flash_attention import (
    _lse_of,
    block_attention_partial,
    finalize_partials,
    flash_attention_bwd_pair,
    flash_attention_partial,
    merge_partials,
)


def sp_mesh(devices: Sequence[jax.Device] | None = None, axis: str = "sp") -> Mesh:
    """1-D sequence-parallel mesh over all (or the given) devices."""
    from mpit_tpu.utils.platform import default_devices

    devs = list(devices if devices is not None else default_devices())
    return Mesh(np.array(devs), (axis,))


def _ring_chunks(q, k, v, *, axis, n, partial_fn, with_lse=False):
    """Shared ring loop: local (B, H, C, D) chunks, returns (B, H, C, D)
    (with ``with_lse``: also the (B, H, C) row log-sum-exp residual the
    flash backward needs).

    ``partial_fn(q, k, v, q_offset, kv_offset) -> (acc, m, l)``.
    """
    my = jax.lax.axis_index(axis)
    chunk = q.shape[-2]
    q_off = my * chunk
    perm = [(i, (i + 1) % n) for i in range(n)]

    acc = jnp.zeros(q.shape[:-1] + (v.shape[-1],), jnp.float32)
    m = jnp.full(q.shape[:-1], float("-inf"), jnp.float32)
    l = jnp.zeros(q.shape[:-1], jnp.float32)

    kb, vb = k, v
    for s in range(n):
        # KV chunk in hand after s hops started at device (my - s).
        owner = (my + (n - s)) % n
        part = partial_fn(q, kb, vb, q_off, owner * chunk)
        acc, m, l = merge_partials((acc, m, l), part)
        if s + 1 < n:
            kb = jax.lax.ppermute(kb, axis, perm)
            vb = jax.lax.ppermute(vb, axis, perm)
    out = finalize_partials(acc, l, dtype=q.dtype)
    return (out, _lse_of(m, l)) if with_lse else out


def _ring_bwd_chunks(q, k, v, do, o, lse, *, axis, n, pair_bwd):
    """Backward ring for the contiguous layout.

    ``pair_bwd(q, k, v, do, lse, delta, q_offset, kv_offset) ->
    (dq, dk, dv)`` is the per-pair flash backward.  KV chunks rotate
    around the ring *together with* their accumulated (dk, dv); after the
    n-th visit one final hop delivers each chunk's gradient back to its
    owner.  dq accumulates locally.  Peak memory per device: the local
    chunks plus one rotating (k, v, dk, dv) set — O(L/n), matching the
    forward."""
    my = jax.lax.axis_index(axis)
    chunk = q.shape[-2]
    q_off = my * chunk
    perm = [(i, (i + 1) % n) for i in range(n)]

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
    dq = jnp.zeros(q.shape, jnp.float32)
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)

    kb, vb = k, v
    for s in range(n):
        owner = (my + (n - s)) % n
        dqi, dki, dvi = pair_bwd(q, kb, vb, do, lse, delta, q_off,
                                 owner * chunk)
        dq = dq + dqi.astype(jnp.float32)
        dk = dk + dki.astype(jnp.float32)
        dv = dv + dvi.astype(jnp.float32)
        if s + 1 < n:
            kb = jax.lax.ppermute(kb, axis, perm)
            vb = jax.lax.ppermute(vb, axis, perm)
            dk = jax.lax.ppermute(dk, axis, perm)
            dv = jax.lax.ppermute(dv, axis, perm)
    # The chunk in hand after the loop belongs to (my+1)%n: one final hop
    # brings every accumulated (dk, dv) home.
    dk = jax.lax.ppermute(dk, axis, perm)
    dv = jax.lax.ppermute(dv, axis, perm)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _ring_chunks_zigzag(q, k, v, *, axis, n, partial_fn, with_lse=False):
    """Load-balanced causal ring: each device holds TWO half-chunks of the
    zigzag layout — global chunk ``d`` and chunk ``2n-1-d`` — so causal
    useful work is ~2 half-blocks per device per step instead of the
    contiguous layout's all-or-nothing (device 0 would mask away n-1 of
    its n steps while device n-1 computes all of them).

    Liveness per (q-half, kv-half) pair at ring step s (owner ``o``):
    (early_q=d, early_kv=o) live iff d >= o (runtime); (early_q,
    late_kv=2n-1-o) never live (late chunks are always ahead of early
    ones); (late_q=2n-1-d, early_kv) always live; (late_q, late_kv) live
    iff o >= d (runtime).  The two static cases are resolved at trace
    time; the two data-dependent ones are ``lax.cond`` so dead blocks
    cost nothing at runtime.
    """
    my = jax.lax.axis_index(axis)
    if q.shape[-2] % 2:
        raise ValueError(
            f"zigzag layout needs an even per-device chunk, got "
            f"{q.shape[-2]} (global L must divide evenly by 2n={2 * n})"
        )
    c = q.shape[-2] // 2
    q_halves = (q[..., :c, :], q[..., c:, :])
    q_offs = (my * c, (2 * n - 1 - my) * c)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def zero_like_part(qh):
        return (
            jnp.zeros(qh.shape[:-1] + (v.shape[-1],), jnp.float32),
            jnp.full(qh.shape[:-1], float("-inf"), jnp.float32),
            jnp.zeros(qh.shape[:-1], jnp.float32),
        )

    parts = [zero_like_part(qh) for qh in q_halves]
    kb, vb = k, v
    for s in range(n):
        owner = (my + (n - s)) % n
        kv_halves = (
            (kb[..., :c, :], vb[..., :c, :]),
            (kb[..., c:, :], vb[..., c:, :]),
        )
        kv_offs = (owner * c, (2 * n - 1 - owner) * c)

        def compute(qi, ki):
            return partial_fn(
                q_halves[qi], kv_halves[ki][0], kv_halves[ki][1],
                q_offs[qi], kv_offs[ki],
            )

        # (late_q, early_kv): statically live.
        parts[1] = merge_partials(parts[1], compute(1, 0))
        # (early_q, early_kv): live iff my >= owner.
        parts[0] = merge_partials(
            parts[0],
            jax.lax.cond(
                my >= owner, lambda: compute(0, 0),
                lambda: zero_like_part(q_halves[0]),
            ),
        )
        # (late_q, late_kv): live iff owner >= my.
        parts[1] = merge_partials(
            parts[1],
            jax.lax.cond(
                owner >= my, lambda: compute(1, 1),
                lambda: zero_like_part(q_halves[1]),
            ),
        )
        # (early_q, late_kv): statically dead — skipped.
        if s + 1 < n:
            kb = jax.lax.ppermute(kb, axis, perm)
            vb = jax.lax.ppermute(vb, axis, perm)
    outs = [
        finalize_partials(acc, l, dtype=q.dtype) for (acc, _m, l) in parts
    ]
    out = jnp.concatenate(outs, axis=-2)
    if with_lse:
        lse = jnp.concatenate(
            [_lse_of(m, l) for (_acc, m, l) in parts], axis=-1
        )
        return out, lse
    return out


def _ring_bwd_chunks_zigzag(q, k, v, do, o, lse, *, axis, n, pair_bwd):
    """Backward ring for the zigzag layout: same two-half decomposition
    and static/dynamic pair liveness as the forward (see
    :func:`_ring_chunks_zigzag`), with (dk, dv) riding the KV rotation
    exactly as in :func:`_ring_bwd_chunks`."""
    my = jax.lax.axis_index(axis)
    c = q.shape[-2] // 2
    q_halves = (q[..., :c, :], q[..., c:, :])
    do_halves = (do[..., :c, :], do[..., c:, :])
    lse_halves = (lse[..., :c], lse[..., c:])
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
    delta_halves = (delta[..., :c], delta[..., c:])
    q_offs = (my * c, (2 * n - 1 - my) * c)
    perm = [(i, (i + 1) % n) for i in range(n)]

    dq_halves = [jnp.zeros(qh.shape, jnp.float32) for qh in q_halves]
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)

    kb, vb = k, v
    for s in range(n):
        owner = (my + (n - s)) % n
        kv_halves = (
            (kb[..., :c, :], vb[..., :c, :]),
            (kb[..., c:, :], vb[..., c:, :]),
        )
        kv_offs = (owner * c, (2 * n - 1 - owner) * c)

        def pair(qi, ki):
            return pair_bwd(
                q_halves[qi], kv_halves[ki][0], kv_halves[ki][1],
                do_halves[qi], lse_halves[qi], delta_halves[qi],
                q_offs[qi], kv_offs[ki],
            )

        def zeros(qi, ki):
            return lambda: (
                jnp.zeros(q_halves[qi].shape, q.dtype),
                jnp.zeros(kv_halves[ki][0].shape, k.dtype),
                jnp.zeros(kv_halves[ki][1].shape, v.dtype),
            )

        def add(qi, ki, grads):
            dqi, dki, dvi = grads
            dq_halves[qi] = dq_halves[qi] + dqi.astype(jnp.float32)
            lo, hi = (0, c) if ki == 0 else (c, 2 * c)
            return (
                dk.at[..., lo:hi, :].add(dki.astype(jnp.float32)),
                dv.at[..., lo:hi, :].add(dvi.astype(jnp.float32)),
            )

        # (late_q, early_kv): statically live.
        dk, dv = add(1, 0, pair(1, 0))
        # (early_q, early_kv): live iff my >= owner.
        dk, dv = add(0, 0, jax.lax.cond(
            my >= owner, lambda: pair(0, 0), zeros(0, 0)))
        # (late_q, late_kv): live iff owner >= my.
        dk, dv = add(1, 1, jax.lax.cond(
            owner >= my, lambda: pair(1, 1), zeros(1, 1)))
        # (early_q, late_kv): statically dead — skipped.
        if s + 1 < n:
            kb = jax.lax.ppermute(kb, axis, perm)
            vb = jax.lax.ppermute(vb, axis, perm)
            dk = jax.lax.ppermute(dk, axis, perm)
            dv = jax.lax.ppermute(dv, axis, perm)
    dk = jax.lax.ppermute(dk, axis, perm)
    dv = jax.lax.ppermute(dv, axis, perm)
    dq = jnp.concatenate(dq_halves, axis=-2)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def zigzag_order(n: int):
    """Global chunk ids in device order for the zigzag layout: device d
    owns chunks (d, 2n-1-d)."""
    order = []
    for d in range(n):
        order.extend([d, 2 * n - 1 - d])
    return order


def zigzag_permute(x: jnp.ndarray, n: int, axis: int = 1) -> jnp.ndarray:
    """Reorder a sequence axis of 2n equal chunks into the zigzag device
    layout (inverse: :func:`zigzag_unpermute`)."""
    L = x.shape[axis]
    if L % (2 * n):
        raise ValueError(f"sequence length {L} not divisible by 2n={2 * n}")
    c = L // (2 * n)
    idx = jnp.concatenate(
        [jnp.arange(g * c, (g + 1) * c) for g in zigzag_order(n)]
    )
    return jnp.take(x, idx, axis=axis)


def zigzag_unpermute(x: jnp.ndarray, n: int, axis: int = 1) -> jnp.ndarray:
    L = x.shape[axis]
    if L % (2 * n):
        raise ValueError(f"sequence length {L} not divisible by 2n={2 * n}")
    c = L // (2 * n)
    order = zigzag_order(n)
    inv = [0] * (2 * n)
    for pos, g in enumerate(order):
        inv[g] = pos
    idx = jnp.concatenate(
        [jnp.arange(p * c, (p + 1) * c) for p in inv]
    )
    return jnp.take(x, idx, axis=axis)


def _precision_ctx(precision):
    return (jax.default_matmul_precision(precision) if precision
            else contextlib.nullcontext())


_RING_LOOPS = {"contiguous": _ring_chunks, "zigzag": _ring_chunks_zigzag}


def _ring_jnp(q, k, v, *, axis, n, causal, sm_scale, precision=None,
              layout="contiguous"):
    fn = lambda q2, k2, v2, qo, ko: block_attention_partial(
        q2, k2, v2, causal=causal, sm_scale=sm_scale, q_offset=qo, kv_offset=ko
    )
    with _precision_ctx(precision):
        return _RING_LOOPS[layout](q, k, v, axis=axis, n=n, partial_fn=fn)


def _ring_pallas(q, k, v, *, axis, n, causal, sm_scale, block_q, block_k,
                 interpret, precision, layout="contiguous", with_lse=False):
    fn = lambda q2, k2, v2, qo, ko: flash_attention_partial(
        q2, k2, v2, causal=causal, sm_scale=sm_scale, q_offset=qo,
        kv_offset=ko, block_q=block_q, block_k=block_k, interpret=interpret,
        precision=precision,
    )
    return _RING_LOOPS[layout](
        q, k, v, axis=axis, n=n, partial_fn=fn, with_lse=with_lse
    )


_RING_BWD_LOOPS = {
    "contiguous": _ring_bwd_chunks, "zigzag": _ring_bwd_chunks_zigzag,
}


def _ring_pallas_bwd(q, k, v, do, o, lse, *, axis, n, causal, sm_scale,
                     block_q, block_k, interpret, precision,
                     layout="contiguous"):
    fn = lambda q2, k2, v2, do2, lse2, delta2, qo, ko: (
        flash_attention_bwd_pair(
            q2, k2, v2, do2, lse2, delta=delta2, causal=causal,
            sm_scale=sm_scale, q_offset=qo, kv_offset=ko, block_q=block_q,
            block_k=block_k, interpret=interpret, precision=precision,
        )
    )
    return _RING_BWD_LOOPS[layout](
        q, k, v, do, o, lse, axis=axis, n=n, pair_bwd=fn
    )


@functools.lru_cache(maxsize=64)
def _make_local_fn(axis, n, causal, sm_scale, impl, block_q, block_k,
                   interpret, precision, layout="contiguous"):
    jnp_fn = functools.partial(
        _ring_jnp, axis=axis, n=n, causal=causal, sm_scale=sm_scale,
        precision=precision, layout=layout,
    )
    if impl == "jnp":
        return jnp_fn

    cfg = dict(
        axis=axis, n=n, causal=causal, sm_scale=sm_scale, block_q=block_q,
        block_k=block_k, interpret=interpret, precision=precision,
        layout=layout,
    )
    pallas_fwd = functools.partial(_ring_pallas, **cfg)

    @jax.custom_vjp
    def fn(q, k, v):
        return pallas_fwd(q, k, v)

    def fwd(q, k, v):
        # One forward with the LSE residual kept: the backward ring then
        # needs no O(C^2) recompute — each pair re-derives P blockwise
        # inside the pallas backward kernels.
        out, lse = pallas_fwd(q, k, v, with_lse=True)
        return out, (q, k, v, out, lse)

    def bwd(res, g):
        q, k, v, o, lse = res
        return _ring_pallas_bwd(q, k, v, g.astype(q.dtype), o, lse, **cfg)

    fn.defvjp(fwd, bwd)
    return fn


def ring_attention(
    mesh: Mesh,
    axis: str = "sp",
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    impl: str = "auto",
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    precision: str | None = None,
    layout: str = "contiguous",
    permute_inputs: bool = True,
    batch_axis: str | None = None,
) -> Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray], jnp.ndarray]:
    """Build the sequence-parallel attention fn over ``mesh[axis]``.

    Takes/returns global ``(B, L, H, D)`` arrays with L sharded over
    ``axis`` (L must divide evenly).  ``impl``: 'jnp', 'pallas', or
    'auto' (pallas on TPU, jnp elsewhere).  Callable from inside jit.

    ``batch_axis`` additionally shards B over another mesh axis (the
    dp x sp composition: independent rings run per data-parallel group;
    without it, calling from a dp-sharded program would all-gather the
    batch at the shard_map boundary).

    ``layout='zigzag'`` (causal only) balances causal work across the
    ring — each device owns an early and a late half-chunk, halving the
    worst-device compute per step.  With ``permute_inputs`` (default) the
    returned fn takes/returns natural sequence order, paying one
    cross-shard permutation per call; a model calling attention per layer
    can instead pre-permute activations once with
    :func:`zigzag_permute`, pass ``permute_inputs=False``, and
    un-permute final outputs with :func:`zigzag_unpermute`.
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if impl not in ("jnp", "pallas"):
        raise ValueError(f"impl must be auto|jnp|pallas, got {impl!r}")
    if layout not in ("contiguous", "zigzag"):
        raise ValueError(f"layout must be contiguous|zigzag, got {layout!r}")
    if layout == "zigzag" and not causal:
        raise ValueError(
            "layout='zigzag' requires causal=True (the static block-"
            "liveness it exploits is the causal structure)"
        )
    n = mesh.shape[axis]
    local = _make_local_fn(
        axis, n, bool(causal),
        # Static cache key: reject traced sm_scale with a clear error.
        None if sm_scale is None else float(sm_scale),
        impl,
        None if block_q is None else int(block_q),
        None if block_k is None else int(block_k),
        interpret, precision, layout,
    )

    def _local(q, k, v):
        # (B, C, H, D) chunk -> heads-major for the block math, and back.
        qh, kh, vh = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
        return local(qh, kh, vh).transpose(0, 2, 1, 3)

    spec = P(batch_axis, axis, None, None)
    mapped = shard_map(
        _local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    if layout == "contiguous" or not permute_inputs:
        return mapped

    def zigzagged(q, k, v):
        qz, kz, vz = (zigzag_permute(x, n, axis=1) for x in (q, k, v))
        return zigzag_unpermute(mapped(qz, kz, vz), n, axis=1)

    return zigzagged
