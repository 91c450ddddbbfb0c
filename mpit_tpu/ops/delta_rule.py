"""The gated delta rule with a channel-wise decay (Kimi Delta Attention,
arXiv:2510.26692) as a chunked scan, forward and backward: the one
operator of the program that carries state along the sequence
(``models/transformer.py`` ``KimiBlock``'s ``kda`` mixer).

A head holds a ``d_k x d_v`` matrix ``S``, zero at the start of every
sequence.  At position ``t``, with ``alpha_t = exp(g_t)`` in
``(0, 1]^{d_k}`` (``g`` is the log-decay, never positive) and ``beta_t``
in ``(0, 1)``::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

Token by token (:func:`kda_scan_reference`) that is ``L`` dependent
steps of matrix-vector work.  **In chunks** of ``C`` positions
(:func:`kda_scan`; 64 is the size the block runs at) it is dense
products.  With ``G_t`` the log-decays summed from the chunk's start,
``S_0`` the state the chunk starts from and ``w_t = beta_t (v_t -
(Diag(alpha_t) S_{t-1})^T k_t)``, the correction the delta rule writes::

    S_t   = Diag(exp(G_t)) S_0 + sum_{s<=t} (k_s * exp(G_t - G_s)) w_s^T
    A_ts  = sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])       (s <  t)
    B_ts  = sum_c q_t[c] k_s[c] exp(G_t[c] - G_s[c])       (s <= t)
    (I + Diag(beta) A) W = Diag(beta) (V - (K * exp(G)) S_0)
    O     = (Q * exp(G)) S_0 + B W
    S_C   = Diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T W

so a chunk is: the two ``C x C`` matrices, **one unit-lower-triangular
system solved** (``T = (I + Diag(beta) A)^{-1}`` by five doublings of
the nilpotent part, :func:`unit_lower_inverse`; ``W = U - W_k S_0`` with
``U = T Diag(beta) V`` and ``W_k = T Diag(beta) (K * exp(G))``, which do
not depend on the state, so every chunk's are computed side by side),
and a scan over the ``L / C`` chunk states of four small products each.

**Every decay is the ``exp`` of a difference of summed log-decays that
is not positive.**  ``A = (K * exp(G)) (K * exp(-G))^T`` would be one
product, and ``exp(-G)`` overflows float32 within a chunk at the seeded
decays (``alpha`` down to 0.2: ``exp(1.6 x 64)``).  So a chunk is cut
into sub-blocks of :data:`SUB` positions: between two sub-blocks the
decay goes through the later one's first boundary (``G_t - G_ref <= 0``
on the rows, ``G_ref - G_s <= 0`` on the columns: a product whose
operands are each at most 1 in size), and inside a sub-block the
``SUB x SUB x d_k`` differences are taken one by one.  A product that
underflows is of a pair whose true weight underflows too.

**The backward pass is the operator's own rule** (``jax.custom_vjp``):
it keeps ``q, k, v, g, beta`` and nothing of the forward pass, computes
the chunks' matrices and the chunk states again (``L / C`` states of
``d_k x d_v``, never ``L``), and differentiates that: the solve by its
own rule (``dM = -T^T dT T^T``, two products, not the doublings'
transposes), the sub-blocks' differences computed again and not kept.
The result is named :data:`KDA_OUT` for a caller's checkpoint policy
(``jax.ad_checkpoint.checkpoint_name``), as the flash rule names its
two.

Shapes: ``q, k, g (B, L, H, d_k)``, ``v (B, L, H, d_v)``, ``beta (B, L,
H)``; the result ``(B, L, H, d_v)``.  Any ``L``: a last chunk that is
not whole is filled with positions that neither decay nor write
(``g = 0``, ``beta = 0``, ``k = 0``).  No state crosses the batch axis.
XLA's fusions and products, no Mosaic kernel: ``chipbench/arithmetic/
kimi.py`` ``kda_scan_cost`` counts what the algorithm needs, and
``kda_scan_roofline`` holds the scope's device time to it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

#: the name of the scan's result for a checkpoint policy
KDA_OUT = "kda_out"
#: positions of a chunk, and of a sub-block inside it
CHUNK, SUB = 64, 16
#: heads whose chunks' matrices are made, and transposed in the backward
#: pass, at a time: what is alive meanwhile is a dozen arrays of a
#: head's ``L x d`` each (the cell's compiled step at 8192 positions: 4
#: heads 14.81 GB, 8 14.62, 16 13.95 before its last repairs, 32 14.56)
HEAD_GROUP = 8
#: the solve's products: the inverse's entries are sums of products of
#: up to ``CHUNK`` of ``A``'s, and one bf16 pass on them is felt in
#: every position of the chunk
SOLVE_PRECISION = jax.lax.Precision.HIGHEST


def kda_scan_reference(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                       g: jnp.ndarray, beta: jnp.ndarray) -> jnp.ndarray:
    """The recurrence as it is defined, one position a step."""
    b, _, h, dk = q.shape

    def step(state, at):
        q_t, k_t, v_t, g_t, beta_t = at          # (B, H, d), beta (B, H)
        state = state * jnp.exp(g_t)[..., None]
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", k_t, beta_t[..., None] * (v_t - seen))
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    along = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    return jnp.moveaxis(jax.lax.scan(step, state, along)[1], 0, 1)


# -- the triangular solve ------------------------------------------------------


@jax.custom_vjp
def unit_lower_inverse(n: jnp.ndarray) -> jnp.ndarray:
    """``(I + N)^{-1}`` for strictly lower-triangular ``N (..., C, C)``:
    ``N^C = 0``, so the inverse is ``(I - N)(I + N^2)(I + N^4) ...`` up
    to ``N^(C/2)``, ``2 log2(C) - 2`` products and no loop over rows."""
    size = n.shape[-1]
    mm = partial(jnp.matmul, precision=SOLVE_PRECISION)
    inverse = jnp.eye(size, dtype=n.dtype) - n
    power, reach = mm(n, n), 2
    while reach < size:
        inverse = inverse + mm(inverse, power)
        reach *= 2
        if reach < size:
            power = mm(power, power)
    return inverse


def _inverse_fwd(n):
    inverse = unit_lower_inverse(n)
    return inverse, inverse


def _inverse_bwd(inverse, ct):
    mm = partial(jnp.matmul, precision=SOLVE_PRECISION)
    t = jnp.swapaxes(inverse, -1, -2)
    return (-mm(mm(t, ct), t),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


# -- a chunk's matrices --------------------------------------------------------


@jax.checkpoint
def _within(rows: jnp.ndarray, ks: jnp.ndarray, gs: jnp.ndarray
            ) -> jnp.ndarray:
    """The sub-blocks on the diagonal: ``sum_c rows[r, t, c] k[s, c]
    exp(G_t[c] - G_s[c])`` for ``s <= t`` inside one sub-block (the
    caller masks the rest), the differences one by one.  ``rows (..., 2,
    SUB, d)`` are ``k`` and ``q``; under ``jax.checkpoint``, so the
    ``SUB x SUB x d`` decays are made again in the backward pass and
    never kept.  A sum a kind of row, not one over the stacked rows: the
    compiler fuses the decays into each sum, where it wrote the
    ``SUB x SUB x d`` products of the stacked form to memory (a quarter
    of a GB a group of heads, a seventh of the operator's time: PERF.md
    section 6, PR 43)."""
    decay = jnp.exp(jnp.minimum(gs[..., :, None, :] - gs[..., None, :, :],
                                0.0))
    return jnp.stack(
        [jnp.sum(rows[..., r, :, None, :] * ks[..., None, :, :] * decay,
                 axis=-1) for r in (0, 1)], axis=-3)


def _pair_matrices(q, k, gsum):
    """``(A, B)`` of every chunk, ``(..., C, C)`` each, ``A`` strictly
    and ``B`` weakly lower-triangular; ``q, k, gsum (..., C, d)``."""
    lead, (chunk, d) = q.shape[:-2], q.shape[-2:]
    sub = min(SUB, chunk)
    m = chunk // sub
    split = lead + (m, sub, d)
    qs, ks, gs = q.reshape(split), k.reshape(split), gsum.reshape(split)
    rows = jnp.stack([ks, qs], axis=-3)                  # (..., m, 2, sub, d)
    inside = _within(rows, ks, gs)                       # (..., m, 2, sub, sub)
    # sub-block i's reference: the summed log-decay at the last position
    # before it.  Rows decay from it (G_t - G_ref <= 0), columns up to it
    # (G_ref - G_s <= 0 for every s before the sub-block).
    blocks = []
    for i in range(m):
        parts = []
        if i:
            ref = gs[..., i - 1, -1:, :]                 # (..., 1, d)
            left = rows[..., i, :, :, :] * jnp.exp(gs[..., i, :, :] - ref
                                                   )[..., None, :, :]
            before = k[..., :i * sub, :] * jnp.exp(
                ref - gsum[..., :i * sub, :])
            parts.append(jnp.einsum("...rtc,...sc->...rts", left, before))
        parts.append(inside[..., i, :, :, :])
        if i < m - 1:
            parts.append(jnp.zeros(lead + (2, sub, chunk - (i + 1) * sub),
                                   q.dtype))
        blocks.append(jnp.concatenate(parts, axis=-1))   # (..., 2, sub, C)
    both = jnp.concatenate(blocks, axis=-2)              # (..., 2, C, C)
    at = jnp.arange(chunk)
    a = jnp.where(at[:, None] > at[None, :], both[..., 0, :, :], 0.0)
    b = jnp.where(at[:, None] >= at[None, :], both[..., 1, :, :], 0.0)
    return a, b


def _prepare(q, k, v, g, beta):
    """What a chunk is before its state is known, every chunk side by
    side: ``(U, W_k, K * exp(G_C - G), exp(G_C), Q * exp(G), B)`` from
    ``q, k, v, g (..., n, C, d)`` and ``beta (..., n, C, 1)``."""
    dv = v.shape[-1]
    gsum = jnp.cumsum(g, axis=-2)                    # from the chunk's start
    a, pairs = _pair_matrices(q, k, gsum)
    solve = unit_lower_inverse(beta * a)             # (..., n, C, C)
    into = jnp.exp(gsum)                             # from the start to t
    solved = solve @ (beta * jnp.concatenate([v, k * into], axis=-1))
    k_end = k * jnp.exp(gsum[..., -1:, :] - gsum)    # from s to the end
    return (solved[..., :dv], solved[..., dv:], k_end, into[..., -1, :],
            q * into, pairs)


def _carry(u, wk, k_end, keep):
    """The state every chunk starts from, ``(..., n, d_k, d_v)``: the
    scan over the chunks, two products a step."""
    at = u.ndim - 3                                  # the chunks' axis

    def step(state, of):
        u_n, wk_n, k_end_n, keep_n = of
        w_n = u_n - wk_n @ state
        return (keep_n[..., None] * state
                + jnp.swapaxes(k_end_n, -1, -2) @ w_n), state

    along = tuple(jnp.moveaxis(x, at, 0) for x in (u, wk, k_end, keep))
    zero = jnp.zeros(u.shape[:at] + (wk.shape[-1], u.shape[-1]), jnp.float32)
    return jnp.moveaxis(jax.lax.scan(step, zero, along)[1], 0, at)


def _read(q_in, pairs, u, wk, starts):
    """``O = (Q * exp(G)) S_0 + B (U - W_k S_0)``, every chunk side by
    side."""
    return q_in @ starts + pairs @ (u - wk @ starts)


def _head_group(heads: int) -> int:
    """Heads :func:`_prepare` takes at a time: the largest divisor of
    ``heads`` up to :data:`HEAD_GROUP`."""
    return max(n for n in range(1, HEAD_GROUP + 1) if heads % n == 0)


def _mapped(fn, *xs):
    """``fn`` over the leading axis (the groups of heads), one group
    after another and the results stacked.  Unrolled, not a
    ``lax.map``: inside a ``while`` the compiler gives every group's
    temporaries a place of their own for the whole loop, and the
    donated step of the five-layer cell read 3.3 GB more (PERF.md
    section 6, PR 43)."""
    groups = jax.tree_util.tree_leaves(xs)[0].shape[0]
    outs = [fn(*jax.tree_util.tree_map(lambda x: x[i], xs))
            for i in range(groups)]
    return jax.tree_util.tree_map(lambda *of: jnp.stack(of), *outs)


@jax.custom_vjp
def _chunks_out(q, k, v, g, beta):
    """The chunked form on ``(groups, B, heads a group, n, C, d)``."""
    u, wk, k_end, keep, q_in, pairs = _mapped(_prepare, q, k, v, g, beta)
    return _read(q_in, pairs, u, wk, _carry(u, wk, k_end, keep))


def _chunks_fwd(q, k, v, g, beta):
    return _chunks_out(q, k, v, g, beta), (q, k, v, g, beta)


def _chunks_bwd(kept, ct):
    u, wk, k_end, keep, q_in, pairs = _mapped(_prepare, *kept)
    starts, carry_vjp = jax.vjp(_carry, u, wk, k_end, keep)
    d_q_in, d_pairs, d_u, d_wk, d_starts = jax.vjp(
        _read, q_in, pairs, u, wk, starts)[1](ct)
    e_u, e_wk, d_k_end, d_keep = carry_vjp(d_starts)
    cts = (d_u + e_u, d_wk + e_wk, d_k_end, d_keep, d_q_in, d_pairs)
    return _mapped(lambda *of: jax.vjp(_prepare, *of[:5])[1](of[5]),
                   *kept, cts)


_chunks_out.defvjp(_chunks_fwd, _chunks_bwd)


def kda_scan(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, g: jnp.ndarray,
             beta: jnp.ndarray) -> jnp.ndarray:
    """The recurrence of the module's docstring in chunks of
    :data:`CHUNK` positions, with its own backward rule.  The chunks'
    products are at the backend's default precision (one bf16 pass on a
    TPU), the solve at :data:`SOLVE_PRECISION`, the decays in float32."""
    b, length, h, _ = q.shape
    chunk = min(CHUNK, -(-length // SUB) * SUB)  # a short sequence: one chunk
    n = -(-length // chunk)
    group = _head_group(h)

    def chunks(x):              # (groups, B, heads a group, n, C, width)
        x = jnp.pad(x, ((0, 0), (0, n * chunk - length), (0, 0), (0, 0)))
        return x.reshape(b, n, chunk, h // group, group, x.shape[-1]
                         ).transpose(3, 0, 4, 1, 2, 5)

    out = _chunks_out(*map(chunks, (q, k, v, g, beta[..., None])))
    return checkpoint_name(out.transpose(1, 3, 4, 0, 2, 5).reshape(
        b, n * chunk, h, out.shape[-1])[:, :length], KDA_OUT)
