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
decays (``alpha`` down to 0.2: ``exp(1.6 x 64)``).  So the decay from
``s`` to ``t`` always goes through a position between them, ``exp(G_t -
G_ref) exp(G_ref - G_s)``: a product whose operands are each at most 1
in size, and one that underflows is of a pair whose true weight
underflows too.  The XLA form cuts a chunk into sub-blocks of
:data:`SUB` positions (between two sub-blocks the later one's first
boundary is the reference; inside one the ``SUB x SUB x d_k``
differences are taken one by one); the kernels halve the chunk level by
level (the comment above :data:`LEVELS`).

**Two ways to compile the one algorithm, chosen by the shapes**
(:func:`kda_scan`).  Head widths of whole lanes (``d_k`` and ``d_v``
multiples of 128: every configuration's) run as **three Mosaic
kernels** in which a chunk's matrices are made, used and dropped in
VMEM: a grid step is one chunk of :data:`HEADS_A_STEP` heads, read as
the block ``(CHUNK, heads x d)`` of the row-major ``(L, H d)`` view
where the projections leave it (no transpose into a heads-major layout
of chunks and none back), the state a VMEM scratch carried along the
chunk axis of the grid; the heads of a grid step are written stage by
stage, not head by head, because the kernel's compiler runs the small
products in the order they are written (:func:`_in_step`).  The forward
kernel reads the five inputs and writes ``o``.  Every other width runs
the chunked form as XLA's fusions and batched products
(:func:`kda_scan_xla`), the kernels' second oracle beside the
recurrence.

**The backward pass is the operator's own rule** (``jax.custom_vjp``):
it keeps ``q, k, v, g, beta`` and nothing of the forward pass, computes
the chunks' matrices and the chunk states again (``L / C`` states of
``d_k x d_v``, never ``L``), and differentiates that: the solve by its
own rule (``dM = -T^T dT T^T``, two products, not the doublings'
transposes), the decays computed again and not kept.  In the kernels
the rule is two calls: the forward kernel again, which now also writes
every chunk's starting state and solve (``L / C`` of ``d_v x d_k`` and
of ``C x C`` a head, alive inside the rule only), and a kernel that
walks the chunks from the last to the first with the state's cotangent
in VMEM, makes the chunk's other matrices again and writes the five
gradients, the log-decays' as the sums' tables transposed (``G``'s is
the reverse cumulative sum), not the transpose of the forward graph.
The XLA form's rule is ``jax.vjp`` of its parts.  The result is named
:data:`KDA_OUT` for a caller's checkpoint policy
(``jax.ad_checkpoint.checkpoint_name``), as the flash rule names its
two.

**One scalar decay a head, key heads shared by value heads**
(:func:`gdn_scan`, Gated DeltaNet: ``models/transformer.py``
``Qwen3NextBlock``'s ``linear_attention`` mixer) is the same recurrence
with ``alpha_t`` one number a head and ``H_k`` key heads under ``H_v =
r H_k`` value heads.  Every channel's difference of sums is then the
same number, so the halving has nothing to do: ``A = (K K^T) * D`` and
``B = (Q K^T) * D`` with ``D_ts = exp(min(G_t - G_s, 0))``, **one
product a key head under a ``C x C`` decay matrix a value head**, each
decay still the ``exp`` of a difference that is not positive, and
``exp(G)``, ``exp(G_C - G)``, ``exp(G_C)`` columns and a scalar.  At
head widths of whole lanes that is **three Mosaic kernels of its own**
(:class:`_ScalarChunk`, the section at the module's end) with the
solve, the stage-by-stage writing, the block specs and the rule's shape
of the channel-wise ones: the grid runs over key heads, a step reads a
key head's ``q`` and ``k`` once for its ``r`` value heads, ``g`` as
``beta`` a float a head, and no array of a call is wider than the
operand the caller made (no key repeated, no decay broadcast); in the
walk back the log-decay's gradient is row and column sums of the decay
matrix's cotangent times the matrix, then the reverse cumulative sum,
and ``dq``, ``dk`` are summed over a key head's value heads inside the
step.  Narrow head widths run the XLA form with the pair matrices of
:func:`_scalar_pairs`.

Shapes: ``q, k, g (B, L, H, d_k)``, ``v (B, L, H, d_v)``, ``beta (B, L,
H)``; the result ``(B, L, H, d_v)``.  Any ``L``: a last chunk that is
not whole is filled with positions that neither decay nor write
(``g = 0``, ``beta = 0``, ``k = 0``).  No state crosses the batch axis.
``chipbench/arithmetic/kimi.py`` ``kda_scan_cost`` counts what the
algorithm needs (five inputs read, ``o`` written; the same again and
the gradients in the rule), and ``kda_scan_roofline`` holds the scope's
device time to it, whichever form runs under the scope.  Off a TPU the
kernels run in Pallas interpret mode, on float32 operands.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpit_tpu.ops.tiles import LANE, round_up, use_interpret

#: the name of the scan's result for a checkpoint policy
KDA_OUT = "kda_out"
#: positions of a chunk, and of a sub-block inside it
CHUNK, SUB = 64, 16
#: the XLA form's (narrow head widths): heads whose chunks' matrices are
#: made, and transposed in the backward pass, at a time: what is alive
#: meanwhile is a dozen arrays of a head's ``L x d`` each (at 128-wide
#: heads and 8192 positions, before the kernels took those widths: 4
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


# -- a chunk's matrices, the XLA form -------------------------------------------


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


def _scalar_pairs(q, k, gsum):
    """:func:`_pair_matrices` where the decay is one number a head and
    position (``gsum (..., C, 1)``, the gated delta rule of
    :func:`gdn_scan`): every channel's difference of sums is the same
    number, so a pair matrix is **one product under a ``C x C`` decay
    matrix**, ``A_ts = (k_t . k_s) exp(G_t - G_s)``, each decay still
    the ``exp`` of a difference that is not positive."""
    chunk, gs = q.shape[-2], gsum[..., 0]
    decay = jnp.exp(jnp.minimum(gs[..., :, None] - gs[..., None, :], 0.0))
    at, k_t = jnp.arange(chunk), jnp.swapaxes(k, -1, -2)
    a = jnp.where(at[:, None] > at[None, :], (k @ k_t) * decay, 0.0)
    b = jnp.where(at[:, None] >= at[None, :], (q @ k_t) * decay, 0.0)
    return a, b


def _prepare(q, k, v, g, beta):
    """What a chunk is before its state is known, every chunk side by
    side: ``(U, W_k, K * exp(G_C - G), exp(G_C), Q * exp(G), B)`` from
    ``q, k, v, g (..., n, C, d)`` and ``beta (..., n, C, 1)``; a ``g``
    of one column is a scalar decay a head (:func:`_scalar_pairs`) and
    broadcasts over the keys' channels everywhere else."""
    dv = v.shape[-1]
    gsum = jnp.cumsum(g, axis=-2)                    # from the chunk's start
    a, pairs = (_scalar_pairs if g.shape[-1] == 1 and k.shape[-1] > 1
                else _pair_matrices)(q, k, gsum)
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
    """The XLA form's (narrow head widths): ``fn`` over the leading
    axis (the groups of heads), one group after another and the results
    stacked.  Unrolled, not a
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


# -- the chunk as Mosaic kernels (head widths of whole lanes) -------------------
#
# One grid step is one chunk of :data:`HEADS_A_STEP` heads: the block
# ``(CHUNK, heads x d)`` of the row-major ``(L, H x d)`` view, lane-aligned
# as the projections leave it.  Everything a chunk needs is a value inside
# the step; the state (transposed, ``d_v x d_k``, so that a channel's decay
# scales lanes) is a scratch carried along the grid's last axis.
#
# The pair matrices by halving: positions ``s < t`` of a chunk first part
# ways at one bit of their index, level ``l`` (halves of ``2^l``), and
# there the decay from ``s`` to ``t`` goes through the last position of
# the lower half: ``exp(G_t - G_mid) exp(G_mid - G_s)``, two exponents
# that are sums of log-decays and so never positive.  A level is one
# ``exp`` of a ``(C, d_k)`` array (every row is in one half or the
# other) and one product masked to the level's pairs; the sums come from
# one product of ``g`` with a table of 0s and 1s (:func:`_tables`), the
# summed log-decay ``G`` among them.

#: heads a grid step holds: their chains of small products are
#: independent and written stage by stage (:func:`_in_step`), so that
#: one head's waits are filled with another's products (the three
#: kernels of a layer at 1, 2, 4, 8 heads: 36.8, 24.8, 22.4, 21.6 ms;
#: at 16 the walk back does not fit the scoped VMEM)
HEADS_A_STEP = 4
#: levels of the halving: ``log2(CHUNK)``; ``lev == LEVELS`` on the diagonal
LEVELS = CHUNK.bit_length() - 1


def _tables(one_pass: bool = False):
    """``(sums, sums_t, lev)``.  ``sums ((LEVELS + 1) C, C)`` of 0s and
    1s, whose product with a chunk's ``g`` is ``G`` (rows ``0..C``: the
    sum from the chunk's start to ``t``) and below it a level's
    exponents (from the middle to ``t`` in an upper half, from ``t`` to
    the middle in a lower one); ``sums_t`` its transpose, for the
    log-decays' gradient; ``lev (C, C)``: the level at which ``s < t``
    part ways, :data:`LEVELS` on the diagonal, -1 above it.  With
    ``one_pass`` the two tables are in bf16, where they are exact, three
    times side by side: :meth:`_Chunk.table`."""
    at = np.arange(CHUNK)
    t, u = at[:, None], at[None, :]
    sums = [u <= t]
    for level in range(LEVELS):
        half = 1 << level
        mid = (t >> (level + 1) << (level + 1)) + half - 1
        upper = (t >> level) & 1 == 1
        sums.append(np.where(upper, (mid < u) & (u <= t),
                             (t < u) & (u <= mid)))
    sums = np.concatenate(sums).astype(np.float32)
    lev = np.full((CHUNK, CHUNK), -1, np.int32)
    below = t > u
    lev[below] = np.floor(np.log2((t ^ u)[below])).astype(np.int32)
    lev[at, at] = LEVELS
    tables = (sums, sums.T)
    if one_pass:
        tables = (jnp.asarray(np.tile(table, (1, 3)), jnp.bfloat16)
                  for table in tables)
    return (*map(jnp.asarray, tables), jnp.asarray(lev))


def _dot(a, b, dims, exact=False):
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=jnp.float32,
        precision=SOLVE_PRECISION if exact else None)


_nn = partial(_dot, dims=((1,), (0,)))      # a b
_nt = partial(_dot, dims=((1,), (1,)))      # a b^T
_tn = partial(_dot, dims=((0,), (0,)))      # a^T b


class _Chunk:
    """The arithmetic of one chunk and head on values, shared by the
    three kernels.  ``one_pass``: the chunk's products take their
    operands in bf16 (one MXU pass, float32 sums: what the backend's
    default precision is to the XLA form); the solve's products and the
    tables' are exact either way."""

    def __init__(self, sums, sums_t, lev, one_pass):
        self.sums, self.sums_t, self.lev = sums, sums_t, lev
        self.one_pass = one_pass
        self.eye = (lev == LEVELS).astype(jnp.float32)

    def low(self, x):
        return x.astype(jnp.bfloat16) if self.one_pass else x

    def table(self, table, x):
        """A table's product at float32's precision: the table is exact
        in bf16, so ``x`` is cut in three bf16 parts, stacked along the
        contraction against the table three times side by side
        (:func:`_tables` lays it so): one product, one sum."""
        if not self.one_pass:
            return _nn(table, x)
        parts = []
        for _ in range(3):
            parts.append(x.astype(jnp.bfloat16))
            x = x - parts[-1].astype(jnp.float32)
        return _nn(table, jnp.concatenate(parts, axis=0))

    # The three methods below are generators: each ``yield`` stands
    # between two products of which the second needs the first, and
    # :func:`_in_step` takes every head of a grid step to the same
    # ``yield`` before any goes on.  The compiler schedules the products
    # in the order they are written, so written head by head one head's
    # chain of small products runs alone, each waiting on the last;
    # written stage by stage one head's product runs while another's
    # drains (the operator alone: the forward kernel 11.1 -> 6.7 ms, the
    # walk back 11.5 -> 8.6; PERF.md section 6, PR 44).

    def pairs(self, q, k, g):
        """``G``, ``A`` (strictly lower), ``B`` (weakly lower) and each
        level's ``(decay, k decayed, q decayed)``."""
        sums = self.table(self.sums, g)
        yield
        a = jnp.zeros((CHUNK, CHUNK), jnp.float32)
        b = self.eye * jnp.sum(q * k, axis=1, keepdims=True)
        levels = []
        for level in range(LEVELS):
            decay = jnp.exp(sums[(level + 1) * CHUNK:(level + 2) * CHUNK])
            ke, qe = k * decay, q * decay
            both = _nt(self.low(jnp.concatenate([ke, qe], axis=0)),
                       self.low(ke))
            here = self.lev == level
            a = a + jnp.where(here, both[:CHUNK], 0.0)
            b = b + jnp.where(here, both[CHUNK:], 0.0)
            levels.append((decay, ke, qe))
        return sums[:CHUNK], a, b, levels

    def solve(self, n):
        """:func:`unit_lower_inverse` on one ``(C, C)`` value."""
        inverse = self.eye - n
        power, reach = _nn(n, n, exact=True), 2
        while reach < CHUNK:
            yield
            inverse = inverse + _nn(inverse, power, exact=True)
            reach *= 2
            if reach < CHUNK:
                power = _nn(power, power, exact=True)
        return inverse

    def forward(self, q, k, v, g, beta, state, t=None):
        """``(o, the next state)`` and what the backward pass uses again;
        ``state (d_v, d_k)`` is the chunk's starting state transposed,
        ``t`` the solve where it is at hand."""
        low = self.low
        gsum, a, b, levels = yield from self.pairs(q, k, g)
        yield
        if t is None:
            t = yield from self.solve(beta * a)
            yield
        into = jnp.exp(gsum)
        kg, qg = k * into, q * into
        rhs = beta * jnp.concatenate([v, kg], axis=1)
        solved = _nn(low(t), low(rhs))
        yield
        u, wk = solved[:, :v.shape[1]], solved[:, v.shape[1]:]
        seen = _nt(low(jnp.concatenate([wk, qg], axis=0)), low(state))
        yield
        w = u - seen[:CHUNK]
        o = seen[CHUNK:] + _nn(low(b), low(w))
        last = gsum[CHUNK - 1:]                       # (1, d_k)
        out_of = jnp.exp(last - gsum)
        kend = k * out_of
        after = state * jnp.exp(last) + _tn(low(w), low(kend))
        return o, after, dict(
            a=a, b=b, levels=levels, t=t, into=into, kg=kg, qg=qg, rhs=rhs,
            wk=wk, w=w, last=last, out_of=out_of, kend=kend)

    def to_the_pairs(self, q, k, v, g, beta, state, t, do, dafter):
        """The walk back as far as the decay's kind does not matter: the
        chunk's matrices again but for the solve ``t``, then, from
        ``do`` and the next state's ``dafter``, what :meth:`forward`
        kept and the cotangents ``(dA, dB, d(k exp(G)), d(q exp(G)),
        d(k exp(G_C - G)), d rhs, dbeta, dstate)``: the solve by ``dM =
        -T^T dT T^T``."""
        low, dv = self.low, v.shape[1]
        _, _, f = yield from self.forward(q, k, v, g, beta, state, t)
        a, b, w, wk, kg, qg, kend = (f[name] for name in (
            "a", "b", "w", "wk", "kg", "qg", "kend"))
        dw = _tn(low(b), low(do)) + _nt(low(kend), low(dafter))
        db = _nt(low(do), low(w))
        dkend = _nn(low(w), low(dafter))
        yield
        through = _nn(low(jnp.concatenate([do, dw], axis=0)), low(state))
        dstate = dafter * jnp.exp(f["last"]) + _tn(
            low(jnp.concatenate([do, -dw], axis=0)),
            low(jnp.concatenate([qg, wk], axis=0)))
        yield
        dqg, dwk = through[:CHUNK], -through[CHUNK:]
        dsolved = jnp.concatenate([dw, dwk], axis=1)
        dt = _nt(low(dsolved), low(f["rhs"]))
        drhs = _tn(low(t), low(dsolved))
        yield
        half = _tn(t, dt, exact=True)
        yield
        dn = -_nt(half, t, exact=True)
        yield
        da = beta * dn
        dbeta = jnp.sum(dn * a, axis=1, keepdims=True) + jnp.sum(
            drhs * jnp.concatenate([v, kg], axis=1), axis=1, keepdims=True)
        dkg = beta * drhs[:, dv:]
        return f, da, db, dkg, dqg, dkend, drhs, dbeta, dstate

    def backward(self, q, k, v, g, beta, state, t, do, dafter):
        """The cotangents of the five inputs and of the starting state
        (:meth:`to_the_pairs`), the pair matrices' level by level, the
        log-decays' by the tables' transposes (``G``'s is the reverse
        cumulative sum)."""
        low, dv = self.low, v.shape[1]
        f, da, db, dkg, dqg, dkend, drhs, dbeta, dstate = \
            yield from self.to_the_pairs(q, k, v, g, beta, state, t, do,
                                         dafter)
        kg, qg, kend = f["kg"], f["qg"], f["kend"]
        on_diagonal = jnp.sum(self.eye * db, axis=1, keepdims=True)
        d_k = dkg * f["into"] + dkend * f["out_of"] + on_diagonal * q
        d_q = dqg * f["into"] + on_diagonal * k
        leaving = dkend * kend
        dlast = jnp.exp(f["last"]) * jnp.sum(
            state * dafter, axis=0, keepdims=True) + jnp.sum(
                leaving, axis=0, keepdims=True)
        row = jax.lax.broadcasted_iota(jnp.int32, leaving.shape, 0)
        dsums = [dkg * kg + dqg * qg - leaving
                 + jnp.where(row == CHUNK - 1, dlast, 0.0)]
        for level, (decay, ke, qe) in enumerate(f["levels"]):
            here = self.lev == level
            cut = jnp.concatenate([jnp.where(here, da, 0.0),
                                   jnp.where(here, db, 0.0)], axis=0)
            along = _nn(low(cut), low(ke))
            dke = along[:CHUNK] + _tn(
                low(cut), low(jnp.concatenate([ke, qe], axis=0)))
            dke, dqe = dke * decay, along[CHUNK:] * decay
            d_k, d_q = d_k + dke, d_q + dqe
            dsums.append(dke * k + dqe * q)
        yield
        d_g = self.table(self.sums_t, jnp.concatenate(dsums, axis=0))
        return d_q, d_k, beta * drhs[:, :dv], d_g, dbeta, dstate


def _in_step(heads):
    """The results of the generators ``heads``, each taken to its next
    ``yield`` in turn until all have returned."""
    results, live = [None] * len(heads), dict(enumerate(heads))
    while live:
        for j, head in list(live.items()):
            try:
                next(head)
            except StopIteration as done:
                results[j] = done.value
                del live[j]
    return results


def _heads_a_step(keys: int, per: int = 1, most: int = HEADS_A_STEP) -> int:
    """Value heads a grid step holds: whole key heads of ``per`` value
    heads each (1 where every head has its own keys), as many as divide
    ``keys`` and come to ``most`` heads or fewer, and one key head at
    the least."""
    return per * max(n for n in range(1, max(most // per, 1) + 1)
                     if keys % n == 0)


def _column_of(block, head):
    """A head's column ``(C, 1)`` of the block ``(C, H)``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(lane == head, block, 0.0), axis=1,
                   keepdims=True)


def _beta_of(beta_ref, head):
    """A head's ``beta`` as a column ``(C, 1)`` of the block ``(C, H)``."""
    return _column_of(beta_ref[0], head)


def _zero_at_the_first(state_ref):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)


def _write_forward(made, states, o_ref, state_ref, kept_refs, dv):
    """What a forward kernel writes of its heads' ``(o, the next state,
    what was kept)``: ``o``, the state for the next chunk and, where the
    rule asks, the state the chunk started from and its solve."""
    for j, (o, after, kept) in enumerate(made):
        o_ref[0, :, j * dv:(j + 1) * dv] = o
        state_ref[j] = after
        if kept_refs:
            kept_refs[0][0, j, 0], kept_refs[1][0, j, 0] = states[j], kept["t"]


def _forward_kernel(sums_ref, lev_ref, q_ref, k_ref, v_ref, g_ref, beta_ref,
                    o_ref, *rest, group, dk, dv, one_pass):
    """``rest``: where the rule asks for them, every chunk's starting
    state (``(1, group, 1, d_v, d_k)``) and solve (``(1, group, 1, C,
    C)``); then the state's scratch."""
    *kept_refs, state_ref = rest
    _zero_at_the_first(state_ref)
    chunk = _Chunk(sums_ref[...], None, lev_ref[...], one_pass)
    states = [state_ref[j] for j in range(group)]
    made = _in_step([chunk.forward(
        q_ref[0, :, j * dk:(j + 1) * dk], k_ref[0, :, j * dk:(j + 1) * dk],
        v_ref[0, :, j * dv:(j + 1) * dv], g_ref[0, :, j * dk:(j + 1) * dk],
        _beta_of(beta_ref, pl.program_id(1) * group + j), states[j])
        for j in range(group)])
    _write_forward(made, states, o_ref, state_ref, kept_refs, dv)


def _backward_kernel(sums_ref, sums_t_ref, lev_ref, q_ref, k_ref, v_ref,
                     g_ref, beta_ref, do_ref, starts_ref, solves_ref, dq_ref,
                     dk_ref, dv_ref, dg_ref, dbeta_ref, dstate_ref, *, group,
                     dk, dv, one_pass):
    _zero_at_the_first(dstate_ref)
    chunk = _Chunk(sums_ref[...], sums_t_ref[...], lev_ref[...], one_pass)
    keys = [slice(j * dk, (j + 1) * dk) for j in range(group)]
    values = [slice(j * dv, (j + 1) * dv) for j in range(group)]
    made = _in_step([chunk.backward(
        q_ref[0, :, keys[j]], k_ref[0, :, keys[j]], v_ref[0, :, values[j]],
        g_ref[0, :, keys[j]], _beta_of(beta_ref, pl.program_id(1) * group + j),
        starts_ref[0, j, 0], solves_ref[0, j, 0], do_ref[0, :, values[j]],
        dstate_ref[j]) for j in range(group)])
    lane = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, group), 1)
    dbeta = jnp.zeros((CHUNK, group), jnp.float32)
    for j, (d_q, d_k, d_v, d_g, d_beta, dstate) in enumerate(made):
        dq_ref[0, :, keys[j]], dk_ref[0, :, keys[j]] = d_q, d_k
        dv_ref[0, :, values[j]], dg_ref[0, :, keys[j]] = d_v, d_g
        dbeta = jnp.where(lane == j, d_beta, dbeta)
        dstate_ref[j] = dstate
    dbeta_ref[0, 0] = dbeta


class _Calls:
    """What the three calls share: the sizes and the block specs of one
    walk over the chunks (``back``: from the last to the first).  A grid
    step holds ``group`` value heads, whole key heads of ``per`` value
    heads each (1 in the channel-wise kernels, where ``q`` has ``v``'s
    heads)."""

    def __init__(self, q, v, back, interpret, most=HEADS_A_STEP):
        self.b, self.length, keys, self.dk = q.shape
        self.h, self.dv = v.shape[2:]
        n = q.shape[1] // CHUNK
        self.per = self.h // keys
        self.n, self.group = n, _heads_a_step(keys, self.per, most)
        interpret = use_interpret(interpret)
        self.at = (lambda c: n - 1 - c) if back else (lambda c: c)
        self.static = dict(group=self.group, dk=self.dk, dv=self.dv,
                           one_pass=not interpret)
        self.call = dict(
            grid=(self.b, self.h // self.group, n), interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")))

    def tables(self):          # the channel-wise kernels' (:func:`_tables`)
        return _tables(one_pass=self.static["one_pass"])

    def rows(self, width, heads=None):
        # a chunk of a group's heads (or of its ``heads`` key heads), row-major
        return pl.BlockSpec(
            (1, CHUNK, (self.group if heads is None else heads) * width),
            lambda i, j, c: (i, self.at(c), j))

    def heads_rows(self):      # beta: a chunk of every head
        return pl.BlockSpec((1, CHUNK, self.h),
                            lambda i, j, c: (i, self.at(c), 0))

    def kept(self, *shape):    # a chunk's and head's state or solve
        return pl.BlockSpec((1, self.group, 1) + shape,
                            lambda i, j, c: (i, j, self.at(c), 0, 0))

    def dbeta(self):           # a chunk of a group's heads, a lane each
        return pl.BlockSpec((1, 1, CHUNK, self.group),
                            lambda i, j, c: (i, j, self.at(c), 0))

    def by_position(self, x):  # what ``dbeta`` blocks, as ``(B, L, H)``
        return x.transpose(0, 2, 1, 3).reshape(self.b, self.length, self.h)

    @staticmethod
    def whole(table):          # held for the whole grid
        return pl.BlockSpec(table.shape, lambda i, j, c: (0,) * table.ndim)


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _flat(x):
    """``(B, L, H, d)`` as its row-major ``(B, L, H d)`` view."""
    return x.reshape(x.shape[0], x.shape[1], -1)


def _kernel_forward(q, k, v, g, beta, keep=False, interpret=None):
    """``o (B, L, H, d_v)``, and with ``keep`` every chunk's starting
    state ``(B, H, n, d_v, d_k)`` and solve ``(B, H, n, C, C)``; ``L``
    whole chunks."""
    c = _Calls(q, v, False, interpret)
    sums, _, lev = c.tables()
    kept = ((c.dv, c.dk), (CHUNK, CHUNK)) if keep else ()
    o, *rest = pl.pallas_call(
        partial(_forward_kernel, **c.static),
        in_specs=[c.whole(sums), c.whole(lev), c.rows(c.dk),
                  c.rows(c.dk), c.rows(c.dv), c.rows(c.dk), c.heads_rows()],
        out_specs=[c.rows(c.dv)] + [c.kept(*shape) for shape in kept],
        out_shape=[_f32(c.b, c.length, c.h * c.dv)] + [
            _f32(c.b, c.h, c.n, *shape) for shape in kept],
        scratch_shapes=[pltpu.VMEM((c.group, c.dv, c.dk), jnp.float32)],
        **c.call,
    )(sums, lev, _flat(q), _flat(k), _flat(v), _flat(g), beta)
    o = o.reshape(v.shape)
    return (o, *rest) if keep else o


def _kernel_backward(q, k, v, g, beta, starts, solves, do, interpret=None):
    """The five cotangents, the chunks walked from the last to the first
    with the state's cotangent in VMEM."""
    c = _Calls(q, v, True, interpret)
    sums, sums_t, lev = c.tables()
    keys, values = c.rows(c.dk), c.rows(c.dv)
    d_keys, d_values = (_f32(c.b, c.length, c.h * d) for d in (c.dk, c.dv))
    d_q, d_k, d_v, d_g, d_beta = pl.pallas_call(
        partial(_backward_kernel, **c.static),
        in_specs=[c.whole(sums), c.whole(sums_t), c.whole(lev),
                  keys, keys, values, keys, c.heads_rows(), values,
                  c.kept(c.dv, c.dk), c.kept(CHUNK, CHUNK)],
        out_specs=[keys, keys, values, keys, c.dbeta()],
        out_shape=[d_keys, d_keys, d_values, d_keys,
                   _f32(c.b, c.h // c.group, c.length, c.group)],
        scratch_shapes=[pltpu.VMEM((c.group, c.dv, c.dk), jnp.float32)],
        **c.call,
    )(sums, sums_t, lev, _flat(q), _flat(k), _flat(v), _flat(g), beta,
      _flat(do), starts, solves)
    return (d_q.reshape(q.shape), d_k.reshape(k.shape), d_v.reshape(v.shape),
            d_g.reshape(g.shape), c.by_position(d_beta))


def _whole_chunks(x):
    """``x (B, L, ...)`` filled to whole chunks with positions that
    neither decay nor write (zeros)."""
    pad = round_up(x.shape[1], CHUNK) - x.shape[1]
    return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) \
        if pad else x


@jax.custom_vjp
def _kernels_out(q, k, v, g, beta):
    """The chunked form as Mosaic kernels; head widths of whole lanes."""
    length = q.shape[1]
    return _kernel_forward(*map(_whole_chunks, (q, k, v, g, beta))
                           )[:, :length]


def _kernels_fwd(q, k, v, g, beta):
    return _kernels_out(q, k, v, g, beta), (q, k, v, g, beta)


def _kernels_bwd(kept, ct):
    kept_whole = tuple(map(_whole_chunks, kept))
    _, starts, solves = _kernel_forward(*kept_whole, keep=True)
    grads = _kernel_backward(*kept_whole, starts, solves, _whole_chunks(ct))
    length = ct.shape[1]
    return tuple(x[:, :length] for x in grads)


_kernels_out.defvjp(_kernels_fwd, _kernels_bwd)


def kda_scan_xla(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 g: jnp.ndarray, beta: jnp.ndarray) -> jnp.ndarray:
    """The chunked form as XLA's fusions and batched products, any head
    width: what :func:`kda_scan` runs where a width is no whole number
    of lanes, and the kernels' second oracle beside the recurrence."""
    b, length, h, _ = q.shape
    chunk = min(CHUNK, -(-length // SUB) * SUB)  # a short sequence: one chunk
    n = -(-length // chunk)
    group = _head_group(h)

    def chunks(x):              # (groups, B, heads a group, n, C, width)
        x = jnp.pad(x, ((0, 0), (0, n * chunk - length), (0, 0), (0, 0)))
        return x.reshape(b, n, chunk, h // group, group, x.shape[-1]
                         ).transpose(3, 0, 4, 1, 2, 5)

    out = _chunks_out(*map(chunks, (q, k, v, g, beta[..., None])))
    return out.transpose(1, 3, 4, 0, 2, 5).reshape(
        b, n * chunk, h, out.shape[-1])[:, :length]


def kda_scan(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, g: jnp.ndarray,
             beta: jnp.ndarray) -> jnp.ndarray:
    """The recurrence of the module's docstring in chunks of
    :data:`CHUNK` positions, with its own backward rule.  Which form
    runs is read off the shapes: head widths of whole lanes (``d_k`` and
    ``d_v`` multiples of 128) take the Mosaic kernels, every other width
    :func:`kda_scan_xla`.  The chunks' products take one bf16 pass on a
    TPU (the backend's default precision in the XLA form, bf16 operands
    in the kernels), the solve :data:`SOLVE_PRECISION`, the decays and
    their sums float32."""
    lanes = q.shape[-1] % LANE == 0 and v.shape[-1] % LANE == 0
    return checkpoint_name(
        (_kernels_out if lanes else kda_scan_xla)(q, k, v, g, beta), KDA_OUT)


# -- a scalar decay a head, key heads shared by value heads ----------------------
#
# The gated delta rule of Gated DeltaNet (arXiv:2412.06464; the ``qwen3_next``
# module's ``linear_attention`` layers): the recurrence of this module's
# docstring with ``alpha_t`` **one number a head** and ``H_k`` key heads
# under ``H_v = r H_k`` value heads, key head ``j`` serving value heads ``r j
# .. r j + r - 1``.
#
# The kernels (head widths of whole lanes): a grid step is one chunk of
# whole key heads with their value heads side by side.  What one step
# makes once: the summed log-decays of every head, as columns and as rows,
# by two thin exact products of the ``(C, H_v)`` block of ``g`` with a
# triangle of ones (:meth:`_ScalarChunk.sums`), and ``[k; q] k^T`` a key
# head (one bf16 pass; ``B``'s diagonal, ``q_t . k_t`` under no decay, a
# float32 sum).  A value head's decay matrix is its column minus
# its row under one ``exp``, and multiplies the float32 product: nothing
# is rounded that the channel-wise form did not round.  From there on a
# head is :meth:`_Chunk.forward` as it stands, the decays broadcasting
# over lanes where the channel-wise ones multiply them.

#: the name of :func:`gdn_scan`'s result for a checkpoint policy
GDN_OUT = "gdn_out"
#: the dtype the log-decays' sums from a chunk's start are held in:
#: float32.  Read at every call of :func:`gdn_scan`, so that the probe of
#: the reference's tolerances can lower it for one build
#: (``chipbench/reference/probe_qwen3next.py``: a sum of up to 64
#: log-decays then carries three digits, and every decay inside a chunk
#: is the ``exp`` of a difference of two such sums)
GDN_SUM_DTYPE = jnp.float32


def _sums_held_in(g: jnp.ndarray, dtype) -> jnp.ndarray:
    """``g (B, L, H)`` changed so that its sums from each chunk's start
    are the true sums rounded to ``dtype``: what a scan that holds them
    in ``dtype`` decays by, whichever form then sums them in float32
    (the differences of neighbouring rounded sums add up to the rounded
    sum exactly)."""
    b, length, h = g.shape
    whole = jnp.pad(g, ((0, 0), (0, -length % CHUNK), (0, 0)))
    sums = jnp.cumsum(whole.reshape(b, -1, CHUNK, h), axis=2)
    sums = sums.astype(dtype).astype(jnp.float32)
    return jnp.diff(sums, axis=2, prepend=0.0).reshape(b, -1, h)[:, :length]


def gdn_scan_reference(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                       g: jnp.ndarray, beta: jnp.ndarray) -> jnp.ndarray:
    """The recurrence as it is defined, one position a step: ``q, k (B,
    L, H_k, d_k)``, ``v (B, L, H_v, d_v)``, ``g, beta (B, L, H_v)``; no
    key is repeated and no decay broadcast."""
    b, length, hk, dk = q.shape
    hv, dv = v.shape[2:]
    r = hv // hk

    def step(state, at):                     # state (B, H_k, r, d_k, d_v)
        q_t, k_t, v_t, g_t, beta_t = at
        state = state * jnp.exp(g_t)[..., None, None]
        seen = jnp.einsum("bjk,bjrkv->bjrv", k_t, state)
        state = state + jnp.einsum(
            "bjk,bjrv->bjrkv", k_t, beta_t[..., None] * (v_t - seen))
        return state, jnp.einsum("bjk,bjrkv->bjrv", q_t, state)

    along = (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0),
             jnp.moveaxis(v, 1, 0).reshape(length, b, hk, r, dv),
             jnp.moveaxis(g, 1, 0).reshape(length, b, hk, r),
             jnp.moveaxis(beta, 1, 0).reshape(length, b, hk, r))
    state = jnp.zeros((b, hk, r, dk, dv), jnp.float32)
    out = jax.lax.scan(step, state, along)[1]
    return jnp.moveaxis(out, 0, 1).reshape(b, length, hv, dv)


#: value heads a grid step of the scalar kernels holds at the most
#: (:func:`_heads_a_step`): the bodies keep no levels, so twice
#: :data:`HEADS_A_STEP` fit the scoped VMEM (16 key heads under 32 value
#: heads of 128, the three kernels of a layer at 2, 4, 8 value heads a
#: step: 13.1, 9.9, 8.5 ms; at 16 the walk back does not fit; PERF.md
#: section 6, PR 62)
SCALAR_HEADS_A_STEP = 8


class _ScalarChunk(_Chunk):
    """:class:`_Chunk` where the decay is one number a head and
    position: the summed log-decays are a column, every place a decay
    is applied a column or a scalar broadcast over lanes, and a chunk's
    pair matrices **one product a key head** (:meth:`products`) under a
    ``C x C`` decay matrix a value head (:meth:`pairs`).  No levels and
    no tables: the masks are comparisons of iotas and the sums two thin
    exact products of a grid step's ``g`` (:meth:`sums`).  Where
    :class:`_Chunk`'s methods take a head's ``g``, this class's take
    what :meth:`decays` returns."""

    def __init__(self, one_pass):
        self.one_pass = one_pass
        t = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
        s = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)
        self.before, self.upto = s < t, s <= t
        self.eye = (s == t).astype(jnp.float32)
        self.since = (s >= t).astype(jnp.float32)

    def sums(self, g):
        """The log-decays ``g (C, H)`` of every head summed from the
        chunk's start: positions by heads, and heads by positions (a
        decay matrix is a column minus a row)."""
        return (_nn(self.upto.astype(jnp.float32), g, exact=True),
                _tn(g, self.since, exact=True))

    def products(self, q, k):
        """A key head's ``[k; q] k^T``, ``(2 C, C)``, and ``q_t . k_t`` as
        a column: ``B``'s diagonal, which no decay touches, is taken in
        float32 as the channel-wise form takes it."""
        return (_nt(self.low(jnp.concatenate([k, q], axis=0)), self.low(k)),
                jnp.sum(q * k, axis=1, keepdims=True))

    @staticmethod
    def decays(sums, head, products):
        """What the methods below take as a value head's ``g``: its
        summed log-decays as a column ``(C, 1)`` and as a row ``(1,
        C)``, and its key head's :meth:`products`."""
        columns, rows = sums
        at = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0)
        return (_column_of(columns, head),
                jnp.sum(jnp.where(at == head, rows, 0.0), axis=0,
                        keepdims=True), products)

    def pairs(self, q, k, g):
        """``G`` (a column), ``A``, ``B`` and, in the levels' place, the
        decay matrix ``exp(min(G_t - G_s, 0))``."""
        gsum, across, (products, diagonal) = g
        decay = jnp.exp(jnp.minimum(gsum - across, 0.0))
        yield
        a = jnp.where(self.before, products[:CHUNK] * decay, 0.0)
        b = jnp.where(self.before, products[CHUNK:] * decay, 0.0
                      ) + self.eye * diagonal
        return gsum, a, b, decay

    def backward(self, q, k, v, g, beta, state, t, do, dafter):
        """As :meth:`_Chunk.backward`, but for what a key head's value
        heads share: ``(dq, dk`` but for the pair products' part, the
        cotangent of :meth:`products`, ``dv``, the summed log-decays'
        cotangent as a column and what is to be taken from it as a row,
        ``dbeta, dstate)``.  The decay matrix's part is row and column
        sums of its cotangent times the matrix."""
        dv = v.shape[1]
        f, da, db, dkg, dqg, dkend, drhs, dbeta, dstate = \
            yield from self.to_the_pairs(q, k, v, g, beta, state, t, do,
                                         dafter)
        decay = f["levels"]
        d_products = jnp.concatenate(
            [jnp.where(self.before, d * decay, 0.0) for d in (da, db)],
            axis=0)
        on_diagonal = jnp.sum(self.eye * db, axis=1, keepdims=True)
        leaving = dkend * f["kend"]
        dlast = jnp.exp(f["last"]) * jnp.sum(
            state * dafter, keepdims=True) + jnp.sum(leaving, keepdims=True)
        moved = da * f["a"] + db * f["b"]             # d G_t - d G_s
        row = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, 1), 0)
        d_gsum = jnp.sum(moved, axis=1, keepdims=True) + jnp.sum(
            dkg * f["kg"] + dqg * f["qg"] - leaving, axis=1, keepdims=True
        ) + jnp.where(row == CHUNK - 1, dlast, 0.0)
        return (dqg * f["into"] + on_diagonal * k,
                dkg * f["into"] + dkend * f["out_of"] + on_diagonal * q,
                d_products, beta * drhs[:, :dv], d_gsum,
                jnp.sum(moved, axis=0, keepdims=True), dbeta, dstate)


def _scalar_heads(chunk, q_ref, k_ref, v_ref, g_ref, beta_ref, group, per, dk,
                  dv):
    """A grid step's key heads ``[(q, k)]`` and, a value head, the
    leading arguments of :class:`_ScalarChunk`'s ``forward`` and
    ``backward``: ``(q, k, v, decays, beta)``."""
    first = pl.program_id(1) * group                 # the step's value heads
    sums = chunk.sums(g_ref[0])
    keys = [tuple(ref[0, :, i * dk:(i + 1) * dk] for ref in (q_ref, k_ref))
            for i in range(group // per)]
    products = [chunk.products(q, k) for q, k in keys]
    return keys, [
        (*keys[j // per], v_ref[0, :, j * dv:(j + 1) * dv],
         chunk.decays(sums, first + j, products[j // per]),
         _beta_of(beta_ref, first + j)) for j in range(group)]


def _scalar_forward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest,
                           group, per, dk, dv, one_pass):
    """:func:`_forward_kernel` on ``per`` value heads a key head: ``q``
    and ``k`` blocks of ``group / per`` heads, ``g`` a block of every
    head as ``beta``."""
    *kept_refs, state_ref = rest
    _zero_at_the_first(state_ref)
    chunk = _ScalarChunk(one_pass)
    _, heads = _scalar_heads(chunk, q_ref, k_ref, v_ref, g_ref, beta_ref,
                             group, per, dk, dv)
    states = [state_ref[j] for j in range(group)]
    made = _in_step([chunk.forward(*heads[j], states[j])
                     for j in range(group)])
    _write_forward(made, states, o_ref, state_ref, kept_refs, dv)


def _scalar_backward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, do_ref,
                            starts_ref, solves_ref, dq_ref, dk_ref, dv_ref,
                            dg_ref, dbeta_ref, dstate_ref, *, group, per, dk,
                            dv, one_pass):
    """:func:`_backward_kernel` on ``per`` value heads a key head:
    ``dq`` and ``dk`` summed over them and written once, the pair
    products' part one product each way a key head; ``dg`` a lane a
    head as ``dbeta``, the reverse cumulative sum of the summed
    log-decays' cotangent."""
    _zero_at_the_first(dstate_ref)
    chunk = _ScalarChunk(one_pass)
    keys, heads = _scalar_heads(chunk, q_ref, k_ref, v_ref, g_ref, beta_ref,
                                group, per, dk, dv)
    made = _in_step([chunk.backward(
        *heads[j], starts_ref[0, j, 0], solves_ref[0, j, 0],
        do_ref[0, :, j * dv:(j + 1) * dv], dstate_ref[j])
        for j in range(group)])
    lane = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, group), 1)
    at = jax.lax.broadcasted_iota(jnp.int32, (group, CHUNK), 0)
    dbeta = d_gsum = jnp.zeros((CHUNK, group), jnp.float32)
    taken = jnp.zeros((group, CHUNK), jnp.float32)
    for j, (_, _, _, d_v, d_column, d_row, d_beta, dstate) in enumerate(made):
        dv_ref[0, :, j * dv:(j + 1) * dv] = d_v
        dbeta = jnp.where(lane == j, d_beta, dbeta)
        d_gsum = jnp.where(lane == j, d_column, d_gsum)
        taken = jnp.where(at == j, d_row, taken)
        dstate_ref[j] = dstate
    dbeta_ref[0, 0] = dbeta
    dg_ref[0, 0] = _nn(chunk.since, d_gsum, exact=True) - _nt(
        chunk.since, taken, exact=True)
    shared = [tuple(sum(made[j][part] for j in range(i * per, (i + 1) * per))
                    for part in range(3)) for i in range(group // per)]
    along = [_nn(chunk.low(d_products), chunk.low(k))
             for (_, k), (_, _, d_products) in zip(keys, shared)]
    for i, ((q, k), (d_q, d_k, d_products)) in enumerate(zip(keys, shared)):
        mine = slice(i * dk, (i + 1) * dk)
        dq_ref[0, :, mine] = d_q + along[i][CHUNK:]
        dk_ref[0, :, mine] = d_k + along[i][:CHUNK] + _tn(
            chunk.low(d_products),
            chunk.low(jnp.concatenate([k, q], axis=0)))


# The two calls are ``jax.jit``s that the step's trace takes **inline**: a
# body is traced once a process and shape, not once a layer and program,
# and every layer still lowers to a Mosaic call of its own, which is what
# the benchmark counts (``chipbench/child.py``: ``tpu_custom_call``s in the
# lowered text).  What a trace reads is in its key: ``most`` is
# :data:`SCALAR_HEADS_A_STEP` as the rule finds it when it is called.


@partial(jax.jit, static_argnames=("keep", "most", "interpret"), inline=True)
def _scalar_forward(q, k, v, g, beta, keep, most, interpret):
    """:func:`_kernel_forward` for ``q, k (B, L, H_k, d_k)`` under ``v
    (B, L, H_v, d_v)`` and ``g, beta (B, L, H_v)``: no array of the call
    is wider than its operand."""
    c = _Calls(q, v, False, interpret, most)
    keys = c.rows(c.dk, c.group // c.per)
    kept = ((c.dv, c.dk), (CHUNK, CHUNK)) if keep else ()
    o, *rest = pl.pallas_call(
        partial(_scalar_forward_kernel, per=c.per, **c.static),
        in_specs=[keys, keys, c.rows(c.dv), c.heads_rows(), c.heads_rows()],
        out_specs=[c.rows(c.dv)] + [c.kept(*shape) for shape in kept],
        out_shape=[_f32(c.b, c.length, c.h * c.dv)] + [
            _f32(c.b, c.h, c.n, *shape) for shape in kept],
        scratch_shapes=[pltpu.VMEM((c.group, c.dv, c.dk), jnp.float32)],
        **c.call,
    )(_flat(q), _flat(k), _flat(v), g, beta)
    o = o.reshape(v.shape)
    return (o, *rest) if keep else o


@partial(jax.jit, static_argnames=("most", "interpret"), inline=True)
def _scalar_backward(q, k, v, g, beta, starts, solves, do, most, interpret):
    """:func:`_kernel_backward` at the operands' own shapes."""
    c = _Calls(q, v, True, interpret, most)
    keys, values = c.rows(c.dk, c.group // c.per), c.rows(c.dv)
    small = _f32(c.b, c.h // c.group, c.length, c.group)
    d_keys = _f32(c.b, c.length, q.shape[2] * c.dk)
    d_q, d_k, d_v, d_g, d_beta = pl.pallas_call(
        partial(_scalar_backward_kernel, per=c.per, **c.static),
        in_specs=[keys, keys, values, c.heads_rows(), c.heads_rows(), values,
                  c.kept(c.dv, c.dk), c.kept(CHUNK, CHUNK)],
        out_specs=[keys, keys, values, c.dbeta(), c.dbeta()],
        out_shape=[d_keys, d_keys, _f32(c.b, c.length, c.h * c.dv), small,
                   small],
        scratch_shapes=[pltpu.VMEM((c.group, c.dv, c.dk), jnp.float32)],
        **c.call,
    )(_flat(q), _flat(k), _flat(v), g, beta, _flat(do), starts, solves)
    return (d_q.reshape(q.shape), d_k.reshape(k.shape), d_v.reshape(v.shape),
            c.by_position(d_g), c.by_position(d_beta))


@jax.custom_vjp
def _scalar_kernels_out(q, k, v, g, beta):
    """The chunked form as the scalar rule's Mosaic kernels; head widths
    of whole lanes."""
    length = q.shape[1]
    return _scalar_forward(*map(_whole_chunks, (q, k, v, g, beta)),
                           keep=False, most=SCALAR_HEADS_A_STEP,
                           interpret=use_interpret(None))[:, :length]


def _scalar_kernels_fwd(q, k, v, g, beta):
    return _scalar_kernels_out(q, k, v, g, beta), (q, k, v, g, beta)


def _scalar_kernels_bwd(kept, ct):
    kept_whole = tuple(map(_whole_chunks, kept))
    how = dict(most=SCALAR_HEADS_A_STEP, interpret=use_interpret(None))
    _, starts, solves = _scalar_forward(*kept_whole, keep=True, **how)
    grads = _scalar_backward(*kept_whole, starts, solves, _whole_chunks(ct),
                             **how)
    length = ct.shape[1]
    return tuple(x[:, :length] for x in grads)


_scalar_kernels_out.defvjp(_scalar_kernels_fwd, _scalar_kernels_bwd)


def gdn_scan(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, g: jnp.ndarray,
             beta: jnp.ndarray) -> jnp.ndarray:
    """The gated delta rule with a scalar decay a head in chunks of
    :data:`CHUNK` positions: ``q, k (B, L, H_k, d_k)``, ``v (B, L, H_v,
    d_v)``, ``g, beta (B, L, H_v)`` with ``H_v`` a multiple of ``H_k``;
    the result ``(B, L, H_v, d_v)``, named :data:`GDN_OUT`.  The solve,
    the carry and the backward rule's shape are the module's own, a
    chunk's pair matrices one product each under a ``C x C`` decay
    matrix.  Which form runs is read off the shapes, as
    :func:`kda_scan`'s: head widths of whole lanes take **the scalar
    rule's three Mosaic kernels** (the forward, the forward again
    keeping every chunk's starting state and solve, the walk back:
    :class:`_ScalarChunk`) on the five operands as they are handed
    over, the gradients written at the operands' own shapes; any narrow
    head width takes the XLA form on the keys repeated for their value
    heads (:func:`_scalar_pairs`; the repeat's transpose is a sum).
    ``chipbench/arithmetic/qwen3next.py`` ``gdn_scan_cost`` counts the
    algorithm at the operands' sizes, whichever runs."""
    hk, hv = k.shape[2], v.shape[2]
    if hv % hk or g.shape != v.shape[:3] or beta.shape != v.shape[:3]:
        raise ValueError(f"gdn_scan: {hv} value heads over {hk} key heads, "
                         f"g {g.shape}, beta {beta.shape}, v {v.shape}")
    if GDN_SUM_DTYPE != jnp.float32:
        g = _sums_held_in(g, GDN_SUM_DTYPE)
    if q.shape[-1] % LANE == 0 and v.shape[-1] % LANE == 0:
        out = _scalar_kernels_out(q, k, v, g, beta)
    else:
        if hv != hk:
            q, k = (jnp.repeat(x, hv // hk, axis=2) for x in (q, k))
        out = kda_scan_xla(q, k, v, g[..., None], beta)
    return checkpoint_name(out, GDN_OUT)
