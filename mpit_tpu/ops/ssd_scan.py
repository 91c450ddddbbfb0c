"""The state of a scalar-decay state-space layer (Mamba-2's SSD, Dao &
Gu, arXiv:2405.21060) as a chunked scan, forward and backward: the
operator of ``models/transformer.py`` ``NemotronBlock``'s ``mamba``
mixer.

A head ``h`` of group ``g = h // (H / G)`` holds a ``P x N`` matrix
``S``, zero at the start of every sequence.  At position ``t``, with the
step ``dt_t > 0`` a head, the head's rate ``A < 0`` and so the decay
``a_t = exp(dt_t A)`` in ``(0, 1)``, one scalar a head and position, and
``B_t``, ``C_t`` of width ``N`` shared by the heads of a group::

    S_t = a_t S_{t-1} + dt_t x_t (x) B_t
    y_t = S_t C_t

Position by position (:func:`ssd_scan_reference`) that is ``L``
dependent steps of rank-one work.  **In chunks** of ``Q`` positions
(:func:`ssd_scan`; 128 is the size the block runs at) it is dense
products.  With ``cum_t`` the log-decays ``dt A`` summed from the
chunk's start to ``t`` inclusive and ``S_0`` the state the chunk starts
from::

    y_t  = sum_{s<=t} (C_t . B_s) exp(cum_t - cum_s) dt_s x_s
           + exp(cum_t) S_0 C_t
    S_Q  = exp(cum_Q) S_0 + sum_s exp(cum_Q - cum_s) dt_s x_s (x) B_s

so a chunk is one ``Q x Q`` pair matrix a group (``C B^T``), a ``Q x Q``
decay matrix a head, the masked product of the two applied to ``x``,
the chunk's own contribution to the state, and the read-out of the
state it starts from.  No system is solved: the decay is a scalar, not a
matrix, and nothing is corrected (not ``ops/delta_rule.py``'s family).
**Across chunks** the ``L / Q`` chunk-start states follow from the
chunks' contributions by the same recurrence a chunk at a time, at full
float32 precision: a state is carried exactly however many chunks it
crosses.

**Every decay is the ``exp`` of a difference of summed log-decays that
is not positive**: ``cum_t - cum_s`` for ``s <= t``, ``cum_Q - cum_s``,
and the chunks' sums between two chunk boundaries.  The pairs above the diagonal are
masked before the ``exp``, so nothing overflows however fast a head
forgets (``exp(-cum_s)`` alone would, within a chunk, at the decays the
seeds give).  The sums are float32.

**Two ways to compile the one algorithm, chosen by the shapes**
(:func:`takes_kernels`; no flag).  Widths of whole lanes (the state
``N`` and a group's heads ``per x P`` multiples of 128, a head a whole
number of lane tiles or a whole number of heads a tile, chunks of 128:
the published configuration's) run as **three Mosaic kernels** in which
a chunk's matrices are made, used and dropped in VMEM.  The grid is
``(batch, head blocks, chunks)``, the last axis walked in order; a grid
step is one chunk of one **head block**: the whole of a group of up to
:data:`HEAD_BLOCK` heads (Nemotron's 8 groups of 8), or ``per`` heads
of a wider one (Granite's one group of 64 is eight blocks of 8: a
step's decay matrices, masked products and state are a block's, not
the group's, which would not fit VMEM), the blocks of a group one
after another on the grid's second axis.  ``x`` comes as the block
``(128, per P)`` of the row-major ``(B, L, H P)`` view, ``B`` and ``C``
as ``(128, N)`` blocks of ``(B, L, G N)`` **indexed by the block's
group**, the step and the summed log-decays a head as columns (and the
sums as rows too: a decay matrix is a column minus a row).  ``C B^T``
is made once a step (once a group where the group is one block; again
a head block where it is not, ``Q^2 N`` of a block's ``per (Q^2 P + 4 Q
P N)``), a head's decay matrix from the masked difference of sums,
their product applied to ``dt x``; the read-out of the state and the
chunk's contribution are one wide product each for the block's heads,
against the block's states side by side and transposed (``N x per P``,
float32): **a VMEM scratch carried along the chunk axis**, zeroed at a
row's first chunk, ``S <- exp(cum_Q) S + added`` elementwise in
float32, which is the recurrence itself a chunk at a time: no product
over the chunks and no decay matrix over them.  Every
other shape (a narrow head, a narrow state, another chunk size) runs
:func:`ssd_chunked`, the same algorithm as XLA's products and fusions,
which carries the state by :data:`CARRY_PRECISION`'s product: the
kernels' second oracle beside the recurrence.  The two share the
wrapper, the sums and no arithmetic.  Off a TPU the kernels run in
Pallas interpret mode, on float32 operands.

**Precision, either form**: the chunk's four products (``C B^T``, the
masked product on ``dt x``, the read-out, the contribution) one bf16 MXU
pass with float32 accumulation (the backend's default in the XLA form;
in the kernels the operands rounded to bf16 where they enter a product:
the masked product's weights, ``dt x`` and ``dt x exp(cum_Q - cum)``
once each, the state where the read-out reads it), and so their
transposes in the rule; the carry from chunk to chunk, the log-decays'
sums (:data:`SUM_DTYPE`), every ``exp`` and every elementwise product
float32.

**The backward pass is the operator's own rule** (``jax.custom_vjp``):
it keeps ``x, dt, A, B, C`` (and the skip, where one is given) and
nothing of the forward pass.  The XLA
form makes the chunks' matrices and the chunk-start states again and
transposes that graph (``jax.vjp`` of :func:`ssd_chunked`).  The kernels
make the ``L / Q`` chunk-start states again with a walk that computes
only the contributions and the carry (one product a step; ``N x per P``
a group and chunk, alive inside the rule only), then walk the chunks
**from the last to the first** with the state's cotangent in the VMEM
scratch, make the chunk's matrices again and write the gradients by
these equations, not the transpose of the forward graph.  With ``G = C
B^T``, ``L_ts = exp(cum_t - cum_s)`` for ``s <= t`` (else 0), ``W = G *
L`` a head, ``X = dt x``, ``e_s = exp(cum_Q - cum_s)``, ``S_0`` the
state the chunk starts from, ``dy`` the result's cotangent and ``dS``
the cotangent of the state the chunk ends in (``*`` elementwise, sums
over a group's heads where a head's term meets ``B`` or ``C``)::

    dW   = (dy X^T) * [s <= t]            dG = sum_heads dW * L
    dX   = W^T dy + e * (B dS)            dx = dt * dX + D * dy
    d dt = sum_p dX * x                   dD = sum_t,p dy * x
    dC   = dG B   + (exp(cum) * dy) S_0
    dB   = dG^T C + (e * X) dS^T
    dS_0 = exp(cum_Q) dS + C^T (exp(cum) * dy)
    d cum_t = sum_s (dW * W)_ts - sum_s (dW * W)_st
              + exp(cum_t) sum_p dy_t * (S_0 C_t)
              - e_t sum_p (B dS)_t * X_t                 (t < Q)
    d cum_Q = the same + sum_s e_s sum_p (B dS)_s * X_s
              + exp(cum_Q) sum (S_0 * dS)

Where a group is one head block, ``B`` and ``C`` of a group are whole
in one grid step and nothing is summed across steps but the state's
cotangent; where it is several, each block writes its heads' part of
``dB`` and ``dC`` (``dG`` summed over the block's heads) and the wrapper
adds the parts of a group.  What is one number a
head and position stays XLA's, in the wrapper: ``cum`` is the cumulative
sum of ``dt A`` inside a chunk in :data:`SUM_DTYPE`, and its transpose
(the reverse sum of ``d cum`` inside a chunk to ``d (dt A)``, then ``d
dt += A d(dt A)`` and ``dA = sum dt d(dt A)``) is ``jax.vjp`` of that
one function.  The result is named :data:`SSD_OUT` for a caller's
checkpoint policy (``jax.ad_checkpoint.checkpoint_name``), as the delta
rule names its own: a block that keeps it runs the forward kernel once
a step and in the backward pass the rule's two walks, the second of
which makes the chunk's matrices again.

Shapes: ``x (B, L, H, P)``, ``dt (B, L, H)``, ``a (H,)``, ``b, c (B, L,
G, N)`` with ``G`` dividing ``H``; the result ``(B, L, H, P)``, plus
``D x`` where the caller hands over Mamba-2's skip ``D (H,)`` (the
forward kernel adds it where ``x`` and ``y`` are both in VMEM).  Any
``L``: a last chunk that is not whole is filled with positions that
neither decay nor write (``dt = 0``).  No state crosses the batch axis,
and none is reset inside a row.
``chipbench/arithmetic/nemotron.py`` and ``granite.py``
``ssd_scan_cost`` count what the chunked algorithm needs (``C B^T`` once
a group and chunk, however many head blocks make it) and ``ssd_scan_roofline`` holds the
scope's device time to it, whichever form runs under the scope.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# ``a b``, ``a b^T``, ``a^T b`` into float32, and the row-major view
from mpit_tpu.ops.delta_rule import _flat, _nn, _nt, _tn
from mpit_tpu.ops.tiles import LANE, use_interpret

#: the name of the scan's result for a checkpoint policy
SSD_OUT = "ssd_out"
#: positions of a chunk (``chunk_size``)
CHUNK = 128
#: the products that carry a state from chunk to chunk: exact, and a
#: five-hundredth of the operator's work
CARRY_PRECISION = jax.lax.Precision.HIGHEST
#: Heads of a group in one grid step of the kernels, where a group has
#: more (:func:`heads_a_step`): Nemotron's group, whose bodies are known
#: to fit scoped VMEM (64 would hold 4 MB of decay matrices and as much
#: again of masked products at once).  Read at every call of
#: :func:`ssd_scan` and handed on as a static argument, as
#: :data:`SUM_DTYPE` is.
HEAD_BLOCK = 8
#: what the log-decays are summed in.  Read at every call of
#: :func:`ssd_scan` and handed on as a static argument: the probe of the
#: reference's tolerances lowers it for one build
#: (``chipbench/reference/probe_nemotron.py``)
SUM_DTYPE = jnp.float32


def ssd_scan_reference(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
                       b: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """The recurrence as it is defined, one position a step."""
    batch, _, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    per = heads // groups

    def step(state, at):
        x_t, dt_t, b_t, c_t = at     # (B, H, P), (B, H), (B, G, N) twice
        b_t, c_t = (jnp.repeat(m, per, axis=1) for m in (b_t, c_t))
        state = state * jnp.exp(dt_t * a)[..., None, None] + jnp.einsum(
            "bhp,bhn->bhpn", x_t * dt_t[..., None], b_t)
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    along = tuple(jnp.moveaxis(m, 1, 0) for m in (x, dt, b, c))
    state = jnp.zeros((batch, heads, p, n), jnp.float32)
    return jnp.moveaxis(jax.lax.scan(step, state, along)[1], 0, 1)


def _masked_decay(later: jnp.ndarray, earlier: jnp.ndarray,
                  strict: bool) -> jnp.ndarray:
    """``exp(later[..., t] - earlier[..., s])`` where ``s <= t`` (``s <
    t`` with ``strict``), else 0, as ``(..., t, s)``: the difference is
    not positive wherever it is used, and masked before the ``exp``
    wherever it is not."""
    size = later.shape[-1]
    t, s = jnp.arange(size)[:, None], jnp.arange(size)[None, :]
    live = s < t if strict else s <= t
    diff = later[..., :, None] - earlier[..., None, :]
    return jnp.exp(jnp.where(live, diff, -jnp.inf))


def ssd_chunked(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
                b: jnp.ndarray, c: jnp.ndarray, chunk: int,
                sum_dtype=jnp.float32) -> jnp.ndarray:
    """The chunked form written out (the module's docstring has the
    equations): what :func:`ssd_scan` computes and what its rule
    transposes.  ``sum_dtype``: what the log-decays are summed in."""
    batch, length, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    per = heads // groups
    short = -length % chunk
    if short:
        x, dt, b, c = (jnp.pad(m, ((0, 0), (0, short)) + ((0, 0),) * (
            m.ndim - 2)) for m in (x, dt, b, c))
    count = (length + short) // chunk
    # chunks; a head is (group, head in the group)
    x = x.reshape(batch, count, chunk, groups, per, p)
    dt = dt.reshape(batch, count, chunk, groups, per)
    b = b.reshape(batch, count, chunk, groups, n)
    c = c.reshape(batch, count, chunk, groups, n)
    # summed log-decays inside a chunk, float32, heads before positions
    cum = jnp.cumsum((dt * a.reshape(groups, per)).astype(sum_dtype),
                     axis=2).astype(jnp.float32)
    cum = cum.transpose(0, 1, 3, 4, 2)                 # (B, c, G, per, Q)
    written = dt[..., None] * x                        # dt_s x_s

    # inside a chunk: the pairs s <= t
    pairs = jnp.einsum("bcqgn,bcsgn->bcgqs", c, b)
    weights = pairs[:, :, :, None] * _masked_decay(cum, cum, strict=False)
    y = jnp.einsum("bcghqs,bcsghp->bcqghp", weights, written)

    # what each chunk adds to the state by its end
    total = cum[..., -1]                               # (B, c, G, per)
    to_end = jnp.exp(total[..., None] - cum)           # (B, c, G, per, Q)
    added = jnp.einsum("bcsghp,bcsgn->bcghpn",
                       written * to_end.transpose(0, 1, 4, 2, 3)[..., None],
                       b)

    # the state each chunk starts from: the earlier chunks'
    # contributions, decayed over the chunks between
    through = jnp.cumsum(total, axis=1).transpose(0, 2, 3, 1)  # (B,G,per,c)
    before = through - total.transpose(0, 2, 3, 1)     # to the chunk's start
    start = jnp.einsum("bghcd,bdghpn->bcghpn",
                       _masked_decay(before, through, strict=True), added,
                       precision=CARRY_PRECISION)
    y = y + jnp.einsum("bcqgn,bcghpn->bcqghp", c, start) * jnp.exp(
        cum).transpose(0, 1, 4, 2, 3)[..., None]
    return y.reshape(batch, count * chunk, heads, p)[:, :length]


# -- the chunk as Mosaic kernels (widths of whole lanes) ------------------------
#
# One grid step is one chunk of one head block (``per`` heads of one
# group: the whole group where it has no more than HEAD_BLOCK): x as the
# block ``(CHUNK, per x P)`` of the row-major ``(B, L, H P)`` view, B and
# C as ``(CHUNK, N)`` blocks of ``(B, L, G N)``, where the convolution's
# slices leave them (no transpose into chunks and none back).  What is
# one number a head and position (the step, the summed log-decays) comes
# a block at a time, as columns ``(CHUNK, per)`` and, the sums, as rows
# ``(per, CHUNK)`` too: a decay matrix is a column minus a row.  The
# block's states, transposed and side by side (``N x per P``: a head's
# decay scales its lanes, and the read-out and the contribution are one
# wide product each for the block), are a VMEM scratch carried along the
# grid's last axis.  A lane
# tile of 128 holds ``128 / P`` heads where a head is narrower: a product
# a head is taken against the whole tile and its lanes kept, so no value
# is cut or joined inside a tile.  The heads of a step are written stage
# by stage (every decay matrix, then every masked product, then every
# product), not head by head: the kernel's compiler runs the small
# products in the order they are written (``ops/delta_rule.py``
# ``_in_step``).


class _Group:
    """The arithmetic of one chunk and head block (``per`` heads of one
    group) on values, shared by the three kernels.  ``one_pass``: the products take their operands in
    bf16 (one MXU pass, float32 sums: what the backend's default
    precision is to the XLA form, rounded where it rounds); everything
    else is float32 either way."""

    def __init__(self, per, p, one_pass):
        self.per, self.p, self.one_pass = per, p, one_pass
        self.width = max(p, LANE)              # of a lane tile
        self.share = self.width // p           # heads in one
        at = partial(jax.lax.broadcasted_iota, jnp.int32, (CHUNK, CHUNK))
        self.live = at(1) <= at(0)             # s <= t, as (t, s)
        self.masks = {}

    def low(self, x):
        return x.astype(jnp.bfloat16) if self.one_pass else x

    def tile(self, x, j):
        """The lane tile of ``x (rows, per P)`` that holds head ``j``."""
        k = j // self.share
        return x[:, k * self.width:(k + 1) * self.width]

    def mine(self, j, rows):
        """The lanes of head ``j`` in its tile, ``(rows, width)``; made
        once a place in the tile and height."""
        place = j % self.share
        if (place, rows) not in self.masks:
            lane = jax.lax.broadcasted_iota(jnp.int32, (rows, self.width), 1)
            self.masks[place, rows] = (lane >= place * self.p) & (
                lane < (place + 1) * self.p)
        return self.masks[place, rows]

    def heads(self, tiles):
        """``(rows, per P)`` of a head's ``(rows, width)`` each: a tile's
        lanes from the head that owns them."""
        out = []
        for k in range(self.per // self.share):
            first = k * self.share
            kept = tiles[first]
            for j in range(first + 1, first + self.share):
                kept = jnp.where(self.mine(j, kept.shape[0]), tiles[j], kept)
            out.append(kept)
        return jnp.concatenate(out, axis=1) if len(out) > 1 else out[0]

    def spread(self, block):
        """``(rows, per)``, a head a lane, as ``(rows, per P)``: a head's
        number in each of its lanes."""
        rows = block.shape[0]
        return self.heads([jnp.broadcast_to(block[:, j:j + 1],
                                            (rows, self.width))
                           for j in range(self.per)])

    def gather(self, x):
        """The sums over a head's lanes of ``x (rows, per P)``, as
        ``(rows, per)``."""
        rows = x.shape[0]
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, self.per), 1)
        out = jnp.zeros((rows, self.per), jnp.float32)
        for j in range(self.per):
            of = jnp.sum(jnp.where(self.mine(j, rows), self.tile(x, j), 0.0),
                         axis=1, keepdims=True)
            out = jnp.where(lane == j, of, out)
        return out

    def decays(self, cum, cum_rows):
        """A head's ``exp(cum_t - cum_s)`` where ``s <= t``, else 0, as
        ``(t, s)``: masked before the ``exp``."""
        return [jnp.exp(jnp.where(
            self.live, cum[:, j:j + 1] - cum_rows[j:j + 1, :], -jnp.inf))
            for j in range(self.per)]

    def scaled(self, x, dt, cum):
        """``(dt x, dt x exp(cum_Q - cum))`` and, a head's number in each
        of its lanes, ``(dt, exp(cum_Q - cum), exp(cum_Q))``."""
        total = cum[CHUNK - 1:]                           # (1, per)
        by = (self.spread(dt), self.spread(jnp.exp(total - cum)),
              self.spread(jnp.exp(total)))
        written = x * by[0]
        return written, written * by[1], by

    def carried(self, state, leaving, whole, b):
        """The state at the chunk's end."""
        return state * whole + _tn(self.low(b), self.low(leaving))

    def forward(self, state, x, dt, cum, cum_rows, b, c, skip):
        """``(y + skip x, the state at the chunk's end)``."""
        low = self.low
        written, leaving, (_, _, whole) = self.scaled(x, dt, cum)
        pairs = _nt(low(c), low(b))
        weights = [low(pairs * decay) for decay in self.decays(cum, cum_rows)]
        written = low(written)
        inside = self.heads([_nn(weights[j], self.tile(written, j))
                             for j in range(self.per)])
        read = _nn(low(c), low(state)) * self.spread(jnp.exp(cum))
        return (inside + read + x * self.spread(skip),
                self.carried(state, leaving, whole, b))

    def backward(self, state, x, dt, cum, cum_rows, b, c, skip, dy, dafter):
        """The chunk's matrices again, then ``(dx, d dt, d cum as
        columns, d cum as rows (to be added), dB, dC, the chunk's part
        of d skip, the cotangent of the state the chunk starts from)``
        from ``dy`` and the cotangent ``dafter`` of the state it ends
        in: the module's docstring has the equations."""
        low, per = self.low, self.per
        written, leaving, (by_dt, to_end, whole) = self.scaled(x, dt, cum)
        pairs = _nt(low(c), low(b))
        decays = self.decays(cum, cum_rows)
        low_written, low_dy = low(written), low(dy)
        # the masked products' cotangents, a head: dW = dy (dt x)^T
        dweights = [_nt(low(jnp.where(self.mine(j, CHUNK),
                                      self.tile(dy, j), 0.0)),
                        self.tile(low_written, j)) for j in range(per)]
        dpairs = sum(dw * decay for dw, decay in zip(dweights, decays))
        weights = [pairs * decay for decay in decays]
        moved = [dw * w for dw, w in zip(dweights, weights)]  # d cum_t - d cum_s
        through = _nn(low(b), low(dafter))                    # B dS
        dwritten = self.heads([_tn(low(weights[j]), self.tile(low_dy, j))
                               for j in range(per)]) + through * to_end
        dread = dy * self.spread(jnp.exp(cum))
        read = _nn(low(c), low(state))
        dc = _nn(low(dpairs), low(b)) + _nt(low(dread), low(state))
        db = _tn(low(dpairs), low(c)) + _nt(low(leaving), low(dafter))
        dstate = dafter * whole + _tn(low(c), low(dread))
        # the summed log-decays': the read-out's exp(cum_t), the
        # contribution's exp(cum_Q - cum_s), the carry's exp(cum_Q), and
        # the decay matrices' rows and columns
        left = through * leaving
        dtotal = jnp.sum(left, axis=0, keepdims=True) + whole * jnp.sum(
            state * dafter, axis=0, keepdims=True)
        row = jax.lax.broadcasted_iota(jnp.int32, left.shape, 0)
        dcum = self.gather(dread * read - left
                           + jnp.where(row == CHUNK - 1, dtotal, 0.0))
        lane = jax.lax.broadcasted_iota(jnp.int32, dcum.shape, 1)
        at = jax.lax.broadcasted_iota(jnp.int32, cum_rows.shape, 0)
        dcum_rows = jnp.zeros(cum_rows.shape, jnp.float32)
        for j in range(per):
            dcum = dcum + jnp.where(
                lane == j, jnp.sum(moved[j], axis=1, keepdims=True), 0.0)
            dcum_rows = jnp.where(
                at == j, -jnp.sum(moved[j], axis=0, keepdims=True), dcum_rows)
        return (dwritten * by_dt + dy * self.spread(skip),
                self.gather(dwritten * x), dcum, dcum_rows, db, dc,
                self.gather(jnp.sum(dy * x, axis=0, keepdims=True)), dstate)


def _zero_at_the_first(state_ref):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)


def _forward_kernel(x_ref, dt_ref, cum_ref, cum_rows_ref, b_ref, c_ref,
                    skip_ref, y_ref, state_ref, **static):
    _zero_at_the_first(state_ref)
    y_ref[0], state_ref[...] = _Group(**static).forward(
        state_ref[...], x_ref[0], dt_ref[0, 0], cum_ref[0, 0],
        cum_rows_ref[0, 0], b_ref[0], c_ref[0], skip_ref[0])


def _states_kernel(x_ref, dt_ref, cum_ref, b_ref, starts_ref, state_ref,
                   **static):
    """Every chunk's starting state: the contributions and the carry,
    one product a step."""
    _zero_at_the_first(state_ref)
    group = _Group(**static)
    _, leaving, (_, _, whole) = group.scaled(x_ref[0], dt_ref[0, 0],
                                             cum_ref[0, 0])
    starts_ref[0, 0, 0] = state_ref[...]
    state_ref[...] = group.carried(state_ref[...], leaving, whole, b_ref[0])


def _backward_kernel(x_ref, dt_ref, cum_ref, cum_rows_ref, b_ref, c_ref,
                     skip_ref, dy_ref, starts_ref, dx_ref, ddt_ref, dcum_ref,
                     dcum_rows_ref, db_ref, dc_ref, dskip_ref, dstate_ref,
                     **static):
    """``dskip_ref`` is one block a row and group, summed over the walk."""
    _zero_at_the_first(dstate_ref)
    _zero_at_the_first(dskip_ref)
    (dx_ref[0], ddt_ref[0, 0], dcum_ref[0, 0], dcum_rows_ref[0, 0], db_ref[0],
     dc_ref[0], dskip, dstate_ref[...]) = _Group(**static).backward(
        starts_ref[0, 0, 0], x_ref[0], dt_ref[0, 0], cum_ref[0, 0],
        cum_rows_ref[0, 0], b_ref[0], c_ref[0], skip_ref[0], dy_ref[0],
        dstate_ref[...])
    dskip_ref[0, 0] += dskip


class _Calls:
    """What the three calls share: the sizes and the block specs of one
    walk over the chunks (``back``: from the last to the first), and the
    views the kernels read."""

    def __init__(self, x, b, interpret, per, back=False):
        self.b, self.length, self.h, self.p = x.shape
        groups, self.n = b.shape[2:]
        # ``g`` head blocks of ``per`` heads, ``self.blocks`` a group
        self.per, count = per, self.length // CHUNK
        self.g, self.blocks = self.h // per, self.h // groups // per
        self.count = count
        at = (lambda c: count - 1 - c) if back else (lambda c: c)
        blocks = self.blocks
        group_of = (lambda j: j) if blocks == 1 else (lambda j: j // blocks)
        self.static = dict(per=self.per, p=self.p, one_pass=not interpret)
        self.call = dict(
            grid=(self.b, self.g, count), interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")))
        # a chunk of a head block: its heads' x, its group's B or C,
        # row-major; a block's part of dB or dC, the blocks side by side
        self.wide = pl.BlockSpec((1, CHUNK, self.per * self.p),
                                 lambda i, j, c: (i, at(c), j))
        self.shared = pl.BlockSpec((1, CHUNK, self.n),
                                   lambda i, j, c: (i, at(c), group_of(j)))
        self.parts = pl.BlockSpec((1, CHUNK, self.n),
                                  lambda i, j, c: (i, at(c), j))
        # a number a head and position: positions by heads, heads by
        # positions
        self.columns = pl.BlockSpec((1, 1, CHUNK, self.per),
                                    lambda i, j, c: (i, j, at(c), 0))
        self.rows = pl.BlockSpec((1, 1, self.per, CHUNK),
                                 lambda i, j, c: (i, j, 0, at(c)))
        self.starts = pl.BlockSpec((1, 1, 1, self.n, self.per * self.p),
                                   lambda i, j, c: (i, j, at(c), 0, 0))
        # a number a head: the skip a block, its gradient a row and block
        self.skip = pl.BlockSpec((1, 1, self.per), lambda i, j, c: (j, 0, 0))
        self.dskip = pl.BlockSpec((1, 1, 1, self.per),
                                  lambda i, j, c: (i, j, 0, 0))
        self.scratch = [pltpu.VMEM((self.n, self.per * self.p), jnp.float32)]

    def by_group(self, x):
        """``(B, L, H)`` as ``(B, head blocks, L, per)``."""
        return x.reshape(self.b, self.length, self.g, self.per
                         ).transpose(0, 2, 1, 3)

    def by_position(self, x):
        """``(B, head blocks, L, per)`` as ``(B, L, H)``."""
        return x.transpose(0, 2, 1, 3).reshape(self.b, self.length, self.h)

    def of_group(self, parts, like):
        """A group's ``dB`` or ``dC`` from its head blocks' parts ``(B, L,
        head blocks x N)``, in the shape of ``like (B, L, G, N)``."""
        if self.blocks == 1:
            return parts.reshape(like.shape)
        return jnp.sum(parts.reshape(like.shape[:3] + (self.blocks, self.n)),
                       axis=3)

    def f32(self, *shape):
        return jax.ShapeDtypeStruct((self.b,) + shape, jnp.float32)


# The three calls are ``jax.jit``s of their own: a step's layers share one
# trace and one lowering of each kernel, not one a layer and program.


@partial(jax.jit, static_argnames=("interpret", "per"))
def _kernel_forward(x, dt, cum, b, c, skip, interpret, per):
    """``y + skip x (B, L, H P)``; ``L`` whole chunks, ``cum (B, L, H)``
    the log-decays summed from each chunk's start, ``skip (H,)``, ``per``
    heads a grid step."""
    k = _Calls(x, b, interpret, per)
    cum = k.by_group(cum)
    return pl.pallas_call(
        partial(_forward_kernel, **k.static),
        in_specs=[k.wide, k.columns, k.columns, k.rows, k.shared, k.shared,
                  k.skip],
        out_specs=k.wide, out_shape=k.f32(k.length, k.h * k.p),
        scratch_shapes=k.scratch, **k.call,
    )(_flat(x), k.by_group(dt), cum, cum.transpose(0, 1, 3, 2), _flat(b),
      _flat(c), skip.reshape(k.g, 1, k.per))


@partial(jax.jit, static_argnames=("interpret", "per"))
def _kernel_states(x, dt, cum, b, interpret, per):
    """The state every chunk starts from, transposed and a head block's
    side by side: ``(B, head blocks, L / CHUNK, N, per P)``."""
    k = _Calls(x, b, interpret, per)
    return pl.pallas_call(
        partial(_states_kernel, **k.static),
        in_specs=[k.wide, k.columns, k.columns, k.shared],
        out_specs=k.starts,
        out_shape=k.f32(k.g, k.count, k.n, k.per * k.p),
        scratch_shapes=k.scratch, **k.call,
    )(_flat(x), k.by_group(dt), k.by_group(cum), _flat(b))


@partial(jax.jit, static_argnames=("interpret", "per"))
def _kernel_backward(x, dt, cum, b, c, skip, starts, dy, interpret, per):
    """``(dx, d dt, d cum, dB, dC, d skip)`` from ``dy (B, L, H P)``,
    the chunks walked from the last to the first with the state's
    cotangent in VMEM; ``dB`` and ``dC`` a head block's part a step,
    added a group."""
    k = _Calls(x, b, interpret, per, back=True)
    cum = k.by_group(cum)
    columns, rows = k.f32(k.g, k.length, k.per), k.f32(k.g, k.per, k.length)
    dx, ddt, dcum, dcum_rows, db, dc, dskip = pl.pallas_call(
        partial(_backward_kernel, **k.static),
        in_specs=[k.wide, k.columns, k.columns, k.rows, k.shared, k.shared,
                  k.skip, k.wide, k.starts],
        out_specs=[k.wide, k.columns, k.columns, k.rows, k.parts, k.parts,
                   k.dskip],
        out_shape=[k.f32(k.length, k.h * k.p), columns, columns, rows,
                   k.f32(k.length, k.g * k.n), k.f32(k.length, k.g * k.n),
                   k.f32(k.g, 1, k.per)],
        scratch_shapes=k.scratch, **k.call,
    )(_flat(x), k.by_group(dt), cum, cum.transpose(0, 1, 3, 2), _flat(b),
      _flat(c), skip.reshape(k.g, 1, k.per), dy, starts)
    return (dx.reshape(x.shape), k.by_position(ddt),
            k.by_position(dcum + dcum_rows.transpose(0, 1, 3, 2)),
            k.of_group(db, b), k.of_group(dc, c),
            jnp.sum(dskip, axis=0).reshape(k.h))


def _sums(dt, a, sum_dtype):
    """The log-decays ``dt a`` summed from each chunk's start to ``t``
    inclusive, ``(B, L, H)`` float32, summed in ``sum_dtype``; ``L``
    whole chunks of :data:`CHUNK`.  A narrower dtype's sums are rounded
    to it by name too: between two casts the chip's compiler keeps them
    at float32's precision, and a lowered dtype (the probe's) would
    read as the stated one."""
    batch, length, heads = dt.shape
    steps = (dt * a).astype(sum_dtype).reshape(batch, -1, CHUNK, heads)
    cum = jnp.cumsum(steps, axis=2).astype(jnp.float32).reshape(dt.shape)
    lower = jnp.finfo(sum_dtype)
    if lower.nmant < jnp.finfo(jnp.float32).nmant:
        cum = jax.lax.reduce_precision(cum, lower.nexp, lower.nmant)
    return cum


def _whole_chunks(x):
    """``x (B, L, ...)`` filled to whole chunks with positions that
    neither decay nor write (zeros)."""
    short = -x.shape[1] % CHUNK
    return jnp.pad(x, ((0, 0), (0, short)) + ((0, 0),) * (x.ndim - 2)) \
        if short else x


def _no_skip(x):
    return jnp.zeros((x.shape[2],), jnp.float32)


def _kernels_forward(x, dt, a, b, c, skip, sum_dtype, per):
    length = x.shape[1]
    x, dt, b, c = map(_whole_chunks, (x, dt, b, c))
    return _kernel_forward(x, dt, _sums(dt, a, sum_dtype), b, c,
                           _no_skip(x) if skip is None else skip,
                           use_interpret(None), per)[:, :length]


def _kernels_backward(x, dt, a, b, c, skip, sum_dtype, per, g):
    length = x.shape[1]
    x, dt, b, c, g = map(_whole_chunks, (x, dt, b, c, g))
    # the sums and their transposes (the reverse sum inside a chunk, the
    # rates' as a sum) are XLA's: a number a head and position
    cum, sums_back = jax.vjp(partial(_sums, sum_dtype=sum_dtype), dt, a)
    interpret = use_interpret(None)
    starts = _kernel_states(x, dt, cum, b, interpret, per)
    dx, ddt, dcum, db, dc, dskip = _kernel_backward(
        x, dt, cum, b, c, _no_skip(x) if skip is None else skip, starts, g,
        interpret, per)
    through_sums, da = sums_back(dcum)
    return (dx[:, :length], (ddt + through_sums)[:, :length], da,
            db[:, :length], dc[:, :length], None if skip is None else dskip)


def takes_kernels(x, b, chunk) -> bool:
    """Whether these shapes run as the Mosaic kernels: chunks of
    :data:`CHUNK`, a state width and a group's heads of whole lanes, a
    head that divides a lane tile or is whole tiles."""
    (heads, p), (groups, n) = x.shape[2:], b.shape[2:]
    return (chunk == CHUNK == LANE and n % LANE == 0
            and (heads // groups * p) % LANE == 0
            and (p % LANE == 0 or LANE % p == 0))


def heads_a_step(x, b, most: int) -> int:
    """Heads of a group in one grid step of the kernels: the whole group
    where it has no more than ``most``, else the largest part of it up
    to ``most`` that divides it in whole lane tiles (the whole group
    where no part does)."""
    (heads, p), groups = x.shape[2:], b.shape[2]
    per = heads // groups
    return next((k for k in range(min(per, most), 0, -1)
                 if per % k == 0 and k * p % LANE == 0), per)


def _xla_form(x, dt, a, b, c, skip, chunk, sum_dtype):
    y = ssd_chunked(x, dt, a, b, c, chunk, sum_dtype)
    return _flat(y if skip is None else y + skip[:, None] * x)


def _forward(x, dt, a, b, c, skip, chunk, sum_dtype, most):
    """The result as its row-major ``(B, L, H P)`` view: what the
    kernels write, and what a block's checkpoint keeps by name (a head
    narrower than a lane tile would be kept in a layout of its own, a
    relayout each way).  ``most``: :data:`HEAD_BLOCK` as the call read
    it."""
    if takes_kernels(x, b, chunk):
        return _kernels_forward(x, dt, a, b, c, skip, sum_dtype,
                                heads_a_step(x, b, most))
    return _xla_form(x, dt, a, b, c, skip, chunk, sum_dtype)


_ssd_scan = jax.custom_vjp(_forward, nondiff_argnums=(6, 7, 8))


def _ssd_scan_fwd(x, dt, a, b, c, skip, chunk, sum_dtype, most):
    return (_forward(x, dt, a, b, c, skip, chunk, sum_dtype, most),
            (x, dt, a, b, c, skip))


def _ssd_scan_bwd(chunk, sum_dtype, most, kept, g):
    if takes_kernels(kept[0], kept[3], chunk):
        return _kernels_backward(*kept, sum_dtype,
                                 heads_a_step(kept[0], kept[3], most), g)
    # the chunks and the chunk-start states again, and their transposes
    _, back = jax.vjp(partial(_xla_form, chunk=chunk, sum_dtype=sum_dtype),
                      *kept)
    return back(g)


_ssd_scan.defvjp(_ssd_scan_fwd, _ssd_scan_bwd)


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
             b: jnp.ndarray, c: jnp.ndarray, chunk: int = CHUNK,
             skip: jnp.ndarray | None = None) -> jnp.ndarray:
    """``y (B, L, H, P)`` of the state-space recurrence in chunks of
    ``chunk`` positions, plus ``skip[h] x`` where a skip ``(H,)`` is
    given (Mamba-2's ``D``: the kernels add it where ``x`` and ``y`` are
    both in VMEM); the module's docstring has the shapes, the algorithm,
    the two forms and the rule.  Which form runs is read off the shapes
    (:func:`takes_kernels`), and so is how many heads of a group a grid
    step of the kernels holds (:func:`heads_a_step`)."""
    heads, groups = x.shape[2], b.shape[2]
    if heads % groups or b.shape != c.shape or dt.shape != x.shape[:3] or (
            skip is not None and skip.shape != (heads,)):
        raise ValueError(f"ssd_scan: x {x.shape}, dt {dt.shape}, b "
                         f"{b.shape}, c {c.shape}"
                         + ("" if skip is None else f", skip {skip.shape}"))
    return checkpoint_name(
        _ssd_scan(x, dt, a, b, c, skip, int(chunk), SUM_DTYPE, HEAD_BLOCK),
        SSD_OUT
    ).reshape(x.shape)
