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
chunks' contributions by the same recurrence a chunk at a time; it is
written as one more masked decay matrix (``L / Q`` square, a head) times
the contributions, at full float32 precision: a state is carried
exactly however many chunks it crosses.

**Every decay is the ``exp`` of a difference of summed log-decays that
is not positive**: ``cum_t - cum_s`` for ``s <= t`` and the chunks'
sums between two chunk boundaries.  The pairs above the diagonal are
masked before the ``exp``, so nothing overflows however fast a head
forgets (``exp(-cum_s)`` alone would, within a chunk, at the decays the
seeds give).  The sums are float32.

**The backward pass is the operator's own rule** (``jax.custom_vjp``):
it keeps ``x, dt, A, B, C`` and nothing of the forward pass, makes the
chunks' matrices and the chunk-start states again (``L / Q`` states of
``P x N`` a head, never ``L``) and transposes that, chunk by chunk, not
the recurrence's ``L`` steps.  The result is named :data:`SSD_OUT` for
a caller's checkpoint policy (``jax.ad_checkpoint.checkpoint_name``), as
the delta rule names its own: a block that keeps it runs the chunks
three times a step (forward, again inside this rule, and the rule's
transposes), not four.

Shapes: ``x (B, L, H, P)``, ``dt (B, L, H)``, ``a (H,)``, ``b, c (B, L,
G, N)`` with ``G`` dividing ``H``; the result ``(B, L, H, P)``.  Any
``L``: a last chunk that is not whole is filled with positions that
neither decay nor write (``dt = 0``).  No state crosses the batch axis,
and none is reset inside a row.  XLA's products and fusions, no Mosaic
kernel; ``chipbench/arithmetic/nemotron.py`` ``ssd_scan_cost`` counts
what the chunked algorithm needs and ``ssd_scan_roofline`` holds the
scope's device time to it, whichever form runs under the scope.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

#: the name of the scan's result for a checkpoint policy
SSD_OUT = "ssd_out"
#: positions of a chunk (``chunk_size``)
CHUNK = 128
#: the products that carry a state from chunk to chunk: exact, and a
#: five-hundredth of the operator's work
CARRY_PRECISION = jax.lax.Precision.HIGHEST
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


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd_scan(x, dt, a, b, c, chunk, sum_dtype):
    return ssd_chunked(x, dt, a, b, c, chunk, sum_dtype)


def _ssd_scan_fwd(x, dt, a, b, c, chunk, sum_dtype):
    return ssd_chunked(x, dt, a, b, c, chunk, sum_dtype), (x, dt, a, b, c)


def _ssd_scan_bwd(chunk, sum_dtype, kept, g):
    # the chunks and the chunk-start states again, and their transposes
    _, back = jax.vjp(partial(ssd_chunked, chunk=chunk,
                              sum_dtype=sum_dtype), *kept)
    return back(g)


_ssd_scan.defvjp(_ssd_scan_fwd, _ssd_scan_bwd)


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
             b: jnp.ndarray, c: jnp.ndarray,
             chunk: int = CHUNK) -> jnp.ndarray:
    """``y (B, L, H, P)`` of the state-space recurrence in chunks of
    ``chunk`` positions; the module's docstring has the shapes, the
    algorithm and the rule."""
    heads, groups = x.shape[2], b.shape[2]
    if heads % groups or b.shape != c.shape or dt.shape != x.shape[:3]:
        raise ValueError(f"ssd_scan: x {x.shape}, dt {dt.shape}, b "
                         f"{b.shape}, c {c.shape}")
    return checkpoint_name(
        _ssd_scan(x, dt, a, b, c, int(chunk), SUM_DTYPE), SSD_OUT)
