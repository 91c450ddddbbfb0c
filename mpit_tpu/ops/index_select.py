"""A learned selection of keys: the indexer's scores and an exact top-k
a query, as bits.

Learned sparse attention (DeepSeek-V3.2's lightning indexer; the block
that uses it is ``models/transformer.py`` ``KeyeBlock``) lets a query
attend the ``topk`` earlier positions a small scorer ranks highest, the
same set for all its heads.  This file computes that set:

- the score of query ``t`` for key ``j <= t`` is ``I[t, j] = sum_h w[t,
  h] * ReLU(qI[t, h] . kI[j])`` over ``H`` narrow heads against **one**
  key head, in float32 at full precision (membership in the top ``k``
  flips where two scores are close, ``ROUTER_PRECISION``'s reason);
- ``S_t`` is the positions of the ``min(t + 1, topk)`` largest, **a tie
  to the lower position**: exactly that many a row, always.

The scores exist a block of :data:`ROWS` rows at a time (``ROWS x T``
floats, never ``(H, T, T)``) inside a ``lax.map``; a block wholly below
``topk`` takes every earlier position and computes no score.  The
``k``-th largest of a row is found by **bisection on the bits** of the
scores' order-preserving integer keys (32 passes of compare-and-count
over the block, each a fused reduction: ``lax.top_k`` at ``k`` 2048 of
8192 is a sort), then the ties at the threshold by a second bisection on
the position, run only where a row has more equals than it needs.

The set leaves as **bits**, in the layout the flash kernels' tile
dictates (``ops/select_bits.py``: ``pack``; 8 MB a layer at 8192
positions).

No gradient: the set is piecewise constant in everything it is made
from, so the inputs are stopped here and the backward pass has nothing
to run.  XLA's fusions and products; no Mosaic kernel yet (ROADMAP).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from mpit_tpu.ops.select_bits import pack

#: rows whose scores exist at once (``ROWS x Lk`` float32 a head before
#: the heads' sum: 16 heads x 256 x 8192 is 134 MB); a shorter sequence
#: is one block
ROWS = 256

#: the scores' products: full float32, for the reason the router's are
#: (``models/transformer.py`` ``ROUTER_PRECISION``)
SCORE_PRECISION = jax.lax.Precision.HIGHEST

_I32_MIN = np.int32(-2**31)


def index_scores(qi: jnp.ndarray, ki: jnp.ndarray,
                 w: jnp.ndarray) -> jnp.ndarray:
    """``I (R, Lk)`` of ``qi (R, H, D)``, ``ki (Lk, D)``, ``w (R, H)``:
    the heads' products at full float32 precision, the ReLU and the
    weighted sum elementwise in float32 (no product over the heads)."""
    s = jnp.einsum("rhd,kd->hrk", qi, ki,
                   precision=SCORE_PRECISION,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w.T[:, :, None], axis=0)


def _keys(scores: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """float32 scores as uint32 keys of the same order (a negative
    float's bits reversed, the sign bit flipped; ``-0.0`` is ``0.0``),
    0 where not ``valid``: below every finite score's key."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    bits = jnp.where(bits == _I32_MIN, 0, bits)
    ordered = bits ^ ((bits >> 31) & np.int32(0x7FFFFFFF))
    keys = jax.lax.bitcast_convert_type(ordered, jnp.uint32) ^ jnp.uint32(
        0x80000000)
    return jnp.where(valid, keys, jnp.uint32(0))


def _largest_where(count_of, target: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Per row the largest uint32 ``x`` below ``2 ** bits`` with
    ``keep(count_of(x), target)``, for a ``count_of`` that does not grow
    with ``x``: one bit a pass from the highest down."""
    def step(i, x):
        candidate = x | (jnp.uint32(1) << (bits - 1 - i).astype(jnp.uint32))
        return jnp.where(count_of(candidate) >= target, candidate, x)

    return jax.lax.fori_loop(0, bits, step, jnp.zeros_like(target, jnp.uint32))


def top_k_mask(scores: jnp.ndarray, valid: jnp.ndarray,
               k: jnp.ndarray) -> jnp.ndarray:
    """``(R, Lk)`` booleans: per row the ``k (R,)`` largest ``scores``
    among the ``valid`` (``1 <= k <=`` their count), a tie to the lower
    column; exactly ``k`` a row."""
    keys = _keys(scores, valid)
    k = k.astype(jnp.int32)[:, None]
    count = lambda hit: jnp.sum(hit, axis=-1, keepdims=True, dtype=jnp.int32)
    # the k-th largest key: the largest x that k keys reach
    kth = _largest_where(lambda x: count(keys >= x), k, 32)
    above, equal = keys > kth, keys == kth
    need = k - count(above)   # of the equals, the lowest columns: >= 1
    column = jnp.arange(scores.shape[-1], dtype=jnp.uint32)[None, :]

    def tied(_):
        # the largest p with fewer than ``need`` equals below it: the
        # column of the last equal taken
        span = max(int(scores.shape[-1] - 1).bit_length(), 1)
        return _largest_where(
            lambda p: need - count(equal & (column < p)),
            jnp.ones_like(need), span)

    last = jax.lax.cond(
        jnp.any(count(equal) > need), tied,
        lambda _: jnp.full_like(kth, scores.shape[-1]), None)
    return above | (equal & (column <= last))


def index_select(qi: jnp.ndarray, ki: jnp.ndarray, w: jnp.ndarray,
                 topk: int) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The selection of ``qi (B, L, H, D)``, ``ki (B, L, D)``, ``w (B,
    L, H)``: ``(select, kept_share, window_overlap)``.  ``select (B, L,
    words_of(L))`` int32 is the set as bits (``ops/select_bits.py``);
    ``kept_share`` the chosen pairs over the causal pairs (``1.0``: nothing is left out);
    ``window_overlap`` over the rows that have a choice (``t >= topk``)
    the share of chosen positions among the row's ``topk`` most recent
    (``1.0``: the indexer is a sliding window).  No key crosses
    sequences; no gradient.  One ``lax.map`` over every sequence's
    blocks of :data:`ROWS` rows (not a ``vmap`` over sequences: its
    ``cond``s would become selects and the ties' passes run always)."""
    qi, ki, w = (jax.lax.stop_gradient(x.astype(jnp.float32))
                 for x in (qi, ki, w))
    b, l, h, d = qi.shape
    topk, rows = int(topk), min(ROWS, l)
    blocks = -(-l // rows)
    pad = blocks * rows - l
    qi_b = jnp.pad(qi, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        b * blocks, rows, h, d)
    w_b = jnp.pad(w, ((0, 0), (0, pad), (0, 0))).reshape(b * blocks, rows, h)
    column = jnp.arange(l, dtype=jnp.int32)[None, :]

    def block(args):
        sequence, start, qi_r, w_r = args
        row = start + jnp.arange(rows, dtype=jnp.int32)[:, None]
        causal = (column <= row) & (row < l)

        def chosen_of(_):
            return top_k_mask(index_scores(qi_r, ki[sequence], w_r), causal,
                              jnp.clip(row[:, 0] + 1, 1, topk)) & causal

        # a block wholly below topk takes every earlier position
        chosen = jax.lax.cond(start + rows <= topk, lambda _: causal,
                              chosen_of, None)
        kept = jnp.sum(chosen, axis=-1, dtype=jnp.int32)
        recent = jnp.sum(chosen & (row >= topk) & (row - column < topk),
                         axis=-1, dtype=jnp.int32)
        return pack(chosen), kept, recent

    sequences = jnp.repeat(jnp.arange(b, dtype=jnp.int32), blocks)
    starts = jnp.tile(jnp.arange(blocks, dtype=jnp.int32) * rows, b)
    words, kept, recent = jax.lax.map(block, (sequences, starts, qi_b, w_b))
    flat = lambda x: x.reshape(b, blocks * rows, *x.shape[2:])[:, :l]
    words, kept, recent = flat(words), flat(kept), flat(recent)
    kept_share = jnp.sum(kept) / (b * l * (l + 1) / 2)
    chosen_late = jnp.sum(jnp.where(jnp.arange(l)[None, :] >= topk, kept, 0))
    overlap = jnp.sum(recent) / jnp.maximum(chosen_late, 1)
    return words, kept_share.astype(jnp.float32), overlap.astype(jnp.float32)


def index_select_reference(qi, ki, w, topk: int) -> jnp.ndarray:
    """The same set ``(B, L, L)`` as booleans by a **stable sort** of
    whole rows of materialised scores: descending, equal scores in
    ascending position."""
    def one(qi, ki, w):
        l = qi.shape[0]
        position = jnp.arange(l)
        causal = position[None, :] <= position[:, None]
        scores = jnp.where(causal, index_scores(qi, ki, w), -jnp.inf)
        order = jnp.argsort(-scores, axis=-1, stable=True)
        rank = jnp.argsort(order, axis=-1, stable=True)
        return causal & (rank < jnp.minimum(position + 1, topk)[:, None])

    return jax.vmap(one)(*(x.astype(jnp.float32) for x in (qi, ki, w)))
