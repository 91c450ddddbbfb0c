"""A learned selection of keys: the indexer's scores and an exact top-k
a query, as bits, one Mosaic kernel a call.

Learned sparse attention (DeepSeek-V3.2's lightning indexer; the block
that uses it is ``models/transformer.py`` ``KeyeBlock``) lets a query
attend the ``topk`` earlier positions a small scorer ranks highest, the
same set for all its heads.  This file computes that set:

- the score of query ``t`` for key ``j <= t`` is ``I[t, j] = sum_h w[t,
  h] * ReLU(qI[t, h] . kI[j])`` over ``H`` narrow heads against **one**
  key head, in float32 at full precision (membership in the top ``k``
  flips where two scores are close, ``ROUTER_PRECISION``'s reason), the
  heads added in their order (:func:`_tile_scores`, the one place a
  score is made);
- ``S_t`` is the positions of the ``min(t + 1, topk)`` largest, **a tie
  to the lower position**: exactly that many a row, always.

:func:`index_select` is one ``pallas_call`` over ``(sequence, block of``
:data:`ROWS` ``rows)``, interpreted off a TPU.  A grid step walks the
tiles of columns up to its block's last row and nothing past it: a
tile's scores (a head at a time on the MXU, the sequence's key head
resident in VMEM) become order-preserving integer keys in a VMEM scratch
laid out **positions by rows**, so that a count over a row's keys is an
add across vector registers and a row's threshold one lane.  The
``k``-th largest of a row is found there by **bisection on the bits**
of the keys (32 passes of compare-and-count over the live tiles:
``lax.top_k`` at ``k`` 2048 of 8192 is a sort), then the ties at the
threshold by a second bisection on the position, run only where a row
of the block has more equals than it needs (:func:`_thresholds`, which
:func:`top_k_mask` runs on whole rows).  No score and no key crosses
HBM.  A block wholly below ``topk`` takes every earlier position and
computes no score.

**The longest sequence.**  A block's keys (``L x 256`` int32) and the
sequence's whole key head (twice, the pipeline's two buffers, its 64
columns padded to 128 lanes) are about 2 KB a position of VMEM on top
of 18.5 MiB: 34.5 MiB at 8192 positions, and the chip's 128 MiB
(:data:`VMEM_BYTES`) at **54,272 positions** with 16 heads of 64.  Past
that :func:`index_select` raises a ``ValueError`` where Mosaic would
refuse to compile (the configuration publishes positions up to 262,144;
the cell trains at 8192).  The way on is the key head left in HBM and a
tile of it copied in, and the keys' scratch walked in groups of
columns, not a second path.

The set leaves as **bits**, packed in the same step in the layout the
flash kernels' tile dictates (``ops/select_bits.py``; 8 MB a layer at
8192 positions; columns past a block's last row are zero bits), beside
two counts a row.

No gradient: the set is piecewise constant in everything it is made
from, so the inputs are stopped here and the backward pass has nothing
to run.  :func:`index_select_reference` is the same set by a stable
sort of whole rows.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpit_tpu.ops.select_bits import SUPER, words_of
from mpit_tpu.ops.tiles import LANE, SUBLANE, round_up, use_interpret

#: rows a grid step selects for, and the columns of a tile of their
#: scores (whole sublane tiles of 8): the block's keys are ``L x ROWS``
#: int32 in VMEM (8 MB at 8192 positions); a shorter sequence is one
#: block of whole lanes
ROWS = 256

#: the scores' products: full float32, for the reason the router's are
#: (``models/transformer.py`` ``ROUTER_PRECISION``).  Read when
#: :func:`index_select` or :func:`index_scores` is called and handed
#: down from there (a static argument of the kernel's ``jit``): a build
#: that lowers it (``chipbench/reference/probe_keye.py``'s planted
#: fault) is another trace, also after a call at the same shapes
SCORE_PRECISION = jax.lax.Precision.HIGHEST

#: what a call may ask of VMEM: the chip's (128 MiB on the v5e)
VMEM_BYTES = 2**27

_I32_MIN = np.int32(-2**31)


def _tile_scores(ki: jnp.ndarray, heads: int, head, precision) -> jnp.ndarray:
    """``(T, R)`` scores, **keys by queries**, of the keys ``ki (T, D)``
    for ``heads`` heads, ``head(h)`` the head's queries ``(D, R)`` and
    weights ``(1, R)``, queries along the lanes: a head at a time its
    product at ``precision`` (:data:`SCORE_PRECISION` as the caller
    read it), the ReLU, times the head's weight, added to the heads
    before it in float32.  The one place a score is computed: the
    kernel's tiles and :func:`index_scores` are this function."""
    scores = None
    for h in range(heads):
        q, w = head(h)
        s = jax.lax.dot_general(
            ki, q, (((1,), (0,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)
        s = jnp.maximum(s, 0.0) * w
        scores = s if scores is None else scores + s
    return scores


def index_scores(qi: jnp.ndarray, ki: jnp.ndarray,
                 w: jnp.ndarray) -> jnp.ndarray:
    """``I (R, Lk)`` of ``qi (R, H, D)``, ``ki (Lk, D)``, ``w (R, H)``:
    the heads' products at full float32 precision, the ReLU and the
    weighted sum in float32, the heads added in their order.  The
    queries are laid out as the kernel is handed them before any
    product (behind a barrier: folded into the product, the transpose
    changes the order a CPU sums a product in, in the last bit)."""
    q = jax.lax.optimization_barrier(qi.transpose(1, 2, 0))
    return _tile_scores(ki, qi.shape[1], lambda h: (q[h], w[None, :, h]),
                        SCORE_PRECISION).T


def _keys(scores: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """float32 scores as int32 keys of the same order (a negative
    float's bits reversed; ``-0.0`` is ``0.0``), the least int32 where
    not ``valid``: below every finite score's key.  Signed, because the
    vector unit compares signed."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    bits = jnp.where(bits == _I32_MIN, 0, bits)
    return jnp.where(valid, bits ^ ((bits >> 31) & np.int32(0x7FFFFFFF)),
                     _I32_MIN)


def _signed(x: jnp.ndarray) -> jnp.ndarray:
    """uint32 ``x`` as the int32 of the same rank (the sign bit
    flipped): a bisection's unsigned candidate among :func:`_keys`."""
    return jax.lax.bitcast_convert_type(x ^ jnp.uint32(0x80000000), jnp.int32)


def _largest_where(count_of, target: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Per row the largest uint32 ``x`` below ``2 ** bits`` with
    ``keep(count_of(x), target)``, for a ``count_of`` that does not grow
    with ``x``: one bit a pass from the highest down."""
    def step(i, x):
        candidate = x | (jnp.uint32(1) << (bits - 1 - i).astype(jnp.uint32))
        return jnp.where(count_of(candidate) >= target, candidate, x)

    return jax.lax.fori_loop(0, bits, step, jnp.zeros_like(target, jnp.uint32))


#: ``hit(keys, column) -> booleans``: a test of a tile of keys and
#: their positions
Hit = Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]


def _thresholds(count: Callable[[Hit], jnp.ndarray], k: jnp.ndarray,
                columns: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(kth, last)`` a row: its ``k``-th largest key and, of the keys
    equal to it, the position of the last one taken.  ``count(hit)`` is
    the row's number of keys that ``hit``, in ``k``'s shape, however the
    keys are laid out and walked (the whole rows of
    :func:`top_k_mask`, the kernel's tiles of a VMEM scratch)."""
    # the k-th largest key: the largest x that k keys reach
    kth = _signed(_largest_where(
        lambda x: count(lambda keys, column: keys >= _signed(x)), k, 32))
    need = k - count(lambda keys, column: keys > kth)  # of the equals: >= 1

    def tied(_):
        # the largest p with fewer than ``need`` equals below it: the
        # position of the last equal taken
        return _largest_where(
            lambda p: need - count(lambda keys, column: (keys == kth) & (
                column < p.astype(jnp.int32))),
            jnp.ones_like(need), max(int(columns - 1).bit_length(), 1)
        ).astype(jnp.int32)

    last = jax.lax.cond(
        jnp.any(count(lambda keys, column: keys == kth) > need), tied,
        lambda _: jnp.full_like(need, columns), None)
    return kth, last


def _chosen(keys: jnp.ndarray, column: jnp.ndarray, kth: jnp.ndarray,
            last: jnp.ndarray) -> jnp.ndarray:
    return (keys > kth) | ((keys == kth) & (column <= last))


def _fold(hit: jnp.ndarray) -> jnp.ndarray:
    """``hit (n, R)`` booleans, ``n`` whole sublane tiles, counted into
    one ``(8, R)`` tile: adds across vector registers, nothing moved
    within one."""
    return jnp.sum(hit.astype(jnp.int32).reshape(-1, SUBLANE, hit.shape[-1]),
                   axis=0)


def top_k_mask(scores: jnp.ndarray, valid: jnp.ndarray,
               k: jnp.ndarray) -> jnp.ndarray:
    """``(R, Lk)`` booleans: per row the ``k (R,)`` largest ``scores``
    among the ``valid`` (``1 <= k <=`` their count), a tie to the lower
    column; exactly ``k`` a row.  The kernel's rule on whole rows: the
    tests' form of :func:`_thresholds`, which no other code calls."""
    keys = _keys(scores, valid)
    column = jnp.arange(scores.shape[-1], dtype=jnp.int32)[None, :]
    kth, last = _thresholds(
        lambda hit: jnp.sum(hit(keys, column), axis=-1, keepdims=True,
                            dtype=jnp.int32),
        k.astype(jnp.int32)[:, None], scores.shape[-1])
    return _chosen(keys, column, kth, last)


def _select_kernel(q_ref, k_ref, w_ref, words_ref, counts_ref, keys_ref, *,
                   topk: int, precision):
    """One block of rows of one sequence: ``q_ref (1, H D, R)`` and
    ``w_ref (1, H, R)``, rows along the lanes (what the compiler lays a
    head of 64 out as by itself: no pad to whole lanes, no copy in
    front of the call), the sequence's whole key head ``k_ref (1, L,
    D)``; ``words_ref (1, R, words)`` the block's sets as bits and
    ``counts_ref (1, 1, 2, R)`` a row's chosen keys and how many of them
    are among its ``topk`` most recent.  ``keys_ref (L, R)`` is the
    block's keys, **positions by rows**: a count over a row's keys is an
    add across vector registers and a row's threshold a lane's."""
    rows, heads, d = q_ref.shape[2], w_ref.shape[1], k_ref.shape[2]
    block = pl.program_id(1)
    row = block * rows + jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)

    def columns(first, count):
        return first + jax.lax.broadcasted_iota(jnp.int32, (count, rows), 0)

    def tile(t):
        return pl.ds(pl.multiple_of(t * rows, rows), rows)

    def scored(_):
        # the tiles of columns up to the block's last row: nothing of a
        # tile above the diagonal is computed, kept or counted
        def fill(t, carry):
            scores = _tile_scores(
                k_ref[0, tile(t), :], heads,
                lambda h: (q_ref[0, h * d:(h + 1) * d, :],
                           w_ref[0, h:h + 1, :]), precision)
            keys_ref[tile(t), :] = _keys(scores,
                                         columns(t * rows, rows) <= row)
            return carry

        jax.lax.fori_loop(0, block + 1, fill, 0)

        def count(hit):
            """Over the live tiles, one reduction across sublanes a
            count."""
            total = jax.lax.fori_loop(
                0, block + 1,
                lambda t, total: total + _fold(hit(keys_ref[tile(t), :],
                                                   columns(t * rows, rows))),
                jnp.zeros((SUBLANE, rows), jnp.int32))
            return jnp.sum(total, axis=0, keepdims=True)

        return _thresholds(count, jnp.clip(row + 1, 1, topk), k_ref.shape[1])

    # a block wholly below topk takes every earlier position and computes
    # no score: whatever the scratch holds is at or over the least key
    kth, last = jax.lax.cond(
        (block + 1) * rows <= topk,
        lambda _: (jnp.full((1, rows), _I32_MIN, jnp.int32),
                   jnp.full((1, rows), k_ref.shape[1], jnp.int32)),
        scored, None)

    # the pack (``ops/select_bits.py``): 128 positions are one bit of a
    # group's 128 words, so a word is an OR of shifted comparisons and
    # only the words' transpose moves a lane
    live = jax.lax.shift_right_logical(
        (block + 1) * rows + (LANE - 1), LANE.bit_length() - 1)
    kept = recent = jnp.zeros((SUBLANE, rows), jnp.int32)
    for group in range(words_ref.shape[2] // LANE):
        def chunk(bit, carry):
            word, kept, recent = carry
            first = pl.multiple_of(group * SUPER + bit * LANE, LANE)
            column = columns(first, LANE)
            chosen = _chosen(keys_ref[pl.ds(first, LANE), :], column, kth,
                             last) & (column <= row)
            near = chosen & (row >= topk) & (row - column < topk)
            return (word | (chosen.astype(jnp.uint32)
                            << bit.astype(jnp.uint32)),
                    kept + _fold(chosen), recent + _fold(near))

        word, kept, recent = jax.lax.fori_loop(
            0, jnp.clip(live - group * (SUPER // LANE), 0, SUPER // LANE),
            chunk, (jnp.zeros((LANE, rows), jnp.uint32), kept, recent))
        words_ref[0, :, group * LANE:(group + 1) * LANE] = (
            jax.lax.bitcast_convert_type(word, jnp.int32).T)
    counts_ref[0, 0, 0:1, :] = jnp.sum(kept, axis=0, keepdims=True)
    counts_ref[0, 0, 1:2, :] = jnp.sum(recent, axis=0, keepdims=True)


# A ``jax.jit`` of its own: a step's layers share one trace and one
# lowering of the kernel.
@partial(jax.jit, static_argnames=("topk", "words", "rows", "precision",
                                   "interpret"))
def _select_blocks(qi, ki, w, *, topk, words, rows, precision, interpret):
    """``(select (B, Lp, words), counts (B, Lp / rows, 2, rows))`` of
    ``qi (B, H D, Lp)``, ``ki (B, Lp, D)``, ``w (B, H, Lp)``, ``Lp``
    whole blocks of ``rows``."""
    (b, padded, d), h = ki.shape, w.shape[1]
    blocks = padded // rows
    vmem = _vmem_bytes(h, padded, d, rows, words)
    if vmem > VMEM_BYTES:
        raise ValueError(
            f"index_select: {padded} positions ask {vmem / 2**20:.1f} MiB of "
            f"VMEM (a block's keys {padded} x {rows} and the sequence's "
            f"whole key head, twice), over the chip's "
            f"{VMEM_BYTES / 2**20:.0f} MiB")
    return pl.pallas_call(
        partial(_select_kernel, topk=topk, precision=precision),
        grid=(b, blocks),
        in_specs=[pl.BlockSpec((1, h * d, rows), lambda i, j: (i, 0, j)),
                  # the key head stays where it is across a sequence's blocks
                  pl.BlockSpec((1, padded, d), lambda i, j: (i, 0, 0)),
                  pl.BlockSpec((1, h, rows), lambda i, j: (i, 0, j))],
        out_specs=[pl.BlockSpec((1, rows, words), lambda i, j: (i, j, 0)),
                   pl.BlockSpec((1, 1, 2, rows), lambda i, j: (i, j, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, padded, words), jnp.int32),
                   jax.ShapeDtypeStruct((b, blocks, 2, rows), jnp.int32)],
        # the pack reads whole groups of 128 positions
        scratch_shapes=[pltpu.VMEM((round_up(padded, LANE), rows),
                                   jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
    )(qi, ki, w)


def _vmem_bytes(h: int, padded: int, d: int, rows: int, words: int) -> int:
    """What the call asks of VMEM: the keys, the blocks twice (the
    pipeline's two buffers, a last dimension in whole lanes), and the
    stock 16 MB for the body's own temporaries."""
    wide = lambda n: round_up(n, LANE)
    blocks = (h * d * wide(rows) + padded * wide(d) + h * wide(rows)
              + rows * words + 8 * wide(rows))
    return 2**24 + 4 * (round_up(padded, LANE) * wide(rows) + 2 * blocks)


def index_select(qi: jnp.ndarray, ki: jnp.ndarray, w: jnp.ndarray,
                 topk: int) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The selection of ``qi (B, L, H, D)``, ``ki (B, L, D)``, ``w (B,
    L, H)``: ``(select, kept_share, window_overlap)``.  ``select (B, L,
    words_of(L))`` int32 is the set as bits (``ops/select_bits.py``);
    ``kept_share`` the chosen pairs over the causal pairs (``1.0``: nothing is left out);
    ``window_overlap`` over the rows that have a choice (``t >= topk``)
    the share of chosen positions among the row's ``topk`` most recent
    (``1.0``: the indexer is a sliding window).  No key crosses
    sequences; no gradient.  One Mosaic call over every sequence's
    blocks of :data:`ROWS` rows, a length that is no whole number of
    them padded to one."""
    qi, ki, w = (jax.lax.stop_gradient(x.astype(jnp.float32))
                 for x in (qi, ki, w))
    b, l, h, d = qi.shape
    topk, rows = int(topk), min(ROWS, round_up(l, LANE))
    pad = round_up(l, rows) - l
    words, counts = _select_blocks(
        jnp.pad(qi, ((0, 0), (0, pad), (0, 0), (0, 0))).transpose(
            0, 2, 3, 1).reshape(b, h * d, l + pad),
        jnp.pad(ki, ((0, 0), (0, pad), (0, 0))),
        jnp.pad(w, ((0, 0), (0, pad), (0, 0))).transpose(0, 2, 1),
        topk=topk, words=words_of(l), rows=rows, precision=SCORE_PRECISION,
        interpret=use_interpret(None))
    kept, recent = (counts[:, :, i].reshape(b, l + pad)[:, :l]
                    for i in (0, 1))
    kept_share = jnp.sum(kept) / (b * l * (l + 1) / 2)
    chosen_late = jnp.sum(jnp.where(jnp.arange(l)[None, :] >= topk, kept, 0))
    overlap = jnp.sum(recent) / jnp.maximum(chosen_late, 1)
    return (words[:, :l], kept_share.astype(jnp.float32),
            overlap.astype(jnp.float32))


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
