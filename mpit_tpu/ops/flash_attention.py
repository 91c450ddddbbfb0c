"""Blockwise (flash) attention for TPU — standalone op and ring building
block.

The reference has no attention at all (conv/pool models only — SURVEY.md
§5 "long-context: absent"); this op is the TPU-native long-context
showcase the rebuild adds on top of capability parity.  Design:

- MXU-shaped: scores and the PV product are ``jnp.dot`` with
  ``preferred_element_type=f32``; blocks are (block_q, block_k) tiles.
  At the default precision the operands are bf16 whatever the caller's
  arrays are (:func:`operand_dtype`): a float32 operand is rounded to
  bf16 by the MXU on its way in, every time a tile pair loads it, at
  half the rows a push and twice the bytes a load; rounded once where
  it is made it is the same number (the kernels' results agree to the
  bit at equal tiles on a v5e: PERF.md section 6, PR 57).
- **Every operand and result at the width it has**: rows and keys are
  padded to whole blocks, a head's width never is.  An array whose last
  dimension is no multiple of 128 (a head of 64, latent attention's keys
  of 192) lies in HBM in whole ``(8, 128)`` tiles already, so a block
  that spans its last dimension is legal as it stands, and the lanes the
  last tile lacks are masked where the products run, in VMEM (Mosaic
  pushes and streams a contraction's partial tile under the MXU
  instructions' own lane mask and stores a narrow result with masked
  stores: docs/tpu_compile_notes.md section 5).  Padding q, k, v and dO
  to whole lanes in XLA, and slicing dQ and dK back, is a copy of each
  through HBM a call (29 ms of a 565 ms step at 192 lanes, PERF.md
  section 6, PR 54) that changes no number, since a zero lane adds an
  exact zero: do not bring it back.  Only the row statistics (``m``,
  ``l``, ``lse``, ``delta``) are 128 lanes wide whatever the head: they
  are the kernels' own format (next item).
- Online softmax: running row-max ``m``, normalizer ``l`` and
  unnormalized accumulator carried across k-blocks in VMEM scratch —
  O(Lq·D) memory regardless of Lk.
- **A row's statistics lie in every lane**: ``m`` and ``l`` (forward)
  and ``lse`` and ``delta`` (backward) are ``(rows, 128)`` blocks that
  hold the row's value in all 128 lanes, and the bodies use them whole
  (:func:`_lanes`: the same vregs side by side against a wider
  operand).  A grid step's body is straight-line code that the
  kernel's compiler schedules vreg by vreg over the whole tile, MXU
  pushes, ``exp`` and reductions of different rows side by side; what
  it cannot hide is a dependent round trip through the cross-lane
  unit, and a statistic read from lane 0 (``ref[:, :1]``) cost one for
  every use: with them gone the forward body at ``(512, 512)`` float32
  is 3,393 bundles for 4,594 (docs/tpu_compile_notes.md section 5).
  Cutting a step into row groups written stage by stage, as the delta
  rule's heads are (``ops/delta_rule.py`` ``_in_step``), was measured
  and gains nothing here: the rows of a tile are independent chains
  already, and the scheduler sees them (PERF.md section 6, PR 52).
- **Global-offset causal masking**: ``q_offset``/``kv_offset`` (traced
  scalars) shift local indices into global sequence positions, which is
  exactly what sequence-parallel ring attention needs — each ring step
  attends a local Q chunk against a remote KV chunk
  (:mod:`mpit_tpu.parallel.ring_attention`).
- ``kv_len`` masks padded keys so inputs need not be block-multiples.
- **Grouped KV heads** by the index map: a group's query heads are
  folded into the kernel's rows over the one KV head they share, so
  ``k`` and ``v`` are read where they lie (no repeat is materialised)
  and ``dk``, ``dv`` are summed over the group in the backward kernel's
  own accumulator.  The head counts travel in the shapes.
- **Two head widths**: ``v`` may be narrower (or wider) than ``q`` and
  ``k`` (latent attention: 192-wide keys over 128-wide values).  Each
  is blocked at its own width: the score products and ``dq``, ``dk``
  run at the keys' lanes, PV, ``o``, ``do`` and ``dv`` at the values',
  so a narrow value pays for no lane it does not have.
- **A sliding window** (``window``, causal): a block wholly outside
  ``[i - window + 1, i]`` is dead like a block above the diagonal.
- **The grids walk live blocks only** (:class:`_Walk`): for an outer
  block the live inner blocks are one range ``[lo, hi]``, worked out
  from the offsets, traced or not, once a call (:func:`_prefetch`) and
  prefetched with the three scalars (``PrefetchScalarGridSpec``), so
  the index maps read it: inner step ``t`` visits block ``lo + t``.
  Under a window the inner axis is the static bound on a range's length
  (4 where a row has 16 blocks, at window 1024 on 512-blocks); under
  plain causal masking it stays the row, and a step past the range names
  the block already held.  A dead block is neither fetched nor computed,
  forward and in every backward kernel; one function holds the bounds
  for index maps and bodies alike.  :func:`flash_step_counts` says how
  many steps a call visits and how many are live.
- **A selection** (``select``): beside the live set that is geometry
  (two positions decide: causal, a window, a padded length), one that is
  **data**: a (query, key) pair is live iff a set computed elsewhere
  holds it (learned sparse attention: ``ops/index_select.py``, the top
  ``k`` keys a query by an indexer's scores, one set for all heads).
  The set travels as bits, ``(B, Lq, words)`` int32
  (``ops/select_bits.py`` owns the layout, which the tile here
  dictates), indexed **by position, not by the kernel's row** (a
  group's folded query heads read the same words): a ``(block_q,
  block_k)`` tile reads one ``(block_q, 128)`` block of words and its
  ``block_k / 128`` bits are whole-lane shifts, no lane moved.  The
  walk stays the geometric one; within it a tile with no chosen pair
  runs no product (its words are all it reads to know), forward and in
  both backward kernels, and every other tile masks its scores by the
  bits (and by the geometry where it is an edge tile).  The backward
  under a selection is the two-kernel schedule, as under a window.
- **The block-diffusion pass** (``blockdiff=(half, block)``): the one
  mask that is neither causal nor inside the causal triangle.  The
  ``2 * half`` rows are a noised copy of a sequence and then its clean
  copy, cut in blocks of ``block`` positions; a noised row sees its own
  noised block (both directions) and the clean blocks strictly before
  it, a clean row the clean blocks up to its own
  (:func:`_blockdiff_live`, the one copy of the rule).  An outer
  block's live inner blocks are then **no one range** (a noised q
  block's are its own diagonal tile and a run of the clean half; a
  clean kv block is seen from a run of each half), so the walk reads
  them from a table made at trace time from the rule itself
  (:func:`_blockdiff_tiles`): inner step ``t`` visits the ``t``-th live
  block, whichever it is, and the inner axis is the longest row's count.
  No ``(2 half, 2 half)`` mask, bias or bit set exists anywhere: an
  edge tile computes its mask from two iotas, a full one takes the
  mask-free path, a dead one (the whole clean-to-noised quadrant among
  them) is neither fetched nor computed, forward and backward.

:func:`flash_attention` is the user op (normalized output, custom VJP:
pallas backward in the standard flash schedule — P is recomputed
blockwise from the saved row log-sum-exp, so backward peak memory is
O(block_q·block_k) scratch, never the (Lq, Lk) score matrix).
:func:`flash_attention_bwd_pair` exposes the same backward for one
(Q chunk, KV chunk) pair — the per-ring-step op of
:mod:`mpit_tpu.parallel.ring_attention`.
:func:`block_attention_partial` returns unnormalized partials
``(acc, m, l)`` for cross-chunk merging; :func:`merge_partials` /
:func:`finalize_partials` implement the log-sum-exp combine.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpit_tpu.ops.select_bits import SUPER, unpack as _unpack_select
from mpit_tpu.ops.tiles import (
    LANE, round_up as _round_up, use_interpret as _interpret,
)

NEG_INF = float("-inf")

# ``checkpoint_name``s of what :func:`flash_attention`'s forward rule
# keeps for its backward one: the output and the rows' log-sum-exp.
FLASH_OUT, FLASH_LSE = "flash_out", "flash_lse"

# In-kernel running-max sentinel.  A FINITE very-negative value instead
# of -inf: every `isneginf` guard in the hot loop disappears (exp of
# (-1e30 - x) underflows to exactly 0, which is what the guards
# computed), worth ~4 MFU points on-chip; the partial outputs convert
# back to -inf at finalize so the public (acc, m, l) contract — and the
# merge/LSE algebra built on isneginf — is unchanged.
_BIG_NEG = -1e30


def _fa_compiler_params(vmem_mb_auto: float = 0.0):
    """Grid dimension semantics for every flash kernel: the first grid
    axis (q rows fwd/dq, kv rows dk/dv) is embarrassingly parallel, the
    second is the sequential accumulation sweep over VMEM scratch.
    Declaring this lets Mosaic schedule the parallel axis freely.
    MPIT_FA_DIMSEM=0 reverts to unannotated grids (A/B lever).

    ``MPIT_FA_VMEM_MB`` raises the scoped-VMEM budget from the 16 MB
    default — required to even compile block combos whose f32 score
    tile exceeds ~4 MB (e.g. block_k=2048 sweeps); the 100 MB-budget
    sweep data in
    docs/tpu_compile_notes.md §2 shows the raise itself is perf-neutral
    for the default tiles.  ``vmem_mb_auto`` is the caller's computed
    floor for configs that cannot compile under the stock budget (the
    length-aware block_q=2048 forward default); the env lever, when
    set, wins over it — including an explicit 0, which pins the stock
    budget (the A/B control) and suppresses the auto raise.  The
    length-aware block defaults honour the pin: a budget below their
    floor makes :func:`_tile_dims` keep the flat 1024 blocks
    (:func:`_long_blocks_fit_vmem`), so the control combination stays
    compilable."""
    kwargs = {}
    env = os.environ.get("MPIT_FA_VMEM_MB", "")
    vmem_mb = float(env) if env else vmem_mb_auto
    if vmem_mb > 0:
        kwargs["vmem_limit_bytes"] = int(vmem_mb * 2**20)
    if os.environ.get("MPIT_FA_DIMSEM", "1") != "0":
        kwargs["dimension_semantics"] = ("parallel", "arbitrary")
    return pltpu.CompilerParams(**kwargs) if kwargs else None


def _vmem_auto(bq: int, bk: int) -> float:
    """Auto scoped-VMEM floor (MB) for a resolved tile geometry: a
    >4 MB f32 score tile (the length-aware 2048-block defaults) cannot
    compile under the stock budget, so request the 64 MB budget
    measured perf-neutral for every geometry (docs/tpu_compile_notes.md
    §2).  ONE copy shared by forward and backward so a retune cannot
    diverge them; an explicit MPIT_FA_VMEM_MB (incl. =0) still wins in
    :func:`_fa_compiler_params`."""
    return 64.0 if bq * bk * 4 > 4 * 2**20 else 0.0


def _long_blocks_fit_vmem(bq: int, bk: int) -> bool:
    """Whether the length-aware 2048-block *default* may be used under
    the effective scoped-VMEM budget.  An explicit ``MPIT_FA_VMEM_MB``
    wins over the auto raise — including ``=0``, the stock-budget A/B
    control — so when it pins a budget below the floor the big tile
    needs (:func:`_vmem_auto`), the default must fall back to the flat
    1024 blocks instead of resolving a geometry that cannot compile
    (ADVICE round 5).  Explicitly-passed block sizes are never second-
    guessed; only the length-aware default growth is gated here."""
    env = os.environ.get("MPIT_FA_VMEM_MB", "")
    return not env or float(env) >= _vmem_auto(bq, bk)


# ---------------------------------------------------------------------------
# jnp reference + partial/merge algebra (differentiable, CPU-friendly)
# ---------------------------------------------------------------------------


def _blockdiff_live(qi, kj, half: int, block: int, xp=jnp):
    """The block-diffusion pass's mask, the ONE copy of its rule: rows
    and keys ``[0, half)`` are the noised copy of a sequence, ``[half, 2
    half)`` its clean copy, a position's block is ``(p mod half) //
    block`` (``block`` a power of two: a shift).  A noised row sees the noised keys of its own block and the
    clean keys of the blocks strictly before it; a clean row the clean
    keys of the blocks up to and including its own; nothing else (no
    clean row sees a noised key, a noised block never its own clean
    copy).  ``xp`` is ``jnp`` (the reference's whole mask, an edge
    tile's inside a kernel) or ``numpy`` (the walk's table at trace
    time: :func:`_blockdiff_tiles`)."""
    shift = block.bit_length() - 1
    noised_q, clean_k = qi < half, kj >= half
    qb = xp.where(noised_q, qi, qi - half) >> shift
    kb = xp.where(clean_k, kj - half, kj) >> shift
    # in and-or form (Mosaic selects no booleans): a clean key of an
    # earlier block is seen from either half; a key of the row's own
    # block iff exactly one of "the key is clean" and "the row is
    # noised" holds (a noised row's own noised block, a clean row's own
    # clean block)
    return (clean_k & (kb < qb)) | ((kb == qb) & (clean_k ^ noised_q))


def _mask(sh_q: int, sh_k: int, q_offset, kv_offset, kv_len, causal: bool,
          window: int | None = None, blockdiff: tuple | None = None):
    """Boolean (Lq, Lk) validity mask in *global* coordinates.  With a
    ``window`` (causal only) query ``i`` sees key ``j`` iff ``0 <= i - j
    < window``: its own position and the ``window - 1`` before it.
    ``blockdiff``: :func:`_blockdiff_live`'s rule."""
    qi = q_offset + jnp.arange(sh_q)[:, None]
    kj = kv_offset + jnp.arange(sh_k)[None, :]
    valid = (kj - kv_offset) < kv_len
    if causal:
        valid = valid & (qi >= kj)
    if window is not None:
        valid = valid & (qi - kj < window)
    if blockdiff is not None:
        valid = valid & _blockdiff_live(qi, kj, *blockdiff)
    return valid


def _check_blockdiff(blockdiff, lq: int, lk: int, causal: bool, window,
                     select) -> tuple | None:
    """``(half, block)`` of a block-diffusion pass: the noised and the
    clean copy are the call's whole rows and keys, in whole blocks, and
    the mask is all of the call's geometry."""
    if blockdiff is None:
        return None
    half, block = (int(x) for x in blockdiff)
    if causal or window is not None or select is not None:
        raise ValueError("blockdiff is the whole mask: no causal, window "
                         "or select beside it")
    if (block < 1 or block & (block - 1) or half % block
            or lq != 2 * half or lk != 2 * half):
        raise ValueError(f"blockdiff {(half, block)}: {lq} rows over {lk} "
                         f"keys are not a noised and a clean copy of "
                         f"{half} positions in blocks of {block}, a power "
                         f"of two")
    return half, block


@functools.lru_cache(maxsize=64)
def _blockdiff_tiles(half: int, block: int, bq: int, bk: int,
                     q_blocks: int, kv_blocks: int):
    """``(live, full)``: for every ``(bq, bk)`` tile of the padded ``(q
    blocks, kv blocks)`` rectangle, whether :func:`_blockdiff_live` has
    any true entry in it and whether every entry is true (a key past ``2
    half`` is no key).  Worked out at trace time from the rule itself, a
    strip of ``bq`` rows at a time in numpy: nothing of the square ever
    reaches the program."""
    kj = np.arange(kv_blocks * bk, dtype=np.int32)[None, :]
    live = np.zeros((q_blocks, kv_blocks), bool)
    full = np.zeros((q_blocks, kv_blocks), bool)
    for i in range(q_blocks):
        qi = (i * bq + np.arange(bq, dtype=np.int32))[:, None]
        seen = _blockdiff_live(qi, kj, half, block, np) & (kj < 2 * half)
        count = seen.reshape(bq, kv_blocks, bk).sum(axis=(0, 2))
        live[i], full[i] = count > 0, count == bq * bk
    return live, full


def _check_window(window, causal: bool) -> int | None:
    """A window is the sliding causal one: it bounds how far *back* a
    query looks, so without ``causal`` it would be half a mask."""
    if window is None:
        return None
    if not causal or int(window) < 1:
        raise ValueError("window needs causal=True and window >= 1")
    return int(window)


def _group_queries(q, k):
    """Grouped KV heads travel in the shapes: ``q (..., Hq, Lq, D)``
    over ``k (..., Hkv, Lk, D)`` with ``Hq = G * Hkv`` is returned as
    ``(..., Hkv, G, Lq, D)`` (query head ``g`` attends KV head ``g //
    G``), one rank above ``k``; equal heads come back as they are."""
    if q.ndim < 3 or q.shape[-3] == k.shape[-3]:
        return q
    hq, hkv = q.shape[-3], k.shape[-3]
    if hq % hkv:
        raise ValueError(f"{hq} query heads over {hkv} KV heads")
    return q.reshape(*q.shape[:-3], hkv, hq // hkv, *q.shape[-2:])


def attention_reference(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    sm_scale: float | None = None,
    q_offset=0,
    kv_offset=0,
    window: int | None = None,
    select: jnp.ndarray | None = None,
    blockdiff: tuple | None = None,
) -> jnp.ndarray:
    """Plain softmax attention over the last two axes; leading axes batch.
    Rows with no valid key return zeros (matches the ring/partial path).
    ``window`` as in :func:`_mask`; fewer KV heads than query heads
    (axis -3) are repeated over their groups, materialised; ``select``
    as :func:`flash_attention` takes it, unpacked to a whole mask;
    ``blockdiff`` as :func:`flash_attention` takes it, the whole mask
    materialised."""
    window = _check_window(window, causal)
    blockdiff = _check_blockdiff(blockdiff, q.shape[-2], k.shape[-2],
                                 causal, window, select)
    if q.ndim >= 3 and q.shape[-3] != k.shape[-3]:
        groups = q.shape[-3] // k.shape[-3]
        k, v = (jnp.repeat(x, groups, axis=-3) for x in (k, v))
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32) * scale
    valid = _mask(q.shape[-2], k.shape[-2], q_offset, kv_offset,
                  k.shape[-2], causal, window, blockdiff)
    if select is not None:
        chosen = _unpack_select(select, k.shape[-2])
        # a sequence's, the first of ``s``'s axes: every head's alike
        valid = valid & chosen.reshape(
            *chosen.shape[:-2], *(1,) * (s.ndim - chosen.ndim),
            *chosen.shape[-2:])
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("...qk,...kd->...qd", p, v.astype(jnp.float32))
    return (out / jnp.where(l == 0.0, 1.0, l)).astype(q.dtype)


def block_attention_partial(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    sm_scale: float | None = None,
    q_offset=0,
    kv_offset=0,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Unnormalized attention partials for one (Q chunk, KV chunk) pair:
    ``acc = exp(s - m) @ v``, rowwise max ``m`` and normalizer ``l``, all
    f32.  Differentiable jnp implementation — the per-ring-step op of
    :func:`mpit_tpu.parallel.ring_attention.ring_attention`."""
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32) * scale
    valid = _mask(q.shape[-2], k.shape[-2], q_offset, kv_offset,
                  k.shape[-2], causal)
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.where(valid, jnp.exp(s - m_safe[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("...qk,...kd->...qd", p, v.astype(jnp.float32))
    return acc, m, l


def merge_partials(a, b):
    """Log-sum-exp combine of two ``(acc, m, l)`` partials (the cross-step
    merge of ring attention; associative and commutative)."""
    acc1, m1, l1 = a
    acc2, m2, l2 = b
    m = jnp.maximum(m1, m2)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    c1 = jnp.where(jnp.isneginf(m1), 0.0, jnp.exp(m1 - m_safe))
    c2 = jnp.where(jnp.isneginf(m2), 0.0, jnp.exp(m2 - m_safe))
    acc = acc1 * c1[..., None] + acc2 * c2[..., None]
    l = l1 * c1 + l2 * c2
    return acc, m, l


def finalize_partials(acc, l, dtype=jnp.float32):
    """Normalize merged partials; all-masked rows yield zeros."""
    return (acc / jnp.where(l == 0.0, 1.0, l)[..., None]).astype(dtype)


# ---------------------------------------------------------------------------
# pallas kernel
# ---------------------------------------------------------------------------


def _binds(prim, swap=False):
    def op(self, other):
        x, y = self.v, other.v if isinstance(other, _Int) else other
        return _Int(prim(y, x) if swap else prim(x, y))
    return op


class _Int:
    """A traced integer (or boolean) whose operators bind ``lax``
    primitives directly.  ``jnp``'s operators on a tracer each go
    through a jitted ufunc, a nested trace an operation: written with
    them, the walk's arithmetic added seconds to every start-up of a
    ten-layer model, compile cache warm or not (PERF.md section 6,
    PR 33).  Also the ``xp`` of
    :meth:`_Walk.live_range` for traced operands, beside ``numpy`` for
    concrete ones.  Division truncates where ``//`` floors: the walk
    divides a negative number only where it discards the result."""

    __slots__ = ("v",)

    def __init__(self, v):
        self.v = v

    __add__ = __radd__ = _binds(jax.lax.add)
    __sub__ = _binds(jax.lax.sub)
    __rsub__ = _binds(jax.lax.sub, swap=True)
    __mul__ = __rmul__ = _binds(jax.lax.mul)
    __floordiv__ = _binds(jax.lax.div)
    __mod__ = _binds(jax.lax.rem)
    __lt__ = _binds(jax.lax.lt)
    __le__ = _binds(jax.lax.le)
    __ge__ = _binds(jax.lax.ge)
    __gt__ = _binds(jax.lax.gt)
    __or__ = __ror__ = _binds(jax.lax.bitwise_or)
    __and__ = __rand__ = _binds(jax.lax.bitwise_and)
    minimum = staticmethod(lambda a, b: _binds(jax.lax.min)(_Int.of(a), b))
    maximum = staticmethod(lambda a, b: _binds(jax.lax.max)(_Int.of(a), b))

    @staticmethod
    def of(x):
        return x if isinstance(x, _Int) else _Int(x)

    @staticmethod
    def where(cond, a, b):
        """``lax.select`` takes three arrays of one shape."""
        cond, a, b = (_Int.of(x).v for x in (cond, a, b))
        shape = np.broadcast_shapes(*map(np.shape, (cond, a, b)))

        def whole(x, dtype):
            x = x if isinstance(x, jax.Array) else np.asarray(x, dtype)
            return x if x.shape == shape else jax.lax.broadcast(x, shape)

        return _Int(jax.lax.select(whole(cond, bool), whole(a, np.int32),
                                   whole(b, np.int32)))


@dataclasses.dataclass(frozen=True)
class _Walk:
    """The live-block walk of one kernel call: which inner blocks a grid
    row visits, and the ONE copy of the off-by-one-sensitive causal,
    window and ``kv_len`` boundary rules, shared by the index maps (what
    is fetched) and the kernel bodies (what is computed) of the forward
    and every backward kernel: both read the ranges that
    :func:`_prefetch` makes with :meth:`live_range`.

    The outer grid axis is the q blocks (forward, dQ) or, with
    ``kv_outer``, the kv blocks (dK/dV, fused); the inner axis walks the
    other side.  For an outer block the inner blocks in which the mask
    has any true entry are one contiguous range ``[lo, hi]``
    (:meth:`live_range`); inner step ``t`` visits block ``lo + t``, and
    a step past ``hi`` names block ``hi`` again, which the pipeline
    already holds and does not fetch, and runs no product.  Under a
    ``window`` a range is never longer than :attr:`extent`, a static
    bound far below the row's length, and that is the inner axis; under
    plain causal masking a range can be the whole row, the extent stays
    and the dead steps are the clamped ones.  So a block above the
    diagonal, outside the window or beyond ``kv_len`` is never fetched.

    With grouped KV heads (``groups`` > 1) the group's query heads lie
    one after another along the rows, ``q_blocks`` blocks each
    (:func:`_fold`): a q-outer row starts its positions over at every
    head, and a kv-outer row walks its range once a head, ``extent``
    steps each.

    Under ``blockdiff`` (the block-diffusion pass: :func:`_blockdiff_live`)
    an outer block's live inner blocks are not one range (two, most of
    them: a piece of each half), and the offsets are 0: the walk reads
    them from a **table** made at trace time from the rule
    (:meth:`table`).  The two prefetched vectors then hold, in place of
    ``lo`` and ``hi``, the table's rows laid end to end (entry ``t`` of
    a row: its ``t``-th live inner block, twice, plus 1 where the tile
    is full; past the row's count the last live block again, which the
    pipeline holds) and the rows' counts; :attr:`extent` is the longest
    row's count.  :meth:`fetch`, :meth:`tile` and :meth:`steps` read
    those, and :meth:`live_range` is not asked."""

    kv_outer: bool
    causal: bool
    window: int | None
    block_q: int
    block_k: int
    q_blocks: int   # q blocks of one head
    kv_blocks: int
    groups: int = 1
    #: a selection's bits ride along (``_chosen``): a tile's words are
    #: ``fetch_select``'s block, and no tile takes the mask-free path
    select: bool = False
    #: ``(half, block)``: the block-diffusion pass's mask is the call's
    #: geometry, walked by :meth:`table`
    blockdiff: tuple | None = None

    @property
    def inner_blocks(self) -> int:
        return self.q_blocks if self.kv_outer else self.kv_blocks

    def tiles(self):
        """``(live, full)`` of the block-diffusion pass, ``(q blocks, kv
        blocks)`` each (:func:`_blockdiff_tiles`)."""
        return _blockdiff_tiles(*self.blockdiff, self.block_q, self.block_k,
                                self.q_blocks, self.kv_blocks)

    @functools.lru_cache(maxsize=64)
    def table(self):
        """``(entries (rows, extent), counts (rows,))`` int32, a row an
        outer block (of one head): the walk of the block-diffusion pass
        as the class's docstring lays it out.  Made once a walk (the
        class is frozen, so equal walks share it)."""
        live, full = self.tiles()
        if self.kv_outer:
            live, full = live.T, full.T
        counts = live.sum(axis=1).astype(np.int32)
        entries = np.zeros((live.shape[0], max(1, int(counts.max()))),
                           np.int32)
        for row, (seen, whole) in enumerate(zip(live, full)):
            inner = np.flatnonzero(seen)
            if inner.size:
                entries[row, :inner.size] = 2 * inner + whole[inner]
                entries[row, inner.size:] = 2 * inner[-1]
        return entries, counts

    @property
    def extent(self) -> int:
        """Inner steps of one range: the static bound on its length.
        ``window + b_outer - 1`` consecutive positions see an outer
        block, and ``n`` of them touch at most ``ceil((n - 1) / b) + 1``
        inner blocks, whatever the offsets.  Under ``blockdiff``, the
        longest row of the table."""
        if self.blockdiff is not None:
            return self.table()[0].shape[1]
        if self.window is None:
            return self.inner_blocks
        b_outer, b_inner = ((self.block_k, self.block_q) if self.kv_outer
                            else (self.block_q, self.block_k))
        return min(self.inner_blocks,
                   -(-(self.window + b_outer - 2) // b_inner) + 1)

    @property
    def grid(self) -> Tuple[int, int]:
        if self.kv_outer:
            return self.kv_blocks, self.groups * self.extent
        return self.groups * self.q_blocks, self.extent

    def live_range(self, outer, q_off, kv_off, kv_len, xp=_Int):
        """``(lo, hi)``: the inner blocks of outer block ``outer`` in
        which :func:`_mask` has any true entry; ``hi < lo`` when there
        is none (all keys masked: a ring step wholly above the diagonal,
        ``kv_len`` 0).  A q block's rows see the keys from its first
        row's window start to its last row's own position; a kv block's
        keys are seen by the rows from its first key's position to its
        last key's window end: the same rule read from either side,
        shifted to the inner side's local index and clipped to its
        length.  Integer arithmetic on whatever ``xp`` computes with:
        traced :class:`_Int` scalars in an index map or a body (or a
        traced vector of outer blocks, :func:`_sum_visited`), numpy
        arrays of outer blocks for the trace-time step counts and the
        tests."""
        bq, bk = self.block_q, self.block_k
        lo_pos = hi_pos = None
        if self.kv_outer:
            first = kv_off + outer * bk
            last = kv_off + xp.minimum((outer + 1) * bk, kv_len) - 1
            if self.causal:
                lo_pos = first - q_off
            if self.window is not None:
                hi_pos = last + (self.window - 1) - q_off
            inner_len, b_inner = self.q_blocks * bq, bq
        else:
            head_i = outer % self.q_blocks if self.groups > 1 else outer
            first = q_off + head_i * bq
            last = first + (bq - 1)
            if self.window is not None:
                lo_pos = first - (self.window - 1) - kv_off
            if self.causal:
                hi_pos = last - kv_off
            inner_len, b_inner = kv_len, bk
        lo_pos = 0 if lo_pos is None else xp.maximum(lo_pos, 0)
        hi_pos = (inner_len - 1 if hi_pos is None
                  else xp.minimum(hi_pos, inner_len - 1))
        empty = hi_pos < lo_pos
        if self.kv_outer:  # a kv block wholly beyond kv_len has no key
            empty = empty | (last < first)
        lo = lo_pos // b_inner
        return lo, xp.where(empty, lo - 1, hi_pos // b_inner)

    def fetch(self, outer, t, lo_ref, hi_ref, *_scalars):
        """Index-map half: the inner block held at inner step ``t`` (a
        folded row block under ``kv_outer``): the walk's block, held at
        the range's end past it; an empty range holds any valid block.
        The ranges are the prefetched ones (:func:`_prefetch`)."""
        if self.blockdiff is not None:
            head, _row, _t, entry = self._entry(outer, t, lo_ref)
            return (head + entry // 2).v
        t, lo, hi = _Int(t), _Int(lo_ref[outer]), _Int(hi_ref[outer])
        head = 0
        if self.kv_outer and self.groups > 1:
            head, t = t // self.extent * self.q_blocks, t % self.extent
        held = _Int.minimum(_Int.maximum(_Int.minimum(lo + t, hi), 0),
                            self.inner_blocks - 1)
        return (head + held).v

    def _entry(self, outer, t, table_ref):
        """Inner step ``t`` of grid row ``outer`` under ``blockdiff``:
        ``(the folded head's first row block, the table's row, the step
        within it, the table's entry)``."""
        row, t, head = _Int(outer), _Int(t), 0
        if self.groups > 1:
            if self.kv_outer:
                head, t = t // self.extent * self.q_blocks, t % self.extent
            else:
                row = row % self.q_blocks
        return head, row, t, _Int(table_ref[(row * self.extent + t).v])

    def fetch_select(self, outer, t, *prefetched):
        """Index-map half of a selection: the ``(block_q, 128)`` block
        of words of the tile at inner step ``t``: the rows of its
        **position** block (a folded head's rows are its positions') and
        the 128-word group that holds its kv block's bits."""
        held, per = _Int(self.fetch(outer, t, *prefetched)), SUPER // self.block_k
        if self.kv_outer:
            rows = held % self.q_blocks if self.groups > 1 else held
            return rows.v, (_Int(outer) // per).v
        rows = _Int(outer) % self.q_blocks if self.groups > 1 else outer
        return _Int.of(rows).v, (held // per).v

    def tile(self, lo_ref, hi_ref, qoff_ref, kvoff_ref, kvlen_ref):
        """Body half, for this grid step: ``(q_lo, j, live, full, first,
        last)``: the global position of the tile's first query row, its
        kv block, the triage (dead tiles skip everything, full ones take
        the mask-free fast path, edge ones (diagonal, window edge,
        ``kv_len``-straddling) mask) and whether the step is the
        walk's first or last (init, finalize)."""
        outer, t = pl.program_id(0), _Int(pl.program_id(1))
        first, last = t <= 0, t >= pl.num_programs(1) - 1
        if self.blockdiff is not None:
            _head, row, t, entry = self._entry(outer, t.v, lo_ref)
            # the table's row is the outer block (of one head), its
            # entry the inner one
            head_i, j = (entry // 2, row) if self.kv_outer else (
                row, entry // 2)
            live, full = t < _Int(hi_ref[row.v]), entry % 2 > 0
            return tuple(x.v for x in (head_i * self.block_q, j, live, full,
                                       first, last))
        q_off, kv_off, kv_len = (_Int(ref[0]) for ref in
                                 (qoff_ref, kvoff_ref, kvlen_ref))
        lo, hi, outer = _Int(lo_ref[outer]), _Int(hi_ref[outer]), _Int(outer)
        if self.kv_outer:
            if self.groups > 1:
                t = t % self.extent
            head_i, j = lo + t, outer
            live = head_i <= hi
        else:
            head_i = outer % self.q_blocks if self.groups > 1 else outer
            j = lo + t
            live = j <= hi
        bq, bk = self.block_q, self.block_k
        q_lo = q_off + head_i * bq
        k_end = (j + 1) * bk  # local, exclusive
        full = k_end <= kv_len
        if self.causal:
            # even the block's last key is <= the first query row
            full = full & (q_lo >= kv_off + k_end - 1)
        if self.window is not None:
            # even its first key is inside the last row's window
            full = full & (q_lo + (bq - 1) - (kv_off + j * bk) < self.window)
        return tuple(x.v for x in (q_lo, j, live, full, first, last))

    def steps(self, q_off=0, kv_off=0, kv_len=None) -> dict:
        """Grid steps of one call: ``visited`` (the grid's shape),
        ``live`` (tiles computed) and ``rect`` (the whole rectangle, the
        grid before the walk), from :meth:`live_range` on concrete
        offsets."""
        if self.blockdiff is not None:
            # ``live`` is the walk's own (the table's counts: the tiles
            # it runs a product on), ``nonempty`` the rule's (the tiles
            # in which it has a true entry): equal while the walk visits
            # nothing dead
            return {
                "visited": self.grid[0] * self.grid[1],
                "live": int(self.table()[1].sum()) * self.groups,
                "rect": self.groups * self.q_blocks * self.kv_blocks,
                "nonempty": int(self.tiles()[0].sum()) * self.groups,
            }
        kv_len = self.kv_blocks * self.block_k if kv_len is None else kv_len
        outer = np.arange(self.grid[0])
        lo, hi = self.live_range(outer, int(q_off), int(kv_off),
                                 int(kv_len), xp=np)
        # a rule that does not depend on the outer block gives scalars
        live = int(np.broadcast_to(np.maximum(hi - lo + 1, 0),
                                   outer.shape).sum())
        return {
            "visited": self.grid[0] * self.grid[1],
            "live": live * (self.groups if self.kv_outer else 1),
            "rect": self.groups * self.q_blocks * self.kv_blocks,
        }


def _valid(q_lo, j, kvoff_ref, kvlen_ref, shape, walk):
    """Elementwise validity of an edge tile: the mask of :func:`_mask`
    on its global query rows (from ``q_lo``) and the local key columns
    of kv block ``j``."""
    qi = q_lo + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    kj_local = (j * walk.block_k
                + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    valid = kj_local < kvlen_ref[0]
    if walk.blockdiff is not None:
        valid = valid & _blockdiff_live(qi, kj_local, *walk.blockdiff)
    if walk.causal:
        valid = valid & (qi >= kvoff_ref[0] + kj_local)
    if walk.window is not None:
        valid = valid & (qi - (kvoff_ref[0] + kj_local) < walk.window)
    return valid


def _first_bit(j, walk):
    """Kv block ``j``'s tile has ``block_k / 128`` bits of every word of
    its group: the first of them."""
    return jax.lax.rem(j, SUPER // walk.block_k) * (walk.block_k // LANE)


def _any_chosen(sel_ref, j, walk):
    """Whether the tile has a chosen pair at all: a scalar, from the
    words alone."""
    lanes = walk.block_k // LANE
    mask = jax.lax.shift_left(
        jnp.int32(-1 if lanes == 32 else (1 << lanes) - 1),
        _first_bit(j, walk))
    return jnp.max(jnp.where((sel_ref[:] & mask) != 0, 1, 0)) > 0


def _chosen(sel_ref, j, walk):
    """The tile's selection ``(block_q, block_k)``: lane group ``b`` of
    the keys is bit ``first + b`` of the words."""
    words, first = sel_ref[:], _first_bit(j, walk)
    bits = [jax.lax.shift_right_logical(
        words, jnp.full(words.shape, first + b, jnp.int32)) & 1
        for b in range(walk.block_k // LANE)]
    return (bits[0] if len(bits) == 1
            else jnp.concatenate(bits, axis=1)) != 0


def _lanes(stat, width: int):
    """A row statistic ``(rows, LANE)`` that holds a row's value in
    every lane, as ``(rows, width)``: the same vregs side by side, so
    that it meets a ``width``-wide operand with no lane moved.  A
    statistic read from lane 0 alone (``ref[:, :1]``) is spread over
    the lanes by the cross-lane unit each time it is used (a
    ``vperm.xlu`` a row block and use), and in the forward kernel that
    round trip stands between a row's scores and its ``exp``: the
    ``P V`` product then waits on it (PERF.md section 6, PR 52)."""
    whole, part = divmod(width, LANE)
    # a width that is no whole number of tiles ends in the first lanes
    # of one more copy
    pieces = [stat] * whole + ([stat[:, :part]] if part else [])
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, axis=1)


def _take_select(walk, refs):
    """``(the selection's ref or None, the refs after it)``: it follows
    the kernel's other inputs where the walk has one."""
    return (refs[0], refs[1:]) if walk.select else (None, refs)


def _run_tile(live, full, block):
    """The triage of :meth:`_Walk.tile` carried out: a dead tile runs
    nothing, a full one ``block(masked=False)``, an edge one the masked
    form."""
    pl.when(jnp.logical_and(live, full))(
        functools.partial(block, masked=False))
    pl.when(jnp.logical_and(live, jnp.logical_not(full)))(
        functools.partial(block, masked=True))


def _fa_kernel(lo_ref, hi_ref, qoff_ref, kvoff_ref, kvlen_ref, q_ref, k_ref,
               v_ref, *rest, walk, scale, partial, precision):
    sel_ref, (o_ref, *rest) = _take_select(walk, rest)
    if partial:
        m_out, l_out, acc_scr, m_scr, l_scr = rest
    else:
        acc_scr, m_scr, l_scr = rest
    # Block triage (see _Walk.tile): shaving the mask passes on interior
    # blocks is a direct win because the per-tile cost is the VPU's
    # dependent chain, not the MXU.
    q_lo, j, live, full, first, last = walk.tile(
        lo_ref, hi_ref, qoff_ref, kvoff_ref, kvlen_ref)
    if walk.select:
        live = jnp.logical_and(live, _any_chosen(sel_ref, j, walk))

    @pl.when(first)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)
        m_scr[:] = jnp.full_like(m_scr, _BIG_NEG)
        l_scr[:] = jnp.zeros_like(l_scr)

    def _block(masked):
        s = jax.lax.dot_general(
            q_ref[:], k_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        ) * scale  # (block_q, block_k) f32

        if masked:
            valid = _valid(q_lo, j, kvoff_ref, kvlen_ref, s.shape, walk)
        if walk.select:
            chosen = _chosen(sel_ref, j, walk)
            valid, masked = (valid & chosen if masked else chosen), True
        if masked:
            s = jnp.where(valid, s, _BIG_NEG)

        # Finite sentinel algebra: m_new >= any valid score, so
        # exp(s - m_new) <= 1 always; rows with no valid score so far
        # keep m == _BIG_NEG and exp underflows to 0 — no isneginf
        # guards anywhere on the dependent path.
        # The row statistics hold a row's value in EVERY lane of their
        # ``(block_q, LANE)`` scratch: a row's maximum and sum come out
        # of the cross-lane reduction in every lane already, and read
        # back whole they meet the scores and the accumulator with no
        # lane moved (:func:`_lanes`).
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, s.shape[1]))
        if masked:
            # A row with NO valid score keeps m_new == _BIG_NEG, making
            # exp(s - m_new) = exp(0) = 1 at its masked positions — the
            # where() zeroes those (edge blocks only; the fast path
            # never has dead rows).
            p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )
        acc_scr[:] = acc_scr[:] * _lanes(alpha, acc_scr.shape[1]) + pv
        m_scr[:] = m_new
        l_scr[:] = l_new

    _run_tile(live, full, _block)

    # A row with an empty range runs no tile and still ends here: zeros,
    # m = -inf and l = 0, as the partials' public contract says.
    @pl.when(last)
    def _finalize():
        if partial:
            o_ref[:] = acc_scr[:]
            # Restore the public sentinel: dead rows report m = -inf
            # (what merge_partials/_lse_of key on), not the internal
            # finite _BIG_NEG.  One where per FINAL block only.
            m_out[:] = jnp.where(m_scr[:] == _BIG_NEG, NEG_INF, m_scr[:])
            l_out[:] = l_scr[:]
        else:
            l = _lanes(l_scr[:], acc_scr.shape[1])
            o_ref[:] = (
                acc_scr[:] / jnp.where(l == 0.0, 1.0, l)
            ).astype(o_ref.dtype)


def _default_blocks(dtype, width: int = LANE,
                    banded: bool = False) -> Tuple[int, int]:
    """Default tiles, from what a call can see: its operands' dtype, the
    keys' width and whether its mask is a band (a window, the
    block-diffusion pass: most live tiles are edge tiles, and a taller
    or wider tile reaches further outside the band).

    float32 operands (a caller that named a ``precision``): ``(512,
    512)``; the float32 backward at 1024-blocks sits at the scoped-VMEM
    edge and crashes the TPU compiler inside larger programs
    (docs/tpu_compile_notes.md section 1).

    Operands of 2 bytes, which is every call at the default precision
    (:func:`operand_dtype`), as priced and timed at PR 57
    (docs/tpu_compile_notes.md section 5 has the bundle counts and the
    chip's milliseconds, PERF.md section 6 the cells): under a band
    ``(512, 512)``, the tile whose geometry the float32 bodies had (at
    a window of 1024 a ``(1024, 512)`` tile computes 5 units of area a
    512 rows for 3, and measured 3% over the float32 kernels where
    ``(512, 512)`` is 9% under); elsewhere 1024 rows, over 1024 keys
    where the keys are one lane tile wide or less and over 512 where
    they are wider (latent attention's 192: at ``(1024, 1024)`` the
    fused sweep's blocks overflow the stock scoped VMEM, and 1024 keys
    a block would halve the fused sweep's transient into its budget and
    flip a two-kernel backward to a 2 GB one: :func:`_use_fused_bwd`).
    By the static schedule all four bf16 tiles price within 4% of each
    other a unit of area and within 10% of the float32 body; the chip
    separates them (Keye's shape, forward and backward: 34.8 ms float32,
    33.4 / 29.5 / 25.9 ms at ``(512, 512)`` / ``(1024, 512)`` / ``(1024,
    1024)``)."""
    if jnp.dtype(dtype).itemsize > 2 or banded:
        return 512, 512
    return 1024, (1024 if width <= LANE else 512)


def _tile_dims(lq, lk, d, block_q, block_k, sm_scale, dtype,
               fwd_long_bq=False, bwd_long_bk=False, banded=False):
    """Shared forward/backward tiling contract: softmax scale, clamped
    block sizes and padded dims (rows, keys, and the head's width in
    whole lanes, which sizes the fused schedule's dQ partial in HBM and
    pads no operand).  The backward's saved-LSE rows only line
    up with recomputed score tiles if both directions use exactly this
    scale/padding; block sizes themselves may differ per direction (the
    forward slices outputs back to true lq, and LSE/delta are per-row).
    ``block_q``/``block_k`` of None resolve to the default of the
    operands' dtype, the keys' width and ``banded``
    (:func:`_default_blocks`).

    Both length-aware defaults below came from July 2026 on-chip
    sweeps the ledger has not reproduced.

    ``fwd_long_bq`` (forward only): at Lq >= 16384 bf16 a 3-rep A/B
    measured block_q=2048 faster than 1024 (16k: 4.90 vs 5.07 ms; 32k:
    18.41 vs 19.00 ms) while at 8k it was ~3% slower, so the default
    grows with the sequence.  MPIT_FA_LONG_BQ=0 pins the flat 1024
    default.

    ``bwd_long_bk`` (backward, fused schedule only — callers pass the
    resolved ``fused`` flag): at Lk >= 32768 bf16 the 32k sweep
    measured block_k=2048 the clear backward winner (fwd+bwd 74.0 ->
    63-67 ms): fewer, wider kv blocks halve the
    fused schedule's dQ-partials transient (4 GB -> 2 GB on the bench
    shape, re-admitting the fused path under the auto budget) on top of
    the wider tile's intrinsic win over the 4 GB fused variant.  The
    two-kernel fallback at bk=2048 is UNMEASURED and keeps the flat
    default.  At 16k the flip is jitter-neutral, so the default grows
    only at 32k+ where the win is measured.  MPIT_FA_LONG_BK_BWD=0 pins
    the flat default.  block_q stays 1024 in the backward (2048x2048
    measured far slower — the backward holds more live tiles per
    program).

    Both length-aware defaults additionally require the effective
    scoped-VMEM budget to admit the 2048 tile
    (:func:`_long_blocks_fit_vmem`): an explicit MPIT_FA_VMEM_MB below
    the 64 MB floor — notably ``=0``, the stock-budget A/B control —
    keeps the flat defaults rather than resolving an uncompilable
    geometry."""
    dq, dk = _default_blocks(dtype, d, banded)
    if (fwd_long_bq and block_q is None and lq >= 16384
            and jnp.dtype(dtype).itemsize <= 2
            and os.environ.get("MPIT_FA_LONG_BQ", "1") != "0"
            and _long_blocks_fit_vmem(2048, dk if block_k is None else block_k)):
        dq = 2048
    if (bwd_long_bk and block_k is None and lk >= 32768
            and jnp.dtype(dtype).itemsize <= 2
            and os.environ.get("MPIT_FA_LONG_BK_BWD", "1") != "0"
            and _long_blocks_fit_vmem(dq if block_q is None else block_q, 2048)):
        dk = 2048
    block_q = dq if block_q is None else block_q
    block_k = dk if block_k is None else block_k
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    bq = min(block_q, _round_up(lq, 8))
    bk = min(block_k, _round_up(lk, LANE))
    return scale, bq, bk, _round_up(lq, bq), _round_up(lk, bk), _round_up(d, LANE)


def _lse_of(m, l):
    """Row log-sum-exp from (m, l) partials; -inf on all-masked (dead)
    rows — the convention the backward kernels' ``exp(s - lse)`` safety
    argument depends on."""
    return m + jnp.log(jnp.where(l == 0.0, 1.0, l))


def _fold(x, lq_p):
    """``(G, lq, d)`` padded to ``(G, lq_p, d)`` rows and laid out as
    ``(G * lq_p, d)``, head after head; a plain ``(lq, d)`` is only
    padded.  The width stays the operand's own (:func:`_pad_rows`)."""
    x = _pad_rows(x, lq_p)
    return x if x.ndim == 2 else x.reshape(-1, x.shape[-1])


def _unfold(x, like, lq_p):
    """The inverse of :func:`_fold` on a kernel's row output: back to
    ``like``'s leading shape and true rows; the width is the output's
    own, as the kernel wrote it."""
    if like.ndim == 2:
        return x[:like.shape[0]]
    g, lq, _ = like.shape
    return x.reshape(g, lq_p, -1)[:, :lq]


def _unfold_stat(x, like, lq_p):
    """Lane 0 of a ``(rows, LANE)`` row statistic, unfolded as
    :func:`_unfold` does the rows."""
    if like.ndim == 2:
        return x[:like.shape[0], 0]
    return x[:, 0].reshape(like.shape[0], lq_p)[:, :like.shape[1]]


def _pad_rows(x, rows):
    """``(..., l, d)`` padded with zero rows to ``(..., rows, d)``.  Rows
    and keys are padded to whole blocks; a head's width never is (the
    module's text says why: a block that spans the last dimension is
    legal as it stands, and the lanes its last tile lacks are masked in
    VMEM)."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 2)
                   + [(0, rows - x.shape[-2]), (0, 0)])


def _walk_specs(walk):
    """Block specs of one walk, each a function of the block's width
    (the operand's own last dimension):
    ``held`` for an operand of the outer side, which stays while the
    inner axis runs, ``walked`` for one of the inner side, fetched as
    :meth:`_Walk.fetch` says.  The index maps take what is prefetched
    (:func:`_prefetch`) after the grid indices."""
    b_outer, b_inner = ((walk.block_k, walk.block_q) if walk.kv_outer
                        else (walk.block_q, walk.block_k))

    def held(width):
        return pl.BlockSpec((b_outer, width), lambda o, t, *s: (o, 0),
                            memory_space=pltpu.VMEM)

    def walked(width):
        return pl.BlockSpec((b_inner, width),
                            lambda o, t, *s: (walk.fetch(o, t, *s), 0),
                            memory_space=pltpu.VMEM)

    return held, walked


def _select_operand(walk, select, lq_p, lk_p):
    """``(in_specs, operands)`` of a walk's selection, each empty where
    it has none: the words padded to the tiles' rows (a padded row
    chooses nothing) and to whole 128-word groups over the padded keys,
    a ``(block_q, 128)`` block a tile (:meth:`_Walk.fetch_select`)."""
    if select is None:
        return [], []
    if SUPER % walk.block_k:
        raise ValueError(f"a selection's bits come {SUPER} keys a group of "
                         f"words: block_k {walk.block_k} must divide it")
    lq, words = select.shape
    padded = jnp.pad(select.astype(jnp.int32), (
        (0, lq_p - lq), (0, max(_round_up(lk_p, SUPER) // 32 - words, 0))))
    spec = pl.BlockSpec((walk.block_q, LANE),
                        lambda o, t, *s: walk.fetch_select(o, t, *s),
                        memory_space=pltpu.VMEM)
    return [spec], [padded]


def _prefetch(walk, q_offset, kv_offset, kv_len):
    """What every kernel prefetches (SMEM, ahead of the grid, so that
    the index maps read it, traced or not): each outer block's live
    range, ``lo`` and ``hi`` by :meth:`_Walk.live_range` on the offsets
    as they are, and the three scalars the bodies' masks read.  The
    ranges are worked out here, once a call and in a few vector
    operations, and not in every index map: an index map is traced once
    an operand, again a ``vmap`` level and again at lowering, and with
    the bounds' arithmetic inside it that was a tenth of a ten-layer
    model's warm start-up (PERF.md section 6, PR 33)."""
    q_off, kv_off, kv_len = (jnp.asarray(x, jnp.int32)
                             for x in (q_offset, kv_offset, kv_len))
    if walk.blockdiff is not None:  # the table's rows and their counts
        entries, counts = walk.table()
        zero = jnp.zeros((1,), jnp.int32)
        return (jnp.asarray(entries.reshape(-1)), jnp.asarray(counts), zero,
                zero, kv_len.reshape(1))
    n_outer = walk.grid[0]
    lo, hi = walk.live_range(_Int(jnp.arange(n_outer, dtype=jnp.int32)),
                             _Int(q_off), _Int(kv_off), _Int(kv_len))
    rows = lambda x: jnp.broadcast_to(
        jnp.asarray(_Int.of(x).v, jnp.int32), (n_outer,))
    return (rows(lo), rows(hi), q_off.reshape(1), kv_off.reshape(1),
            kv_len.reshape(1))


def _fa_2d(q, k, v, q_offset, kv_offset, *, causal, sm_scale, block_q,
           block_k, interpret, partial=False, precision=None, window=None,
           select=None, blockdiff=None, out_dtype=None):
    """Core call on (Lq, D) x (Lk, D) over values (Lk, Dv); pads rows
    and keys to whole blocks, and no width.  Returns the
    normalized (Lq, Dv) output in ``out_dtype`` (None: the operands'),
    or with ``partial`` the unnormalized
    ``(acc, m, l)`` triple (f32) for cross-chunk merging.  ``q`` of
    ``(G, Lq, D)`` is a group of query heads over the one KV head: its
    heads are folded into the rows, so ``k`` and ``v`` are read where
    they lie and never repeated."""
    lq, d = q.shape[-2:]
    lk, dv = v.shape
    groups = q.shape[0] if q.ndim == 3 else 1
    scale, bq, bk, lq_p, lk_p, _ = _tile_dims(
        lq, lk, d, block_q, block_k, sm_scale, q.dtype, fwd_long_bq=True,
        banded=window is not None or blockdiff is not None,
    )
    # every operand and result at its own width: ``d`` for q and k,
    # ``dv`` for v, PV and ``o`` (the keys' own unless the heads are of
    # two widths, latent attention's 192-wide keys over 128-wide values)
    qp = _fold(q, lq_p)
    kp = _pad_rows(k, lk_p)
    vp = _pad_rows(v, lk_p)
    rows = groups * lq_p
    walk = _Walk(False, causal, window, bq, bk, lq_p // bq, lk_p // bk,
                 groups, select is not None, blockdiff)
    held, walked = _walk_specs(walk)
    sel_specs, sel = _select_operand(walk, select, lq_p, lk_p)
    if partial:
        out_specs = (held(dv), held(LANE), held(LANE))
        out_shape = (
            jax.ShapeDtypeStruct((rows, dv), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
        )
    else:
        out_specs = held(dv)
        out_shape = jax.ShapeDtypeStruct((rows, dv), out_dtype or q.dtype)
    res = pl.pallas_call(
        functools.partial(
            _fa_kernel, walk=walk, scale=scale, partial=partial,
            precision=precision,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=walk.grid,
            in_specs=[held(d), walked(d), walked(dv), *sel_specs],
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((bq, dv), jnp.float32),
                pltpu.VMEM((bq, LANE), jnp.float32),
                pltpu.VMEM((bq, LANE), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        interpret=_interpret(interpret),
        compiler_params=_fa_compiler_params(_vmem_auto(bq, bk)),
    )(*_prefetch(walk, q_offset, kv_offset, lk), qp, kp, vp, *sel)
    if partial:
        acc, m, l = res
        return (_unfold(acc, q, lq_p), _unfold_stat(m, q, lq_p),
                _unfold_stat(l, q, lq_p))
    return _unfold(res, q, lq_p)


def _over_leading(f, k, select=None):
    """``f(q2, k2, v2, ...)`` vmapped over ``k``'s leading axes.  A
    selection ``(B, Lq, words)`` is ``f``'s last argument: it has the
    outermost of those axes, a sequence's and never a head's, and goes
    whole to the others."""
    if select is not None and (select.ndim != 3 or k.ndim < 3):
        raise ValueError(f"a selection is (B, Lq, words) beside k (B, ..., "
                         f"Lk, D): got {select.shape} beside {k.shape}")

    def with_select(f, axis):
        return lambda *a: jax.vmap(
            f, in_axes=(0,) * (len(a) - 1) + (axis,))(*a)

    for level in reversed(range(k.ndim - 2)):  # the innermost axis first
        f = jax.vmap(f) if select is None else with_select(
            f, 0 if level == 0 else None)
    return f


def flash_attention_partial(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    sm_scale: float | None = None,
    q_offset=0,
    kv_offset=0,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    precision: str | None = None,
    window: int | None = None,
    select: jnp.ndarray | None = None,
    blockdiff: tuple | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pallas twin of :func:`block_attention_partial`: unnormalized
    ``(acc, m, l)`` over ``(..., L, D)``.  Forward-only — ring attention
    pairs it with :func:`flash_attention_bwd_pair` under a custom VJP at
    the ring level
    (:mod:`mpit_tpu.parallel.ring_attention`).  ``q`` one rank above
    ``k`` is grouped (:func:`_group_queries`); ``select`` as
    :func:`flash_attention` takes it."""
    f = lambda q2, k2, v2, *sel: _fa_2d(
        q2, k2, v2, q_offset, kv_offset, causal=causal, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, interpret=interpret, partial=True,
        precision=precision, window=window, select=sel[0] if sel else None,
        blockdiff=blockdiff,
    )
    return _over_leading(f, k, select)(
        q, k, v, *(() if select is None else (select,)))


# ---------------------------------------------------------------------------
# pallas backward kernels (standard flash-bwd schedule)
#
# Residuals from the forward are O (normalized output) and the row
# log-sum-exp  LSE = m + log(l); the backward recomputes P blockwise as
# exp(scale*QK^T - LSE) — never materializing the (Lq, Lk) score matrix —
# and accumulates
#     delta = rowsum(dO * O)
#     dV    = P^T dO
#     dS    = P * (dO V^T - delta)
#     dQ    = scale * dS K          (kernel 1: grid (i, j), dQ_i in VMEM)
#     dK    = scale * dS^T Q        (kernel 2: grid (j, i), dK_j/dV_j in VMEM)
# Peak extra memory is one (block_q, block_k) tile per program — O(block).
# ---------------------------------------------------------------------------


def _bwd_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
              kvoff_ref, kvlen_ref, q_lo, j, *, walk, scale, precision,
              masked, sel_ref=None):
    """Shared block math: recompute P and dS for the tile whose first
    query row is at ``q_lo`` over kv block ``j``.
    Matmul inputs stay in the dtype they come in (bf16 at the default
    precision: :func:`operand_dtype`), ``p`` and ``dS`` are rounded to
    it before their products; softmax/derivative algebra is f32.
    ``masked=False`` is the interior-block fast path: every element is
    valid by construction, so the iota/compare/where mask algebra is
    skipped entirely."""
    s = jax.lax.dot_general(
        q_ref[:], k_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision,
    ) * scale  # (block_q, block_k) f32

    if masked:
        valid = _valid(q_lo, j, kvoff_ref, kvlen_ref, s.shape, walk)
    if walk.select:
        chosen = _chosen(sel_ref, j, walk)
        valid, masked = (valid & chosen if masked else chosen), True
    # ``lse`` and ``delta`` come with a row's value in every lane
    # (:func:`_rows_to_lanes`): read whole, no lane is moved
    lse = _lanes(lse_ref[:], s.shape[1])
    if masked:
        # exp(s - lse) is only read where valid; all-masked rows have
        # lse = -inf and no valid element, so the inf branch is never
        # taken.
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)
    else:
        # Full blocks contain no dead row (a dead row has no valid key
        # anywhere), so lse is finite and exp needs no guard.
        p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(
        do_ref[:], v_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision,
    )  # (block_q, block_k) f32
    ds = p * (dp - _lanes(delta_ref[:], s.shape[1]))
    return p, ds


def _fa_bwd_dq_kernel(lo_ref, hi_ref, qoff_ref, kvoff_ref, kvlen_ref, q_ref,
                      do_ref, lse_ref, delta_ref, k_ref, v_ref, *rest,
                      walk, scale, precision):
    sel_ref, (dq_ref, dq_scr) = _take_select(walk, rest)
    q_lo, j, live, full, first, last = walk.tile(
        lo_ref, hi_ref, qoff_ref, kvoff_ref, kvlen_ref)
    if walk.select:
        live = jnp.logical_and(live, _any_chosen(sel_ref, j, walk))

    @pl.when(first)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _block(masked):
        _, ds = _bwd_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            kvoff_ref, kvlen_ref, q_lo, j,
            walk=walk, scale=scale, precision=precision, masked=masked,
            sel_ref=sel_ref,
        )
        dq_scr[:] = dq_scr[:] + scale * jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )

    _run_tile(live, full, _block)

    @pl.when(last)
    def _finalize():
        dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)


def _fa_bwd_kv_kernel(lo_ref, hi_ref, qoff_ref, kvoff_ref, kvlen_ref, k_ref,
                      v_ref, q_ref, do_ref, lse_ref, delta_ref,
                      *rest, walk, scale, precision, fused):
    """The kv-outer backward sweep: dK/dV accumulated in VMEM scratch
    over the q blocks of the kv block's live range (over every head of a
    group in turn).  The two-kernel schedule's second kernel as it is;
    with ``fused`` the single sweep, which ALSO writes the dQ
    contribution of each live tile, once, into its slot of a
    (n_kv_blocks, Lq, D) partial that the caller sums: the separate dQ
    kernel's s/P/dS recomputation folds away, 5 matmuls per tile pair
    instead of 7.  A dead pair's slot is never visited and holds
    garbage: the caller sums the visited ones only."""
    sel_ref, (dk_ref, dv_ref, *rest) = _take_select(walk, rest)
    if fused:
        dqp_ref, dk_scr, dv_scr = rest
    else:
        dk_scr, dv_scr = rest
    q_lo, j, live, full, first, last = walk.tile(
        lo_ref, hi_ref, qoff_ref, kvoff_ref, kvlen_ref)
    if walk.select:
        live = jnp.logical_and(live, _any_chosen(sel_ref, j, walk))

    @pl.when(first)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _block(masked):
        p, ds = _bwd_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            kvoff_ref, kvlen_ref, q_lo, j,
            walk=walk, scale=scale, precision=precision, masked=masked,
            sel_ref=sel_ref,
        )
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[:], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )
        dk_scr[:] = dk_scr[:] + scale * jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[:], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )
        if fused:
            dqp_ref[0] = scale * jax.lax.dot_general(
                ds.astype(k_ref.dtype), k_ref[:], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=precision,
            )

    _run_tile(live, full, _block)

    @pl.when(last)
    def _finalize():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def _rows_to_lanes(x, length_p):
    """(L,) f32 row stats -> (L_p, LANE) with the value broadcast across
    lanes (the layout the kernels read whole: :func:`_lanes`); a
    group's (G, L) -> (G * L_p, LANE), as :func:`_fold` lays the rows."""
    pad = [(0, 0)] * (x.ndim - 1) + [(0, length_p - x.shape[-1])]
    xp = jnp.pad(x.astype(jnp.float32), pad).reshape(-1)  # folded heads
    return jnp.broadcast_to(xp[:, None], (xp.shape[0], LANE))


def _sum_visited(dq_part, walk, q_offset, kv_offset, kv_len):
    """The fused sweep's dQ: the sum over kv blocks of the partial
    slots a live tile wrote.  Slot ``(j, i)`` was visited iff ``j`` lies
    in q block ``i``'s live range (the q-outer reading of the same
    rule); the others were never written and may hold anything, so they
    are selected away, not multiplied by zero."""
    if walk.blockdiff is not None:
        visited = walk.tiles()[0].T
    else:
        across = dataclasses.replace(walk, kv_outer=False, groups=1)
        lo, hi = across.live_range(_Int(jnp.arange(walk.q_blocks)),
                                   _Int(q_offset), _Int(kv_offset), kv_len)
        j = jnp.arange(walk.kv_blocks)[:, None]
        visited = (j >= _Int.of(lo).v) & (j <= hi.v)  # (kv_blocks, q_blocks)
    nj, rows, d = dq_part.shape
    part = dq_part.reshape(nj, walk.groups, walk.q_blocks, walk.block_q, d)
    return jnp.sum(
        jnp.where(visited[:, None, :, None, None], part, 0.0), axis=0
    ).reshape(rows, d)


def _fa_2d_bwd(q, k, v, do, lse, delta, q_offset, kv_offset, *, causal,
               sm_scale, block_q, block_k, interpret, precision,
               fused=True, window=None, select=None, blockdiff=None,
               out_dtype=None):
    """Backward core on (Lq, D) x (Lk, D): returns (dq, dk, dv), in
    ``out_dtype`` (None: the operands').

    ``lse``/``delta`` are per-q-row f32 vectors (log-sum-exp from the
    forward; rowsum(dO*O)).  Padded q rows carry dO = 0 so their P/dS
    contribute nothing; padded k rows are masked by ``kv_len``.
    ``q``, ``do`` of ``(G, Lq, D)`` (and ``lse``, ``delta`` of ``(G,
    Lq)``) are a group of query heads over the one KV head, folded into
    the rows as in the forward: ``dk`` and ``dv`` are then summed over
    the group's heads inside the kernel, in the same VMEM accumulator.
    """
    lq, d = q.shape[-2:]
    lk, dv = v.shape
    groups = q.shape[0] if q.ndim == 3 else 1
    out_dtype = out_dtype or q.dtype
    # bwd_long_bk only under the fused schedule: the 32k sweep measured
    # the win THERE (the halved dQ-partials transient is most of it);
    # the two-kernel schedule with bk=2048 is unmeasured, so the
    # fallback keeps its flat default.  _use_fused_bwd models the fused
    # candidate with the same flag, so gate and kernel stay consistent.
    scale, bq, bk, lq_p, lk_p, _ = _tile_dims(
        lq, lk, d, block_q, block_k, sm_scale, q.dtype, bwd_long_bk=fused,
        banded=window is not None or blockdiff is not None,
    )
    # q, k, dq, dk at the keys' own width; v, do, dv at the values'
    qp = _fold(q, lq_p)
    kp = _pad_rows(k, lk_p)
    vp = _pad_rows(v, lk_p)
    dop = _fold(do, lq_p)
    lse_r = _rows_to_lanes(lse, lq_p)
    delta_r = _rows_to_lanes(delta, lq_p)
    rows = groups * lq_p
    kw = dict(scale=scale, precision=precision)
    call = dict(interpret=_interpret(interpret),
                compiler_params=_fa_compiler_params(_vmem_auto(bq, bk)))

    # dK/dV, and under the fused schedule dQ's partials: kv blocks
    # outer, each walking the q blocks of its live range.  Fused is 5
    # matmuls per tile pair vs the two-kernel schedule's 7; its partial
    # buffer costs n_kv_blocks * Lq * D * 4 bytes of transient HBM per
    # (B, H) program (128 MB at L=16k, 512 MB at 32k with 1024-wide kv
    # blocks — x batch*heads live at once under vmap) and one XLA
    # reduction.  Fused-vs-two-kernel selection (incl. the vmapped-batch
    # HBM budget) lives in _use_fused_bwd; this function only executes
    # the chosen schedule.
    walk = _Walk(True, causal, window, bq, bk, lq_p // bq, lk_p // bk,
                 groups, select is not None, blockdiff)
    held, walked = _walk_specs(walk)
    sel_specs, sel = _select_operand(walk, select, lq_p, lk_p)
    out_specs = [held(d), held(dv)]
    out_shape = [jax.ShapeDtypeStruct((lk_p, d), out_dtype),
                 jax.ShapeDtypeStruct((lk_p, dv), out_dtype)]
    if fused:
        out_specs.append(pl.BlockSpec(
            (1, bq, d), lambda j, t, *s: (j, walk.fetch(j, t, *s), 0),
            memory_space=pltpu.VMEM))
        out_shape.append(
            jax.ShapeDtypeStruct((walk.kv_blocks, rows, d), jnp.float32))
    dk, dv_out, *dq_part = pl.pallas_call(
        functools.partial(_fa_bwd_kv_kernel, walk=walk, fused=fused, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=walk.grid,
            in_specs=[held(d), held(dv), walked(d), walked(dv),
                      walked(LANE), walked(LANE), *sel_specs],
            out_specs=tuple(out_specs),
            scratch_shapes=[
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, dv), jnp.float32),
            ],
        ),
        out_shape=tuple(out_shape),
        **call,
    )(*_prefetch(walk, q_offset, kv_offset, lk), kp, vp, qp, dop, lse_r,
      delta_r, *sel)

    if fused:
        dq = _sum_visited(dq_part[0], walk, q_offset, kv_offset,
                          lk).astype(out_dtype)
    else:
        # The two-kernel schedule's dQ: q rows outer, each walking the
        # kv blocks of its live range.
        walk = dataclasses.replace(walk, kv_outer=False)
        held, walked = _walk_specs(walk)
        sel_specs, sel = _select_operand(walk, select, lq_p, lk_p)
        dq = pl.pallas_call(
            functools.partial(_fa_bwd_dq_kernel, walk=walk, **kw),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5,
                grid=walk.grid,
                in_specs=[held(d), held(dv), held(LANE), held(LANE),
                          walked(d), walked(dv), *sel_specs],
                out_specs=held(d),
                scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
            ),
            out_shape=jax.ShapeDtypeStruct((rows, d), out_dtype),
            **call,
        )(*_prefetch(walk, q_offset, kv_offset, lk), qp, dop, lse_r,
          delta_r, kp, vp, *sel)

    return _unfold(dq, q, lq_p), dk[:lk], dv_out[:lk]


def _use_fused_bwd(q_shape, k_shape, d, dtype, sm_scale, block_q, block_k,
                   window=None, select=False, blockdiff=False):
    """Backward-schedule choice (the ONE decision point, made where the
    full vmapped batch shape is visible).

    ``MPIT_FA_FUSED_BWD``: ``1`` forces the fused single sweep, ``0``
    the two-kernel schedule (the CI A/B levers); default ``auto`` uses
    fused only while its dQ-partials transient — (n_kv_blocks, Lq, D)
    f32 *per vmapped (batch, head) program, all live at once* — fits
    ``MPIT_FA_FUSED_BWD_MAX_MB`` (default 2048).  The fused sweep saves
    2 of 7 matmuls per tile pair; a July 2026 on-chip A/B the ledger
    has not reproduced measured it faster at every length (-5.5% at
    8k, -5.7% at 16k, -7.0% at 32k on the B=1 H=8 D=128 bench shape).
    The budget admits the 1 GB transient at 16k and refuses 4 GB; at
    32k the length-aware bwd bk=2048 default halves the transient to
    exactly
    2048 MB, so the bench shape now runs FUSED at 32k by default —
    shave ``MPIT_FA_FUSED_BWD_MAX_MB`` (or set
    ``MPIT_FA_LONG_BK_BWD=0``) to force the two-kernel schedule when a
    composite program needs the HBM back.

    Caveat: the batch factor comes from ``q_shape[:-2]``, i.e. the
    shape :func:`flash_attention` itself receives.  Pass the full
    batched array and let the op vmap internally (as the model zoo
    does); wrapping the op in an OUTER ``jax.vmap`` batches the
    custom-vjp rules per example, so this gate sees a batch of 1 and
    undercounts the transient by the outer batch factor.

    With a ``window``, ``auto`` is the two-kernel schedule: the fused
    sweep's partial has a slot for every (kv block, q block) pair, and
    under a window most pairs are dead (at 8k with window 1024 and
    512-blocks 45 of 256 are live).  A dead slot is no longer visited or
    zeroed (:class:`_Walk`), but the caller's sum still reads the whole
    transient to select the live slots (:func:`_sum_visited`), five
    sixths of it unwritten; the two-kernel schedule reads nothing it
    does not need.  Fused under a window, with the partials laid out by
    a pair's place in its range so that the transient shrinks with the
    window, is UNMEASURED (``MPIT_FA_FUSED_BWD=1`` still forces the
    fused sweep, on the slot layout).

    With a selection it is the two-kernel schedule whatever the lever
    says: a tile with no chosen pair is skipped, its slot of the fused
    sweep's partial never written, and which tiles those are is data the
    caller's sum of the visited slots does not have."""
    if select:
        return False
    mode = os.environ.get("MPIT_FA_FUSED_BWD", "auto") or "auto"
    if mode == "0":
        return False
    if mode == "1":
        return True
    if mode != "auto":
        # Fail loudly: pre-round-5 semantics treated any non-"0" value as
        # force-fused, so a stray "true"/"2" silently flipping to the
        # auto heuristic would corrupt A/B comparisons.
        raise ValueError(
            f"MPIT_FA_FUSED_BWD={mode!r}: expected '0', '1', or 'auto'"
        )
    if window is not None or blockdiff:
        # the block-diffusion pass likewise: 5 of 16 slots are live
        return False
    lq, lk = q_shape[-2], k_shape[-2]
    # bwd_long_bk: the gate must see the SAME bk the executed backward
    # resolves (_fa_2d_bwd), or the transient estimate is for a
    # different schedule than the one that runs.
    _, _, bk, lq_p, lk_p, d_p = _tile_dims(
        lq, lk, d, block_q, block_k, sm_scale, dtype, bwd_long_bk=True
    )
    batch = 1
    for s in q_shape[:-2]:
        batch *= int(s)
    # at the width in whole lanes: the partial is declared ``d`` wide
    # and lies in HBM in whole tiles, so that is what it takes there
    transient_mb = batch * (lk_p // bk) * lq_p * d_p * 4 / 2**20
    budget = float(os.environ.get("MPIT_FA_FUSED_BWD_MAX_MB", "2048"))
    return transient_mb <= budget


_KERNEL_WALKS = {  # kernel -> (kv_outer, the _tile_dims flag it resolves with)
    "fwd": (False, "fwd_long_bq"),
    "dq": (False, None),
    "dkdv": (True, None),
    "fused": (True, "bwd_long_bk"),
}


def flash_step_counts(kernel, q_shape, k_shape, dtype, *, causal=False,
                      window=None, block_q=None, block_k=None, q_offset=0,
                      kv_offset=0, blockdiff=None):
    """Grid steps of one kernel call over ``q_shape`` x ``k_shape`` (as
    :func:`flash_attention` takes them, or ``q`` already grouped one rank
    above ``k``): ``{"visited", "live", "rect"}``, summed over the
    leading (batch, KV head) axes.  ``kernel`` is ``fwd``, ``dq``,
    ``dkdv`` or ``fused``; ``dtype`` the operands' as the kernels take
    them (:func:`operand_dtype` of the caller's).  ``visited`` is what
    the grid's shape says, ``live`` the tiles that run a product,
    ``rect`` the whole rectangle the grid was before it walked live
    ranges; live over visited is the walk's hit share.  The program's
    own numbers: the same
    :class:`_Walk` the kernels lower with, on concrete offsets (an
    offset that is traced counts as 0).  Under ``blockdiff`` also
    ``nonempty``: the tiles of the rectangle in which the mask has a
    true entry, by the rule and not by the walk, so ``live - nonempty``
    is what the walk computes for nothing."""
    kv_outer, long_flag = _KERNEL_WALKS[kernel]
    q_shape, k_shape = tuple(q_shape), tuple(k_shape)
    if len(q_shape) == len(k_shape) and len(q_shape) >= 3:
        groups = q_shape[-3] // k_shape[-3]
    else:
        groups = q_shape[-3] if len(q_shape) > len(k_shape) else 1
    lq, d = q_shape[-2:]
    lk = k_shape[-2]
    _, bq, bk, lq_p, lk_p, _ = _tile_dims(
        lq, lk, d, block_q, block_k, None, dtype,
        banded=window is not None or blockdiff is not None,
        **({long_flag: True} if long_flag else {}))
    walk = _Walk(kv_outer, causal, window, bq, bk, lq_p // bq, lk_p // bk,
                 groups, blockdiff=_check_blockdiff(blockdiff, lq, lk, causal,
                                                    window, None))
    concrete = lambda x: 0 if isinstance(x, jax.core.Tracer) else int(x)
    one = walk.steps(concrete(q_offset), concrete(kv_offset), lk)
    calls = math.prod(k_shape[:-2])
    return {name: n * calls for name, n in one.items()}


def flash_call_counts(q_shape, k_shape, dtype, **mask):
    """:func:`flash_step_counts` summed over the kernels one
    differentiated :func:`flash_attention` call of these shapes lowers:
    the forward and, by :func:`_use_fused_bwd`, the fused sweep or the
    two-kernel backward.  ``mask``: ``causal``, ``window``, ``blockdiff``,
    ``block_q``, ``block_k`` as the call has them."""
    q_shape, k_shape = tuple(q_shape), tuple(k_shape)
    fused = _use_fused_bwd(  # the batch is all it reads of the shapes
        q_shape, k_shape, q_shape[-1], dtype, None, mask.get("block_q"),
        mask.get("block_k"), mask.get("window"), False,
        mask.get("blockdiff") is not None)
    total: dict = {}
    for kernel in ("fwd", *(("fused",) if fused else ("dq", "dkdv"))):
        for name, n in flash_step_counts(kernel, q_shape, k_shape, dtype,
                                         **mask).items():
            total[name] = total.get(name, 0) + n
    return total


def flash_attention_bwd_pair(q, k, v, do, lse, *, causal=False, sm_scale=None,
                             q_offset=0, kv_offset=0, delta=None, o=None,
                             block_q=None, block_k=None, interpret=None,
                             precision=None, window=None, select=None,
                             blockdiff=None, out_dtype=None):
    """Pallas flash backward for one (Q chunk, KV chunk) pair over
    ``(..., L, D)``: returns ``(dq, dk, dv)`` given the forward's row
    ``lse`` (shape ``(..., Lq)``) and either ``delta = rowsum(dO*O)`` or
    ``o`` to compute it from.  This is the per-ring-step backward op of
    :mod:`mpit_tpu.parallel.ring_attention` — O(block) extra memory.
    ``q``, ``do`` (and ``lse``) one rank above ``k`` are grouped
    (:func:`_group_queries`).  The operands go to the kernels as they
    come; the gradients are ``out_dtype`` (None: ``q``'s).
    """
    if delta is None:
        if o is None:
            raise ValueError("flash_attention_bwd_pair needs delta or o")
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
    fused = _use_fused_bwd(q.shape, k.shape, q.shape[-1], q.dtype,
                           sm_scale, block_q, block_k, window,
                           select is not None, blockdiff is not None)
    f = lambda q2, k2, v2, do2, lse2, delta2, *sel: _fa_2d_bwd(
        q2, k2, v2, do2, lse2, delta2, q_offset, kv_offset, causal=causal,
        sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        interpret=interpret, precision=precision, fused=fused,
        window=window, select=sel[0] if sel else None, blockdiff=blockdiff,
        out_dtype=out_dtype,
    )
    return _over_leading(f, k, select)(
        q, k, v, do, lse, delta, *(() if select is None else (select,)))


def operand_dtype(dtype, precision):
    """The dtype :func:`flash_attention`'s kernels take ``q``, ``k``,
    ``v`` and ``dO`` in, the ONE copy of the rule: at the default
    ``precision`` the products are one bf16 MXU pass with float32
    accumulation, and an operand wider than that is rounded to bf16
    once, where it is made, instead of by the MXU every time a tile
    pair loads it: the same product of the same roundings, on half the
    pushes and half the bytes (docs/tpu_compile_notes.md section 5).  A
    caller that names a ``precision`` asked for more than one pass of
    the operands it has, and they stay as they are."""
    dtype = jnp.dtype(dtype)
    wide = jnp.issubdtype(dtype, jnp.floating) and dtype.itemsize > 2
    return jnp.dtype(jnp.bfloat16) if precision is None and wide else dtype


@functools.lru_cache(maxsize=64)
def _make_flash(causal, sm_scale, block_q, block_k, interpret, precision,
                window=None, blockdiff=None):
    """Differentiable flash op for fixed static config: pallas forward,
    pallas backward (flash schedule, O(block) memory — the forward's
    partial outputs provide the LSE residual).  ``sel`` is the
    selection's words where the call has one, else nothing: an integer
    operand, with no cotangent.

    The rules own the operands' rounding (:func:`operand_dtype`): the
    casts stand at the head of each rule, behind whatever made ``q``,
    ``k``, ``v`` and ``dO``, so XLA writes the rounded operand from the
    fusion that made the wide one and no pass over ``(B, H, L, D)`` is
    added; the forward keeps the rounded ``q``, ``k``, ``v`` for the
    backward.  What comes out (``o``, ``dq``, ``dk``, ``dv``) is the
    caller's dtype, and ``lse`` and ``delta`` are float32."""
    kw = dict(causal=causal, sm_scale=sm_scale, block_q=block_q,
              block_k=block_k, interpret=interpret, precision=precision,
              window=window, blockdiff=blockdiff)

    def rounded(*xs):
        return tuple(x.astype(operand_dtype(x.dtype, precision)) for x in xs)

    @jax.custom_vjp
    def fa(q, k, v, q_offset, kv_offset, *sel):
        f = lambda q2, k2, v2, *sel2: _fa_2d(
            q2, k2, v2, q_offset, kv_offset, out_dtype=q.dtype,
            select=sel2[0] if sel2 else None, **kw)
        return _over_leading(f, k, *sel)(*rounded(q, k, v), *sel)

    def fwd(q, k, v, q_offset, kv_offset, *sel):
        qkv = rounded(q, k, v)
        acc, m, l = flash_attention_partial(
            *qkv, q_offset=q_offset, kv_offset=kv_offset,
            select=sel[0] if sel else None, **kw)
        # Named where the rule makes them: a checkpoint whose policy
        # saves these two names keeps the forward kernel's results and
        # does not run it again for the backward pass (``lse`` exists
        # nowhere outside this rule).  Outside such a policy a name
        # lowers to nothing.
        o = checkpoint_name(finalize_partials(acc, l, dtype=q.dtype),
                            FLASH_OUT)
        lse = checkpoint_name(_lse_of(m, l), FLASH_LSE)
        return o, (*qkv, o, lse, q_offset, kv_offset, *sel)

    def bwd(res, g):
        q, k, v, o, lse, q_offset, kv_offset, *sel = res
        # ``delta`` is taken inside, from the rounded ``dO`` the kernels
        # multiply: ``rowsum(P * dP)`` is ``dO . O`` for that ``dO``, and
        # the float32 one then has no reader and is never written
        dq, dk, dv = flash_attention_bwd_pair(
            q, k, v, *rounded(g), lse, q_offset=q_offset,
            kv_offset=kv_offset, o=o, out_dtype=o.dtype,
            select=sel[0] if sel else None, **kw)
        return (dq, dk, dv, None, None, *(None for _ in sel))

    fa.defvjp(fwd, bwd)
    return fa


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    sm_scale: float | None = None,
    q_offset=0,
    kv_offset=0,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    precision: str | None = None,
    window: int | None = None,
    select: jnp.ndarray | None = None,
    blockdiff: tuple | None = None,
) -> jnp.ndarray:
    """Flash attention over ``(..., L, D)`` with global-offset causal
    masking.  Leading axes are batched (vmapped); offsets may be traced.

    **Grouped KV heads** travel in the shapes: ``q (..., Hq, L, D)``
    over ``k, v (..., Hkv, L, D)`` with ``Hq`` a multiple of ``Hkv``;
    query head ``g`` attends KV head ``g // (Hq // Hkv)``.  A group's
    query heads are folded into the kernel's rows, so ``k`` and ``v``
    are read where they lie (no repeat is materialised) and ``dk``,
    ``dv`` are summed over the group inside the backward kernel.

    **Two head widths**: ``v (..., Lk, Dv)`` beside ``q, k (..., L,
    D)``; the output is ``(..., Lq, Dv)`` and the default scale ``1 /
    sqrt(D)``, the keys' width.

    ``window`` (causal only): query ``i`` sees key ``j`` iff ``0 <= i -
    j < window``.  A block wholly outside the window, like one above
    the diagonal or beyond the keys' length, is neither fetched nor
    visited, forward and backward: the grids walk each row's live range
    (:class:`_Walk`), whose length under a window is bounded by the
    window, not the sequence.  The backward under a window is the
    two-kernel schedule (:func:`_use_fused_bwd`).

    ``select``: a chosen set of keys a query, as the bits of
    ``ops/select_bits.py`` ``pack``: ``(B, Lq, words)`` int32 beside ``k
    (B, Hkv, Lk, D)``, a sequence's, the same for every head.  Query ``i``
    sees key ``j`` iff the other rules let it and its bit is set; a tile
    with no bit set runs no product, forward or backward; a row with
    none returns zeros.  Integer: it has no gradient.

    ``blockdiff=(half, block)``: the block-diffusion pass.  The ``2 *
    half`` rows (and keys) are a noised copy of a sequence followed by
    its clean copy, in blocks of ``block`` positions; a noised row sees
    its own noised block and the clean blocks strictly before it, a
    clean row the clean blocks up to its own.  It is the call's whole
    mask (no ``causal``, ``window``, ``select`` or offset beside it),
    walked tile by live tile from a table made at trace time
    (:class:`_Walk`); the backward is the two-kernel schedule unless
    ``MPIT_FA_FUSED_BWD=1`` forces the fused sweep.

    Default blocks come from the operands' dtype, the keys' width and
    the mask (:func:`_default_blocks`), ``block_q`` growing to 2048 at
    L >= 16384 (a July 2026 sweep on a v5e the ledger has not
    reproduced; MPIT_FA_LONG_BQ=0 pins it — the kernel auto-raises
    its scoped-VMEM budget for the bigger score tile).  ``_tile_dims``
    clamps blocks for short sequences, so the default is safe at any L.

    ``precision``: MXU input precision for the two block matmuls (e.g.
    ``"highest"`` for full-f32 inputs), on the operands as they come.
    None is the backend default, one bf16 MXU pass with float32
    accumulation, the standard flash-attention trade: ``q``, ``k``,
    ``v`` and, backward, ``dO`` then go to the kernels rounded to bf16
    (:func:`operand_dtype`: once, by the fusion that makes them, where
    the MXU rounded a float32 operand every time a tile pair loaded it),
    and the output and the gradients are the caller's dtype as before."""
    # sm_scale is a cache key and closed over as a compile-time constant —
    # it must be a static float, not a traced value (float() rejects
    # tracers with a clear error instead of leaking per-trace cache
    # entries).
    window = _check_window(window, causal)
    blockdiff = _check_blockdiff(blockdiff, q.shape[-2], k.shape[-2], causal,
                                 window, select)
    if blockdiff is not None and not (
            isinstance(q_offset, int) and isinstance(kv_offset, int)
            and q_offset == kv_offset == 0):
        raise ValueError("blockdiff takes no offset: its rows are the call's")
    fa = _make_flash(bool(causal),
                     None if sm_scale is None else float(sm_scale),
                     None if block_q is None else int(block_q),
                     None if block_k is None else int(block_k),
                     _interpret(interpret), precision, window, blockdiff)
    out = fa(_group_queries(q, k), k, v, jnp.asarray(q_offset, jnp.int32),
             jnp.asarray(kv_offset, jnp.int32),
             *(() if select is None else (select,)))
    return out.reshape(*q.shape[:-1], v.shape[-1])
