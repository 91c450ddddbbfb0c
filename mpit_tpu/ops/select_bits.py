"""A chosen set of keys a query as bits: the one format a selection
travels in, between what computes it (``ops/index_select.py``) and the
flash kernels that mask by it (``ops/flash_attention.py``, *A
selection*).

``ceil(Lk / 4096) x 128`` int32 words a row, 8 MB a layer at 8192
positions.  The kernels' tile dictates the layout: key ``c`` is bit
``(c % 4096) // 128`` of word ``(c // 4096) * 128 + c % 128``, so a
``(block_q, block_k)`` tile reads one ``(block_q, 128)`` block of words
and its ``block_k / 128`` bits are shifts of whole 128-lane words, no
lane ever moved.  :func:`pack` writes it, :func:`unpack` is the inverse
(the materialised reference attention and the tests), :func:`words_of`
the words a row takes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from mpit_tpu.ops.tiles import LANE

#: keys a 128-word group of a row covers: bit ``b`` of word ``l`` is
#: key ``128 b + l`` of the group
SUPER = 32 * LANE


def words_of(lk: int) -> int:
    """int32 words a row of ``lk`` keys takes."""
    return -(-lk // SUPER) * LANE


def pack(chosen: jnp.ndarray) -> jnp.ndarray:
    """``chosen (..., Lq, Lk)`` booleans as ``(..., Lq, words_of(Lk))``
    int32 words in the layout above; keys past ``Lk`` are unset."""
    *lead, lk = chosen.shape
    supers = -(-lk // SUPER)
    padded = jnp.pad(chosen, [(0, 0)] * len(lead) + [(0, supers * SUPER - lk)])
    bits = padded.reshape(*lead, supers, 32, LANE).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)[:, None]
    words = jnp.sum(bits << shifts, axis=-2, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(
        words.reshape(*lead, supers * LANE), jnp.int32)


def unpack(words: jnp.ndarray, lk: int) -> jnp.ndarray:
    """The inverse of :func:`pack`: ``(..., Lq, Lk)`` booleans."""
    *lead, w = words.shape
    groups = jax.lax.bitcast_convert_type(words, jnp.uint32).reshape(
        *lead, w // LANE, 1, LANE)
    shifts = jnp.arange(32, dtype=jnp.uint32)[:, None]
    bits = (groups >> shifts) & jnp.uint32(1)
    return bits.reshape(*lead, w // LANE * SUPER)[..., :lk] != 0
