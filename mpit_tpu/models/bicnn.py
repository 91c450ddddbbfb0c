"""BiCNN answer-selection model — the reference's flagship workload, TPU-first.

The reference builds FOUR copies of an embedding -> Linear -> tanh ->
TemporalConvolution -> Max -> ReLU -> Normalize tower and manually aliases
every weight/gradient tensor across them with ``:set()`` (reference
BiCNN/bicnn.lua:30-91) because torch-nn graphs cannot share modules.  In
JAX/Flax weight tying is by construction: ONE :class:`BiCNNTower` is
applied to the question, the positive answer, and the negative answer —
same parameters, zero aliasing bookkeeping.  The reference's mmode 1
(one 3-input graph) vs mmode 2 (two paired graphs, bicnn.lua:107-116) are
graph-plumbing variants of identical math, so a single implementation
covers both; the trainer keeps the ``mmode`` flag for config parity.

TPU-native choices:

- **Static shapes**: sequences are padded to a fixed max length with a
  valid-length vector; the conv runs over the padded buffer and invalid
  frames are masked to -inf before the max pool (layers.masked_max_pool)
  — one XLA program for every sentence length, instead of the
  reference's per-example retrace-everything dynamic shapes.
- **Batched towers**: the reference scores one (q, a) pair per forward
  (bicnn.lua:321-359); here towers take (B, L) token batches so the
  embedding matmul and the conv land on the MXU at full tile width.
- The temporal convolution is ``flax.linen.Conv`` with VALID padding over
  the time axis — exactly TemporalConvolution's frame math
  (out_t = W . x[t:t+k] + b), as a batched NLC conv.

GESD similarity head (reference bicnn.lua:98-105):
    ``sim(u, v) = 1 / ((1 + ||u - v||_2) * (1 + exp(-(u.v + 1))))``
built here as one jnp expression instead of nine nn primitives.
"""

from __future__ import annotations

from typing import Callable, Optional

import flax.linen as nn
import jax.numpy as jnp

from mpit_tpu.models.layers import lp_normalize, masked_max_pool


class BiCNNTower(nn.Module):
    """Sentence -> normalized embedding tower (reference bicnn.lua:30-91).

    embed -> Dense(word_hidden) -> tanh -> Conv1D(num_filters, conv_width,
    VALID) -> masked max over time -> ReLU -> L2 normalize.
    """

    vocab_size: int
    embedding_dim: int = 100  # plaunch.lua:47 default
    word_hidden_dim: int = 200  # plaunch.lua:49
    num_filters: int = 3000  # plaunch.lua:50
    conv_width: int = 2  # plaunch.lua:48 contConvWidth
    embedding_init: Optional[Callable] = None  # pretrained vectors (bicnn.lua:34)

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
        """(B, L) int32 tokens + (B,) valid lengths -> (B, num_filters)."""
        embed = nn.Embed(
            self.vocab_size,
            self.embedding_dim,
            embedding_init=self.embedding_init or nn.initializers.normal(1.0),
            name="lookup",
        )
        x = embed(tokens)  # (B, L, D)
        x = jnp.tanh(nn.Dense(self.word_hidden_dim, name="word_hidden")(x))
        # TemporalConvolution(wordHiddenDim, numFilters, contConvWidth)
        # (bicnn.lua:60): VALID conv over time, L - k + 1 output frames.
        x = nn.Conv(
            self.num_filters,
            (self.conv_width,),
            padding="VALID",
            name="conv",
        )(x)  # (B, L-k+1, F)
        # nn.Max(1) over the frames of the *actual* sentence (bicnn.lua:78):
        # a length-l input yields l - k + 1 valid frames.
        n_valid = jnp.maximum(lengths - self.conv_width + 1, 1)
        x = masked_max_pool(x, n_valid)  # (B, F)
        x = nn.relu(x)
        return lp_normalize(x, p=2.0, axis=-1)  # nn.Normalize(2), bicnn.lua:83


def gesd(u: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """GESD similarity over (..., F) embedding pairs (bicnn.lua:98-105,
    and inlined at eval time, bicnn.lua:440-443)."""
    dot = jnp.sum(u * v, axis=-1)
    l2 = jnp.sqrt(jnp.sum((u - v) ** 2, axis=-1) + 1e-12)
    return 1.0 / ((1.0 + l2) * (1.0 + jnp.exp(-(dot + 1.0))))


class BiCNN(nn.Module):
    """The tied-tower ranking model.

    ``__call__`` scores a (q, a+, a-) triple — the mmode-1 3-input graph
    (bicnn.lua:113); :meth:`embed` is the single-tower entry used for
    answer-space embedding at eval (bicnn.lua:467-470) and pairwise
    scoring (mmode 2).
    """

    vocab_size: int
    embedding_dim: int = 100
    word_hidden_dim: int = 200
    num_filters: int = 3000
    conv_width: int = 2
    embedding_init: Optional[Callable] = None

    def setup(self):
        self.tower = BiCNNTower(
            vocab_size=self.vocab_size,
            embedding_dim=self.embedding_dim,
            word_hidden_dim=self.word_hidden_dim,
            num_filters=self.num_filters,
            conv_width=self.conv_width,
            embedding_init=self.embedding_init,
        )

    def embed(self, tokens: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
        return self.tower(tokens, lengths)

    def __call__(self, q, q_len, a_pos, a_pos_len, a_neg, a_neg_len):
        """-> (sim(q, a+), sim(q, a-)), each (B,)."""
        eq = self.tower(q, q_len)
        ep = self.tower(a_pos, a_pos_len)
        en = self.tower(a_neg, a_neg_len)
        return gesd(eq, ep), gesd(eq, en)


def margin_ranking_loss(s_pos: jnp.ndarray, s_neg: jnp.ndarray, margin: float) -> jnp.ndarray:
    """MarginRankingCriterion with target=1 (bicnn.lua:121, :380):
    per-example ``max(0, margin - (s_pos - s_neg))``."""
    return jnp.maximum(0.0, margin - (s_pos - s_neg))
