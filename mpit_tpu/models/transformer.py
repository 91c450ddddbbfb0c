"""Decoder-only transformer with pluggable attention — the long-context
workload.

The reference's model zoo stops at conv/pool nets (SURVEY.md §5: no
attention, no sequence machinery); this model is the TPU-native
long-context showcase built on the framework's own kernels:

- attention is injected as ``attn_fn(q, k, v) -> out`` over
  ``(B, L, H, D)``, so the same module runs single-device with
  :func:`mpit_tpu.ops.flash_attention` (the default) or
  sequence-parallel with
  :func:`mpit_tpu.parallel.ring_attention.ring_attention` — the module
  never knows about meshes;
- MXU-friendly sizing: model/head dims in multiples of 8, all matmuls
  batched over (B, L);
- pre-LN blocks, learned positional embeddings, causal by default.

Ten decoders, each described where it is defined; ``lm/archs.py``
``BLOCKS`` names them with the sizes they take, and ``lm/model.py``
``build(arch=...)`` chooses one.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from mpit_tpu.ops.delta_rule import GDN_OUT, KDA_OUT, gdn_scan, kda_scan
from mpit_tpu.ops.flash_attention import (
    FLASH_LSE, FLASH_OUT, attention_reference, flash_attention,
    flash_call_counts, operand_dtype,
)
from mpit_tpu.ops.index_select import index_select
from mpit_tpu.ops.short_conv import causal_conv_silu, causal_depthwise_conv
from mpit_tpu.ops.ssd_scan import CHUNK as SSD_CHUNK, SSD_OUT, ssd_scan
from mpit_tpu.parallel import moe

#: ``fn(q, k, v, window=None, select=None, blockdiff=None) -> out``.
#: ``select`` is a learned selection of keys, one set a query for all
#: its heads, as the bits of ``ops/select_bits.py`` ``(B, L, words)``;
#: only a block with an indexer passes it (:func:`selected_attention`).
#: ``blockdiff (half, block)`` is the block-diffusion pass's mask over a
#: noised and a clean copy of a sequence, in place of the causal one;
#: only :class:`SdarBlock` passes it.  A block that passes no keyword
#: may be handed a callable of three arguments (ring attention).
AttnFn = Callable[..., jnp.ndarray]


def default_attn(causal: bool = True, use_flash: bool = True,
                 interpret: Optional[bool] = None,
                 precision: Optional[str] = None) -> AttnFn:
    """Single-device attention ``fn(q, k, v, window=None, select=None,
    blockdiff=None)`` over ``q (B, L, Hq, D)`` and ``k, v (B, L, Hkv, D)``: flash kernel
    or the jnp reference (the latter differentiates without a recompute
    pass).  Fewer KV heads than query heads are grouped (query head
    ``g`` on KV head ``g // (Hq // Hkv)``) and ``window`` is the sliding
    causal window, both as ``ops/flash_attention.py`` has them: one
    callable serves a model's full and windowed layers.  ``select (B,
    L, words)`` is a chosen set of keys a query (``ops/index_select.py``
    makes it, :func:`selected_attention` passes it): a pair outside it
    is masked in the kernel and in the reference alike.  ``blockdiff
    (half, block)`` replaces the causal mask by the block-diffusion
    pass's (``ops/flash_attention.py``), in both alike; ``fn.flash``
    says whether the callable is the kernel and ``fn.precision`` what
    it was made with.
    ``interpret`` reaches ``pallas_call``: None interprets everywhere
    but on a TPU (ops/tiles.py), False pins the Mosaic-compiled kernel.
    ``precision`` is the MXU input precision of the two attention
    products, forward and backward (``"highest"``: float32 inputs);
    None is the backend's default, one bf16 pass on a TPU, for which
    the kernel's rules round q, k, v and dO to bf16 themselves
    (``ops/flash_attention.py`` ``operand_dtype``): XLA folds that into
    the transposes below and into the projections' outputs."""

    def fn(q, k, v, window=None, select=None, blockdiff=None):
        qh, kh, vh = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
        # the window, the selection and the block-diffusion mask are
        # keywords only where there is one: the plain causal call is the
        # call it was
        kw = {} if window is None else {"window": window}
        if select is not None:
            kw["select"] = select
        if blockdiff is not None:  # the whole mask, in the causal one's place
            kw["blockdiff"] = blockdiff
        masked = causal and blockdiff is None
        if use_flash:
            out = flash_attention(qh, kh, vh, causal=masked,
                                  interpret=interpret, precision=precision,
                                  **kw)
        else:
            with jax.default_matmul_precision(precision or "default"):
                out = attention_reference(qh, kh, vh, causal=masked, **kw)
        return out.transpose(0, 2, 1, 3)

    fn.flash, fn.precision = use_flash, precision
    return fn


class DecoderBlock(nn.Module):
    d_model: int
    n_heads: int
    mlp_ratio: int = 4
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        b, l, _ = x.shape
        head = self.d_model // self.n_heads
        attn = self.attn_fn if self.attn_fn is not None else default_attn()

        # The scopes name the model's layers on the device operations
        # of a profiler trace (forward and backward alike); they are
        # metadata and change no program.
        with jax.named_scope("attn"):
            h = nn.LayerNorm()(x)
            qkv = nn.Dense(3 * self.d_model, use_bias=False)(h)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(b, l, self.n_heads, head)
            k = k.reshape(b, l, self.n_heads, head)
            v = v.reshape(b, l, self.n_heads, head)
            x = x + nn.Dense(self.d_model, use_bias=False)(
                attn(q, k, v).reshape(b, l, self.d_model)
            )

        with jax.named_scope("mlp"):
            h = nn.LayerNorm()(x)
            h = nn.gelu(nn.Dense(self.mlp_ratio * self.d_model)(h))
            return x + nn.Dense(self.d_model)(h)


class TinyDecoder(nn.Module):
    """Small causal LM: token + learned position embeddings, N pre-LN
    blocks, tied-free output head.  ``attn_fn`` switches between local
    flash attention and mesh ring attention without touching params —
    the two variants are numerically identical, which the tests pin."""

    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 2
    max_len: int = 1024
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, tokens: jnp.ndarray) -> jnp.ndarray:
        b, l = tokens.shape
        if l > self.max_len:
            # Fail at trace time: out-of-range position gathers clamp
            # under jit and would silently reuse the last embedding row.
            raise ValueError(f"sequence length {l} > max_len {self.max_len}")
        with jax.named_scope("embed"):
            x = nn.Embed(self.vocab, self.d_model)(tokens)
            pos = nn.Embed(self.max_len, self.d_model)(jnp.arange(l))
            x = x + pos[None, :, :]
        for _ in range(self.n_layers):
            x = DecoderBlock(
                d_model=self.d_model, n_heads=self.n_heads,
                attn_fn=self.attn_fn,
            )(x)
        with jax.named_scope("head_loss"):  # lm/model.py's NLL joins it
            x = nn.LayerNorm()(x)
            logits = nn.Dense(self.vocab, use_bias=False)(x)
            return nn.log_softmax(logits)


# ---------------------------------------------------------------------------
# The sparse-expert block (OLMoE: Muennighoff et al., arXiv:2409.02060;
# ``model_type`` ``olmoe``).  RMSNorm, rotary positions, RMSNorm on the
# projected queries and keys, bias-free projections, a router and top-k
# of E SiLU-gated experts by sorted dropless dispatch
# (``parallel/moe.py``), an untied head.  Parameters are float32 and
# named by hand, the experts stacked on a leading expert axis (which
# ``lm/plan.py`` may cut between experts).  The plain float32 reference
# it is held to is the benchmark's, ``chipbench/reference/olmoe_plain.py``
# (here in ``tests/test_olmoe.py``, on the chip in every run); that file
# shares no code with this one.
# ---------------------------------------------------------------------------

_INIT = nn.initializers.normal(stddev=0.02)
# The router's product runs in float32 at full precision: it is a
# thousandth of the step's FLOPs, and one bf16 pass here flips top-k
# membership wherever two experts' probabilities are close.
ROUTER_PRECISION = jax.lax.Precision.HIGHEST
# The attention path runs above the one-pass default: the four
# projections at three bf16 passes, the flash kernel's two products on
# float32 inputs (``default_attn``'s ``precision``, which ``lm/model.py``
# hands the block's attention).  With the query/key norm a head's q and k
# have norm sqrt(128), the scores are O(10), and one bf16 pass of q and
# k is percents off in the attention probabilities: against the float32
# reference 1.4-1.8% of the gradient's norm at published widths, where
# everything else in one pass adds 0.25% (v5e, PERF.md section 6, PR
# 26: projections alone 0.8-1.3%, kernel alone 1.4-1.6%, both 0.23-0.27%
# for 23 ms of a 139 ms step).
ATTN_PRECISION = jax.lax.Precision.HIGH
ATTN_KERNEL_PRECISION = "highest"


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    """``x / rms(x) * weight`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def group_rms_norm(x: jnp.ndarray, weight: jnp.ndarray, groups: int,
                   eps: float) -> jnp.ndarray:
    """:func:`rms_norm` over each of ``groups`` equal runs of the last
    axis, one weight a channel, on the array as it lies: a group's mean
    square and its way back to the channels are two products with the
    groups' membership (0s and 1s) at full float32 precision, so the
    channels stay the minor axis throughout (as a reduction over
    ``(..., groups, width)`` the compiler lays the positions minor, and
    a neighbour that wants the channels there, a kernel's result, pays
    a relayout each way)."""
    x = x.astype(jnp.float32)
    width = x.shape[-1] // groups
    member = (jnp.arange(x.shape[-1])[:, None] // width
              == jnp.arange(groups)[None, :]).astype(jnp.float32)
    exact = partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    var = exact(jnp.square(x), member) / width
    return x * exact(jax.lax.rsqrt(var + eps), member.T) * weight


def rope(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary position embedding over the full head width of ``x (B, L,
    H, D)``, rotate-half convention: with the head split into halves
    ``(x1, x2)`` and angles ``pos * theta^(-2i/D)``, ``(x1 cos - x2 sin,
    x2 cos + x1 sin)``.  Angles in float32."""
    _, l, _, d = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(l, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


class OlmoeBlock(nn.Module):
    d_model: int
    n_heads: int
    n_experts: int
    experts_per_tok: int
    expert_width: int
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        b, l, d = x.shape
        head = d // self.n_heads
        e, f = self.n_experts, self.expert_width
        attn = self.attn_fn if self.attn_fn is not None else default_attn()
        ones = nn.initializers.ones

        with jax.named_scope("attn"):
            h = rms_norm(x, self.param("attn_norm", ones, (d,)), self.norm_eps)
            project = partial(jnp.matmul, precision=ATTN_PRECISION)
            q = project(h, self.param("wq", _INIT, (d, d)))
            k = project(h, self.param("wk", _INIT, (d, d)))
            v = project(h, self.param("wv", _INIT, (d, d)))
            # OLMoE normalises the projected queries and keys over their
            # whole width, before the split into heads
            q = rms_norm(q, self.param("q_norm", ones, (d,)), self.norm_eps)
            k = rms_norm(k, self.param("k_norm", ones, (d,)), self.norm_eps)
            q = rope(q.reshape(b, l, self.n_heads, head), self.rope_theta)
            k = rope(k.reshape(b, l, self.n_heads, head), self.rope_theta)
            v = v.reshape(b, l, self.n_heads, head)
            x = x + project(attn(q, k, v).reshape(b, l, d),
                            self.param("wo", _INIT, (d, d)))

        with jax.named_scope("router"):
            h = rms_norm(x, self.param("mlp_norm", ones, (d,)),
                         self.norm_eps).reshape(b * l, d)
            logits = jnp.matmul(h, self.param("router", _INIT, (d, e)),
                                precision=ROUTER_PRECISION)
            probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            weights, experts = moe.route_top_k(probs, self.experts_per_tok)
            # routing imbalance, for telemetry: read only where the
            # caller makes ``intermediates`` mutable (lm/model.py stats)
            self.sow("intermediates", "moe_load", moe.load_max_over_mean(
                moe.expert_counts(experts, e), experts.size))

        wg = self.param("experts_gate", _INIT, (e, d, f))
        wu = self.param("experts_up", _INIT, (e, d, f))
        wd = self.param("experts_down", _INIT, (e, f, d))
        y = moe.dispatch_top_k(h, weights, experts, e, moe.swiglu_experts,
                               wg, wu, wd)
        return x + y.reshape(b, l, d)


class OlmoeDecoder(nn.Module):
    """Causal LM of :class:`OlmoeBlock` layers: a token table (no
    position table: the positions are rotary), the blocks, a final
    RMSNorm and an untied head; returns log-probabilities like
    :class:`TinyDecoder`, so ``lm/model.py`` closes the same loss over
    either."""

    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 2
    n_experts: int = 8
    experts_per_tok: int = 2
    expert_width: int = 32
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, tokens: jnp.ndarray) -> jnp.ndarray:
        d = self.d_model
        with jax.named_scope("embed"):
            x = self.param("embed", _INIT, (self.vocab, d))[tokens]
        for _ in range(self.n_layers):
            x = OlmoeBlock(
                d_model=d, n_heads=self.n_heads, n_experts=self.n_experts,
                experts_per_tok=self.experts_per_tok,
                expert_width=self.expert_width, rope_theta=self.rope_theta,
                norm_eps=self.norm_eps, attn_fn=self.attn_fn,
            )(x)
        with jax.named_scope("head_loss"):  # lm/model.py's NLL joins it
            x = rms_norm(x, self.param("final_norm", nn.initializers.ones,
                                       (d,)), self.norm_eps)
            logits = x @ self.param("head", _INIT, (d, self.vocab))
            return nn.log_softmax(logits)


# ---------------------------------------------------------------------------
# The windowed sparse-expert block (Mellum 2, JetBrains; ``model_type``
# ``mellum``; the configuration's keys are those of its ``config.json``).
# Grouped KV heads of a width of their own (not ``d_model / heads``),
# sliding-window attention on most layers and full attention on every
# ``full_every``-th with a rotary table per layer type (YaRN on the full
# layers), top-k of E experts with the k weights renormalised, every
# layer sparse, and **a share of the experts**: the layer is told which
# contiguous range it holds (``experts_first``, ``experts_held``), routes
# over all ``n_experts`` and computes its own experts' part
# (``parallel/moe.py``, *A share of the experts*).  The plain float32
# reference it is held to is the benchmark's,
# ``chipbench/reference/mellum_plain.py`` (here in
# ``tests/test_mellum.py``, on the chip in every run); that file shares
# no code with this one.
# ---------------------------------------------------------------------------

#: The token table's own scale: what keeps the routing of a share of
#: the experts even.  At std 0.02 a token's row is small beside the
#: attention's output, which is nearly the same at every position, so
#: every token's router input points the same way and the seeded routing
#: collapses onto a few experts (OLMoE's does: PERF.md section 6, PR
#: 26); with a share of the experts held that would make the work
#: anything from nothing to everything by the seed.  At std 1 the seeded
#: routing follows token identity in the first layer, but the later
#: layers already read a max-over-mean of 2.2-2.6, and momentum SGD at
#: any of the rates tried collapses them within 20-50 steps: what the
#: first steps learn (putting down the head's unused rows) is one
#: direction added to every position of the residual stream.  At std 8
#: the token's own row stays the largest thing in the stream for the
#: hundreds of steps a run makes: both counters hold still through the
#: window (v5e, PERF.md section 6, PR 30).  The first operation on the
#: stream is an RMSNorm, so the scale says only how large a token's row
#: is beside what the layers add to it.
MELLUM_EMBED_INIT = nn.initializers.normal(stddev=8.0)


def yarn_inv_freq(head: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's per-pair inverse frequencies (Peng et al., arXiv:2309.00071,
    as Hugging Face's ``_compute_yarn_parameters`` has them): pair ``j``
    of ``head / 2`` keeps ``theta^(-2j/head)`` where it turns more than
    ``beta_fast`` times over the original context, takes it divided by
    ``factor`` where it turns less than ``beta_slow`` times, and a linear
    blend between."""
    def pair_of(turns):  # the pair that makes ``turns`` rotations
        return (head * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), head - 1)
    j = np.arange(head // 2, dtype=np.float64)
    ramp = np.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    plain = theta ** (-2.0 * j / head)
    return ((1.0 - ramp) * plain + ramp * plain / factor).astype(np.float32)


def plain_inv_freq(head: int, theta: float) -> np.ndarray:
    """The plain rotary table's per-pair inverse frequencies,
    ``theta^(-2j/head)`` for pair ``j`` of ``head / 2``."""
    return (theta ** (-np.arange(0, head, 2, dtype=np.float64) / head)
            ).astype(np.float32)


def rope_by(x: jnp.ndarray, inv_freq: np.ndarray, scale: float = 1.0,
            period: int = 0) -> jnp.ndarray:
    """:func:`rope` with the inverse frequencies given and ``cos``,
    ``sin`` multiplied by ``scale`` (YaRN's ``attention_factor``).
    ``period``: row ``r`` is at position ``r mod period`` (the rows are
    whole copies of one sequence, one after another); 0: at ``r``."""
    l = period or x.shape[1]
    angles = (jnp.arange(l, dtype=jnp.float32)[:, None]
              * jnp.asarray(inv_freq)[None, :])
    cos = (jnp.cos(angles) * scale)[None, :, None, :]
    sin = (jnp.sin(angles) * scale)[None, :, None, :]
    if period:
        cos, sin = (jnp.tile(t, (1, x.shape[1] // period, 1, 1))
                    for t in (cos, sin))
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def grouped_attention(h: jnp.ndarray, wq: jnp.ndarray, wk: jnp.ndarray,
                      wv: jnp.ndarray, wo: jnp.ndarray, *, heads: int,
                      kv_heads: int, head_dim: int, inv_freq: np.ndarray,
                      attn: AttnFn, scale: float = 1.0, window: int = 0,
                      qk_norm: Optional[tuple] = None,
                      period: int = 0,
                      gate: Optional[jnp.ndarray] = None,
                      rotary: int = 0,
                      query_scale: float = 1.0) -> jnp.ndarray:
    """Attention over grouped KV heads of their own width on the normed
    stream ``h (B, L, d)``, projected back to ``(B, L, d)``: bias-free
    projections to ``heads`` query and ``kv_heads`` key and value heads
    of ``head_dim``, rotary positions by ``inv_freq`` (:func:`rope_by`;
    None: **no positional term**, nothing is rotated),
    ``attn`` with the ``window`` where there is one.  ``qk_norm``
    ``(q weight, k weight, eps)``: an RMSNorm over each head's width on
    the queries and the keys, before the rotary embedding.  ``period``
    as :func:`rope_by` takes it.  ``gate (d, heads * head_dim)``: the
    heads' output is multiplied elementwise by ``sigmoid(h gate)``
    before ``wo``, under the scope ``attn_gate``.  ``rotary``: **the
    first ``rotary`` dimensions of every query and key head are rotated**
    (``partial_rotary_factor`` x ``head_dim``; ``inv_freq`` is then that
    part's table, ``rotary / 2`` pairs in :func:`rope_by`'s half-split
    pairing over those dimensions alone) and the rest pass as they are;
    0, or ``head_dim``: the whole head, :func:`rope_by`'s result to the
    bit.  ``query_scale``: the query heads are multiplied by it in front
    of ``attn``, which scales the scores by ``1 / sqrt(head_dim)``: a
    block whose softmax scale is another ``m`` hands over ``m
    sqrt(head_dim)`` (Granite's); 1: nothing is multiplied.  Every
    product at the backend's default precision, one bf16 pass on a TPU:
    Mellum's scores are O(1) without a norm, and LFM2's with its per-head
    norm read the same gradient error against the float32 reference with
    the path raised as OLMoE's is (0.159% both ways on the v5e, PERF.md
    section 6, PR 32)."""
    b, l, _ = h.shape
    q, k, v = h @ wq, h @ wk, h @ wv

    def heads_of(x, count, weight):
        x = x.reshape(b, l, count, head_dim)
        if qk_norm is not None:
            x = rms_norm(x, weight, qk_norm[2])
        if inv_freq is not None and rotary not in (0, head_dim):
            return jnp.concatenate(
                [rope_by(x[..., :rotary], inv_freq, scale, period),
                 x[..., rotary:]], axis=-1)
        return x if inv_freq is None else rope_by(x, inv_freq, scale, period)

    q = heads_of(q, heads, qk_norm and qk_norm[0])
    if query_scale != 1.0:
        q = q * query_scale
    k = heads_of(k, kv_heads, qk_norm and qk_norm[1])
    v = v.reshape(b, l, kv_heads, head_dim)
    out = attn(q, k, v, window=window) if window else attn(q, k, v)
    out = out.reshape(b, l, heads * head_dim)
    if gate is not None:
        with jax.named_scope("attn_gate"):
            out = out * jax.nn.sigmoid(h @ gate)
    return out @ wo


def sparse_mlp(x: jnp.ndarray, norm: jnp.ndarray, router: jnp.ndarray,
               experts: tuple, *, route: Callable, eps: float,
               n_experts: int, first: int, held: int,
               expert_fn: Callable = moe.swiglu_experts):
    """The sparse MLP of a block that holds ``held`` of its ``n_experts``
    experts from ``first`` on, on the stream ``x (B, L, d)``: RMSNorm,
    the router's product over all the experts in float32 at full
    precision, ``route(logits) -> (weights, chosen, extra statistics)``
    (the block's own scoring: a softmax or a sigmoid with a selection
    bias, over all ``n_experts`` whether held or not), and the held
    experts' part of the weighted sum by the sorted dropless dispatch
    (``parallel/moe.py``) through ``expert_fn`` on the ``experts``'
    stacked matrices (:func:`~mpit_tpu.parallel.moe.swiglu_experts` on
    gate, up and down; :func:`~mpit_tpu.parallel.moe.relu2_experts` on
    up and down).  Returns the branch's output and its
    statistics: the load's max over mean, the held rows' share, whether
    the dispatch was done in one window of the held run (1.0 or 0.0),
    then ``route``'s own.  Pure in its arguments, so a block wraps it in
    ``jax.checkpoint``."""
    b, l, d = x.shape
    with jax.named_scope("router"):
        h = rms_norm(x, norm, eps).reshape(b * l, d)
        logits = jnp.matmul(h, router, precision=ROUTER_PRECISION)
        weights, chosen, extra = route(logits.astype(jnp.float32))
        stats = (moe.load_max_over_mean(
                     moe.expert_counts(chosen, n_experts), chosen.size),
                 moe.held_rows_share(chosen, first, held),
                 moe.takes_window(chosen, first, held, n_experts,
                                  d).astype(jnp.float32), *extra)
    y = moe.dispatch_top_k(
        h, weights, chosen, n_experts, expert_fn, *experts,
        held=(first, held) if held < n_experts else None)
    return y.reshape(b, l, d), stats


class MellumBlock(nn.Module):
    d_model: int
    n_heads: int
    kv_heads: int
    head_dim: int
    n_experts: int           # the router's width: every expert there is
    experts_per_tok: int
    expert_width: int
    experts_first: int = 0   # the share held here: a contiguous range
    experts_held: int = 0    # 0: all of them
    window: int = 0          # 0: a full-attention layer
    rope_theta: float = 500000.0
    yarn: Optional[tuple] = None  # full layers: (factor, original,
    #                               beta_fast, beta_slow, attention_factor)
    norm_eps: float = 1e-6
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        b, l, d = x.shape
        hq, hkv, hd = self.n_heads, self.kv_heads, self.head_dim
        e, f = self.n_experts, self.expert_width
        held = self.experts_held or e
        attn = self.attn_fn if self.attn_fn is not None else default_attn()
        ones = nn.initializers.ones
        if self.window or self.yarn is None:  # the plain rotary table
            inv_freq, scale = plain_inv_freq(hd, self.rope_theta), 1.0
        else:
            inv_freq = yarn_inv_freq(hd, self.rope_theta, *self.yarn[:4])
            scale = float(self.yarn[4])

        # a windowed layer's operations go under a scope of their own,
        # so that a trace tells the two kernels' time apart; flat, like
        # every scope of the model
        with jax.named_scope("attn_window" if self.window else "attn"):
            # The attention path runs at the backend's default, one bf16
            # pass: without a query/key norm the scores are O(1), and on
            # the v5e the whole path above it (OLMoE's ATTN_PRECISION and
            # the kernel on float32 inputs) moved the gradient's error
            # against the float32 reference from 0.073% to 0.067% for
            # three times the attention's time (PERF.md section 6, PR 30).
            h = rms_norm(x, self.param("attn_norm", ones, (d,)), self.norm_eps)
            x = x + grouped_attention(
                h, self.param("wq", _INIT, (d, hq * hd)),
                self.param("wk", _INIT, (d, hkv * hd)),
                self.param("wv", _INIT, (d, hkv * hd)),
                self.param("wo", _INIT, (hq * hd, d)),
                heads=hq, kv_heads=hkv, head_dim=hd, inv_freq=inv_freq,
                scale=scale, attn=attn, window=self.window)

        norm = self.param("mlp_norm", ones, (d,))
        router = self.param("router", _INIT, (d, e))
        wg = self.param("experts_gate", _INIT, (held, d, f))
        wu = self.param("experts_up", _INIT, (held, d, f))
        wd = self.param("experts_down", _INIT, (held, f, d))
        k_tok, eps = self.experts_per_tok, self.norm_eps

        # A share's dispatch moves the held run of the k T sorted rows a
        # window at a time, twice what uniform routing sends here, and
        # keeps only its arguments for the backward pass, which walks
        # the windows again (parallel/moe.py, *A window over the held
        # run*).  The branch is still computed again as a whole: kept
        # for the backward pass in every layer, the normed stream, the
        # router's scores and, where a block holds half its experts and
        # more, all k T rows would not fit beside the model at the
        # sequence this block trains at.
        @jax.checkpoint
        def sparse(x, norm, router, wg, wu, wd):
            # top-k over all the experts, the k weights renormalised
            # over the chosen k whether held here or not
            return sparse_mlp(
                x, norm, router, (wg, wu, wd), eps=eps, n_experts=e,
                first=self.experts_first, held=held,
                route=lambda logits: (*moe.route_top_k(
                    jax.nn.softmax(logits, axis=-1), k_tok,
                    renormalise=True), ()))

        y, (load, share, compact) = sparse(x, norm, router, wg, wu, wd)
        # telemetry, read only where the caller makes ``intermediates``
        # mutable (lm/model.py stats)
        self.sow("intermediates", "moe_load", load)
        self.sow("intermediates", "moe_held", share)
        self.sow("intermediates", "moe_compact", compact)
        return x + y


class MellumDecoder(nn.Module):
    """Causal LM of :class:`MellumBlock` layers: a token table, the
    blocks (layer ``i`` is a full-attention layer iff ``(i + 1) %
    full_every == 0``, the others slide a window), a final RMSNorm and
    an untied head; returns log-probabilities like the other decoders."""

    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    kv_heads: int = 2
    head_dim: int = 32
    n_layers: int = 4
    n_experts: int = 8
    experts_per_tok: int = 2
    expert_width: int = 32
    experts_first: int = 0
    experts_held: int = 0
    window: int = 16
    full_every: int = 4
    rope_theta: float = 500000.0
    yarn: Optional[tuple] = None
    norm_eps: float = 1e-6
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, tokens: jnp.ndarray) -> jnp.ndarray:
        d = self.d_model
        with jax.named_scope("embed"):
            x = self.param("embed", MELLUM_EMBED_INIT,
                           (self.vocab, d))[tokens]
        for i in range(self.n_layers):
            full = self.full_every and (i + 1) % self.full_every == 0
            x = MellumBlock(
                d_model=d, n_heads=self.n_heads, kv_heads=self.kv_heads,
                head_dim=self.head_dim, n_experts=self.n_experts,
                experts_per_tok=self.experts_per_tok,
                expert_width=self.expert_width,
                experts_first=self.experts_first,
                experts_held=self.experts_held,
                window=0 if full else self.window,
                rope_theta=self.rope_theta, yarn=self.yarn,
                norm_eps=self.norm_eps, attn_fn=self.attn_fn,
            )(x)
        with jax.named_scope("head_loss"):  # lm/model.py's NLL joins it
            x = rms_norm(x, self.param("final_norm", nn.initializers.ones,
                                       (d,)), self.norm_eps)
            logits = x @ self.param("head", _INIT, (d, self.vocab))
            return nn.log_softmax(logits)


# ---------------------------------------------------------------------------
# The hybrid block (LFM2, Liquid AI; ``model_type`` ``lfm2_moe``; the
# configuration's keys are those of its ``config.json``).  Every layer
# is ``x = x + op(RMSNorm(x))`` then ``x = x + ffn(RMSNorm(x))``, and
# both halves are values read from the configuration layer by layer:
# the token mixer ``op`` is a **gated short convolution**
# (``layer_types[i] == "conv"``: no attention, no positions) or
# full causal attention over grouped KV heads with an RMSNorm over each
# head's width on the queries and the keys; the MLP ``ffn`` is dense
# (SiLU-gated, ``dense_width`` wide: the leading ``num_dense_layers``)
# or sparse: a **sigmoid** router over all ``n_experts`` with a
# **selection bias** (it enters the choice of the top-k and not their
# weights), the k scores divided by their sum plus 1e-6 and scaled, and
# this chip's share of the experts, as Mellum's.  The plain float32
# reference it is held to is ``chipbench/reference/lfm2_plain.py``,
# which shares no code with this file (tests/test_lfm2.py).
# ---------------------------------------------------------------------------

#: The short convolution's taps are seeded at the scale of torch's own
#: ``Conv1d`` default for one input channel and three taps (uniform
#: within 1 / sqrt 3: std 1/3), not at 0.02: the operator's output is
#: then of the size of its gated input, as an attention layer's is of
#: its values.  At 0.02 a conv layer adds a thousandth of the stream,
#: its parameters' gradients are lost in the norm of the whole, and the
#: reference check could not tell a reversed convolution from a right
#: one (2e-5 of the gradient's norm at the tiny size).
LFM2_TAPS_INIT = nn.initializers.normal(stddev=1.0 / 3.0)
#: the kinds of token mixer ``layer_types`` may name
LFM2_MIXERS = ("conv", "full_attention")
#: ``norm_topk_prob``'s guard against an all-zero top-k, as published
LFM2_ROUTE_EPS = 1e-6


class Lfm2Block(nn.Module):
    d_model: int
    mixer: str               # of LFM2_MIXERS
    sparse: bool             # the MLP: sparse experts, else dense
    n_heads: int
    kv_heads: int
    head_dim: int
    dense_width: int
    n_experts: int           # the router's width: every expert there is
    experts_per_tok: int
    expert_width: int
    experts_first: int = 0   # the share held here: a contiguous range
    experts_held: int = 0    # 0: all of them
    conv_kernel: int = 3
    route_scale: float = 1.0
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        if self.mixer not in LFM2_MIXERS:
            raise ValueError(f"layer type {self.mixer!r}; have {LFM2_MIXERS}")
        d, eps = self.d_model, self.norm_eps
        ones = nn.initializers.ones
        op_norm = self.param("op_norm", ones, (d,))
        x = (self.short_conv if self.mixer == "conv"
             else self.attention)(x, op_norm)
        ffn_norm = self.param("ffn_norm", ones, (d,))
        if not self.sparse:
            with jax.named_scope("mlp"):
                h = rms_norm(x, ffn_norm, eps)
                w1 = self.param("w1", _INIT, (d, self.dense_width))
                w3 = self.param("w3", _INIT, (d, self.dense_width))
                w2 = self.param("w2", _INIT, (self.dense_width, d))
                return x + (jax.nn.silu(h @ w1) * (h @ w3)) @ w2
        return x + self.sparse_experts(x, ffn_norm)

    def short_conv(self, x, norm):
        """``x + (C * conv(B * z)) W_out`` with ``[B, C, z] = RMSNorm(x)
        W_in``: two products under the scope ``conv``, and between them
        the two gates and the depthwise causal convolution
        (``ops/short_conv.py``) under ``conv_mix``, which are elementwise
        and bound by memory; no bias anywhere (``conv_bias`` false)."""
        d = self.d_model
        w_in = self.param("conv_in", _INIT, (d, 3 * d))
        taps = self.param("conv_taps", LFM2_TAPS_INIT,
                          (self.conv_kernel, d))
        w_out = self.param("conv_out", _INIT, (d, d))
        with jax.named_scope("conv"):
            bcz = rms_norm(x, norm, self.norm_eps) @ w_in
        with jax.named_scope("conv_mix"):
            gate_in, gate_out, z = jnp.split(bcz, 3, axis=-1)
            y = gate_out * causal_depthwise_conv(gate_in * z, taps)
        with jax.named_scope("conv"):
            return x + y @ w_out

    def attention(self, x, norm):
        d, hq, hkv, hd = (self.d_model, self.n_heads, self.kv_heads,
                          self.head_dim)
        attn = self.attn_fn if self.attn_fn is not None else default_attn()
        inv_freq = plain_inv_freq(hd, self.rope_theta)
        ones = nn.initializers.ones
        with jax.named_scope("attn"):
            return x + grouped_attention(
                rms_norm(x, norm, self.norm_eps),
                self.param("wq", _INIT, (d, hq * hd)),
                self.param("wk", _INIT, (d, hkv * hd)),
                self.param("wv", _INIT, (d, hkv * hd)),
                self.param("wo", _INIT, (hq * hd, d)),
                heads=hq, kv_heads=hkv, head_dim=hd, inv_freq=inv_freq,
                attn=attn, qk_norm=(self.param("q_norm", ones, (hd,)),
                                    self.param("k_norm", ones, (hd,)),
                                    self.norm_eps))

    def sparse_experts(self, x, norm):
        d, e, f = self.d_model, self.n_experts, self.expert_width
        held = self.experts_held or e
        router = self.param("router", _INIT, (d, e))
        # the selection bias: part of the vector, seeded away from zero
        # so that the selection it changes is exercised; no gradient
        # reaches it.  The rule that would move it is
        # ``parallel/moe.py`` ``balance_step`` (``shared_sparse_experts``
        # applies it where a block has a ``bias_rate``); this block's
        # rate is 0, because its configuration publishes none
        bias = self.param("router_bias", _INIT, (e,))
        wg = self.param("experts_gate", _INIT, (held, d, f))
        wu = self.param("experts_up", _INIT, (held, d, f))
        wd = self.param("experts_down", _INIT, (held, f, d))

        # recomputed in the backward pass, as Mellum's and for its
        # reason (the dispatch itself keeps only its arguments and
        # walks its windows again)
        @jax.checkpoint
        def sparse(x, norm, router, bias, wg, wu, wd):
            def route(logits):
                scores = jax.nn.sigmoid(logits)
                weights, chosen = moe.route_top_k(
                    scores, self.experts_per_tok, renormalise=True,
                    bias=bias, eps=LFM2_ROUTE_EPS, scale=self.route_scale)
                return weights, chosen, (
                    moe.bias_flips_share(scores, chosen),)

            return sparse_mlp(
                x, norm, router, (wg, wu, wd), route=route,
                eps=self.norm_eps, n_experts=e, first=self.experts_first,
                held=held)

        y, (load, share, compact, flips) = sparse(x, norm, router, bias,
                                                  wg, wu, wd)
        # telemetry, read only where the caller makes ``intermediates``
        # mutable (lm/model.py stats)
        self.sow("intermediates", "moe_load", load)
        self.sow("intermediates", "moe_held", share)
        self.sow("intermediates", "moe_compact", compact)
        self.sow("intermediates", "moe_flips", flips)
        return y


class Lfm2Decoder(nn.Module):
    """Causal LM of :class:`Lfm2Block` layers: a token table (at
    :data:`MELLUM_EMBED_INIT`'s scale, for the same reason: a share of
    the experts is held), the blocks, a final RMSNorm and an untied
    head; returns log-probabilities like the other decoders.  Layer
    ``i``'s token mixer is ``layer_types[i]`` and its MLP is dense iff
    ``i < dense_layers``: the layers held here are a run of the
    published model's, so both are given for that run."""

    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    kv_heads: int = 2
    head_dim: int = 16
    layer_types: tuple = ("conv", "full_attention", "conv", "conv")
    dense_layers: int = 1
    dense_width: int = 128
    n_experts: int = 8
    experts_per_tok: int = 2
    expert_width: int = 32
    experts_first: int = 0
    experts_held: int = 0
    conv_kernel: int = 3
    route_scale: float = 1.0
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, tokens: jnp.ndarray) -> jnp.ndarray:
        d = self.d_model
        with jax.named_scope("embed"):
            x = self.param("embed", MELLUM_EMBED_INIT,
                           (self.vocab, d))[tokens]
        for i, mixer in enumerate(self.layer_types):
            x = Lfm2Block(
                d_model=d, mixer=mixer, sparse=i >= self.dense_layers,
                n_heads=self.n_heads, kv_heads=self.kv_heads,
                head_dim=self.head_dim, dense_width=self.dense_width,
                n_experts=self.n_experts,
                experts_per_tok=self.experts_per_tok,
                expert_width=self.expert_width,
                experts_first=self.experts_first,
                experts_held=self.experts_held,
                conv_kernel=self.conv_kernel, route_scale=self.route_scale,
                rope_theta=self.rope_theta, norm_eps=self.norm_eps,
                attn_fn=self.attn_fn,
            )(x)
        with jax.named_scope("head_loss"):  # lm/model.py's NLL joins it
            x = rms_norm(x, self.param("final_norm", nn.initializers.ones,
                                       (d,)), self.norm_eps)
            logits = x @ self.param("head", _INIT, (d, self.vocab))
            return nn.log_softmax(logits)


# ---------------------------------------------------------------------------
# The looped block (Ouro, ByteDance; ``model_type`` ``ouro``; the
# configuration's keys are those of its ``config.json``).  The whole
# stack of layers is applied ``loop_steps`` times **with the same
# weights**: a pass ends in the one final RMSNorm, and its output goes to
# the head, to an **exit gate** and on into the next pass.  Every layer
# is a **sandwich**: an RMSNorm before the sublayer and another on its
# output, ``u = u + rms(op(rms(u)))``, for the attention (as many KV as
# query heads, rotary, no query/key norm, no bias) and for the SiLU-gated
# MLP alike.  The block closes its own loss: each pass's next-token NLL
# weighted by the probability of leaving at that pass, less ``exit_beta``
# times the entropy of that distribution (:func:`exit_distribution`).
# The passes are one ``lax.scan`` body in the program, the parameters
# closed over, so the step is compiled once and not ``loop_steps`` times
# and the backward pass sums a weight's gradient over its applications
# inside a ``while``.  The plain float32 reference it is held to is
# ``chipbench/reference/ouro_plain.py``, which shares no code with this
# file (tests/test_ouro.py).
# ---------------------------------------------------------------------------


def exit_distribution(lam: jnp.ndarray) -> jnp.ndarray:
    """The probability of leaving at each of ``R`` passes from the
    ``R - 1`` gates ``lam (R - 1, ...)``, in float32: ``p_1 = lam_1``,
    ``p_t = lam_t prod_{j<t} (1 - lam_j)``, and the last pass takes what
    is left, ``p_R = prod_{j<R} (1 - lam_j)``; ``(R, ...)``, summing to
    one over the passes.  No gate: ``p = (1)``."""
    ones = jnp.ones((1,) + lam.shape[1:], lam.dtype)
    # stay[t]: past the gates of the passes before pass t + 1
    stay = jnp.concatenate([ones, jnp.cumprod(1.0 - lam, axis=0)], axis=0)
    return jnp.concatenate([lam * stay[:-1], stay[-1:]], axis=0)


def exit_entropy(p: jnp.ndarray) -> jnp.ndarray:
    """``H(p) = -sum_t p_t log p_t`` over the leading axis.  A gate that
    saturates gives a ``p_t`` of exactly 0, whose term and whose
    gradient are taken as 0 (``xlogy``'s gradient there is ``0 x
    -inf``)."""
    live = p > 0
    return -jnp.sum(jnp.where(live, p * jnp.log(jnp.where(live, p, 1.0)),
                              0.0), axis=0)


# What :class:`OuroDecoder`'s checkpoints keep for the backward pass
# beside their inputs, by ``checkpoint_name``: the flash rule's two, the
# MLP's output of a layer application and the head's row log-sum-exp of
# a pass (the decoder's docstring says what each costs and saves).  A
# constant of the module: what a decoder can afford to keep follows from
# its own sizes.
MLP_OUT, HEAD_LSE = "mlp_out", "head_lse"
OURO_KEPT = (FLASH_OUT, FLASH_LSE, MLP_OUT, HEAD_LSE)


@jax.custom_vjp
def row_lse(z: jnp.ndarray) -> jnp.ndarray:
    """``logsumexp`` over the last axis, with a backward rule that reads
    the logits and the result alone (``g exp(z - lse)``): a checkpoint
    that keeps the result by name computes the logits again and not the
    reductions over them."""
    return jax.nn.logsumexp(z, axis=-1)


def _row_lse_fwd(z):
    lse = checkpoint_name(jax.nn.logsumexp(z, axis=-1), HEAD_LSE)
    return lse, (z, lse)


def _row_lse_bwd(res, g):
    z, lse = res
    return ((g[..., None] * jnp.exp(z - lse[..., None])).astype(z.dtype),)


row_lse.defvjp(_row_lse_fwd, _row_lse_bwd)


class OuroBlock(nn.Module):
    """One layer's parameters, and the layer as a pure function of
    them (:meth:`apply_weights`): the decoder applies it inside
    ``lax.scan`` and ``jax.checkpoint``, where no module may be
    called."""

    d_model: int
    n_heads: int
    kv_heads: int
    head_dim: int
    dense_width: int
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-6
    attn_fn: Optional[AttnFn] = None

    def setup(self):
        d, f = self.d_model, self.dense_width
        hq, hkv = self.n_heads * self.head_dim, self.kv_heads * self.head_dim
        ones = nn.initializers.ones
        self.weights = {name: self.param(name, init, shape) for
                        name, init, shape in (
            ("attn_norm", ones, (d,)), ("wq", _INIT, (d, hq)),
            ("wk", _INIT, (d, hkv)), ("wv", _INIT, (d, hkv)),
            ("wo", _INIT, (hq, d)), ("attn_out_norm", ones, (d,)),
            ("mlp_norm", ones, (d,)), ("w_gate", _INIT, (d, f)),
            ("w_up", _INIT, (d, f)), ("w_down", _INIT, (f, d)),
            ("mlp_out_norm", ones, (d,)))}

    def apply_weights(self, u: jnp.ndarray, p: dict) -> jnp.ndarray:
        """The layer on the stream ``u (B, L, d)`` with the weights
        ``p``; pure in both.  Products one bf16 pass on a TPU (the
        scores are O(1) without a query/key norm, as Mellum's), norms
        in float32."""
        eps, hd = self.norm_eps, self.head_dim
        attn = self.attn_fn if self.attn_fn is not None else default_attn()
        with jax.named_scope("attn"):
            a = grouped_attention(
                rms_norm(u, p["attn_norm"], eps), p["wq"], p["wk"], p["wv"],
                p["wo"], heads=self.n_heads, kv_heads=self.kv_heads,
                head_dim=hd, attn=attn,
                inv_freq=plain_inv_freq(hd, self.rope_theta))
            u = u + rms_norm(a, p["attn_out_norm"], eps)
        with jax.named_scope("mlp"):
            b = rms_norm(u, p["mlp_norm"], eps)
            m = (jax.nn.silu(b @ p["w_gate"]) * (b @ p["w_up"])) @ p["w_down"]
            return u + rms_norm(checkpoint_name(m, MLP_OUT),
                                p["mlp_out_norm"], eps)


class OuroDecoder(nn.Module):
    """Causal LM of :class:`OuroBlock` layers run ``loop_steps`` times
    with the same weights: a token table, the passes, and at the end of
    every pass the one final RMSNorm, the one untied head and the exit
    gate (``Linear(d_model -> 1)`` and a sigmoid; the last pass has
    none: it takes what is left).  Unlike the other decoders it is
    called with the targets and returns its own loss, a scalar, with
    the loop's statistics (``lm/model.py`` closes over it):

    - ``loss``: the mean over positions of ``sum_t p_t nll^t - exit_beta
      H(p)``, ``p`` the exit distribution and ``nll^t`` pass ``t``'s
      next-token negative log-likelihood;
    - ``loop_exit_step_mean``: the mean of ``sum_t t p_t`` (``loop_steps``
      when every position runs all passes);
    - ``loop_loss_drop``: mean ``nll^1`` less mean ``nll^R``, in nats:
      what the later passes buy;
    - ``loop_exit_entropy``: the mean of ``H(p)``, at most ``ln R``.

    ``exit_bias`` is what the gate's bias is seeded at (its weight at
    std 0.02): 0 starts every gate at a half, ``p = (1/2, 1/4, 1/8,
    1/8)`` of four passes; a negative one starts the loop nearer to
    running every pass, with a gate that moves slower by ``lam (1 -
    lam)``.  Under plain SGD the gate's logit moves by ``lr * |h|^2 =
    lr * d_model`` a unit of difference between the passes' losses, so a
    gate seeded at a half saturates onto the first pass before the later
    ones have learnt anything (PERF.md section 6, PR 36).

    ``scan`` and ``remat`` are the tests' alone: no caller in the
    program sets them.  ``scan`` False unrolls the passes and ``remat``
    False keeps every activation for the backward pass, the twin that
    tests/test_ouro.py holds the scanned, recomputing decoder to (the
    same numbers to float32 rounding), at a memory and compile time
    that the cell's sizes do not have.  With ``remat`` each layer
    application and each pass's norm, head and loss is computed again in
    the backward pass, but for what is dear to compute twice and cheap
    to hold.  Kept are the layers' inputs, the passes' outputs and, by
    name (:data:`OURO_KEPT`, a ``save_only_these_names`` policy on every
    checkpoint; float32 as computed, on the device), of a layer
    application at ``T`` positions

    - the flash kernel's output and row log-sum-exp, the custom VJP's
      own residuals (``ops/flash_attention.py``: ``T x heads x
      head_dim`` and ``T x heads`` floats), so the backward pass calls
      no forward kernel: a third of the step's flash time;
    - the MLP's output before its out-norm (``T x d_model``), so the
      down product is not run again;

    and of a pass's end the head's row log-sum-exp (``T`` floats,
    :func:`row_lse`), so the backward pass runs the head's product again
    but no reduction over ``(positions, vocab)``, and never holds such
    an array of an earlier pass.  Not kept: the attention branch's
    output (the ``wo`` product it saves is a quarter of the down one,
    and the compiled step was slower with it than without); ``q``, ``k``
    and ``v`` (three times the flash output's bytes) and the MLP's gate
    and up products (``2 T x dense_width``, five times), which at the
    cell's sizes do not fit beside the step: the backward pass makes
    them again from the layer's input.  What each is worth on the chip:
    PERF.md section 5, after PR 37."""

    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    kv_heads: int = 4
    head_dim: int = 16
    n_layers: int = 2
    dense_width: int = 128
    loop_steps: int = 4
    exit_beta: float = 0.1
    exit_bias: float = 0.0
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-6
    attn_fn: Optional[AttnFn] = None
    scan: bool = True
    remat: bool = True

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, targets: jnp.ndarray):
        d, eps, steps = self.d_model, self.norm_eps, self.loop_steps
        embed = self.param("embed", _INIT, (self.vocab, d))
        blocks = [OuroBlock(
            d_model=d, n_heads=self.n_heads, kv_heads=self.kv_heads,
            head_dim=self.head_dim, dense_width=self.dense_width,
            rope_theta=self.rope_theta, norm_eps=eps, attn_fn=self.attn_fn)
            for _ in range(self.n_layers)]
        layers = [(block.apply_weights, block.weights) for block in blocks]
        final_norm = self.param("final_norm", nn.initializers.ones, (d,))
        head = self.param("head", _INIT, (d, self.vocab))
        # named to sort last among the leaves: the bias's one element is
        # then the flat vector's tail, and every other leaf starts on a
        # whole lane as in the other blocks
        gate_w = self.param("loop_gate", _INIT, (d,))
        gate_b = self.param("loop_gate_bias",
                            nn.initializers.constant(self.exit_bias), (1,))
        # a layer's input and a pass's outputs, and the named values
        # the backward pass would pay most to make again (the docstring)
        policy = jax.checkpoint_policies.save_only_these_names(*OURO_KEPT)
        keep = (partial(jax.checkpoint, policy=policy) if self.remat
                else (lambda fn: fn))

        def pass_end(u, final_norm, head):
            with jax.named_scope("head_loss"):
                h = rms_norm(u, final_norm, eps)
                z = h @ head
                nll = row_lse(z) - jnp.take_along_axis(
                    z, targets[..., None], axis=-1)[..., 0]
            return h, nll

        def one_pass(h, _):
            for layer, weights in layers:
                h = keep(layer)(h, weights)
            h, nll = keep(pass_end)(h, final_norm, head)
            return h, (h, nll)

        with jax.named_scope("embed"):
            h = embed[tokens]
        if self.scan:
            _, (ends, nll) = jax.lax.scan(one_pass, h, None, length=steps)
        else:
            rows = []
            for _ in range(steps):
                h, row = one_pass(h, None)
                rows.append(row)
            ends, nll = (jnp.stack(x) for x in zip(*rows))
        with jax.named_scope("exit_gate"):
            # The gate reads the passes' outputs after the loop, which
            # keeps them for the backward pass anyway: its product is
            # then this scope's own operation and not a corner of the
            # head's fusions.  2048 multiply-adds a position and pass,
            # elementwise in float32, no MXU pass; the last pass has no
            # gate.
            lam = jax.nn.sigmoid(jnp.sum(ends[:-1] * gate_w, axis=-1)
                                 + gate_b)               # (R - 1, B, L)
            p = exit_distribution(lam)                   # (R, B, L)
            entropy = exit_entropy(p)
            loss = jnp.mean(jnp.sum(p * nll, axis=0)
                            - self.exit_beta * entropy)
            at = jnp.arange(1, steps + 1, dtype=jnp.float32)
            stats = {
                "loop_exit_step_mean": jnp.mean(
                    jnp.tensordot(at, p, axes=1)),
                "loop_loss_drop": jnp.mean(nll[0]) - jnp.mean(nll[-1]),
                "loop_exit_entropy": jnp.mean(entropy)}
        return loss, stats

    def kept_residual_bytes(self, positions: int, flash: bool) -> int:
        """The bytes :data:`OURO_KEPT` holds from the forward pass to
        the backward one in a step over ``positions`` (batch times
        sequence), from the named values' shapes, float32 as the stream
        is.  The flash rule's two exist only where ``attn_fn`` is the
        kernel (``flash``)."""
        if not self.remat:
            return 0
        layer = self.d_model                                  # mlp_out
        if flash:                                # flash_out, flash_lse
            layer += self.n_heads * (self.head_dim + 1)
        # those a layer application, and head_lse's one float a pass
        return 4 * positions * self.loop_steps * (self.n_layers * layer + 1)


# ---------------------------------------------------------------------------
# The latent-attention block (JoyAI-LLM-Flash, JD; ``model_type``
# ``joyai_llm_flash``, which follows DeepSeek-V3's equations key for key;
# the configuration's keys are those of its ``config.json``).  Attention
# is **multi-head latent attention**: queries and keys/values go through
# low-rank products with an RMSNorm between them (``q_rank``,
# ``kv_rank``); a head's query and key have a part without positions
# (``qk_nope``) and a rotary part (``qk_rope``, interleaved pairs), and
# the rotary key is **one head shared by all query heads**; the values
# are ``v_head`` wide, narrower than the ``qk_nope + qk_rope`` of the
# keys, which is what ``ops/flash_attention.py``'s two widths are for
# (each goes to the kernels at the width it has, 192 and 128: no lane is
# padded in front of a call; what is left round the kernels is this
# file's: :func:`default_attn`'s transposes to heads-major and the join
# of the rotary and plain parts below, ROADMAP S13 (a)).
# The MLP is dense on the leading layers and else a sigmoid router with
# a selection bias over all ``n_experts`` (LFM2's ``noaux_tc``), this
# chip's share of the routed experts, **and a shared expert that every
# token takes**, added to the routed sum.  A **multi-token-prediction
# module** after the last layer (one more sparse layer on the projected
# pair of the next token's embedding and the stack's last hidden state)
# predicts the token after next through the same table and head, and the
# decoder closes its own loss over both heads.  The plain float32
# reference it is held to is ``chipbench/reference/joyai_plain.py``,
# which shares no code with this file (tests/test_joyai.py).
# ---------------------------------------------------------------------------

#: ``norm_topk_prob``'s guard against an all-zero top-k, as published
#: (DeepSeek-V3's ``1e-20``: nothing in float32 beside a sum of sigmoids)
JOYAI_ROUTE_EPS = 1e-20
# What :class:`JoyaiBlock`'s attention checkpoint keeps beside its
# input: the flash rule's own two residuals, so the backward pass runs
# the low-rank products again and not the forward kernel.
JOYAI_ATTN_KEPT = (FLASH_OUT, FLASH_LSE)
#: what a sparse layer's branch counts (``sparse_mlp``'s statistics and
#: the route's own), by the names the step's telemetry, the ``round``
#: span and the gauges ``mpit_<name>`` give them (``lm/model.py``
#: ``MOE_STATS`` has the same for the blocks that ``sow``)
JOYAI_MOE_STATS = ("moe_load_max_over_mean", "moe_held_rows_share",
                   "moe_compact_share", "moe_bias_flips_share")
#: what a sparse layer whose bias moves by the balancing rule counts
#: beside them: the mean of ``|bias|`` over the router's experts as the
#: pass found it, and the share of the experts whose count is off the
#: mean, that is whose step has a sign (1.0 unless a count sits on it)
BIAS_RULE_STATS = ("moe_bias_abs_mean", "moe_bias_step_nonzero_share")


def rope_interleaved(x: jnp.ndarray, inv_freq: np.ndarray) -> jnp.ndarray:
    """Rotary embedding over **interleaved** pairs ``(2j, 2j + 1)`` of
    ``x (B, L, H, D)`` (``rope_interleave``): the pairs are gathered
    into halves, evens then odds, and rotated as :func:`rope_by` rotates
    halves.  The result stays in that order, the same permutation of a
    head's rotary dimensions on queries and keys alike, which no score
    ``q . k`` sees."""
    pairs = x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    return rope_by(jnp.concatenate([pairs[..., 0], pairs[..., 1]], axis=-1),
                   inv_freq)


def latent_attention(x: jnp.ndarray, p: dict, *, heads: int, qk_nope: int,
                     qk_rope: int, v_head: int,
                     inv_freq: Optional[np.ndarray], eps: float,
                     attn: AttnFn) -> jnp.ndarray:
    """Multi-head latent attention on the stream ``x (B, L, d)`` with
    the weights ``p``, projected back to ``(B, L, d)``; pure in both.
    Every product one bf16 pass on a TPU, as Mellum's: the inner norms
    hold the scores at O(1) (PERF.md section 6, PR 38).  **No query
    latent** where ``p`` has ``wq`` and not the pair ``wq_a``, ``wq_b``
    with the norm between them (``q_lora_rank`` null: one product).
    **No positions** where ``inv_freq`` is None (``mla_use_nope``): the
    ``qk_rope`` dimensions of query and key are kept, the key's still
    one head repeated into every head's, and nothing is rotated."""
    b, l, _ = x.shape

    def turned(part):
        return part if inv_freq is None else rope_interleaved(part, inv_freq)

    with jax.named_scope("mla_proj"):
        h = rms_norm(x, p["attn_norm"], eps)
        q = h @ p["wq"] if "wq" in p else rms_norm(
            h @ p["wq_a"], p["q_a_norm"], eps) @ p["wq_b"]
        q = q.reshape(b, l, heads, qk_nope + qk_rope)
        kv_a = h @ p["wkv_a"]                       # (B, L, kv_rank + rope)
        kv_rank = kv_a.shape[-1] - qk_rope
        kv = rms_norm(kv_a[..., :kv_rank], p["kv_a_norm"], eps) @ p["wkv_b"]
        kv = kv.reshape(b, l, heads, qk_nope + v_head)
        # the rotary key: one head, used by every query head
        k_rope = turned(kv_a[..., None, kv_rank:])
        q = jnp.concatenate(
            [q[..., :qk_nope], turned(q[..., qk_nope:])], axis=-1)
        k = jnp.concatenate(
            [kv[..., :qk_nope],
             jnp.broadcast_to(k_rope, (b, l, heads, qk_rope))], axis=-1)
        v = kv[..., qk_nope:]
    with jax.named_scope("attn"):
        return attn(q, k, v).reshape(b, l, heads * v_head) @ p["wo"]


def latent_mixer(block, x):
    """The latent attention of ``block`` (:class:`JoyaiBlock`,
    :class:`KimiBlock`) on the stream ``x``: its parameters made in the
    block's own scope, the query's low-rank pair where ``q_rank`` is not
    0 and one product where it is, rotary positions where ``rope_theta``
    is not 0.

    Kept for the backward pass: the layer's input and the flash rule's
    two.  q and k (T x heads x 192 floats each), v and the latents are
    made again from the input: five products of which the largest is
    1536 x 6144, a twentieth of the layer's attention kernels at 8192
    positions, for 0.8 GB a layer that the step does not have (PERF.md
    section 4, the compile's row)."""
    d, hq = block.d_model, block.n_heads
    qk, ones = block.qk_nope + block.qk_rope, nn.initializers.ones
    query = ((("wq_a", _INIT, (d, block.q_rank)),
              ("q_a_norm", ones, (block.q_rank,)),
              ("wq_b", _INIT, (block.q_rank, hq * qk)))
             if block.q_rank else (("wq", _INIT, (d, hq * qk)),))
    p = {name: block.param(name, init, shape) for name, init, shape in (
        ("attn_norm", ones, (d,)), *query,
        ("wkv_a", _INIT, (d, block.kv_rank + block.qk_rope)),
        ("kv_a_norm", ones, (block.kv_rank,)),
        ("wkv_b", _INIT, (block.kv_rank,
                          hq * (block.qk_nope + block.v_head))),
        ("wo", _INIT, (hq * block.v_head, d)))}
    attend = partial(
        latent_attention, heads=hq, qk_nope=block.qk_nope,
        qk_rope=block.qk_rope, v_head=block.v_head, eps=block.norm_eps,
        inv_freq=plain_inv_freq(block.qk_rope, block.rope_theta)
        if block.rope_theta else None,
        attn=block.attn_fn if block.attn_fn is not None else default_attn())
    return jax.checkpoint(
        attend, policy=jax.checkpoint_policies.save_only_these_names(
            *JOYAI_ATTN_KEPT))(x, p)


def swiglu(h: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
           w_down: jnp.ndarray) -> jnp.ndarray:
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def relu2_mlp(h: jnp.ndarray, w_up: jnp.ndarray,
              w_down: jnp.ndarray) -> jnp.ndarray:
    return jnp.square(jax.nn.relu(h @ w_up)) @ w_down


#: an expert's form by ``expert_act``: its matrices' names in the order
#: its function takes them, the routed experts' grouped function and the
#: shared expert's dense one
EXPERT_FORMS = {
    "swiglu": (("gate", "up", "down"), moe.swiglu_experts, swiglu),
    "relu2": (("up", "down"), moe.relu2_experts, relu2_mlp),
}


def shared_sparse_experts(block, x, norm):
    """The sparse MLP of ``block`` with a shared expert
    (:class:`JoyaiBlock`, :class:`KimiBlock`): its parameters made in the
    block's own scope, ``(output, statistics in
    :data:`JOYAI_MOE_STATS`' order)``, then :data:`BIAS_RULE_STATS`'
    where the block has a ``bias_rate`` over 0.  A block with an
    ``expert_act`` of ``relu2`` (:class:`NemotronBlock`) has experts of
    two matrices and no gate (:data:`EXPERT_FORMS`), and one with a
    ``shared_width`` a shared expert of that inner width, not
    ``shared_experts`` times the routed experts'.  A block with a
    ``router_act`` of ``softmax`` (:class:`Qwen3NextBlock`) scores with a
    softmax over all the experts, renormalised over the chosen, **with
    no selection bias**: it has no ``router_bias`` leaf and counts
    :data:`JOYAI_MOE_STATS`' first three.  One with ``shared_gated``
    multiplies the shared expert's output by **a gate of its own**,
    ``sigmoid(h w_s)``, a scalar a token (the leaf
    ``shared_expert_gate (d, 1)``), and the statistics end in the
    gate's mean."""
    d, e, f = block.d_model, block.n_experts, block.expert_width
    held = block.experts_held or e
    shared = getattr(block, "shared_width", 0) or block.shared_experts * f
    names, routed_fn, shared_fn = EXPERT_FORMS[
        getattr(block, "expert_act", "swiglu")]
    router = block.param("router", _INIT, (d, e))
    # the selection bias (``noaux_tc``'s ``e_score_correction_bias``):
    # as LFM2's, part of the vector, seeded away from zero and reached
    # by no gradient.  Where the block has a ``bias_rate`` over 0 (a
    # configuration that publishes the balancing's rate: Trinity's
    # ``load_balance_coeff``) the rule of ``parallel/moe.py``
    # ``balance_step`` moves it from this pass's own counts: the bias's
    # slot of the flat gradient carries minus the step (``carry_step``)
    # and every optimizer moves the vector's plain ranges by exactly
    # that (``models/flat.py`` ``plain_ranges``).  JoyAI's, Kimi's and
    # Nemotron's configurations publish no rate: theirs is 0, nothing
    # moves the bias and their programs are what they were
    soft = getattr(block, "router_act", "sigmoid") == "softmax"
    bias = None if soft else block.param("router_bias", _INIT, (e,))
    rate = float(getattr(block, "bias_rate", 0.0))

    def shape(name, width):
        return (width, d) if name == "down" else (d, width)

    routed = tuple(block.param(f"experts_{name}", _INIT,
                               (held,) + shape(name, f)) for name in names)
    shared_w = tuple(block.param(f"shared_{name}", _INIT,
                                 shape(name, shared))
                     for name in names) if shared else ()
    gate_w = block.param("shared_expert_gate", _INIT, (d, 1)) \
        if shared and getattr(block, "shared_gated", False) else None

    # recomputed in the backward pass, as Mellum's and LFM2's and for
    # their reason; the shared expert with it (three products 768
    # wide: a hundredth of the step)
    @jax.checkpoint
    def branch(x, norm, router, bias, routed, shared_w, gate_w):
        def route(logits):
            if soft:
                return (*moe.route_top_k(
                    jax.nn.softmax(logits, axis=-1), block.experts_per_tok,
                    renormalise=True), ())
            scores = jax.nn.sigmoid(logits)
            weights, chosen = moe.route_top_k(
                scores, block.experts_per_tok, renormalise=True,
                bias=bias, eps=JOYAI_ROUTE_EPS, scale=block.route_scale)
            extra = (moe.bias_flips_share(scores, chosen),)
            if rate > 0:
                with jax.named_scope("bias_rule"):
                    # this pass's counts over all the router's experts,
                    # held or not; the selection above used the bias
                    # before the step
                    counts = moe.expert_counts(chosen, e)
                    weights = moe.carry_step(weights, bias, counts, rate)
                    off_mean = jnp.sum(counts) != e * counts
                    extra += (jnp.mean(jnp.abs(bias)),
                              jnp.mean(off_mean.astype(jnp.float32)))
            return weights, chosen, extra

        y, stats = sparse_mlp(
            x, norm, router, routed, route=route, eps=block.norm_eps,
            n_experts=e, first=block.experts_first, held=held,
            expert_fn=routed_fn)
        if shared_w:
            with jax.named_scope("shared_expert"):
                # every token, whole on every share: counted once
                h = rms_norm(x, norm, block.norm_eps)
                out = shared_fn(h, *shared_w)
                if gate_w is not None:
                    gate = jax.nn.sigmoid(h @ gate_w)        # (B, L, 1)
                    out = gate * out
                    stats += (jnp.mean(jax.lax.stop_gradient(gate)),)
                y = y + out
        return y, stats

    return branch(x, norm, router, bias, routed, shared_w, gate_w)


class JoyaiBlock(nn.Module):
    d_model: int
    n_heads: int
    q_rank: int
    kv_rank: int
    qk_nope: int
    qk_rope: int
    v_head: int
    sparse: bool             # the MLP: routed and shared experts, else dense
    dense_width: int
    n_experts: int           # the router's width: every expert there is
    experts_per_tok: int
    expert_width: int
    experts_first: int = 0   # the share held here: a contiguous range
    experts_held: int = 0    # 0: all of them
    shared_experts: int = 1  # shared experts, each ``expert_width`` wide
    route_scale: float = 1.0
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray):
        """``(the stream after the layer, the sparse branch's statistics
        in :data:`JOYAI_MOE_STATS`' order)``; a dense layer has none,
        ``()``."""
        d, eps = self.d_model, self.norm_eps
        ones = nn.initializers.ones
        x = x + latent_mixer(self, x)

        mlp_norm = self.param("mlp_norm", ones, (d,))
        if not self.sparse:
            with jax.named_scope("mlp"):
                return x + swiglu(
                    rms_norm(x, mlp_norm, eps),
                    self.param("w_gate", _INIT, (d, self.dense_width)),
                    self.param("w_up", _INIT, (d, self.dense_width)),
                    self.param("w_down", _INIT, (self.dense_width, d))), ()
        y, stats = shared_sparse_experts(self, x, mlp_norm)
        return x + y, stats


class JoyaiDecoder(nn.Module):
    """Causal LM of :class:`JoyaiBlock` layers with a multi-token-
    prediction module: a token table (at :data:`MELLUM_EMBED_INIT`'s
    scale, for its reason: a share of the experts is held), the layers
    (layer ``i``'s MLP is dense iff ``i < dense_layers``), a final
    RMSNorm and an untied head; and, where ``mtp_layers`` is 1, the MTP
    module on the stack's last hidden state ``x_L`` before the final
    norm: ``z_i = [RMSNorm(Emb(t_{i+1})) | RMSNorm(x_L,i)] W_eh``
    (embedding first), one sparse layer on ``z``, a final RMSNorm of its
    own, **the main model's table and head**; it predicts ``t_{i+2}`` at
    position ``i``.  Like :class:`OuroDecoder` it is called with the
    targets and returns its own loss with its statistics
    (``lm/model.py`` closes over it):

    - ``loss``: ``NLL_main + mtp_weight * NLL_mtp``, the main head's
      mean next-token NLL and the MTP head's mean NLL of the token after
      next over the positions that have one (every row's last position
      has none and is masked out of the mean);
    - ``lm_main_nll``, ``lm_mtp_nll``: the two terms, unweighted;
    - the routing counters of every sparse layer, the MTP module's
      last, under ``lm/model.py`` ``MOE_STATS``' names.

    Each head's product, norm and loss is under ``jax.checkpoint``,
    keeping the rows' log-sum-exp by name (:func:`row_lse`), as
    :class:`OuroDecoder`'s: the backward pass runs the head's product
    again and never holds a ``(positions, vocab)`` array of the other
    head."""

    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    q_rank: int = 48
    kv_rank: int = 32
    qk_nope: int = 16
    qk_rope: int = 8
    v_head: int = 16
    n_layers: int = 2
    dense_layers: int = 1
    dense_width: int = 128
    n_experts: int = 8
    experts_per_tok: int = 2
    expert_width: int = 32
    experts_first: int = 0
    experts_held: int = 0
    shared_experts: int = 1
    route_scale: float = 1.0
    mtp_layers: int = 1
    mtp_weight: float = 0.3
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, targets: jnp.ndarray):
        if self.mtp_layers not in (0, 1):
            raise ValueError(f"mtp_layers {self.mtp_layers}: 0 or 1")
        d, eps = self.d_model, self.norm_eps
        ones = nn.initializers.ones
        embed = self.param("embed", MELLUM_EMBED_INIT, (self.vocab, d))
        head = self.param("head", _INIT, (d, self.vocab))

        def block(sparse, name):
            return JoyaiBlock(
                d_model=d, n_heads=self.n_heads, q_rank=self.q_rank,
                kv_rank=self.kv_rank, qk_nope=self.qk_nope,
                qk_rope=self.qk_rope, v_head=self.v_head, sparse=sparse,
                dense_width=self.dense_width, n_experts=self.n_experts,
                experts_per_tok=self.experts_per_tok,
                expert_width=self.expert_width,
                experts_first=self.experts_first,
                experts_held=self.experts_held,
                shared_experts=self.shared_experts,
                route_scale=self.route_scale, rope_theta=self.rope_theta,
                norm_eps=eps, attn_fn=self.attn_fn, name=name)

        @partial(jax.checkpoint,
                 policy=jax.checkpoint_policies.save_only_these_names(
                     HEAD_LSE))
        def head_nll(u, norm, head, targets):
            with jax.named_scope("head_loss"):
                z = rms_norm(u, norm, eps) @ head
                return row_lse(z) - jnp.take_along_axis(
                    z, targets[..., None], axis=-1)[..., 0]

        routing = []  # a sparse layer's statistics each, in order
        with jax.named_scope("embed"):
            x = embed[tokens]
        for i in range(self.n_layers):
            x, counted = block(i >= self.dense_layers, f"JoyaiBlock_{i}")(x)
            routing += [counted] if counted else []
        main_nll = head_nll(
            x, self.param("final_norm", ones, (d,)), head, targets)
        with jax.named_scope("head_loss"):
            main = jnp.mean(main_nll)
        stats = {"lm_main_nll": main[None]}
        loss = main
        if self.mtp_layers:
            with jax.named_scope("mtp"):
                # position i: the next token's embedding beside the
                # stack's hidden state; its target the token after next
                z = jnp.concatenate(
                    [rms_norm(embed[targets],
                              self.param("mtp_embed_norm", ones, (d,)), eps),
                     rms_norm(x, self.param("mtp_hidden_norm", ones, (d,)),
                              eps)], axis=-1
                ) @ self.param("mtp_proj", _INIT, (2 * d, d))
                z, counted = block(True, "mtp_block")(z)
                routing.append(counted)
                after_next = jnp.roll(targets, -1, axis=1)
                nll = head_nll(z, self.param("mtp_final_norm", ones, (d,)),
                               head, after_next)
                # a row's last position has no token after next
                mtp = jnp.mean(nll[:, :-1])
            loss = main + self.mtp_weight * mtp
            stats["lm_mtp_nll"] = mtp[None]
        if routing:
            stats.update(zip(JOYAI_MOE_STATS, map(jnp.stack, zip(*routing))))
        return loss, stats


# ---------------------------------------------------------------------------
# The linear-attention hybrid (Kimi-Linear, Moonshot; ``model_type``
# ``kimi_linear``; the configuration's keys are those of its
# ``config.json``, the equations the Kimi Linear report's,
# arXiv:2510.26692).  A layer's token mixer is one of two
# (``layer_types``).  ``kda``, **Kimi Delta Attention**: a gated delta
# rule whose state, a ``kda_head_dim x kda_head_dim`` matrix a head, is
# carried along the sequence, decayed **per key channel** and corrected
# by a rank-one term at every position (``ops/delta_rule.py`` has the
# recurrence and its chunked form); queries, keys and values each go
# through a depthwise causal convolution of ``conv_kernel`` taps and a
# SiLU, queries and keys are L2-normalised a head, the log-decay and the
# output gate are low-rank products of the layer's input, and the
# result is RMSNormed a head and gated before ``W_o``.
# ``full_attention``: JoyAI's latent attention **without a query
# latent** (``q_rank`` 0) **and without positions** (``rope_theta`` 0:
# the hybrid leaves order to the KDA layers).  The MLP is dense on the
# leading layers and else JoyAI's: a sigmoid router with a selection
# bias over all ``n_experts``, this chip's share of the routed experts
# and a shared expert.  The plain float32 reference it is held to is
# ``chipbench/reference/kimi_plain.py``, which shares no code with this
# file and computes the recurrence token by token (tests/test_kimi.py).
# ---------------------------------------------------------------------------

#: the kinds of token mixer ``layer_types`` may name
KIMI_MIXERS = ("kda", "full_attention")
#: the guard of the heads' L2 norm (``q / sqrt(sum q^2 + eps)``)
KDA_L2_EPS = 1e-6
#: What a ``kda`` mixer's checkpoint keeps beside its input: the scan's
#: result (``T x heads x head_dim`` floats a layer), so that the
#: backward pass makes q, k, v, the decay and the gates again, six
#: products and three convolutions, and runs the scan's own backward
#: rule, which computes the chunks again, once and not twice.
KDA_KEPT = (KDA_OUT,)
#: the name of the decay's mean in the step's telemetry (gauge
#: ``mpit_lm_kda_decay_mean``, one entry a ``kda`` layer)
KDA_DECAY_MEAN = "lm_kda_decay_mean"


def kda_a_log_init(key, shape, dtype=jnp.float32):
    """``A_log = log U(1, 16)``, a head."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def kda_dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of a step drawn log-uniformly from ``[1e-3,
    0.1]``: with :func:`kda_a_log_init` the decay ``alpha = exp(-A
    softplus(dt_bias))`` lies in about 0.2-0.999 at the seed, so a
    state's memory is neither none nor everything."""
    step = jnp.exp(jax.random.uniform(
        key, shape, dtype, math.log(1e-3), math.log(0.1)))
    return step + jnp.log(-jnp.expm1(-step))


def l2_norm(x: jnp.ndarray) -> jnp.ndarray:
    return x * jax.lax.rsqrt(
        jnp.sum(x * x, axis=-1, keepdims=True) + KDA_L2_EPS)


def delta_attention(x: jnp.ndarray, p: dict, *, heads: int, head_dim: int,
                    eps: float):
    """Kimi Delta Attention on the stream ``x (B, L, d)`` with the
    weights ``p``, projected back to ``(B, L, d)``, and the mean of the
    decay ``alpha`` over positions, heads and channels; pure in both.
    Three scopes: ``kda_proj`` (the norm, the six products, the
    convolutions, the heads' norms, the decay and ``beta``), ``kda_scan``
    (the chunked state, ``ops/delta_rule.py``) and ``kda_out`` (the
    heads' RMSNorm, the output gate and ``W_o``)."""
    b, l, _ = x.shape
    split = (b, l, heads, head_dim)
    with jax.named_scope("kda_proj"):
        h = rms_norm(x, p["attn_norm"], eps)

        def mixed(w, taps):
            return jax.nn.silu(causal_depthwise_conv(h @ w, taps)
                               ).reshape(split)

        q = l2_norm(mixed(p["wq"], p["conv_q"])) * head_dim ** -0.5
        k = l2_norm(mixed(p["wk"], p["conv_k"]))
        v = mixed(p["wv"], p["conv_v"])
        # the log-decay, a head and key channel: never positive
        g = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(
            (h @ p["wf_a"]) @ p["wf_b"] + p["dt_bias"]).reshape(split)
        beta = jax.nn.sigmoid(h @ p["w_beta"])
        decay_mean = jnp.mean(jnp.exp(jax.lax.stop_gradient(g)))
    with jax.named_scope("kda_scan"):
        o = kda_scan(q, k, v, g, beta)
    with jax.named_scope("kda_out"):
        gate = jax.nn.sigmoid((h @ p["wg_a"]) @ p["wg_b"]).reshape(split)
        o = rms_norm(o, p["o_norm"], eps) * gate
        return o.reshape(b, l, heads * head_dim) @ p["wo"], decay_mean


class KimiBlock(nn.Module):
    d_model: int
    mixer: str               # of KIMI_MIXERS
    sparse: bool             # the MLP: routed and shared experts, else dense
    n_heads: int             # the latent attention's
    kda_heads: int
    kda_head_dim: int
    q_rank: int              # 0: no query latent
    kv_rank: int
    qk_nope: int
    qk_rope: int
    v_head: int
    dense_width: int
    n_experts: int           # the router's width: every expert there is
    experts_per_tok: int
    expert_width: int
    experts_first: int = 0   # the share held here: a contiguous range
    experts_held: int = 0    # 0: all of them
    shared_experts: int = 1
    conv_kernel: int = 4
    route_scale: float = 1.0
    rope_theta: float = 0.0  # 0: no positions in the latent attention
    norm_eps: float = 1e-5
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray):
        """``(the stream after the layer, the ``kda`` mixer's mean decay
        or None, the sparse branch's statistics in
        :data:`JOYAI_MOE_STATS`' order or ``()``)``."""
        if self.mixer not in KIMI_MIXERS:
            raise ValueError(f"layer type {self.mixer!r}; have {KIMI_MIXERS}")
        d, eps = self.d_model, self.norm_eps
        ones = nn.initializers.ones
        decay = None
        if self.mixer == "kda":
            y, decay = self.delta(x)
        else:
            y = latent_mixer(self, x)
        x = x + y
        mlp_norm = self.param("mlp_norm", ones, (d,))
        if not self.sparse:
            # recomputed in the backward pass (its input alone is kept):
            # what a SiLU-gated MLP keeps is six arrays ``dense_width``
            # wide, 1.8 GB at 8192 x 9216, for one more forward pass of
            # three products
            @jax.checkpoint
            def dense(x, norm, w_gate, w_up, w_down):
                with jax.named_scope("mlp"):
                    return swiglu(rms_norm(x, norm, eps), w_gate, w_up,
                                  w_down)

            return x + dense(
                x, mlp_norm,
                self.param("w_gate", _INIT, (d, self.dense_width)),
                self.param("w_up", _INIT, (d, self.dense_width)),
                self.param("w_down", _INIT, (self.dense_width, d))
            ), decay, ()
        y, stats = shared_sparse_experts(self, x, mlp_norm)
        return x + y, decay, stats

    def delta(self, x):
        d, h, hd = self.d_model, self.kda_heads, self.kda_head_dim
        wide, ones = h * hd, nn.initializers.ones
        p = {name: self.param(name, init, shape) for name, init, shape in (
            ("attn_norm", ones, (d,)),
            ("wq", _INIT, (d, wide)), ("wk", _INIT, (d, wide)),
            ("wv", _INIT, (d, wide)),
            # at LFM2's scale and for its reason: at 0.02 the taps'
            # gradients are lost in the norm of the whole
            ("conv_q", LFM2_TAPS_INIT, (self.conv_kernel, wide)),
            ("conv_k", LFM2_TAPS_INIT, (self.conv_kernel, wide)),
            ("conv_v", LFM2_TAPS_INIT, (self.conv_kernel, wide)),
            ("wf_a", _INIT, (d, hd)), ("wf_b", _INIT, (hd, wide)),
            ("a_log", kda_a_log_init, (h,)),
            ("dt_bias", kda_dt_bias_init, (wide,)),
            ("w_beta", _INIT, (d, h)),
            ("wg_a", _INIT, (d, hd)), ("wg_b", _INIT, (hd, wide)),
            ("o_norm", ones, (hd,)),
            ("wo", _INIT, (wide, d)))}
        return jax.checkpoint(
            partial(delta_attention, heads=h, head_dim=hd,
                    eps=self.norm_eps),
            policy=jax.checkpoint_policies.save_only_these_names(*KDA_KEPT)
        )(x, p)


class KimiDecoder(nn.Module):
    """Causal LM of :class:`KimiBlock` layers: a token table (at
    :data:`MELLUM_EMBED_INIT`'s scale, for its reason: a share of the
    experts is held), the layers (layer ``i``'s mixer is
    ``layer_types[i]``, its MLP dense iff ``i < dense_layers``), a final
    RMSNorm and an untied head.  Like :class:`JoyaiDecoder` it is called
    with the targets and returns its own loss, the head's mean
    next-token NLL (no second head: ``num_nextn_predict_layers`` 0),
    with its statistics (``lm/model.py`` closes over it):

    - :data:`KDA_DECAY_MEAN`: the mean of the decay ``alpha`` over
      positions, heads and channels, one entry a ``kda`` layer (at 0 the
      layer has no memory, at 1 it is an undecayed delta rule);
    - the routing counters of every sparse layer under ``lm/model.py``
      ``MOE_STATS``' names.

    The head's product, norm and loss is under ``jax.checkpoint``,
    keeping the rows' log-sum-exp by name (:func:`row_lse`)."""

    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    kda_heads: int = 4
    kda_head_dim: int = 16
    layer_types: tuple = ("kda", "kda", "kda", "full_attention")
    q_rank: int = 0
    kv_rank: int = 32
    qk_nope: int = 16
    qk_rope: int = 8
    v_head: int = 16
    dense_layers: int = 1
    dense_width: int = 128
    n_experts: int = 8
    experts_per_tok: int = 2
    expert_width: int = 32
    experts_first: int = 0
    experts_held: int = 0
    shared_experts: int = 1
    conv_kernel: int = 4
    route_scale: float = 1.0
    rope_theta: float = 0.0
    norm_eps: float = 1e-5
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, targets: jnp.ndarray):
        d, eps = self.d_model, self.norm_eps
        sizes = {field: getattr(self, field) for field in (
            "d_model", "n_heads", "kda_heads", "kda_head_dim", "q_rank",
            "kv_rank", "qk_nope", "qk_rope", "v_head", "dense_width",
            "n_experts", "experts_per_tok", "expert_width", "experts_first",
            "experts_held", "shared_experts", "conv_kernel", "route_scale",
            "rope_theta", "norm_eps", "attn_fn")}

        @partial(jax.checkpoint,
                 policy=jax.checkpoint_policies.save_only_these_names(
                     HEAD_LSE))
        def head_nll(u, norm, head, targets):
            with jax.named_scope("head_loss"):
                z = rms_norm(u, norm, eps) @ head
                return row_lse(z) - jnp.take_along_axis(
                    z, targets[..., None], axis=-1)[..., 0]

        decays, routing = [], []
        with jax.named_scope("embed"):
            x = self.param("embed", MELLUM_EMBED_INIT,
                           (self.vocab, d))[tokens]
        for i, mixer in enumerate(self.layer_types):
            x, decay, counted = KimiBlock(
                mixer=mixer, sparse=i >= self.dense_layers, **sizes)(x)
            decays += [] if decay is None else [decay]
            routing += [counted] if counted else []
        nll = head_nll(
            x, self.param("final_norm", nn.initializers.ones, (d,)),
            self.param("head", _INIT, (d, self.vocab)), targets)
        with jax.named_scope("head_loss"):
            loss = jnp.mean(nll)
        stats = {}
        if decays:
            stats[KDA_DECAY_MEAN] = jnp.stack(decays)
        if routing:
            stats.update(zip(JOYAI_MOE_STATS, map(jnp.stack, zip(*routing))))
        return loss, stats


# ---------------------------------------------------------------------------
# The learned-sparse-attention block (Keye-VL-2.0's language model,
# Kwai-Keye; ``model_type`` ``KeyeVL2``; the block is Qwen3-MoE's key
# for key, the indexer DeepSeek-V3.2's lightning indexer at the config's
# ``sa_config`` sizes).  Every layer's attention is over grouped KV
# heads with an RMSNorm over each head's width on queries and keys
# (LFM2's) and plain rotary positions, **but a query attends only the
# ``index_topk`` earlier positions a small scorer ranks highest**: the
# indexer projects the layer's normed input to ``index_heads`` narrow
# query heads, one key head (LayerNormed) and a weight a head, scores
# every earlier position ``I[t, j] = sum_h w[t, h] ReLU(qI[t, h] .
# kI[j])`` and keeps the largest ``index_topk`` a query, one set for all
# heads (``ops/index_select.py``); the flash kernels mask by that set
# (``ops/flash_attention.py``, *A selection*).  The set is piecewise
# constant, so under the head's NLL no gradient reaches the indexer:
# its leaves are in the vector and stay at their seed (the alignment
# loss that trains one is the recipe's, ROADMAP).  Every MLP is sparse:
# Mellum's softmax router, renormalised, this chip's share of the
# experts, no shared expert.  The plain float32 reference it is held to
# is ``chipbench/reference/keye_plain.py``, which shares no code with
# this file (tests/test_keye.py).
# ---------------------------------------------------------------------------

#: the selection's ``checkpoint_name``: the bits the backward kernels
#: mask by are the forward's own, and the indexer is not run again
DSA_SELECT = "dsa_select"
# What a Keye layer's attention checkpoint keeps beside its input: the
# flash rule's two and the selection (8 MB a layer at 8192 positions).
KEYE_ATTN_KEPT = (FLASH_OUT, FLASH_LSE, DSA_SELECT)
#: the selection's two counters in the step's telemetry, one entry a
#: layer (gauges ``mpit_<name>``): chosen pairs over causal pairs (1.0:
#: nothing is left out), and over the rows that have a choice the share
#: of chosen positions among the row's ``index_topk`` most recent (1.0:
#: the indexer is a sliding window)
KEYE_DSA_STATS = ("lm_dsa_kept_share", "lm_dsa_window_overlap")


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray,
               eps: float) -> jnp.ndarray:
    """LayerNorm over the last axis, in float32."""
    x = x.astype(jnp.float32)
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(centred), axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(var + eps) * weight + bias


def indexer_select(h: jnp.ndarray, p: dict, *, index_heads: int,
                   index_head_dim: int, topk: int, theta: float, eps: float):
    """The lightning indexer on the layer's normed input ``h (B, L,
    d)``: ``(the selection's bits, the chosen pairs' share, the window
    overlap)`` (``ops/index_select.py`` ``index_select``).  Its three
    products at full float32 precision, its key's LayerNorm, the
    rotation of all ``index_head_dim`` dimensions at ``theta``."""
    b, l, _ = h.shape
    full = partial(jnp.matmul, precision=ROUTER_PRECISION)
    turn = plain_inv_freq(index_head_dim, theta)
    qi = rope_by(full(h, p["index_wq"]).reshape(
        b, l, index_heads, index_head_dim), turn)
    ki = rope_by(layer_norm(
        full(h, p["index_wk"]), p["index_k_norm"], p["index_k_bias"],
        eps)[:, :, None, :], turn)[:, :, 0, :]
    return index_select(qi, ki, full(h, p["index_ww"]), topk)


def selected_attention(x: jnp.ndarray, p: dict, *, heads: int,
                       kv_heads: int, head_dim: int, index_heads: int,
                       index_head_dim: int, topk: int, theta: float,
                       eps: float, attn: AttnFn):
    """Grouped attention over a learned selection of keys on the stream
    ``x (B, L, d)`` with the weights ``p``, projected back to ``(B, L,
    d)``, and the selection's two counters (:data:`KEYE_DSA_STATS`);
    pure in both.  Two scopes: ``index`` (:func:`indexer_select`: the
    indexer's products, the scores and the exact top ``topk`` a query)
    and ``attn`` (the norm before the layer, :func:`grouped_attention`
    with the per-head query/key norm at the main heads' ``theta``,
    ``attn`` handed the selection)."""
    with jax.named_scope("attn"):
        h = rms_norm(x, p["attn_norm"], eps)
    with jax.named_scope("index"):
        select, kept, overlap = indexer_select(
            h, p, index_heads=index_heads, index_head_dim=index_head_dim,
            topk=topk, theta=theta, eps=eps)
        select = checkpoint_name(select, DSA_SELECT)
    with jax.named_scope("attn"):
        y = grouped_attention(
            h, p["wq"], p["wk"], p["wv"], p["wo"], heads=heads,
            kv_heads=kv_heads, head_dim=head_dim,
            inv_freq=plain_inv_freq(head_dim, theta),
            attn=partial(attn, select=select),
            qk_norm=(p["q_norm"], p["k_norm"], eps))
    return y, (kept, overlap)


class KeyeBlock(nn.Module):
    d_model: int
    n_heads: int
    kv_heads: int
    head_dim: int
    index_heads: int
    index_head_dim: int
    index_topk: int
    n_experts: int           # the router's width: every expert there is
    experts_per_tok: int
    expert_width: int
    experts_first: int = 0   # the share held here: a contiguous range
    experts_held: int = 0    # 0: all of them
    rope_theta: float = 10000000.0
    norm_eps: float = 1e-6
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray):
        """``(the stream after the layer, the selection's counters in
        :data:`KEYE_DSA_STATS`' order, the sparse branch's statistics in
        :data:`JOYAI_MOE_STATS`' order)``."""
        d, hq, hkv, hd = (self.d_model, self.n_heads, self.kv_heads,
                          self.head_dim)
        hi, di = self.index_heads, self.index_head_dim
        e, f = self.n_experts, self.expert_width
        held, ones = self.experts_held or e, nn.initializers.ones
        p = {name: self.param(name, init, shape) for name, init, shape in (
            ("attn_norm", ones, (d,)),
            ("wq", _INIT, (d, hq * hd)), ("wk", _INIT, (d, hkv * hd)),
            ("wv", _INIT, (d, hkv * hd)), ("wo", _INIT, (hq * hd, d)),
            ("q_norm", ones, (hd,)), ("k_norm", ones, (hd,)),
            ("index_wq", _INIT, (d, hi * di)), ("index_wk", _INIT, (d, di)),
            ("index_ww", _INIT, (d, hi)), ("index_k_norm", ones, (di,)),
            ("index_k_bias", nn.initializers.zeros, (di,)))}
        # Kept for the backward pass: the layer's input, the flash
        # rule's two and the selection; q, k, v are made again from the
        # input (four products, a tenth of the layer's kernels at 8192
        # positions), the indexer is not (nothing that needs a gradient
        # depends on more of it than the kept bits).
        y, dsa = jax.checkpoint(
            partial(selected_attention, heads=hq, kv_heads=hkv, head_dim=hd,
                    index_heads=hi, index_head_dim=di, topk=self.index_topk,
                    theta=self.rope_theta, eps=self.norm_eps,
                    attn=self.attn_fn if self.attn_fn is not None
                    else default_attn()),
            policy=jax.checkpoint_policies.save_only_these_names(
                *KEYE_ATTN_KEPT))(x, p)
        x = x + y

        norm = self.param("mlp_norm", ones, (d,))
        router = self.param("router", _INIT, (d, e))
        experts = tuple(self.param(f"experts_{name}", _INIT, shape)
                        for name, shape in (("gate", (held, d, f)),
                                            ("up", (held, d, f)),
                                            ("down", (held, f, d))))

        # recomputed in the backward pass, as Mellum's and for its
        # reason; Mellum's router: a softmax over all the experts, the k
        # largest renormalised, held or not
        @jax.checkpoint
        def sparse(x, norm, router, experts):
            return sparse_mlp(
                x, norm, router, experts, eps=self.norm_eps, n_experts=e,
                first=self.experts_first, held=held,
                route=lambda logits: (*moe.route_top_k(
                    jax.nn.softmax(logits, axis=-1), self.experts_per_tok,
                    renormalise=True), ()))

        y, stats = sparse(x, norm, router, experts)
        return x + y, dsa, stats


class KeyeDecoder(nn.Module):
    """Causal LM of :class:`KeyeBlock` layers: a token table (at
    :data:`MELLUM_EMBED_INIT`'s scale, for its reason: a share of the
    experts is held), the layers, every one alike, a final RMSNorm and
    an untied head.  Like :class:`KimiDecoder` it is called with the
    targets and returns its own loss, the head's mean next-token NLL,
    with its statistics (``lm/model.py`` closes over it):

    - :data:`KEYE_DSA_STATS`: the selection's two counters, one entry a
      layer;
    - the routing counters of every layer under ``lm/model.py``
      ``MOE_STATS``' names.

    The head's product, norm and loss is under ``jax.checkpoint``,
    keeping the rows' log-sum-exp by name (:func:`row_lse`)."""

    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    kv_heads: int = 2
    head_dim: int = 16
    index_heads: int = 2
    index_head_dim: int = 8
    index_topk: int = 16
    n_layers: int = 2
    n_experts: int = 8
    experts_per_tok: int = 2
    expert_width: int = 32
    experts_first: int = 0
    experts_held: int = 0
    rope_theta: float = 10000000.0
    norm_eps: float = 1e-6
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, targets: jnp.ndarray):
        d, eps = self.d_model, self.norm_eps
        sizes = {field: getattr(self, field) for field in (
            "d_model", "n_heads", "kv_heads", "head_dim", "index_heads",
            "index_head_dim", "index_topk", "n_experts", "experts_per_tok",
            "expert_width", "experts_first", "experts_held", "rope_theta",
            "norm_eps", "attn_fn")}

        @partial(jax.checkpoint,
                 policy=jax.checkpoint_policies.save_only_these_names(
                     HEAD_LSE))
        def head_nll(u, norm, head, targets):
            with jax.named_scope("head_loss"):
                z = rms_norm(u, norm, eps) @ head
                return row_lse(z) - jnp.take_along_axis(
                    z, targets[..., None], axis=-1)[..., 0]

        chosen, routing = [], []
        with jax.named_scope("embed"):
            x = self.param("embed", MELLUM_EMBED_INIT,
                           (self.vocab, d))[tokens]
        for _ in range(self.n_layers):
            x, dsa, counted = KeyeBlock(**sizes)(x)
            chosen.append(dsa)
            routing.append(counted)
        nll = head_nll(
            x, self.param("final_norm", nn.initializers.ones, (d,)),
            self.param("head", _INIT, (d, self.vocab)), targets)
        with jax.named_scope("head_loss"):
            loss = jnp.mean(nll)
        stats = dict(zip(KEYE_DSA_STATS, map(jnp.stack, zip(*chosen))))
        stats.update(zip(JOYAI_MOE_STATS, map(jnp.stack, zip(*routing))))
        return loss, stats


# ---------------------------------------------------------------------------
# The block-diffusion block (SDAR-30B-A3B-Chat, JetLM; ``model_type``
# ``sdar_moe``; the configuration's keys are those of its
# ``config.json``).  The layer is Qwen3-MoE's, as Keye's main heads and
# sparse MLP are: grouped KV heads of their own width with an RMSNorm
# over each head's width on the queries and the keys, rotary positions,
# Mellum's softmax router, renormalised, this chip's share of the
# experts, no shared expert.  What is new is the pass it is trained by:
# **the objective is no next-token NLL**.  A sequence of ``L`` ids is cut
# in blocks of ``block_len``; a seeded subset of every block is replaced
# by the mask id (:func:`block_noise`); the layers see the noised copy
# **and** the clean copy, ``2 L`` rows that share ``L`` rotary positions,
# under a mask that is neither causal nor inside the causal triangle
# (``ops/flash_attention.py`` ``blockdiff``: a noised row sees its own
# noised block, both ways, and the clean blocks strictly before it; a
# clean row the clean blocks up to its own); the head reads the noised
# half alone and the loss is the cross-entropy of the masked positions
# with their **own** ids (no shift), each block's weighted by one over
# its count of masked positions.  The plain float32 reference it is held
# to is ``chipbench/reference/sdar_plain.py``, which shares no code with
# this file (tests/test_sdar.py).
# ---------------------------------------------------------------------------

#: one layer's share of the step's attention tiles, constants of the
#: lowered calls (``ops/flash_attention.py`` ``flash_call_counts``): the
#: tiles the forward and backward kernels run a product on, and those of
#: them in which the mask has a live pair
SDAR_TILE_STATS = ("attn_tiles_visited", "attn_tiles_live")
_GOLDEN, _MIX_A, _MIX_B = 0x9E3779B9, 0x7FEB352D, 0x846CA68B
_BLOCK_SALT, _SPOT_SALT = 0x85EBCA6B, 0xC2B2AE35


def mix32(x: jnp.ndarray) -> jnp.ndarray:
    """A 32-bit integer mix (two multiply-xorshift rounds) on ``uint32``,
    wrapping: what numpy computes to the bit with the same lines."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(_MIX_A)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(_MIX_B)
    return x ^ (x >> 16)


def block_noise(ids: jnp.ndarray, seed: int, block: int):
    """The block-diffusion pass's noise on ``ids (B, L)``: ``(masked (B,
    L) bool, count (B, L) int32)``, a pure function of the row's ids and
    ``seed`` in 32-bit integer arithmetic (no ``jax.random``, whose
    implementation is a flag of the process), which the reference
    repeats in numpy.  A row's checksum ``h = mix(seed ^ sum_i mix(id_i
    + golden (i + 1)))`` keys everything.  Block ``b`` gets the key
    ``mix(h ^ mix(b ^ salt))`` and the count ``1 + rank_b mod block``,
    ``rank_b`` its place among the row's blocks by key (a tie to the
    lower block): a seeded permutation, so ``L / block`` a multiple of
    ``block`` gives every count to exactly as many blocks.  Position
    ``i`` gets the key ``mix(h ^ mix(i ^ salt'))`` and is masked iff
    fewer than its block's count of its block's positions come before
    it by key (a tie to the lower position): a uniform set of that
    size.  ``count`` is the block's count at each of its positions."""
    b, l = ids.shape
    n = l // block
    u32 = lambda x: jnp.asarray(x, jnp.uint32)
    at = jnp.arange(l, dtype=jnp.uint32)
    h = mix32(u32(seed) ^ jnp.sum(
        mix32(ids.astype(jnp.uint32) + u32(_GOLDEN) * (at + u32(1))),
        axis=1, dtype=jnp.uint32))[:, None]
    block_key = mix32(h ^ mix32(jnp.arange(n, dtype=jnp.uint32)
                                ^ u32(_BLOCK_SALT)))
    rank = jnp.argsort(jnp.argsort(block_key, axis=1, stable=True), axis=1,
                       stable=True)
    count = (1 + rank % block).astype(jnp.int32)
    key = mix32(h ^ mix32(at ^ u32(_SPOT_SALT))).reshape(b, n, block)
    mine, other = key[..., :, None], key[..., None, :]
    spot = jnp.arange(block)
    before = (other < mine) | ((other == mine) & (spot[None, :] < spot[:, None]))
    masked = jnp.sum(before, axis=-1) < count[..., None]
    return masked.reshape(b, l), jnp.repeat(count, block, axis=1)


def blockdiff_attention(x: jnp.ndarray, p: dict, *, heads: int,
                        kv_heads: int, head_dim: int, block: int,
                        theta: float, eps: float, attn: AttnFn) -> jnp.ndarray:
    """Grouped attention of the block-diffusion pass on the stream ``x
    (B, 2 L, d)``, a noised copy of the sequence and then its clean one,
    with the weights ``p``: the norm before the layer,
    :func:`grouped_attention` with the per-head query/key norm, row
    ``r`` at rotary position ``r mod L``, ``attn`` handed the mask
    ``blockdiff=(L, block)``."""
    half = x.shape[1] // 2
    with jax.named_scope("attn"):
        return grouped_attention(
            rms_norm(x, p["attn_norm"], eps), p["wq"], p["wk"], p["wv"],
            p["wo"], heads=heads, kv_heads=kv_heads, head_dim=head_dim,
            inv_freq=plain_inv_freq(head_dim, theta),
            attn=partial(attn, blockdiff=(half, block)),
            qk_norm=(p["q_norm"], p["k_norm"], eps), period=half)


class SdarBlock(nn.Module):
    d_model: int
    n_heads: int
    kv_heads: int
    head_dim: int
    n_experts: int           # the router's width: every expert there is
    experts_per_tok: int
    expert_width: int
    experts_first: int = 0   # the share held here: a contiguous range
    experts_held: int = 0    # 0: all of them
    block_len: int = 4
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-6
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray):
        """``(the stream (B, 2 L, d) after the layer, the sparse
        branch's statistics in :data:`JOYAI_MOE_STATS`' order)``."""
        d, hq, hkv, hd = (self.d_model, self.n_heads, self.kv_heads,
                          self.head_dim)
        e, f = self.n_experts, self.expert_width
        held, ones = self.experts_held or e, nn.initializers.ones
        p = {name: self.param(name, init, shape) for name, init, shape in (
            ("attn_norm", ones, (d,)),
            ("wq", _INIT, (d, hq * hd)), ("wk", _INIT, (d, hkv * hd)),
            ("wv", _INIT, (d, hkv * hd)), ("wo", _INIT, (hq * hd, d)),
            ("q_norm", ones, (hd,)), ("k_norm", ones, (hd,)))}
        # kept for the backward pass: the layer's input and the flash
        # rule's two; q, k, v are made again from the input, as Keye's
        x = x + jax.checkpoint(
            partial(blockdiff_attention, heads=hq, kv_heads=hkv, head_dim=hd,
                    block=self.block_len, theta=self.rope_theta,
                    eps=self.norm_eps,
                    attn=self.attn_fn if self.attn_fn is not None
                    else default_attn()),
            policy=jax.checkpoint_policies.save_only_these_names(
                *JOYAI_ATTN_KEPT))(x, p)

        norm = self.param("mlp_norm", ones, (d,))
        router = self.param("router", _INIT, (d, e))
        experts = tuple(self.param(f"experts_{name}", _INIT, shape)
                        for name, shape in (("gate", (held, d, f)),
                                            ("up", (held, d, f)),
                                            ("down", (held, f, d))))

        # recomputed in the backward pass, as Mellum's and for its
        # reason; Mellum's router over all 2 L rows, noised and clean
        @jax.checkpoint
        def sparse(x, norm, router, experts):
            return sparse_mlp(
                x, norm, router, experts, eps=self.norm_eps, n_experts=e,
                first=self.experts_first, held=held,
                route=lambda logits: (*moe.route_top_k(
                    jax.nn.softmax(logits, axis=-1), self.experts_per_tok,
                    renormalise=True), ()))

        y, stats = sparse(x, norm, router, experts)
        return x + y, stats


class SdarDecoder(nn.Module):
    """Block-diffusion LM of :class:`SdarBlock` layers: a token table
    (at :data:`MELLUM_EMBED_INIT`'s scale, for its reason: a share of
    the experts is held), the layers, every one alike, a final RMSNorm
    and an untied head.  Called like the decoders that close their own
    loss (``lm/model.py``), with the packed grid's inputs ``x0 (B, L)``;
    the targets are read by nothing, the objective is no next-token
    NLL.  The noise makes ``xt`` from ``x0`` (:func:`block_noise`, ``mask_id``
    at the masked positions); the layers run on ``[xt ; x0]``, ``2 L``
    rows for ``L`` counted tokens; the head on the noised half alone;
    ``loss = mean over the batch of (1 / n) sum_b (1 / c_b) sum_{i
    masked in b} -log softmax(z_i)[x0_i]`` over the ``n = L /
    block_len`` blocks, ``c_b`` block ``b``'s count of masked positions
    (the block-diffusion bound under the linear schedule in its count
    form: ``chipbench/reference/sdar_plain.py`` has the derivation).
    Its statistics:

    - ``diff_masked_share``: masked positions over ``L`` (``(block_len
      + 1) / (2 block_len)`` by construction: the guard on the noise);
    - ``diff_nll_c1`` .. ``diff_nll_c<block_len>``: the mean NLL of the
      masked positions whose block has that count (``c = block_len``
      sees the clean past alone, ``c = 1`` its block's other positions
      too);
    - :data:`SDAR_TILE_STATS`, where the attention is the flash kernel:
      constants of the lowered calls, one entry a layer;
    - the routing counters of every layer under ``lm/model.py``
      ``MOE_STATS``' names.

    The head's product, norm and loss is under ``jax.checkpoint``,
    keeping the rows' log-sum-exp by name (:func:`row_lse`)."""

    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    kv_heads: int = 2
    head_dim: int = 16
    n_layers: int = 2
    n_experts: int = 8
    experts_per_tok: int = 2
    expert_width: int = 32
    experts_first: int = 0
    experts_held: int = 0
    block_len: int = 4
    mask_id: int = -1        # -1: the table's last row
    noise_seed: int = 0
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-6
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, targets: jnp.ndarray):
        d, eps, block = self.d_model, self.norm_eps, self.block_len
        b, l = tokens.shape
        if l % block:
            raise ValueError(f"{l} positions are no whole blocks of {block}")
        sizes = {field: getattr(self, field) for field in (
            "d_model", "n_heads", "kv_heads", "head_dim", "n_experts",
            "experts_per_tok", "expert_width", "experts_first",
            "experts_held", "block_len", "rope_theta", "norm_eps",
            "attn_fn")}

        @partial(jax.checkpoint,
                 policy=jax.checkpoint_policies.save_only_these_names(
                     HEAD_LSE))
        def head_nll(u, norm, head, ids):
            with jax.named_scope("head_loss"):
                z = rms_norm(u, norm, eps) @ head
                return row_lse(z) - jnp.take_along_axis(
                    z, ids[..., None], axis=-1)[..., 0]

        with jax.named_scope("noise"):
            masked, count = block_noise(tokens, self.noise_seed, block)
            noised = jnp.where(masked, self.mask_id % self.vocab, tokens)
        with jax.named_scope("embed"):
            x = self.param("embed", MELLUM_EMBED_INIT, (self.vocab, d))[
                jnp.concatenate([noised, tokens], axis=1)]
        routing = []
        for _ in range(self.n_layers):
            x, counted = SdarBlock(**sizes)(x)
            routing.append(counted)
        nll = head_nll(
            x[:, :l], self.param("final_norm", nn.initializers.ones, (d,)),
            self.param("head", _INIT, (d, self.vocab)), tokens)
        with jax.named_scope("head_loss"):
            weight = masked / count.astype(jnp.float32)
            loss = jnp.mean(jnp.sum(weight * nll, axis=1)) / (l // block)
            stats = {"diff_masked_share": jnp.mean(
                masked.astype(jnp.float32))}
            for c in range(1, block + 1):
                of_c = (masked & (count == c)).astype(jnp.float32)
                stats[f"diff_nll_c{c}"] = jnp.sum(of_c * nll) / jnp.maximum(
                    jnp.sum(of_c), 1.0)
        attn = self.attn_fn
        if attn is None or getattr(attn, "flash", False):
            steps = flash_call_counts(
                (b, self.n_heads, 2 * l, self.head_dim),
                (b, self.kv_heads, 2 * l, self.head_dim),
                operand_dtype(x.dtype, getattr(attn, "precision", None)),
                blockdiff=(l, block))
            for name, key in zip(SDAR_TILE_STATS, ("live", "nonempty")):
                stats[name] = jnp.full((self.n_layers,), float(steps[key]))
        stats.update(zip(JOYAI_MOE_STATS, map(jnp.stack, zip(*routing))))
        return loss, stats


# ---------------------------------------------------------------------------
# The balanced sparse block (Trinity-Mini, Arcee; ``model_type`` ``afmoe``;
# the configuration's keys are those of its ``config.json``).  Every
# layer is a **double sandwich**: an RMSNorm before each of its two
# branches and another on each one's output, ``u = u + N2(Attn(N1(u)))``,
# ``u = u + N4(Mlp(N3(u)))``.  Attention is over grouped KV heads of
# their own width with an RMSNorm over each head's width on queries and
# keys (LFM2's), and what is its own: **a window layer has rotary
# positions and a full layer has none at all**
# (``layer_types``: ``sliding_attention`` slides ``window`` keys,
# rotated; ``full_attention`` is causal over everything, unrotated), and
# the heads' output is multiplied elementwise by **a sigmoid gate**, a
# fifth product of the layer's normed input, before ``wo``.  The MLP is
# dense on the leading layers and else JoyAI's: a sigmoid router with a
# selection bias over all ``n_experts``, this chip's share of the
# routed experts and a shared expert.  **The bias moves**: the
# configuration publishes the rate of the balancing without an
# auxiliary loss (``load_balance_coeff``, here ``bias_rate``), and after
# every forward pass each sparse layer's bias takes the step
# ``parallel/moe.py`` ``balance_step`` makes of that pass's own counts
# over all the router's experts.  No gradient and no optimizer owns that
# leaf: its slot of the flat gradient carries minus the step and the
# optimizers move the vector's plain ranges by it as it is
# (``models/flat.py`` ``plain_ranges``).  The token table's rows are
# multiplied by ``embed_scale`` (``mup_enabled``: the square root of the
# hidden size).  The plain float32 reference it is held to is
# ``chipbench/reference/trinity_plain.py``, which shares no code with
# this file (tests/test_trinity.py).
# ---------------------------------------------------------------------------

#: the kinds of attention ``layer_types`` may name
TRINITY_MIXERS = ("sliding_attention", "full_attention")
#: the rms of a token's scaled row in the stream, Mellum's and for its
#: reason (:data:`MELLUM_EMBED_INIT`): the table is seeded at this over
#: ``embed_scale``
TRINITY_EMBED_RMS = 8.0


def gated_attention(x: jnp.ndarray, p: dict, *, heads: int, kv_heads: int,
                    head_dim: int, window: int, theta: float, eps: float,
                    attn: AttnFn) -> jnp.ndarray:
    """One Trinity layer's attention on the stream ``x (B, L, d)`` with
    the weights ``p``, before its out-norm: the norm before the layer
    and :func:`grouped_attention` with the per-head query/key norm and
    the sigmoid gate; ``window`` keys and rotary positions, or (0) every
    earlier key and no positions.  Under the scope ``attn_window`` or
    ``attn`` (Mellum's two, so that a trace tells the two kernels' time
    apart), the gate's product and elementwise pass under
    ``attn_gate``."""
    with jax.named_scope("attn_window" if window else "attn"):
        return grouped_attention(
            rms_norm(x, p["attn_norm"], eps), p["wq"], p["wk"], p["wv"],
            p["wo"], heads=heads, kv_heads=kv_heads, head_dim=head_dim,
            inv_freq=plain_inv_freq(head_dim, theta) if window else None,
            attn=attn, window=window,
            qk_norm=(p["q_norm"], p["k_norm"], eps), gate=p["wg"])


class TrinityBlock(nn.Module):
    d_model: int
    n_heads: int
    kv_heads: int
    head_dim: int
    window: int              # 0: a full layer, causal, no positions
    sparse: bool             # the MLP: routed and shared experts, else dense
    dense_width: int
    n_experts: int           # the router's width: every expert there is
    experts_per_tok: int
    expert_width: int
    experts_first: int = 0   # the share held here: a contiguous range
    experts_held: int = 0    # 0: all of them
    shared_experts: int = 1
    route_scale: float = 1.0
    bias_rate: float = 0.0   # the balancing rule's step; 0: no rule
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray):
        """``(the stream after the layer, the sparse branch's statistics
        in :data:`JOYAI_MOE_STATS`' and :data:`BIAS_RULE_STATS`' order)``;
        a dense layer has none, ``()``."""
        d, hq, hkv, hd = (self.d_model, self.n_heads, self.kv_heads,
                          self.head_dim)
        eps, ones = self.norm_eps, nn.initializers.ones
        p = {name: self.param(name, init, shape) for name, init, shape in (
            ("attn_norm", ones, (d,)),
            ("wq", _INIT, (d, hq * hd)), ("wk", _INIT, (d, hkv * hd)),
            ("wv", _INIT, (d, hkv * hd)), ("wg", _INIT, (d, hq * hd)),
            ("wo", _INIT, (hq * hd, d)),
            ("q_norm", ones, (hd,)), ("k_norm", ones, (hd,)))}
        # kept for the backward pass: the layer's input and the flash
        # rule's two; q, k, v and the gate are made again from the
        # input, as JoyAI's and Keye's attention branches are
        a = jax.checkpoint(
            partial(gated_attention, heads=hq, kv_heads=hkv, head_dim=hd,
                    window=self.window, theta=self.rope_theta, eps=eps,
                    attn=self.attn_fn if self.attn_fn is not None
                    else default_attn()),
            policy=jax.checkpoint_policies.save_only_these_names(
                *JOYAI_ATTN_KEPT))(x, p)
        with jax.named_scope("attn_window" if self.window else "attn"):
            x = x + rms_norm(a, self.param("attn_out_norm", ones, (d,)), eps)

        mlp_norm = self.param("mlp_norm", ones, (d,))
        out_norm = self.param("mlp_out_norm", ones, (d,))
        if not self.sparse:
            # recomputed in the backward pass, as Kimi's dense layer and
            # for its reason: its input alone is kept
            @jax.checkpoint
            def dense(x, norm, w_gate, w_up, w_down):
                with jax.named_scope("dense_mlp"):
                    return swiglu(rms_norm(x, norm, eps), w_gate, w_up,
                                  w_down)

            y = dense(x, mlp_norm,
                      self.param("w_gate", _INIT, (d, self.dense_width)),
                      self.param("w_up", _INIT, (d, self.dense_width)),
                      self.param("w_down", _INIT, (self.dense_width, d)))
            with jax.named_scope("dense_mlp"):
                return x + rms_norm(y, out_norm, eps), ()
        y, stats = shared_sparse_experts(self, x, mlp_norm)
        with jax.named_scope("dispatch"):  # the norm of the weighted sum
            return x + rms_norm(y, out_norm, eps), stats


class TrinityDecoder(nn.Module):
    """Causal LM of :class:`TrinityBlock` layers: a token table whose
    rows are multiplied by ``embed_scale`` (seeded at
    :data:`TRINITY_EMBED_RMS` over it, so that the scaled row has
    Mellum's size, for Mellum's reason: a share of the experts is held),
    the layers (layer ``i``'s attention is ``layer_types[i]``, its MLP
    dense iff ``i < dense_layers``), a final RMSNorm and an untied head.
    Like :class:`KimiDecoder` it is called with the targets and returns
    its own loss, the head's mean next-token NLL, with the routing
    counters of every sparse layer under ``lm/model.py`` ``MOE_STATS``'
    names and, where ``bias_rate`` is over 0, :data:`BIAS_RULE_STATS`.

    The head's product, norm and loss is under ``jax.checkpoint``,
    keeping the rows' log-sum-exp by name (:func:`row_lse`)."""

    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    kv_heads: int = 2
    head_dim: int = 16
    layer_types: tuple = ("sliding_attention", "sliding_attention",
                          "full_attention", "sliding_attention",
                          "sliding_attention")
    window: int = 16
    dense_layers: int = 1
    dense_width: int = 128
    n_experts: int = 8
    experts_per_tok: int = 2
    expert_width: int = 32
    experts_first: int = 0
    experts_held: int = 0
    shared_experts: int = 1
    route_scale: float = 1.0
    bias_rate: float = 0.0
    embed_scale: float = 1.0
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, targets: jnp.ndarray):
        d, eps = self.d_model, self.norm_eps
        sizes = {field: getattr(self, field) for field in (
            "d_model", "n_heads", "kv_heads", "head_dim", "dense_width",
            "n_experts", "experts_per_tok", "expert_width", "experts_first",
            "experts_held", "shared_experts", "route_scale", "bias_rate",
            "rope_theta", "norm_eps", "attn_fn")}

        @partial(jax.checkpoint,
                 policy=jax.checkpoint_policies.save_only_these_names(
                     HEAD_LSE))
        def head_nll(u, norm, head, targets):
            with jax.named_scope("head_loss"):
                z = rms_norm(u, norm, eps) @ head
                return row_lse(z) - jnp.take_along_axis(
                    z, targets[..., None], axis=-1)[..., 0]

        routing = []
        with jax.named_scope("embed"):
            table = self.param(
                "embed", nn.initializers.normal(
                    stddev=TRINITY_EMBED_RMS / self.embed_scale),
                (self.vocab, d))
            x = table[tokens] * self.embed_scale
        for i, kind in enumerate(self.layer_types):
            if kind not in TRINITY_MIXERS:
                raise ValueError(f"layer type {kind!r}; have "
                                 f"{TRINITY_MIXERS}")
            x, counted = TrinityBlock(
                window=self.window if kind == "sliding_attention" else 0,
                sparse=i >= self.dense_layers, **sizes)(x)
            routing += [counted] if counted else []
        nll = head_nll(
            x, self.param("final_norm", nn.initializers.ones, (d,)),
            self.param("head", _INIT, (d, self.vocab)), targets)
        with jax.named_scope("head_loss"):
            loss = jnp.mean(nll)
        names = JOYAI_MOE_STATS + (BIAS_RULE_STATS if self.bias_rate > 0
                                   else ())
        stats = dict(zip(names, map(jnp.stack, zip(*routing)))
                     ) if routing else {}
        return loss, stats


# ---------------------------------------------------------------------------
# The state-space hybrid (NVIDIA Nemotron-3-Nano, ``model_type``
# ``nemotron_h``; the configuration's keys are those of its
# ``config.json``, the mixer's equations Mamba-2's, arXiv:2405.21060).
# **A layer is one branch**, ``u = u + Branch(RMSNorm(u))``, of three
# kinds (``layer_types``): every other decoder of this file pairs a
# token mixer with an MLP in each layer.  ``mamba``: a Mamba-2 mixer.
# One product of the normed input gives the gate ``z``, the triple
# ``x | B | C`` and a step ``dt`` a head; the triple goes through one
# depthwise causal convolution of ``conv_kernel`` taps with a bias and a
# SiLU (``ops/short_conv.py``); the state, ``ssm_head_dim x ssm_state``
# a head, decays by the scalar ``exp(softplus(dt + dt_bias) A)`` and is
# written by ``x (x) B``, ``B`` and ``C`` shared by the heads of a group
# (``ops/ssd_scan.py`` has the recurrence and its chunked form); the
# read-out plus ``D x`` is gated by ``SiLU(z)``, RMSNormed over each
# group's channels and projected back.  ``attention``: grouped-head
# attention with **no positional term**, no norm on queries or keys and
# no gate (order comes from the state-space layers).  ``moe``: JoyAI's
# router (a sigmoid with a selection bias over all ``n_experts``,
# renormalised, scaled) over this chip's share of experts **of two
# matrices and no gate**, ``relu(h W_up)^2 W_down``, whose inner width
# need be no whole lane tile (``parallel/moe.py`` ``relu2_experts``),
# beside a shared expert of the same form and its own width.  The plain
# float32 reference it is held to is
# ``chipbench/reference/nemotron_plain.py``, which shares no code with
# this file and steps the state a position at a time
# (tests/test_nemotron.py).
# ---------------------------------------------------------------------------

#: the kinds of layer ``layer_types`` may name
NEMOTRON_LAYERS = ("mamba", "moe", "attention")
#: What a ``mamba`` mixer's checkpoint keeps beside its input: the
#: scan's result (``T x heads x head_dim`` floats a layer), so that the
#: backward pass makes z, x, B, C and the step again (one product and a
#: convolution) and runs the scan's own backward rule, which makes the
#: chunk-start states again (the contributions and the carry) and,
#: walking back, each chunk's matrices: the chunks once and not twice.
SSM_KEPT = (SSD_OUT,)
#: the name of the decay's mean in the step's telemetry (gauge
#: ``mpit_lm_ssm_decay_mean``, one entry a ``mamba`` layer)
SSM_DECAY_MEAN = "lm_ssm_decay_mean"


def ssm_a_log_init(key, shape, dtype=jnp.float32):
    """``A_log = log(1 .. heads)``, a head (Mamba-2's ``A_init_range``
    at whole numbers, as the public ``nemotron_h`` module seeds it): no
    draw."""
    del key
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=dtype))


def state_space_mixer(x: jnp.ndarray, p: dict, *, heads: int, head_dim: int,
                      groups: int, state: int, chunk: int, eps: float):
    """A Mamba-2 mixer on the stream ``x (B, L, d)`` with the weights
    ``p``, projected back to ``(B, L, d)``, and the mean of the decay
    ``a_t`` over positions and heads; pure in both.  Four scopes:
    ``ssm_proj`` (the norm before the layer, ``W_in`` and ``W_out``),
    ``ssm_conv`` (the convolution over x, B and C with its bias and
    SiLU), ``ssd_scan`` (the step, the chunked state and the skip ``D
    x``) and ``ssm_norm`` (the gate and the groups' RMSNorm)."""
    b, l, _ = x.shape
    inner, shared = heads * head_dim, groups * state
    with jax.named_scope("ssm_proj"):
        projected = rms_norm(x, p["norm"], eps) @ p["w_in"]
        z = projected[..., :inner]
        xbc = projected[..., inner:2 * inner + 2 * shared]
        dt = projected[..., 2 * inner + 2 * shared:]
    with jax.named_scope("ssm_conv"):
        xbc = causal_conv_silu(xbc, p["conv_w"], p["conv_b"])
    with jax.named_scope("ssd_scan"):
        xs = xbc[..., :inner].reshape(b, l, heads, head_dim)
        bs = xbc[..., inner:inner + shared].reshape(b, l, groups, state)
        cs = xbc[..., inner + shared:].reshape(b, l, groups, state)
        step = jax.nn.softplus(dt + p["dt_bias"])     # no clamp
        rate = -jnp.exp(p["a_log"])
        decay_mean = jnp.mean(jnp.exp(jax.lax.stop_gradient(step * rate)))
        y = ssd_scan(xs, step, rate, bs, cs, chunk, skip=p["d_skip"])
    with jax.named_scope("ssm_norm"):
        # the gate before the norm; the mean square over a group's
        # channels, one weight a channel
        y = group_rms_norm(y.reshape(b, l, inner) * jax.nn.silu(z),
                           p["ssm_norm"], groups, eps)
    with jax.named_scope("ssm_proj"):
        return y @ p["w_out"], decay_mean


def state_space_leaves(d: int, heads: int, head_dim: int, groups: int,
                       state: int, taps: int, out_init) -> tuple:
    """``(name, initialiser, shape)`` of every leaf
    :func:`state_space_mixer` reads: the layer's norm, ``W_in`` to z,
    x | B | C and a step a head, the convolution's taps and bias, the
    step's bias, ``A_log``, the skip, the gated norm's weight and
    ``W_out`` (seeded by ``out_init``)."""
    ones = nn.initializers.ones
    inner = heads * head_dim
    mixed = inner + 2 * groups * state
    return (
        ("norm", ones, (d,)),
        ("w_in", _INIT, (d, inner + mixed + heads)),
        # at LFM2's scale and for its reason: at 0.02 the taps'
        # gradients are lost in the norm of the whole
        ("conv_w", LFM2_TAPS_INIT, (taps, mixed)),
        ("conv_b", _INIT, (mixed,)),
        ("dt_bias", kda_dt_bias_init, (heads,)),
        ("a_log", ssm_a_log_init, (heads,)),
        ("d_skip", ones, (heads,)),
        ("ssm_norm", ones, (inner,)),
        ("w_out", out_init, (inner, d)))


def plain_attention_leaves(d: int, heads: int, kv_heads: int, head_dim: int,
                           out_init) -> tuple:
    """``(name, initialiser, shape)`` of every leaf
    :func:`plain_attention` reads; ``wo`` is seeded by ``out_init``."""
    return (("norm", nn.initializers.ones, (d,)),
            ("wq", _INIT, (d, heads * head_dim)),
            ("wk", _INIT, (d, kv_heads * head_dim)),
            ("wv", _INIT, (d, kv_heads * head_dim)),
            ("wo", out_init, (heads * head_dim, d)))


def plain_attention(x: jnp.ndarray, p: dict, *, heads: int, kv_heads: int,
                    head_dim: int, eps: float, attn: AttnFn,
                    query_scale: float = 1.0) -> jnp.ndarray:
    """One position-free attention branch on the stream ``x (B, L, d)``:
    the norm before the layer and :func:`grouped_attention` with no
    rotation, no norm on queries or keys, no gate; under ``attn``.
    ``query_scale`` as :func:`grouped_attention` takes it."""
    with jax.named_scope("attn"):
        return grouped_attention(
            rms_norm(x, p["norm"], eps), p["wq"], p["wk"], p["wv"], p["wo"],
            heads=heads, kv_heads=kv_heads, head_dim=head_dim,
            inv_freq=None, attn=attn, query_scale=query_scale)


class NemotronBlock(nn.Module):
    d_model: int
    kind: str                # of NEMOTRON_LAYERS
    n_heads: int
    kv_heads: int
    head_dim: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_groups: int
    ssm_state: int
    n_experts: int           # the router's width: every expert there is
    experts_per_tok: int
    expert_width: int
    shared_width: int        # the shared expert's own inner width
    ssm_chunk: int = SSD_CHUNK
    conv_kernel: int = 4
    experts_first: int = 0   # the share held here: a contiguous range
    experts_held: int = 0    # 0: all of them
    shared_experts: int = 1
    route_scale: float = 1.0
    init_depth: int = 0      # 0: the output projections seeded as the rest
    norm_eps: float = 1e-5
    attn_fn: Optional[AttnFn] = None
    expert_act = "relu2"     # :func:`shared_sparse_experts`' form

    @nn.compact
    def __call__(self, x: jnp.ndarray):
        """``(the stream after the layer, the ``mamba`` mixer's mean
        decay or None, the sparse branch's statistics in
        :data:`JOYAI_MOE_STATS`' order or ``()``)``."""
        d, eps, ones = self.d_model, self.norm_eps, nn.initializers.ones
        # ``rescale_prenorm_residual``: the mixers' output projections
        # are seeded at the std over the square root of the published
        # depth
        out_init = nn.initializers.normal(
            stddev=0.02 / math.sqrt(self.init_depth or 1))
        if self.kind == "moe":
            y, stats = shared_sparse_experts(
                self, x, self.param("norm", ones, (d,)))
            return x + y, None, stats
        if self.kind == "attention":
            hq, hkv, hd = self.n_heads, self.kv_heads, self.head_dim
            p = {name: self.param(name, init, shape)
                 for name, init, shape in plain_attention_leaves(
                     d, hq, hkv, hd, out_init)}
            # kept: the layer's input and the flash rule's two, as
            # Trinity's attention branch
            y = jax.checkpoint(
                partial(plain_attention, heads=hq, kv_heads=hkv, head_dim=hd,
                        eps=eps, attn=self.attn_fn if self.attn_fn is not None
                        else default_attn()),
                policy=jax.checkpoint_policies.save_only_these_names(
                    *JOYAI_ATTN_KEPT))(x, p)
            return x + y, None, ()
        if self.kind != "mamba":
            raise ValueError(f"layer type {self.kind!r}; have "
                             f"{NEMOTRON_LAYERS}")
        h, hd, g, n = (self.ssm_heads, self.ssm_head_dim, self.ssm_groups,
                       self.ssm_state)
        p = {name: self.param(name, init, shape)
             for name, init, shape in state_space_leaves(
                 d, h, hd, g, n, self.conv_kernel, out_init)}
        y, decay = jax.checkpoint(
            partial(state_space_mixer, heads=h, head_dim=hd, groups=g,
                    state=n, chunk=self.ssm_chunk, eps=eps),
            policy=jax.checkpoint_policies.save_only_these_names(*SSM_KEPT)
        )(x, p)
        return x + y, decay, ()


class NemotronDecoder(nn.Module):
    """Causal LM of :class:`NemotronBlock` layers: a token table (at
    :data:`MELLUM_EMBED_INIT`'s scale, for its reason: a share of the
    experts is held), the layers (layer ``i`` is the one branch
    ``layer_types[i]`` names), a final RMSNorm and an untied head.  Like
    :class:`KimiDecoder` it is called with the targets and returns its
    own loss, the head's mean next-token NLL, with its statistics
    (``lm/model.py`` closes over it):

    - :data:`SSM_DECAY_MEAN`: the mean of the decay ``a_t`` over
      positions and heads, one entry a ``mamba`` layer (at 0 the layer
      has no memory, at 1 its state only grows);
    - the routing counters of every ``moe`` layer under ``lm/model.py``
      ``MOE_STATS``' names.

    The head's product, norm and loss is under ``jax.checkpoint``,
    keeping the rows' log-sum-exp by name (:func:`row_lse`)."""

    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    kv_heads: int = 2
    head_dim: int = 16
    ssm_heads: int = 4
    ssm_head_dim: int = 16
    ssm_groups: int = 2
    ssm_state: int = 16
    ssm_chunk: int = SSD_CHUNK
    layer_types: tuple = ("mamba", "moe", "mamba", "attention", "moe")
    n_experts: int = 8
    experts_per_tok: int = 2
    expert_width: int = 32
    shared_width: int = 64
    experts_first: int = 0
    experts_held: int = 0
    shared_experts: int = 1
    conv_kernel: int = 4
    route_scale: float = 1.0
    init_depth: int = 0
    norm_eps: float = 1e-5
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, targets: jnp.ndarray):
        d, eps = self.d_model, self.norm_eps
        sizes = {field: getattr(self, field) for field in (
            "d_model", "n_heads", "kv_heads", "head_dim", "ssm_heads",
            "ssm_head_dim", "ssm_groups", "ssm_state", "ssm_chunk",
            "n_experts", "experts_per_tok", "expert_width", "shared_width",
            "experts_first", "experts_held", "shared_experts", "conv_kernel",
            "route_scale", "init_depth", "norm_eps", "attn_fn")}

        @partial(jax.checkpoint,
                 policy=jax.checkpoint_policies.save_only_these_names(
                     HEAD_LSE))
        def head_nll(u, norm, head, targets):
            with jax.named_scope("head_loss"):
                z = rms_norm(u, norm, eps) @ head
                return row_lse(z) - jnp.take_along_axis(
                    z, targets[..., None], axis=-1)[..., 0]

        decays, routing = [], []
        with jax.named_scope("embed"):
            x = self.param("embed", MELLUM_EMBED_INIT,
                           (self.vocab, d))[tokens]
        for kind in self.layer_types:
            x, decay, counted = NemotronBlock(kind=kind, **sizes)(x)
            decays += [] if decay is None else [decay]
            routing += [counted] if counted else []
        nll = head_nll(
            x, self.param("final_norm", nn.initializers.ones, (d,)),
            self.param("head", _INIT, (d, self.vocab)), targets)
        with jax.named_scope("head_loss"):
            loss = jnp.mean(nll)
        stats = {}
        if decays:
            stats[SSM_DECAY_MEAN] = jnp.stack(decays)
        if routing:
            stats.update(zip(JOYAI_MOE_STATS, map(jnp.stack, zip(*routing))))
        return loss, stats


# ---------------------------------------------------------------------------
# The gated-delta hybrid (Qwen3-Next, ``model_type`` ``qwen3_next``; the
# configuration's keys are those of its ``config.json``, the linear
# layers' equations Gated DeltaNet's, arXiv:2412.06464, as the public
# ``qwen3_next`` module has them).  Three layers of every four are
# **Gated DeltaNet**: one product of the normed input gives q, k, v and
# the output gate ``z``, a narrow one ``beta`` and the decay's step;
# **one** depthwise causal convolution with no bias and a SiLU runs over
# q, k and v together; the state, ``d_k x d_v`` a value head, decays by
# **one scalar a head** and is corrected by the delta rule, **``H_k`` key
# heads serving ``H_v = r H_k`` value heads** (``ops/delta_rule.py``
# ``gdn_scan``); the read-out is RMSNormed a head **and then** gated by
# ``SiLU(z)``.  The fourth is grouped attention with the per-head
# query/key norm, **the first ``rotary_factor`` of each head rotated**
# and the rest passed, and a sigmoid gate cut from the query's product
# (held as a leaf of its own, as Trinity's).  Every MLP is sparse: a
# softmax router with no bias over all ``n_experts``, renormalised over
# the chosen, this chip's share of the routed experts, and a shared
# expert **with a gate of its own**, ``sigmoid(h w_s)``.  **Every norm
# on the stream and on the attention's heads stores its weight as an
# offset from one**, ``x / rms(x) (1 + w)`` with ``w`` seeded at 0; the
# delta layers' head norm is plain.  The plain float32 reference it is
# held to is ``chipbench/reference/qwen3next_plain.py``, which shares no
# code with this file and steps the state a position at a time
# (tests/test_qwen3next.py).
# ---------------------------------------------------------------------------

#: the kinds of token mixer ``layer_types`` may name
QWEN3NEXT_MIXERS = ("linear_attention", "full_attention")
#: What a ``linear_attention`` mixer's checkpoint keeps beside its
#: input: the scan's result (``T x value heads x d_v`` floats a layer),
#: so that the backward pass makes q, k, v, z, the decay and ``beta``
#: again (two products and a convolution) and runs the scan's own
#: backward rule, which computes the chunks again, once and not twice.
GDN_KEPT = (GDN_OUT,)
#: the names of the decay's mean and of the shared expert's gate's mean
#: in the step's telemetry (gauges ``mpit_lm_gdn_decay_mean``, one entry
#: a ``linear_attention`` layer, and ``mpit_lm_shared_gate_mean``, one a
#: layer)
GDN_DECAY_MEAN = "lm_gdn_decay_mean"
SHARED_GATE_MEAN = "lm_shared_gate_mean"
#: the sparse layers' counters: no bias, so nothing flips a choice
QWEN3NEXT_MOE_STATS = JOYAI_MOE_STATS[:3]


def gated_delta_mixer(x: jnp.ndarray, p: dict, *, key_heads: int,
                      value_heads: int, key_dim: int, value_dim: int,
                      eps: float):
    """A Gated DeltaNet mixer on the stream ``x (B, L, d)`` with the
    weights ``p``, projected back to ``(B, L, d)``, and the mean of the
    decay ``alpha`` over positions and value heads; pure in both.  Four
    scopes: ``gdn_proj`` (the norm before the layer, ``W_qkvz``,
    ``W_ba``, the heads' L2 norms, the decay and ``beta``), ``gdn_conv``
    (the one convolution over q, k and v with its SiLU), ``gdn_scan``
    (the chunked state) and ``gdn_out`` (the heads' RMSNorm, **then**
    the gate ``SiLU(z)``, and ``W_out``)."""
    b, l, _ = x.shape
    keys, values = key_heads * key_dim, value_heads * value_dim
    mixed = 2 * keys + values
    with jax.named_scope("gdn_proj"):
        h = rms_norm(x, 1.0 + p["attn_norm"], eps)
        qkvz, ba = h @ p["w_qkvz"], h @ p["w_ba"]
    with jax.named_scope("gdn_conv"):
        qkv = jax.nn.silu(causal_depthwise_conv(qkvz[..., :mixed],
                                                p["conv"]))
    with jax.named_scope("gdn_proj"):
        q = l2_norm(qkv[..., :keys].reshape(b, l, key_heads, key_dim)
                    ) * key_dim ** -0.5
        k = l2_norm(qkv[..., keys:2 * keys].reshape(b, l, key_heads, key_dim))
        v = qkv[..., 2 * keys:].reshape(b, l, value_heads, value_dim)
        beta = jax.nn.sigmoid(ba[..., :value_heads])
        # the log-decay, one number a value head: never positive
        g = -jnp.exp(p["a_log"]) * jax.nn.softplus(
            ba[..., value_heads:] + p["dt_bias"])
        decay_mean = jnp.mean(jnp.exp(jax.lax.stop_gradient(g)))
    with jax.named_scope("gdn_scan"):
        o = gdn_scan(q, k, v, g, beta)
    with jax.named_scope("gdn_out"):
        z = qkvz[..., mixed:].reshape(b, l, value_heads, value_dim)
        o = rms_norm(o, p["o_norm"], eps) * jax.nn.silu(z)
        return o.reshape(b, l, values) @ p["wo"], decay_mean


def partly_rotated_attention(x: jnp.ndarray, p: dict, *, heads: int,
                             kv_heads: int, head_dim: int, rotary: int,
                             theta: float, eps: float,
                             attn: AttnFn) -> jnp.ndarray:
    """One ``full_attention`` layer's branch on the stream ``x (B, L,
    d)``: the offset norm before the layer and :func:`grouped_attention`
    with the per-head offset norms on queries and keys, the first
    ``rotary`` dimensions of a head rotated and the sigmoid gate; under
    ``attn``, the gate's product and elementwise pass under
    ``attn_gate``."""
    with jax.named_scope("attn"):
        return grouped_attention(
            rms_norm(x, 1.0 + p["attn_norm"], eps), p["wq"], p["wk"],
            p["wv"], p["wo"], heads=heads, kv_heads=kv_heads,
            head_dim=head_dim, inv_freq=plain_inv_freq(rotary, theta),
            attn=attn, qk_norm=(1.0 + p["q_norm"], 1.0 + p["k_norm"], eps),
            gate=p["wg"], rotary=rotary)


class Qwen3NextBlock(nn.Module):
    d_model: int
    mixer: str               # of QWEN3NEXT_MIXERS
    n_heads: int
    kv_heads: int
    head_dim: int
    rotary: int              # the rotated dimensions of a head, from 0 on
    gdn_key_heads: int
    gdn_value_heads: int
    gdn_key_dim: int
    gdn_value_dim: int
    n_experts: int           # the router's width: every expert there is
    experts_per_tok: int
    expert_width: int
    shared_width: int        # the shared expert's own inner width
    experts_first: int = 0   # the share held here: a contiguous range
    experts_held: int = 0    # 0: all of them
    shared_experts: int = 1
    conv_kernel: int = 4
    rope_theta: float = 1e7
    norm_eps: float = 1e-6
    attn_fn: Optional[AttnFn] = None
    router_act = "softmax"   # :func:`shared_sparse_experts`' router
    shared_gated = True      # and its shared expert's own gate

    @nn.compact
    def __call__(self, x: jnp.ndarray):
        """``(the stream after the layer, the ``linear_attention``
        mixer's mean decay or None, the sparse branch's statistics in
        :data:`QWEN3NEXT_MOE_STATS`' order and the shared gate's
        mean)``."""
        d, eps = self.d_model, self.norm_eps
        ones, zeros = nn.initializers.ones, nn.initializers.zeros
        decay = None
        if self.mixer == "linear_attention":
            hk, hv = self.gdn_key_heads, self.gdn_value_heads
            dk, dv = self.gdn_key_dim, self.gdn_value_dim
            mixed = 2 * hk * dk + hv * dv
            p = {name: self.param(name, init, shape)
                 for name, init, shape in (
                     ("attn_norm", zeros, (d,)),
                     ("w_qkvz", _INIT, (d, mixed + hv * dv)),
                     ("w_ba", _INIT, (d, 2 * hv)),
                     # at LFM2's scale and for its reason: at 0.02 the
                     # taps' gradients are lost in the norm of the whole
                     ("conv", LFM2_TAPS_INIT, (self.conv_kernel, mixed)),
                     ("a_log", kda_a_log_init, (hv,)),
                     ("dt_bias", kda_dt_bias_init, (hv,)),
                     ("o_norm", ones, (dv,)),
                     ("wo", _INIT, (hv * dv, d)))}
            y, decay = jax.checkpoint(
                partial(gated_delta_mixer, key_heads=hk, value_heads=hv,
                        key_dim=dk, value_dim=dv, eps=eps),
                policy=jax.checkpoint_policies.save_only_these_names(
                    *GDN_KEPT))(x, p)
        elif self.mixer == "full_attention":
            hq, hkv, hd = self.n_heads, self.kv_heads, self.head_dim
            p = {name: self.param(name, init, shape)
                 for name, init, shape in (
                     ("attn_norm", zeros, (d,)),
                     ("wq", _INIT, (d, hq * hd)), ("wk", _INIT, (d, hkv * hd)),
                     ("wv", _INIT, (d, hkv * hd)), ("wg", _INIT, (d, hq * hd)),
                     ("wo", _INIT, (hq * hd, d)),
                     ("q_norm", zeros, (hd,)), ("k_norm", zeros, (hd,)))}
            # kept: the layer's input and the flash rule's two, as
            # Trinity's attention branch
            y = jax.checkpoint(
                partial(partly_rotated_attention, heads=hq, kv_heads=hkv,
                        head_dim=hd, rotary=self.rotary,
                        theta=self.rope_theta, eps=eps,
                        attn=self.attn_fn if self.attn_fn is not None
                        else default_attn()),
                policy=jax.checkpoint_policies.save_only_these_names(
                    *JOYAI_ATTN_KEPT))(x, p)
        else:
            raise ValueError(f"layer type {self.mixer!r}; have "
                             f"{QWEN3NEXT_MIXERS}")
        x = x + y
        y, stats = shared_sparse_experts(
            self, x, 1.0 + self.param("mlp_norm", zeros, (d,)))
        return x + y, decay, stats


class Qwen3NextDecoder(nn.Module):
    """Causal LM of :class:`Qwen3NextBlock` layers: a token table (at
    :data:`MELLUM_EMBED_INIT`'s scale, for its reason: a share of the
    experts is held), the layers (layer ``i``'s mixer is
    ``layer_types[i]``, every MLP sparse), a final offset RMSNorm and an
    untied head.  Like :class:`KimiDecoder` it is called with the targets
    and returns its own loss, the head's mean next-token NLL, with its
    statistics (``lm/model.py`` closes over it):

    - :data:`GDN_DECAY_MEAN`: the mean of the decay ``alpha`` over
      positions and value heads, one entry a ``linear_attention`` layer
      (at 0 the layer has no memory, at 1 it is an undecayed delta rule);
    - :data:`SHARED_GATE_MEAN`: the mean of ``sigmoid(h w_s)`` over the
      tokens, one entry a layer (at 0 the shared expert is off, at 1 it
      is ungated);
    - the routing counters of every layer under ``lm/model.py``
      ``MOE_STATS``' first three names.

    The head's product, norm and loss is under ``jax.checkpoint``,
    keeping the rows' log-sum-exp by name (:func:`row_lse`)."""

    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    kv_heads: int = 2
    head_dim: int = 16
    rotary_factor: float = 0.25
    gdn_key_heads: int = 2
    gdn_value_heads: int = 4
    gdn_key_dim: int = 16
    gdn_value_dim: int = 16
    layer_types: tuple = ("linear_attention", "linear_attention",
                          "linear_attention", "full_attention")
    n_experts: int = 8
    experts_per_tok: int = 2
    expert_width: int = 32
    shared_width: int = 32
    experts_first: int = 0
    experts_held: int = 0
    shared_experts: int = 1
    conv_kernel: int = 4
    rope_theta: float = 1e7
    norm_eps: float = 1e-6
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, targets: jnp.ndarray):
        d, eps = self.d_model, self.norm_eps
        sizes = {field: getattr(self, field) for field in (
            "d_model", "n_heads", "kv_heads", "head_dim", "gdn_key_heads",
            "gdn_value_heads", "gdn_key_dim", "gdn_value_dim", "n_experts",
            "experts_per_tok", "expert_width", "shared_width",
            "experts_first", "experts_held", "shared_experts", "conv_kernel",
            "rope_theta", "norm_eps", "attn_fn")}
        rotary = int(self.head_dim * self.rotary_factor)

        @partial(jax.checkpoint,
                 policy=jax.checkpoint_policies.save_only_these_names(
                     HEAD_LSE))
        def head_nll(u, norm, head, targets):
            with jax.named_scope("head_loss"):
                z = rms_norm(u, 1.0 + norm, eps) @ head
                return row_lse(z) - jnp.take_along_axis(
                    z, targets[..., None], axis=-1)[..., 0]

        decays, counted = [], []
        with jax.named_scope("embed"):
            x = self.param("embed", MELLUM_EMBED_INIT,
                           (self.vocab, d))[tokens]
        for mixer in self.layer_types:
            x, decay, stats = Qwen3NextBlock(
                mixer=mixer, rotary=rotary, **sizes)(x)
            decays += [] if decay is None else [decay]
            counted.append(stats)
        nll = head_nll(
            x, self.param("final_norm", nn.initializers.zeros, (d,)),
            self.param("head", _INIT, (d, self.vocab)), targets)
        with jax.named_scope("head_loss"):
            loss = jnp.mean(nll)
        stats = dict(zip(QWEN3NEXT_MOE_STATS + (SHARED_GATE_MEAN,),
                         map(jnp.stack, zip(*counted))))
        if decays:
            stats[GDN_DECAY_MEAN] = jnp.stack(decays)
        return loss, stats


# ---------------------------------------------------------------------------
# The dense state-space hybrid (IBM Granite-4.0-H-Micro, ``model_type``
# ``granitemoehybrid`` with ``num_local_experts`` 0; the configuration's
# keys are those of its ``config.json``, the mixer's equations Mamba-2's
# as the public ``granitemoehybrid`` module has them).  **A layer is two
# sublayers**: a token mixer by ``layer_types`` (``mamba``:
# :func:`state_space_mixer`, here with **all the heads in one group**,
# so ``B`` and ``C`` are shared by every head and the gated RMSNorm's
# mean square is over all the inner channels; ``attention``:
# :func:`plain_attention`, grouped heads with no positional term), then a
# gated SiLU MLP whose two input matrices are one leaf, ``[W_a | W_b]``.
# **Four multipliers scale the stream**: the looked-up rows are
# multiplied by ``embed_scale`` (``embedding_multiplier``), every
# branch by ``residual_scale`` (``residual_multiplier``) before it joins
# the stream, the attention's scores by ``attn_scale``
# (``attention_multiplier``) **in place of** ``1 / sqrt(head_dim)``, and
# the logits are divided by ``logits_scale`` (``logits_scaling``).
# **The head is tied**: the logits are the normed stream against the
# token table transposed, and the table's gradient has two sources, the
# look-up and the head.  The plain float32 reference it is held to is
# ``chipbench/reference/granite_plain.py``, which shares no code with
# this file and steps the state a position at a time
# (tests/test_granite.py).
# ---------------------------------------------------------------------------

#: the kinds of token mixer ``layer_types`` may name
GRANITE_MIXERS = ("mamba", "attention")
#: the name of the stream's root mean square on its way into the final
#: norm in the step's telemetry (gauge ``mpit_lm_stream_rms``, one
#: entry): what ``embed_scale`` and ``residual_scale`` set
STREAM_RMS = "lm_stream_rms"


def gated_mlp(x: jnp.ndarray, p: dict, *, eps: float) -> jnp.ndarray:
    """A gated SiLU MLP on the stream ``x (B, L, d)`` behind its norm:
    ``(SiLU(h W_a) * (h W_b)) W_o`` with ``[W_a | W_b]`` one matrix
    ``mlp_in (d, 2 f)``; under the scope ``mlp``."""
    with jax.named_scope("mlp"):
        both = rms_norm(x, p["mlp_norm"], eps) @ p["mlp_in"]
        width = both.shape[-1] // 2
        return (jax.nn.silu(both[..., :width]) * both[..., width:]
                ) @ p["mlp_out"]


class GraniteBlock(nn.Module):
    d_model: int
    mixer: str               # of GRANITE_MIXERS
    n_heads: int
    kv_heads: int
    head_dim: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_groups: int
    ssm_state: int
    dense_width: int         # the MLP's inner width
    ssm_chunk: int = SSD_CHUNK
    conv_kernel: int = 4
    residual_scale: float = 1.0
    attn_scale: float = 0.0  # 0: 1 / sqrt(head_dim)
    norm_eps: float = 1e-5
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray):
        """``(the stream after the layer, the ``mamba`` mixer's mean
        decay or None)``.  Each sublayer is under ``jax.checkpoint``: a
        ``mamba`` mixer keeps its input and the scan's result
        (:data:`SSM_KEPT`), the attention its input and the flash rule's
        two, the MLP its input alone (``h W_a`` and ``h W_b``, ``T x 2
        f`` floats, are made again)."""
        d, eps, ones = self.d_model, self.norm_eps, nn.initializers.ones
        r, decay = self.residual_scale, None
        if self.mixer == "attention":
            hq, hkv, hd = self.n_heads, self.kv_heads, self.head_dim
            p = {name: self.param(name, init, shape)
                 for name, init, shape in plain_attention_leaves(
                     d, hq, hkv, hd, _INIT)}
            # the flash kernels and both plain forms scale the scores by
            # 1 / sqrt(head_dim): the queries are multiplied by
            # ``attn_scale sqrt(head_dim)`` in front of the call, which
            # is the same scores (the published 1/64 at heads of 64: by
            # 1/8, a power of two, exact at any precision)
            y = jax.checkpoint(
                partial(plain_attention, heads=hq, kv_heads=hkv, head_dim=hd,
                        eps=eps, attn=self.attn_fn if self.attn_fn is not None
                        else default_attn(),
                        query_scale=self.attn_scale * math.sqrt(hd)
                        if self.attn_scale else 1.0),
                policy=jax.checkpoint_policies.save_only_these_names(
                    *JOYAI_ATTN_KEPT))(x, p)
            with jax.named_scope("attn"):
                x = x + r * y
        elif self.mixer == "mamba":
            h, hd, g, n = (self.ssm_heads, self.ssm_head_dim, self.ssm_groups,
                           self.ssm_state)
            p = {name: self.param(name, init, shape)
                 for name, init, shape in state_space_leaves(
                     d, h, hd, g, n, self.conv_kernel, _INIT)}
            y, decay = jax.checkpoint(
                partial(state_space_mixer, heads=h, head_dim=hd, groups=g,
                        state=n, chunk=self.ssm_chunk, eps=eps),
                policy=jax.checkpoint_policies.save_only_these_names(
                    *SSM_KEPT))(x, p)
            with jax.named_scope("ssm_proj"):
                x = x + r * y
        else:
            raise ValueError(f"layer type {self.mixer!r}; have "
                             f"{GRANITE_MIXERS}")
        f = self.dense_width
        y = jax.checkpoint(partial(gated_mlp, eps=eps))(x, {
            "mlp_norm": self.param("mlp_norm", ones, (d,)),
            "mlp_in": self.param("mlp_in", _INIT, (d, 2 * f)),
            "mlp_out": self.param("mlp_out", _INIT, (f, d))})
        with jax.named_scope("mlp"):
            return x + r * y, decay


class GraniteDecoder(nn.Module):
    """Causal LM of :class:`GraniteBlock` layers: a token table whose
    rows are multiplied by ``embed_scale`` (seeded at the std of every
    other matrix: the multiplier is what sets a row's size beside the
    branches), the layers (layer ``i``'s mixer is ``layer_types[i]``,
    every MLP dense and gated), a final RMSNorm and **the table again as
    the head**, the logits divided by ``logits_scale``.  Like
    :class:`KimiDecoder` it is called with the targets and returns its
    own loss, the head's mean next-token NLL, with its statistics
    (``lm/model.py`` closes over it):

    - :data:`SSM_DECAY_MEAN`: the mean of the decay ``a_t`` over
      positions and heads, one entry a ``mamba`` layer;
    - :data:`STREAM_RMS`: the root mean square of the stream entering
      the final norm, one entry.

    The head's norm, product and loss is under ``jax.checkpoint``,
    keeping the rows' log-sum-exp by name (:func:`row_lse`)."""

    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    kv_heads: int = 2
    head_dim: int = 16
    ssm_heads: int = 4
    ssm_head_dim: int = 16
    ssm_groups: int = 1
    ssm_state: int = 16
    ssm_chunk: int = SSD_CHUNK
    layer_types: tuple = ("mamba", "mamba", "attention", "mamba")
    dense_width: int = 96
    conv_kernel: int = 4
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    attn_scale: float = 0.0
    logits_scale: float = 1.0
    norm_eps: float = 1e-5
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, targets: jnp.ndarray):
        d, eps = self.d_model, self.norm_eps
        sizes = {field: getattr(self, field) for field in (
            "d_model", "n_heads", "kv_heads", "head_dim", "ssm_heads",
            "ssm_head_dim", "ssm_groups", "ssm_state", "ssm_chunk",
            "dense_width", "conv_kernel", "residual_scale", "attn_scale",
            "norm_eps", "attn_fn")}

        @partial(jax.checkpoint,
                 policy=jax.checkpoint_policies.save_only_these_names(
                     HEAD_LSE))
        def head_nll(u, norm, table, targets):
            with jax.named_scope("head_loss"):
                # the table's rows against the normed stream: its leaf
                # as it lies, contracted over the stream's width
                z = jnp.einsum("bld,vd->blv", rms_norm(u, norm, eps),
                               table) / self.logits_scale
                return row_lse(z) - jnp.take_along_axis(
                    z, targets[..., None], axis=-1)[..., 0]

        decays = []
        with jax.named_scope("embed"):
            table = self.param("embed", _INIT, (self.vocab, d))
            x = table[tokens] * self.embed_scale
        for mixer in self.layer_types:
            x, decay = GraniteBlock(mixer=mixer, **sizes)(x)
            decays += [] if decay is None else [decay]
        nll = head_nll(
            x, self.param("final_norm", nn.initializers.ones, (d,)), table,
            targets)
        with jax.named_scope("head_loss"):
            loss = jnp.mean(nll)
            stats = {STREAM_RMS: jnp.sqrt(jnp.mean(jnp.square(
                jax.lax.stop_gradient(x))))[None]}
        if decays:
            stats[SSM_DECAY_MEAN] = jnp.stack(decays)
        return loss, stats
