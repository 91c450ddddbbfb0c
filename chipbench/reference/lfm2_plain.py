"""The plain reference of the LFM2 block: what a configuration with
``"reference": "lfm2_plain"`` is held to.  Forward pass, loss and
gradient in straightforward ``jax.numpy``, float32, every matrix product
at ``default_matmul_precision("highest")``.  The short convolution as
three shifted products, the experts dense over the range held (every
token through each of them, masked by its router weights), a
materialised ``L x L`` causal mask; no kernel, no sort, no grouped
product, no parameter server.  It imports nothing of the program and
exists once: the CPU tests (``tests/test_lfm2.py``) hold the program to
this very module.  ``chipbench/spec.py`` finds it by the
configuration's key and has the contract of such a module
(``loss_and_grad_flat``, ``LOSS_TOL_NATS``, ``GRAD_REL_TOL``);
``chipbench/compare.py`` is the comparison every reference is held by.

The block (LFM2, Liquid AI, ``model_type`` ``lfm2_moe``; the
configuration's keys are those of its ``config.json``, the form that of
Hugging Face's ``modeling_lfm2_moe.py``).  For hidden ``x`` of width
``hidden_size``, in layer ``i`` of the published model::

    x = x + op_i(RMSNorm(x))                      # weight only, norm_eps
    x = x + ffn_i(RMSNorm(x))

    layer_types[i] == "conv":
        [B, C, z] = h W_in          # 3 x hidden_size, in that order
        u = B * z
        c_t = sum_{j < conv_L_cache} k_j * u_{t - (conv_L_cache - 1) + j}
                                    # per channel; u is zero before the
                                    # sequence; the last tap is on t
        op = (C * c) W_out          # no bias anywhere (conv_bias false)
    layer_types[i] == "full_attention":
        q = h Wq   (num_attention_heads x head)   # head = hidden / heads
        k, v = h Wk, h Wv  (num_key_value_heads x head)
        q, k = RMSNorm_head(q), RMSNorm_head(k)   # a weight of ``head``
        q, k = RoPE(q), RoPE(k)   # rotate-half, theta^(-2j/head)
        query head g attends KV head g // (heads / kv heads), causally,
            scores q k^T / sqrt(head), softmax
        op = Attention Wo
    i < num_dense_layers:
        ffn = (SiLU(h W1) * (h W3)) W2            # intermediate_size
    else:
        s = sigmoid(h Wr)                         # over all the experts
        chosen: the num_experts_per_tok largest of s + b
        w_e = s_e / (sum_chosen s + 1e-6) * routed_scaling_factor
        ffn = sum_{e chosen and held} w_e (SiLU(h Wg_e) * (h Wu_e)) Wd_e

Then a final RMSNorm and an untied head; the loss is the mean next-token
negative log-likelihood over a packed grid.

**The share.**  ``router_experts`` is the router's width (the published
``num_experts``); ``num_experts`` counts the experts held here, the
contiguous range from ``experts_first``.  The sigmoid, the choice with
its bias and the normalisation run over all ``router_experts``; the sum
runs over the held ones only.  **The layers.**  ``first_layer`` says
which of the published ``layer_types`` the ``num_hidden_layers`` held
here are (layers ``first_layer .. first_layer + num_hidden_layers -
1``), and with the published ``num_dense_layers`` which of them are
dense.  With ``first_layer`` 0, every layer and every expert this is
the whole model.

Departures from the published model and its recipe, each also a line of
``assumed`` in ``chipbench/configs/lfm2-24b-l5e8.json``: table and head
are kept apart; the bias ``b`` is a parameter no rule updates, seeded
away from zero, and its gradient is zero; no auxiliary loss, no dropout;
no token is dropped; ties in the choice go to the lower expert index;
weights are the program's seeded initialisation, not the checkpoint.

Memory.  Each layer is under ``jax.checkpoint`` (the backward pass keeps
a layer's input and computes the layer again), and inside it the
attention is walked in blocks of :data:`HEAD_BLOCK` query heads, each
under ``jax.checkpoint``, so that one block's ``L x L`` scores live at a
time: at the published widths and 8192 positions that is what lets the
reference run beside the system's own vectors on a 16 GB chip.
Recomputation changes no number.

Parameters come as the program's own pytree (the ``unravel`` of the flat
vector), read by the names ``models/transformer.py`` gave them.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

HEAD_BLOCK = 2

# Tolerances, from the v5e at the published widths of
# ``lfm2-l5e8-local`` (PERF.md section 6, PR 32; ``probe_lfm2.py``
# beside this file and the cell's own runs made every reading, one
# seeded sequence of 8192 a seed).  A limit lies between two readings.
# The system's (float32 in memory, one bf16 pass a product, the router's
# at full precision): 0.157..0.169% of the gradient's norm over seventeen
# seeds and 0..1.4e-4 nats; with the attention path raised as OLMoE's
# is (projections at three passes, the kernel on float32 inputs) 0.159%
# where one pass read 0.159%, so it stays at one pass.  What the limits
# must refuse, the reference's own arithmetic with parameters and
# activations in bf16, the nearest precision below the configuration's:
# 0.384, 0.387 and 0.403% over three seeds and 1.1e-4..2.0e-4 nats.  The
# gradient's limit is about the geometric mean of the two, 1.5 times the
# system's largest and 1.5 under the bf16 reference's smallest; the loss
# cannot tell them apart (both sides read under 2.1e-4) and its limit is
# only a guard against a wrong loss.  Also refused, each the reference
# with one thing wrong: the convolution's taps reversed (24% and 2.5e-3
# nats), the convolution one position late (29%, 7.6e-3 nats), the
# gates B and C exchanged (24%), the query/key norm over the whole
# projection in place of a head (0.41%), query heads on the wrong KV
# head (3.8%); fewer KV heads than the file says do not fit the
# matrices' shapes at all.  NOT refused, and it
# cannot be at this arithmetic: a selection bias of std 0.02 that leaks
# into the weights reads 0.093% on seeded weights, under the system's
# own rounding; the CPU tests refuse it (``tests/test_lfm2.py``).
LOSS_TOL_NATS = 1.0e-3
GRAD_REL_TOL = 2.5e-3


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


# -- the gated short convolution ------------------------------------------------


def shifted(u: jnp.ndarray, back: int) -> jnp.ndarray:
    """``u (batch, seq, d)`` moved ``back`` positions later along the
    sequence, zeros coming in at its start: row ``t`` is ``u[t -
    back]``."""
    if back == 0:
        return u
    seq = u.shape[1]
    zeros = jnp.zeros_like(u[:, :min(back, seq)])
    return jnp.concatenate([zeros, u[:, :max(seq - back, 0)]], axis=1)


def short_conv(u: jnp.ndarray, taps: jnp.ndarray) -> jnp.ndarray:
    """``c_t = sum_j taps[j] * u[t - (K - 1) + j]``: tap ``j`` is on the
    position ``K - 1 - j`` back, the last tap on the current one."""
    k = taps.shape[0]
    out = jnp.zeros_like(u)
    for j in range(k):
        out = out + taps[j] * shifted(u, k - 1 - j)
    return out


def gated_conv(h: jnp.ndarray, p: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    d = h.shape[-1]
    bcz = h @ p["conv_in"]
    b_gate, c_gate, z = bcz[..., :d], bcz[..., d:2 * d], bcz[..., 2 * d:]
    return (c_gate * short_conv(b_gate * z, p["conv_taps"])) @ p["conv_out"]


# -- attention ---------------------------------------------------------------------


def rotary_table(seq: int, head: int, rope: Dict[str, Any]
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``cos, sin (seq, head)`` of ``rope_parameters``; the default type
    only (LFM2's)."""
    if rope["rope_type"] != "default":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    freq = float(rope["rope_theta"]) ** (
        -jnp.arange(0, head, 2, dtype=jnp.float32) / head)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)       # (seq, head)
    return jnp.cos(angle), jnp.sin(angle)


def rotate(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Rotary embedding of ``x (batch, heads, seq, head)`` in the
    rotate-half convention: ``x cos + rotate_half(x) sin`` with
    ``rotate_half((a, b)) = (-b, a)`` over the head's two halves."""
    head = x.shape[-1]
    a, b = x[..., : head // 2], x[..., head // 2:]
    return x * cos + jnp.concatenate([-b, a], axis=-1) * sin


@jax.checkpoint
def _heads(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
           mask: jnp.ndarray) -> jnp.ndarray:
    """Masked softmax attention of ``q (batch, heads, seq, head)`` over
    one KV head ``k, v (batch, seq, head)``."""
    scores = jnp.einsum("bhqd,bkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    scores = jnp.where(mask, scores, -jnp.inf)
    return jnp.einsum("bhqk,bkd->bhqd", jax.nn.softmax(scores, axis=-1), v)


def attention(h: jnp.ndarray, p: Dict[str, jnp.ndarray], n_head: int,
              n_kv: int, eps: float, rope: Dict[str, Any]) -> jnp.ndarray:
    b, seq, d = h.shape
    head = d // n_head

    def split(x, count):
        return x.reshape(b, seq, count, head).transpose(0, 2, 1, 3)

    q = rms_norm(split(h @ p["wq"], n_head), p["q_norm"], eps)
    k = rms_norm(split(h @ p["wk"], n_kv), p["k_norm"], eps)
    v = split(h @ p["wv"], n_kv)
    cos, sin = rotary_table(seq, head, rope)
    q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    mask = jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None]
    group = n_head // n_kv
    step = min(HEAD_BLOCK, group)
    out = [_heads(q[:, lo:lo + step], k[:, lo // group], v[:, lo // group],
                  mask)
           for lo in range(0, n_head, step)]
    out = jnp.concatenate(out, axis=1)
    return out.transpose(0, 2, 1, 3).reshape(b, seq, n_head * head) @ p["wo"]


# -- the two MLPs ------------------------------------------------------------------


def dense_mlp(h: jnp.ndarray, p: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    return (jax.nn.silu(h @ p["w1"]) * (h @ p["w3"])) @ p["w2"]


def router_gates(h: jnp.ndarray, router: jnp.ndarray, bias: jnp.ndarray,
                 top_k: int, normalise: bool, scale: float) -> jnp.ndarray:
    """``(T, E)`` over all the router's experts: the sigmoid scores of
    the ``top_k`` experts chosen by ``score + bias``, the rest zero;
    (``normalise``) divided by their sum plus 1e-6; times ``scale``.  An
    expert is chosen if fewer than ``top_k`` others beat it; of two
    equal ones the lower index beats the higher.  The bias is in the
    comparison only."""
    scores = jax.nn.sigmoid(h @ router)
    ranked = scores + bias
    index = jnp.arange(scores.shape[-1])
    other, mine = ranked[:, None, :], ranked[:, :, None]
    beats = (other > mine) | ((other == mine)
                              & (index[None, None, :] < index[None, :, None]))
    gates = jnp.where(jnp.sum(beats, axis=-1) < top_k, scores, 0.0)
    if normalise:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-6)
    return gates * scale


def experts(h: jnp.ndarray, p: Dict[str, jnp.ndarray], top_k: int,
            normalise: bool, scale: float, first: int) -> jnp.ndarray:
    """The sparse MLP on tokens ``h (T, d)``, densely, over the experts
    whose matrices ``p`` holds: the router's experts ``first .. first +
    held - 1``."""
    gates = router_gates(h, p["router"], p["router_bias"], top_k, normalise,
                         scale)
    held = p["experts_gate"].shape[0]
    gates = gates[:, first:first + held]
    hidden = jax.nn.silu(jnp.einsum("td,edf->etf", h, p["experts_gate"])) \
        * jnp.einsum("td,edf->etf", h, p["experts_up"])
    return jnp.einsum(
        "etd,te->td", jnp.einsum("etf,efd->etd", hidden, p["experts_down"]),
        gates)


# -- the model ---------------------------------------------------------------------


def layer(x: jnp.ndarray, p: Dict[str, jnp.ndarray], kind: str, dense: bool,
          config: Dict[str, Any]) -> jnp.ndarray:
    eps = float(config["norm_eps"])
    h = rms_norm(x, p["op_norm"], eps)
    if kind == "conv":
        x = x + gated_conv(h, p)
    elif kind == "full_attention":
        x = x + attention(h, p, int(config["num_attention_heads"]),
                          int(config["num_key_value_heads"]), eps,
                          config["rope_parameters"])
    else:
        raise ValueError(f"layer type {kind!r}")
    h = rms_norm(x, p["ffn_norm"], eps)
    if dense:
        return x + dense_mlp(h, p)
    b, seq, d = x.shape
    y = experts(h.reshape(b * seq, d), p, int(config["num_experts_per_tok"]),
                bool(config["norm_topk_prob"]),
                float(config["routed_scaling_factor"]),
                int(config.get("experts_first", 0)))
    return x + y.reshape(b, seq, d)


def forward(params: Dict[str, Any], inputs: jnp.ndarray,
            config: Dict[str, Any]) -> jnp.ndarray:
    """Log-probabilities ``(batch, seq, vocab)`` for int32 ``inputs``."""
    first = int(config.get("first_layer", 0))
    x = params["embed"][inputs]
    for i in range(int(config["num_hidden_layers"])):
        kind = config["layer_types"][first + i]
        dense = first + i < int(config["num_dense_layers"])
        x = jax.checkpoint(
            lambda x, p, kind=kind, dense=dense: layer(x, p, kind, dense,
                                                      config)
        )(x, params[f"Lfm2Block_{i}"])
    x = rms_norm(x, params["final_norm"], float(config["norm_eps"]))
    return jax.nn.log_softmax(x @ params["head"], axis=-1)


def loss(params: Dict[str, Any], tokens: jnp.ndarray,
         config: Dict[str, Any]) -> jnp.ndarray:
    """Mean next-token negative log-likelihood over a packed grid
    ``(batch, seq + 1)``: every cell is a target."""
    logp = forward(params, tokens[:, :-1], config)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


def loss_and_grad_flat(w: jnp.ndarray, unravel: Any, tokens: jnp.ndarray,
                       config: Dict[str, Any]
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """From the program's flat vector to the loss and a flat gradient,
    in one jitted program, so that no pytree of the model's size
    outlives it; ``config`` holds LFM2's own keys (the module's
    docstring names each).  The tokens are an argument, never a constant
    of the program."""
    fn = jax.jit(jax.value_and_grad(
        lambda flat, tok: loss(unravel(flat), tok, config)))
    with jax.default_matmul_precision("highest"):
        return fn(w, tokens)
