"""The plain reference of the Keye block: what a configuration with
``"reference": "keye_plain"`` is held to.  Forward pass, loss and
gradient in straightforward ``jax.numpy``, float32, every matrix product
at ``default_matmul_precision("highest")``.  The indexer's scores
materialised a block of rows at a time, the chosen set by a **stable
sort** of whole rows, the attention a masked softmax over the whole row,
dense over the experts held; no kernel, no bits, no bisection, no sort
of tokens, no grouped product, no checkpoint policy, no parameter
server.  It imports nothing of the program.  ``chipbench/spec.py`` finds
it by the configuration's key and has the contract of such a module
(``loss_and_grad_flat``, ``LOSS_TOL_NATS``, ``GRAD_REL_TOL``);
``chipbench/compare.py`` is the comparison every reference is held by.

The block (Keye-VL-2.0's language model, Kwai-Keye, ``model_type``
``KeyeVL2``: Qwen3-MoE's block with DeepSeek-V3.2's lightning indexer at
``sa_config``'s sizes; the configuration's keys are those of its
``config.json``).  For hidden ``u`` of width ``hidden_size``, position
``t`` of a sequence of ``T``, in every layer::

    x = RMSNorm(u)                                   # weight only
    q = rope(RMSNorm_h(x Wq))   (num_attention_heads x head_dim)
    k = rope(RMSNorm_h(x Wk)),  v = x Wv   (num_key_value_heads x head_dim)
        # RMSNorm_h: over each head's width, one weight of head_dim for
        # the queries and one for the keys, before the rotation; no bias
    qI = rope(x WqI)            (indexer_num_heads x indexer_head_dim)
    kI = rope(LayerNorm(x WkI)) (one head; weight and bias)
    w  = x Ww                   (indexer_num_heads scalars)
    I[t, j] = sum_h w[t, h] ReLU(qI[t, h] . kI[j])          for j <= t
    S_t = the positions of the topk largest I[t, j], j <= t; all of them
          where t < topk; a tie goes to the lower j
    query head g attends KV head g // (heads / kv heads) over S_t alone:
        p[t, j] = softmax over j in S_t of q_t . k_j / sqrt(head_dim)
    u = u + (sum_{j in S_t} p[t, j] v_j) Wo
    h = RMSNorm(u)
    g = softmax(h Wr)                                # over all the experts
    the num_experts_per_tok largest g_e, divided by their sum
    u = u + sum_{e held} g_e (SiLU(h Wg_e) * (h Wu_e)) Wd_e

``rope`` is the rotate-half rotation at ``rope_theta`` over the whole
width of the head it turns (128 for the main heads, all
``indexer_head_dim`` 64 for the indexer's).  Then a final RMSNorm and an
untied head; the loss is the mean next-token negative log-likelihood
over a packed grid.  ``S_t`` is piecewise constant in the weights: no
gradient reaches ``WqI``, ``WkI``, ``Ww`` or the LayerNorm.

**The share.**  As ``mellum_plain.py``: ``router_experts`` is the
router's width, ``num_experts`` the experts held from ``experts_first``;
the top-k and its renormalisation run over all, the sum over the held.

Memory.  The scores are walked in blocks of :data:`ROW_BLOCK` rows (16
heads x 1024 x 8192 floats, 537 MB), the attention in blocks of
:data:`HEAD_BLOCK` query heads, both one after another inside a
``lax.map``, and the experts one block, each under ``jax.checkpoint``,
and so is each layer as a whole with its
chosen sets as an argument (64 MB a layer as booleans), so that the
backward pass holds one layer's activations and one block's ``L x L``
scores at a time and sorts nothing twice: at the published widths and
8192 positions that is what lets the reference run beside the system's
own operands on a 16 GB chip (without the layers' checkpoints the
compile for the described chip read 14.6 GB of temporaries).
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
ROW_BLOCK = 1024

# Tolerances, from the v5e at the published widths of ``keye-l6e8-local``
# (PERF.md section 6, PR 46; ``probe_keye.py`` beside this file and the
# cell's own runs made every reading, one seeded sequence of 8192 a
# seed).  A limit lies between two readings.  The system's (float32 in
# memory, one bf16 pass a product, the router's and the indexer's at
# full precision): 0.0739..0.0760% of the gradient's norm over eleven
# seeds and 0.9e-5..8.3e-5 nats.  What the limits must refuse, the
# reference's own arithmetic with parameters and activations in bf16,
# the nearest precision below the configuration's: 0.1970..0.1991% over
# three seeds and 0.3e-5..7.7e-5 nats.  Between them lies the system
# with the router's and the indexer's products at one bf16 pass, one
# product's precision lowered: 0.0987..0.1016% over the same three
# seeds, and the gradient's limit is set under it: 1.25 times the
# system's largest reading, 1.04 under the lowered build's smallest and
# 2.07 under the bf16 reference's (the system's readings lie within
# 1.5% of their mean, so a quarter is room enough).  The loss cannot
# tell any of them apart (every side reads under 1e-4) and its limit is
# only a guard against a wrong loss.  Also refused, each the reference
# with one thing wrong: the selection left out (2.88%), the 2048 most
# recent positions in place of the indexer's choice (3.30%), the ReLU
# left out of the score (1.33%), the heads' query/key norm left out
# (1.52%).  The two sides choose their sets from streams that differ by
# the program's bf16 rounding, so a share of the rows differ in one of
# their 2048 keys, the one nearest the threshold: 0.012% of layer 0's
# rows (same table rows in, full-precision products on both sides) and
# 10.7, 15.5, 20.5, 23.7, 26.3% of the later layers', 1.0-1.09 keys a
# differing row (the probe counts them and holds them to its own
# limits); that is part of the system's reading above.
LOSS_TOL_NATS = 1.0e-3
GRAD_REL_TOL = 9.5e-4


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def head_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    """The RMSNorm over each head's width of ``x (..., head)``."""
    return rms_norm(x, weight, eps)


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray,
               eps: float) -> jnp.ndarray:
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * weight + bias


def rotate(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embedding of ``x (batch, seq, heads, head)`` over the whole
    head, rotate-half: ``x cos + rotate_half(x) sin`` with
    ``rotate_half((a, b)) = (-b, a)`` and angles ``t theta^(-2i/head)``."""
    seq, head = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, head, 2, dtype=jnp.float32) / head)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    a, b = x[..., : head // 2], x[..., head // 2:]
    return x * jnp.cos(angle) + jnp.concatenate([-b, a], axis=-1) * jnp.sin(
        angle)


def index_scores(qi: jnp.ndarray, ki: jnp.ndarray,
                 w: jnp.ndarray) -> jnp.ndarray:
    """``I (rows, seq)`` of one sequence: ``qi (rows, heads, head)``,
    ``ki (seq, head)``, ``w (rows, heads)``."""
    per_head = jax.nn.relu(jnp.einsum("rhd,kd->rhk", qi, ki))
    return jnp.sum(per_head * w[:, :, None], axis=1)


def choose(scores: jnp.ndarray, first_row: int, topk: int) -> jnp.ndarray:
    """``(rows, seq)`` bool: row ``r`` is position ``t = first_row + r``;
    its set is the ``min(t + 1, topk)`` largest ``scores[r, j]`` over ``j
    <= t`` by a stable sort, descending: of two equal scores the lower
    position comes first."""
    rows, seq = scores.shape
    t = first_row + jnp.arange(rows)[:, None]
    causal = jnp.arange(seq)[None, :] <= t
    order = jnp.argsort(-jnp.where(causal, scores, -jnp.inf), axis=-1,
                        stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return causal & (rank < jnp.minimum(t + 1, topk))


def selection(x: jnp.ndarray, p: Dict[str, jnp.ndarray],
              config: Dict[str, Any]) -> jnp.ndarray:
    """The chosen sets ``(batch, seq, seq)`` bool of the layer with
    weights ``p`` on its normed input ``x (batch, seq, d)``."""
    sa = config["sa_config"]
    heads, head = int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"])
    topk, theta = int(sa["topk"]), float(config["rope_theta"])
    eps = float(config["rms_norm_eps"])
    b, seq, _ = x.shape
    qi = rotate((x @ p["index_wq"]).reshape(b, seq, heads, head), theta)
    ki = rotate(layer_norm(x @ p["index_wk"], p["index_k_norm"],
                           p["index_k_bias"], eps)[:, :, None, :],
                theta)[:, :, 0, :]
    w = x @ p["index_ww"]
    # a block of rows at a time, one after another (a ``lax.map``: as a
    # Python loop the compiler was free to hold every block's scores at
    # once)
    rows = min(ROW_BLOCK, seq)
    blocks = -(-seq // rows)
    pad = blocks * rows - seq

    def block(args):
        lo, qi_rows, w_rows = args
        return jnp.stack([
            choose(index_scores(qi_rows[i], ki[i], w_rows[i]), lo, topk)
            for i in range(b)])

    chosen = jax.lax.map(block, (
        jnp.arange(blocks) * rows,
        jnp.pad(qi, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
            b, blocks, rows, heads, head).transpose(1, 0, 2, 3, 4),
        jnp.pad(w, ((0, 0), (0, pad), (0, 0))).reshape(
            b, blocks, rows, heads).transpose(1, 0, 2, 3)))
    chosen = chosen.transpose(1, 0, 2, 3).reshape(b, blocks * rows, seq)
    return jax.lax.stop_gradient(chosen[:, :seq])


@jax.checkpoint
def _heads(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
           mask: jnp.ndarray) -> jnp.ndarray:
    """Masked softmax attention of ``q (batch, heads, seq, head)`` over
    one KV head ``k, v (batch, seq, head)``; ``mask (batch, seq, seq)``."""
    scores = jnp.einsum("bhqd,bkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    scores = jnp.where(mask[:, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkd->bhqd", jax.nn.softmax(scores, axis=-1), v)


def attention(x: jnp.ndarray, p: Dict[str, jnp.ndarray], mask: jnp.ndarray,
              config: Dict[str, Any]) -> jnp.ndarray:
    """Grouped attention on the normed input ``x`` over the pairs of
    ``mask (batch, seq, seq)``."""
    n_head = int(config["num_attention_heads"])
    n_kv = int(config["num_key_value_heads"])
    head, eps = int(config["head_dim"]), float(config["rms_norm_eps"])
    theta = float(config["rope_theta"])
    b, seq, _ = x.shape
    q = rotate(head_norm((x @ p["wq"]).reshape(b, seq, n_head, head),
                         p["q_norm"], eps), theta).transpose(0, 2, 1, 3)
    k = rotate(head_norm((x @ p["wk"]).reshape(b, seq, n_kv, head),
                         p["k_norm"], eps), theta).transpose(0, 2, 1, 3)
    v = (x @ p["wv"]).reshape(b, seq, n_kv, head).transpose(0, 2, 1, 3)
    group = n_head // n_kv
    step = min(HEAD_BLOCK, group)
    # a block of query heads at a time over the KV head they share, one
    # after another (a ``lax.map``: as a Python loop the compiler laid
    # the blocks' ``L x L`` scores side by side, 537 MB each)
    blocks = n_head // step
    kv_of = jnp.arange(blocks) * step // group
    out = jax.lax.map(
        lambda block: _heads(block[0], block[1], block[2], mask),
        (q.reshape(b, blocks, step, seq, head).transpose(1, 0, 2, 3, 4),
         k.transpose(1, 0, 2, 3)[kv_of], v.transpose(1, 0, 2, 3)[kv_of]))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, n_head, seq, head)
    return out.transpose(0, 2, 1, 3).reshape(b, seq, n_head * head) @ p["wo"]


def router_gates(h: jnp.ndarray, router: jnp.ndarray, top_k: int,
                 renormalise: bool) -> jnp.ndarray:
    """``(T, E)`` over all the router's experts: its softmax, the
    ``top_k`` largest of each row kept and (``renormalise``) divided by
    their sum, the rest zero; of two equal ones the lower index wins."""
    probs = jax.nn.softmax(h @ router, axis=-1)
    index = jnp.arange(probs.shape[-1])
    other, mine = probs[:, None, :], probs[:, :, None]
    beats = (other > mine) | ((other == mine)
                              & (index[None, None, :] < index[None, :, None]))
    gates = jnp.where(jnp.sum(beats, axis=-1) < top_k, probs, 0.0)
    if renormalise:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates


@jax.checkpoint
def _expert_block(h: jnp.ndarray, gates: jnp.ndarray, wg: jnp.ndarray,
                  wu: jnp.ndarray, wd: jnp.ndarray) -> jnp.ndarray:
    """``sum_e gates[:, e] (SiLU(h Wg_e) * (h Wu_e)) Wd_e`` over the
    experts given: every token through every one of them."""
    hidden = jax.nn.silu(jnp.einsum("td,edf->etf", h, wg)) \
        * jnp.einsum("td,edf->etf", h, wu)
    return jnp.einsum("etd,te->td", jnp.einsum("etf,efd->etd", hidden, wd),
                      gates)


def experts(h: jnp.ndarray, p: Dict[str, jnp.ndarray],
            config: Dict[str, Any]) -> jnp.ndarray:
    """The sparse layer on tokens ``h (T, d)``, densely, over the
    experts whose matrices ``p`` holds: the router's experts
    ``experts_first .. experts_first + held - 1``."""
    gates = router_gates(h, p["router"], int(config["num_experts_per_tok"]),
                         bool(config["norm_topk_prob"]))
    first, held = int(config.get("experts_first", 0)), p[
        "experts_gate"].shape[0]
    return _expert_block(h, gates[:, first:first + held], p["experts_gate"],
                         p["experts_up"], p["experts_down"])


def layer(u: jnp.ndarray, p: Dict[str, jnp.ndarray], mask: jnp.ndarray,
          config: Dict[str, Any]) -> jnp.ndarray:
    """One layer on the stream ``u (batch, seq, d)`` over the pairs of
    ``mask``: the attention and the sparse MLP, each added to the
    stream."""
    eps = float(config["rms_norm_eps"])
    b, seq, d = u.shape
    u = u + attention(rms_norm(u, p["attn_norm"], eps), p, mask, config)
    h = rms_norm(u, p["mlp_norm"], eps).reshape(b * seq, d)
    return u + experts(h, p, config).reshape(b, seq, d)


def layers(params: Dict[str, Any], inputs: jnp.ndarray,
           config: Dict[str, Any], sets: Any = None) -> jnp.ndarray:
    """The stream after the last layer.  A layer's sets are chosen from
    its normed input first, then the layer runs under ``jax.checkpoint``
    with the sets as an argument: the backward pass holds one layer's
    activations at a time and sorts nothing again.  ``sets``, a list, is
    given each layer's chosen sets in order (the probe counts the rows
    that differ from the program's)."""
    eps = float(config["rms_norm_eps"])
    u = params["embed"][inputs]
    run = jax.checkpoint(lambda u, p, mask: layer(u, p, mask, config))
    for i in range(int(config["num_hidden_layers"])):
        p = params[f"KeyeBlock_{i}"]
        mask = selection(rms_norm(u, p["attn_norm"], eps), p, config)
        if sets is not None:
            sets.append(mask)
        u = run(u, p, mask)
    return u


def loss(params: Dict[str, Any], tokens: jnp.ndarray,
         config: Dict[str, Any]) -> jnp.ndarray:
    """Mean next-token negative log-likelihood over a packed grid
    ``(batch, seq + 1)``: every cell is a target."""
    u = layers(params, tokens[:, :-1], config)
    x = rms_norm(u, params["final_norm"], float(config["rms_norm_eps"]))
    logp = jax.nn.log_softmax(x @ params["head"], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


def loss_and_grad_flat(w: jnp.ndarray, unravel: Any, tokens: jnp.ndarray,
                       config: Dict[str, Any]
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """From the program's flat vector to the loss and a flat gradient,
    in one jitted program, so that no pytree of the model's size
    outlives it; ``config`` holds the model's own keys (the module's
    docstring names each).  The tokens are an argument, never a constant
    of the program."""
    fn = jax.jit(jax.value_and_grad(
        lambda flat, tok: loss(unravel(flat), tok, config)))
    with jax.default_matmul_precision("highest"):
        return fn(w, tokens)
