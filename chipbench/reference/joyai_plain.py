"""The plain reference of the JoyAI-LLM-Flash block: what a configuration
with ``"reference": "joyai_plain"`` is held to.  Forward pass, loss and
gradient in straightforward ``jax.numpy``, float32, every matrix product
at ``default_matmul_precision("highest")``.  The rotary embedding on the
interleaved pairs where they lie, the rotary key repeated over the
heads, a materialised ``L x L`` causal mask, the experts dense over the
range held (every token through each of them, masked by its router
weights); no kernel, no sort, no grouped product, no parameter server.
It imports nothing of the program and exists once: the CPU tests
(``tests/test_joyai.py``) hold the program to this very module.
``chipbench/spec.py`` finds it by the configuration's key and has the
contract of such a module (``loss_and_grad_flat``, ``LOSS_TOL_NATS``,
``GRAD_REL_TOL``); ``chipbench/compare.py`` is the comparison every
reference is held by.

The block (JoyAI-LLM-Flash, JD; ``model_type`` ``joyai_llm_flash``; the
configuration's keys are those of its ``config.json``, the equations
DeepSeek-V3's, which that model type follows key for key).  All norms
are RMSNorm, weight only, eps ``rms_norm_eps``.  For hidden ``x`` of
width ``hidden_size``, in layer ``i`` of the published model::

    h = RMSNorm(x)
    c_q = RMSNorm(h W_qa)                           # q_lora_rank
    [q_nope | q_rope] = c_q W_qb                    # heads x (nope + rope)
    [c_kv | k_r] = h W_kva                          # kv_lora_rank + rope
    [k_nope | v] = RMSNorm(c_kv) W_kvb              # heads x (nope + v)
    q_rope, k_r = RoPE(q_rope), RoPE(k_r)   # interleaved pairs (2j, 2j+1),
                                            # rope_theta^(-2j/rope); k_r is
                                            # ONE head, used by every head
    q_h = [q_nope_h | q_rope_h],  k_h = [k_nope_h | k_r]
    scores q_h k_h^T / sqrt(nope + rope), causal, softmax
    x = x + concat_h(P_h v_h) W_o                   # heads x v -> hidden

    h = RMSNorm(x)
    i < first_k_dense_replace:
        x = x + (SiLU(h W_g) * (h W_u)) W_d         # intermediate_size
    else:
        s = sigmoid(h W_r)                          # over all the experts
        chosen: the num_experts_per_tok largest of s + b
        w_e = s_e / (sum_chosen s + 1e-20) * routed_scaling_factor
        x = x + sum_{e chosen and held} w_e E_e(h) + S(h)
                  # E_e, S: SiLU-gated, moe_intermediate_size wide; S the
                  # shared expert (n_shared_experts of them, side by side)

Then a final RMSNorm and an untied head give the main next-token NLL.
**The multi-token-prediction module** (``num_nextn_predict_layers`` 1),
on the stack's last hidden state ``x_L`` before the final norm::

    z_i = [RMSNorm_e(Emb(t_{i+1})) | RMSNorm_h(x_L,i)] W_eh   # 2 hidden -> hidden
    z = one sparse layer as above on z
    logits = RMSNorm_mtp(z) Head         # the main model's table and head

predicts ``t_{i+2}`` at position ``i``; a row's last position has no such
token and is left out of the mean.  The loss is ``NLL_main +
mtp_loss_weight * NLL_mtp``.

**The share.**  ``router_experts`` is the router's width (the published
``n_routed_experts``); ``n_routed_experts`` counts the experts held
here, the contiguous range from ``experts_first``.  The sigmoid, the
choice with its bias and the normalisation run over all
``router_experts``; the routed sum runs over the held ones only; the
shared expert is whole.  **The layers.**  Layers ``0 ..
num_hidden_layers - 1`` of the published model are held, so with the
published ``first_k_dense_replace`` the first of them is dense.

Departures from the published model and its recipe, each also a line of
``assumed`` in ``chipbench/configs/joyai-flash-48b-l5e8.json``: the MTP
projection takes the embedding first; ``mtp_loss_weight`` 0.3; the
masked last position; the bias ``b`` is a parameter no rule updates,
seeded away from zero, and its gradient is zero; no auxiliary loss, no
dropout; no token is dropped; ties in the choice go to the lower expert
index; weights are the program's seeded initialisation.

Memory.  Each layer and each head is under ``jax.checkpoint``, and
inside a layer the attention is walked in blocks of :data:`HEAD_BLOCK`
heads, each under ``jax.checkpoint``, so that one block's ``L x L``
scores live at a time.  Recomputation changes no number.

Parameters come as the program's own pytree (the ``unravel`` of the flat
vector), read by the names ``models/transformer.py`` gave them.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

HEAD_BLOCK = 2

# Tolerances: PERF.md section 6, PR 38 has every reading
# (``probe_joyai.py`` beside this file and the cell's own runs made
# them, on the v5e at the published widths, one seeded sequence of 8192
# a seed).  The gradient's limit lies between two readings: the
# system's largest (float32 in memory, one bf16 pass a product, the
# router's at full precision: 0.194-0.232% of the gradient's norm over
# fourteen seeds) and the smallest of this file's own arithmetic with
# parameters and activations held in bf16, the nearest precision below
# the configuration's (0.276-0.306% over four seeds).  The two lie close
# because a product of one bf16 pass already rounds both operands: what
# the lower precision adds is the rounding of what is stored between
# products.  The loss cannot tell the two apart (the system 4e-6 to
# 9e-5 nats off, the bf16 reference 3e-5 to 1.6e-4) and its limit is
# the accepted sparse cells', a guard against a wrong loss only: the
# MTP head on the wrong target is 2.2e-2 off, the projection with its
# halves exchanged 6.9e-3.
LOSS_TOL_NATS = 1.0e-3
GRAD_REL_TOL = 2.55e-3


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


# -- latent attention ----------------------------------------------------------


def rotary_angles(seq: int, width: int, theta: float) -> jnp.ndarray:
    """``(seq, width / 2)``: position times ``theta^(-2j / width)``."""
    freq = float(theta) ** (-jnp.arange(0, width, 2, dtype=jnp.float32)
                            / width)
    return jnp.arange(seq, dtype=jnp.float32)[:, None] * freq[None, :]


def rotate_pairs(x: jnp.ndarray, angle: jnp.ndarray) -> jnp.ndarray:
    """Rotary embedding of ``x (batch, heads, seq, width)`` over the
    interleaved pairs ``(x_2j, x_2j+1)``, each turned by ``angle[t, j]``,
    and left where it lies."""
    even, odd = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                       axis=-1)
    return turned.reshape(x.shape)


@jax.checkpoint
def _heads(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
           mask: jnp.ndarray) -> jnp.ndarray:
    """Masked softmax attention of ``q, k (batch, heads, seq, qk)`` and
    ``v (batch, heads, seq, v)``, head by head."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    scores = jnp.where(mask, scores, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)


def attention(h: jnp.ndarray, p: Dict[str, jnp.ndarray],
              config: Dict[str, Any]) -> jnp.ndarray:
    b, seq, _ = h.shape
    heads = int(config["num_attention_heads"])
    nope, rope = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    v_dim, rank = int(config["v_head_dim"]), int(config["kv_lora_rank"])
    eps = float(config["rms_norm_eps"])

    def split(x, width):
        return x.reshape(b, seq, heads, width).transpose(0, 2, 1, 3)

    q = split(rms_norm(h @ p["wq_a"], p["q_a_norm"], eps) @ p["wq_b"],
              nope + rope)
    kv_a = h @ p["wkv_a"]
    kv = split(rms_norm(kv_a[..., :rank], p["kv_a_norm"], eps) @ p["wkv_b"],
               nope + v_dim)
    angle = rotary_angles(seq, rope, config["rope_theta"])
    q_rope = rotate_pairs(q[..., nope:], angle)
    k_rope = rotate_pairs(kv_a[:, None, :, rank:], angle)   # one head
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.repeat(k_rope, heads, axis=1)],
                        axis=-1)
    v = kv[..., nope:]
    mask = jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None]
    out = jnp.concatenate(
        [_heads(q[:, lo:lo + HEAD_BLOCK], k[:, lo:lo + HEAD_BLOCK],
                v[:, lo:lo + HEAD_BLOCK], mask)
         for lo in range(0, heads, HEAD_BLOCK)], axis=1)
    return out.transpose(0, 2, 1, 3).reshape(b, seq, heads * v_dim) @ p["wo"]


# -- the MLPs ------------------------------------------------------------------


def gated_mlp(h: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
              w_down: jnp.ndarray) -> jnp.ndarray:
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def router_gates(h: jnp.ndarray, router: jnp.ndarray, bias: jnp.ndarray,
                 top_k: int, normalise: bool, scale: float) -> jnp.ndarray:
    """``(T, E)`` over all the router's experts: the sigmoid scores of
    the ``top_k`` experts chosen by ``score + bias``, the rest zero;
    (``normalise``) divided by their sum plus 1e-20; times ``scale``.
    An expert's rank is its place in a stable descending sort, so of two
    equal ones the lower index comes first.  The bias is in the ranking
    only."""
    scores = jax.nn.sigmoid(h @ router)
    order = jnp.argsort(-(scores + bias), axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    gates = jnp.where(rank < top_k, scores, 0.0)
    if normalise:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return gates * scale


def routed_experts(h: jnp.ndarray, p: Dict[str, jnp.ndarray],
                   config: Dict[str, Any]) -> jnp.ndarray:
    """The routed part of the sparse MLP on tokens ``h (T, d)``,
    densely, over the experts whose matrices ``p`` holds: the router's
    experts ``experts_first .. experts_first + held - 1``."""
    gates = router_gates(h, p["router"], p["router_bias"],
                         int(config["num_experts_per_tok"]),
                         bool(config["norm_topk_prob"]),
                         float(config["routed_scaling_factor"]))
    first, held = int(config.get("experts_first", 0)), \
        p["experts_gate"].shape[0]
    gates = gates[:, first:first + held]
    hidden = jax.nn.silu(jnp.einsum("td,edf->etf", h, p["experts_gate"])) \
        * jnp.einsum("td,edf->etf", h, p["experts_up"])
    return jnp.einsum(
        "etd,te->td", jnp.einsum("etf,efd->etd", hidden, p["experts_down"]),
        gates)


def shared_expert(h: jnp.ndarray, p: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    return gated_mlp(h, p["shared_gate"], p["shared_up"], p["shared_down"])


# -- the model -----------------------------------------------------------------


def layer(x: jnp.ndarray, p: Dict[str, jnp.ndarray], dense: bool,
          config: Dict[str, Any]) -> jnp.ndarray:
    eps = float(config["rms_norm_eps"])
    x = x + attention(rms_norm(x, p["attn_norm"], eps), p, config)
    h = rms_norm(x, p["mlp_norm"], eps)
    if dense:
        return x + gated_mlp(h, p["w_gate"], p["w_up"], p["w_down"])
    b, seq, d = x.shape
    tokens = h.reshape(b * seq, d)
    y = routed_experts(tokens, p, config)
    if int(config.get("n_shared_experts", 0)):
        y = y + shared_expert(tokens, p)
    return x + y.reshape(b, seq, d)


def _layer(x, p, dense, config):
    return jax.checkpoint(lambda x, p: layer(x, p, dense, config))(x, p)


def head_nll(x: jnp.ndarray, norm: jnp.ndarray, head: jnp.ndarray,
             targets: jnp.ndarray, eps: float) -> jnp.ndarray:
    """``(batch, seq)``: the negative log-likelihood of ``targets``."""
    logp = jax.nn.log_softmax(rms_norm(x, norm, eps) @ head, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def stack(params: Dict[str, Any], inputs: jnp.ndarray,
          config: Dict[str, Any]) -> jnp.ndarray:
    """The stack's last hidden state, before the final norm."""
    x = params["embed"][inputs]
    for i in range(int(config["num_hidden_layers"])):
        x = _layer(x, params[f"JoyaiBlock_{i}"],
                   i < int(config["first_k_dense_replace"]), config)
    return x


def mtp_hidden(params: Dict[str, Any], x_last: jnp.ndarray,
               next_tokens: jnp.ndarray, config: Dict[str, Any]
               ) -> jnp.ndarray:
    """The MTP module's hidden state before its final norm: position
    ``i`` from the embedding of ``next_tokens[i]`` (the token at ``i +
    1``) and ``x_last[i]``, the embedding first."""
    eps = float(config["rms_norm_eps"])
    pair = jnp.concatenate(
        [rms_norm(params["embed"][next_tokens], params["mtp_embed_norm"], eps),
         rms_norm(x_last, params["mtp_hidden_norm"], eps)], axis=-1)
    return _layer(pair @ params["mtp_proj"], params["mtp_block"], False,
                  config)


def losses(params: Dict[str, Any], tokens: jnp.ndarray,
           config: Dict[str, Any]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(NLL_main, NLL_mtp)`` over a packed grid ``(batch, seq + 1)``:
    every cell is a target of the main head, and every cell but a row's
    first two of the MTP head (position ``i`` predicts ``tokens[i +
    2]``, so the last position of the ``seq`` has nothing to
    predict)."""
    eps = float(config["rms_norm_eps"])
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x_last = stack(params, inputs, config)
    nll = jax.checkpoint(lambda x, n, hd: head_nll(x, n, hd, targets, eps))
    main = jnp.mean(nll(x_last, params["final_norm"], params["head"]))
    if not int(config.get("num_nextn_predict_layers", 0)):
        return main, jnp.zeros_like(main)
    z = mtp_hidden(params, x_last, targets, config)
    after_next = tokens[:, 2:]                       # (batch, seq - 1)
    nll = jax.checkpoint(lambda z, n, hd: head_nll(z, n, hd, after_next, eps))
    mtp = jnp.mean(nll(z[:, :-1], params["mtp_final_norm"], params["head"]))
    return main, mtp


def loss(params: Dict[str, Any], tokens: jnp.ndarray,
         config: Dict[str, Any]) -> jnp.ndarray:
    main, mtp = losses(params, tokens, config)
    return main + float(config["mtp_loss_weight"]) * mtp


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
