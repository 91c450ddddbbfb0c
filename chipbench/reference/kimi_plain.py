"""The plain reference of the Kimi-Linear block: what a configuration
with ``"reference": "kimi_plain"`` is held to.  Forward pass, loss and
gradient in straightforward ``jax.numpy``, float32, every matrix product
at ``default_matmul_precision("highest")``.  The delta attention's state
**token by token** (one ``lax.scan`` step a position: the definition, no
chunks, no triangular solve), the latent attention over a materialised
``L x L`` causal mask, the experts dense over the range held (every
token through each of them, masked by its router weights); no kernel, no
sort, no grouped product, no parameter server.  It imports nothing of
the program and exists once: the CPU tests (``tests/test_kimi.py``) hold
the program to this very module.  ``chipbench/spec.py`` finds it by the
configuration's key and has the contract of such a module
(``loss_and_grad_flat``, ``LOSS_TOL_NATS``, ``GRAD_REL_TOL``);
``chipbench/compare.py`` is the comparison every reference is held by.

The block (Kimi-Linear-48B-A3B, Moonshot; ``model_type``
``kimi_linear``; the configuration's keys are those of its
``config.json``; what the config has no key for is from the Kimi Linear
report, arXiv:2510.26692, and its public ``fla`` implementation).  All
norms are RMSNorm, weight only, eps ``rms_norm_eps``.  For hidden ``x``
of width ``hidden_size``, a layer is ``x = x + mixer(RMSNorm(x))``, ``x
= x + mlp(RMSNorm(x))``.  The mixer of layer ``n`` (counted from 1) is
**Kimi Delta Attention** where ``n`` is in ``linear_attn_config``'s
``kda_layers`` (``H`` heads of ``D``: ``num_heads``, ``head_dim``
there)::

    q~ = SiLU(Conv(h W_q)), k~ = SiLU(Conv(h W_k)), v = SiLU(Conv(h W_v))
          # depthwise causal, short_conv_kernel_size taps, no bias; the
          # last tap on the current position
    q_t = q~_t / sqrt(sum_head q~_t^2 + 1e-6) / sqrt(D)
    k_t = k~_t / sqrt(sum_head k~_t^2 + 1e-6)
    g_t = -exp(A_log_head) * softplus((h W_f1) W_f2 + dt_bias)  # H x D
    beta_t = sigmoid(h w_beta)                                  # H
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                  # S_0 = 0 at the start of a sequence
    y_t = [RMSNorm_head(o_t) * sigmoid((h W_g1) W_g2)] W_o

and **latent attention** where ``n`` is in ``full_attn_layers``:
DeepSeek-V3's with no query latent (``q_lora_rank`` null) and nothing
rotated (``mla_use_nope`` true)::

    [q_nope | q_rope] = h W_q                       # heads x (nope + rope)
    [c_kv | k_r] = h W_kva                          # kv_lora_rank + rope
    [k_nope | v] = RMSNorm(c_kv) W_kvb              # heads x (nope + v)
    q_h = [q_nope_h | q_rope_h],  k_h = [k_nope_h | k_r]   # k_r ONE head
    scores q_h k_h^T / sqrt(nope + rope), causal, softmax
    y = concat_h(P_h v_h) W_o

The MLP of layer ``n <= first_k_dense_replace`` is dense (SiLU-gated,
``intermediate_size``), else sparse::

    s = sigmoid(h W_r)                              # over all the experts
    chosen: the num_experts_per_token largest of s + b
    w_e = s_e / (sum_chosen s + 1e-20) * routed_scaling_factor
    y = sum_{e chosen and held} w_e E_e(h) + S(h)
              # E_e, S: SiLU-gated, moe_intermediate_size wide; S the
              # shared expert (num_shared_experts of them, side by side)

Then a final RMSNorm and an untied head; the loss is the mean next-token
NLL.

**The share.**  ``router_experts`` is the router's width (the published
``num_experts``); ``num_experts`` counts the experts held here, the
contiguous range from ``experts_first``.  The sigmoid, the choice with
its bias and the normalisation run over all ``router_experts``; the
routed sum runs over the held ones only; the shared expert is whole.
**The layers.**  Layers ``1 .. num_hidden_layers`` of the published
model are held, and the configuration's two lists name the mixer of
each.

Departures from the published model and its recipe, each also a line of
``assumed`` in ``chipbench/configs/kimi-linear-48b-l5e8.json``: the
output gate's second map has no bias; the selection bias ``b`` is a
parameter no rule updates, seeded away from zero, and its gradient is
zero; no auxiliary loss, no dropout; no token is dropped; ties in the
choice go to the lower expert index; weights are the program's seeded
initialisation.

Memory.  Each layer and the head is under ``jax.checkpoint``.  The
recurrence is walked in blocks of :data:`SCAN_BLOCK` positions, each
block under ``jax.checkpoint``: the backward pass keeps the state at a
block's start (``L / SCAN_BLOCK`` of them) and walks the block again,
where a plain ``lax.scan`` over ``L`` positions would keep ``L`` states
(17 GB a layer at 8192 x 32 heads of 128 x 128).  The latent attention
is walked in blocks of :data:`HEAD_BLOCK` heads, as JoyAI's reference.
Recomputation changes no number: the arithmetic is one position a step
either way.

Parameters come as the program's own pytree (the ``unravel`` of the flat
vector), read by the names ``models/transformer.py`` gave them.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

HEAD_BLOCK = 2
SCAN_BLOCK = 128

# Tolerances: PERF.md section 6, PR 43 has every reading
# (``probe_kimi.py`` beside this file and the cell's own runs made them,
# on the v5e at the published widths, one seeded sequence of 8192 a
# seed).  The gradient's limit lies between two readings: the system's
# largest (float32 in memory, one bf16 pass a product, the router's and
# the chunk's triangular solve at full precision: 0.1600-0.1651% of the
# gradient's norm over fifteen readings on fourteen seeds) and this
# file's own arithmetic with parameters and activations held in bf16,
# the nearest precision below the configuration's (0.875% on seed 1,
# 0.926% on seed 2): 2.4 times above the one and 2.2 times under the
# other.  The loss's limit is the accepted sparse cells' and refuses
# the lower precision too (the system 1e-6 to 1.3e-4 nats off, bf16 in
# memory 7.6e-3 and 2.2e-2).  What else they refuse: the delta
# term left out 14.4% and 3.8e-3 nats, a scalar decay a head 16.3%, the
# convolution reversed 28.5% and 6.0e-3, no shared expert 16.5%.  One
# product alone at one bf16 pass (the router's 0.177%, the solve's
# 0.1616%) is inside both, as in every cell.
LOSS_TOL_NATS = 1.0e-3
GRAD_REL_TOL = 4.0e-3


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


# -- Kimi Delta Attention ------------------------------------------------------


def causal_conv(u: jnp.ndarray, taps: jnp.ndarray) -> jnp.ndarray:
    """``c[t] = sum_j taps[j] u[t - (K - 1) + j]`` a channel, ``u`` zero
    before the sequence; ``u (batch, seq, channels)``, ``taps (K,
    channels)``."""
    k, seq = taps.shape[0], u.shape[1]
    out = jnp.zeros_like(u)
    for j in range(k):
        back = k - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros_like(u[:, :back]), u[:, :seq - back]], axis=1)
        out = out + taps[j] * shifted
    return out


def log_decay(h: jnp.ndarray, p: Dict[str, jnp.ndarray], heads: int
              ) -> jnp.ndarray:
    """``g (batch, seq, heads, D)``: a head's ``-exp(A_log)`` times the
    softplus of the low-rank map plus ``dt_bias``, a key channel."""
    raw = (h @ p["wf_a"]) @ p["wf_b"] + p["dt_bias"]
    raw = raw.reshape(*h.shape[:-1], heads, -1)
    return -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(raw)


def delta_step(state: jnp.ndarray, q: jnp.ndarray, k: jnp.ndarray,
               v: jnp.ndarray, g: jnp.ndarray, beta: jnp.ndarray
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One position: ``state (batch, heads, D, D)`` decayed a key
    channel, corrected by the delta term, read by the query."""
    state = jnp.exp(g)[..., None] * state
    seen = jnp.einsum("bhk,bhkv->bhv", k, state)
    state = state + beta[..., None, None] * jnp.einsum(
        "bhk,bhv->bhkv", k, v - seen)
    return state, jnp.einsum("bhk,bhkv->bhv", q, state)


def delta_rule(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
               g: jnp.ndarray, beta: jnp.ndarray) -> jnp.ndarray:
    """The recurrence over ``(batch, seq, heads, D)`` (``beta (batch,
    seq, heads)``), one position a step, in checkpointed blocks."""
    b, seq, heads, d = q.shape

    def walk(state, block):
        return jax.lax.scan(lambda s, at: delta_step(s, *at), state, block)

    along = [jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)]
    whole = seq // SCAN_BLOCK * SCAN_BLOCK
    state = jnp.zeros((b, heads, d, v.shape[-1]), v.dtype)
    out = []
    if whole:
        blocks = [x[:whole].reshape(whole // SCAN_BLOCK, SCAN_BLOCK,
                                    *x.shape[1:]) for x in along]
        state, o = jax.lax.scan(jax.checkpoint(walk), state, tuple(blocks))
        out.append(o.reshape(whole, *o.shape[2:]))
    if whole < seq:
        out.append(jax.checkpoint(walk)(
            state, tuple(x[whole:] for x in along))[1])
    return jnp.moveaxis(jnp.concatenate(out, axis=0), 0, 1)


def delta_attention(h: jnp.ndarray, p: Dict[str, jnp.ndarray],
                    config: Dict[str, Any]) -> jnp.ndarray:
    b, seq, _ = h.shape
    linear = config["linear_attn_config"]
    heads, d = int(linear["num_heads"]), int(linear["head_dim"])
    eps = float(config["rms_norm_eps"])

    def mixed(w, taps):
        return jax.nn.silu(causal_conv(h @ w, taps)).reshape(b, seq, heads, d)

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    q = unit(mixed(p["wq"], p["conv_q"])) / math.sqrt(d)
    k = unit(mixed(p["wk"], p["conv_k"]))
    v = mixed(p["wv"], p["conv_v"])
    o = delta_rule(q, k, v, log_decay(h, p, heads),
                   jax.nn.sigmoid(h @ p["w_beta"]))
    gate = jax.nn.sigmoid((h @ p["wg_a"]) @ p["wg_b"])
    o = rms_norm(o, p["o_norm"], eps) * gate.reshape(b, seq, heads, d)
    return o.reshape(b, seq, heads * d) @ p["wo"]


# -- latent attention ----------------------------------------------------------


@jax.checkpoint
def _heads(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
           mask: jnp.ndarray) -> jnp.ndarray:
    """Masked softmax attention of ``q, k (batch, heads, seq, qk)`` and
    ``v (batch, heads, seq, v)``, head by head."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    scores = jnp.where(mask, scores, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)


def latent_attention(h: jnp.ndarray, p: Dict[str, jnp.ndarray],
                     config: Dict[str, Any]) -> jnp.ndarray:
    b, seq, _ = h.shape
    heads = int(config["num_attention_heads"])
    nope, rope = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    v_dim, rank = int(config["v_head_dim"]), int(config["kv_lora_rank"])
    eps = float(config["rms_norm_eps"])

    def split(x, width):
        return x.reshape(b, seq, heads, width).transpose(0, 2, 1, 3)

    q = split(h @ p["wq"], nope + rope)
    kv_a = h @ p["wkv_a"]
    kv = split(rms_norm(kv_a[..., :rank], p["kv_a_norm"], eps) @ p["wkv_b"],
               nope + v_dim)
    k_shared = kv_a[:, None, :, rank:]                       # one head
    k = jnp.concatenate([kv[..., :nope], jnp.repeat(k_shared, heads, axis=1)],
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
                         int(config["num_experts_per_token"]),
                         bool(config["moe_renormalize"]),
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


def mixer_of(number: int, config: Dict[str, Any]) -> str:
    """The mixer of the published model's layer ``number``, from 1."""
    linear = config["linear_attn_config"]
    if number in linear["kda_layers"]:
        return "kda"
    if number in linear["full_attn_layers"]:
        return "full_attention"
    raise ValueError(f"layer {number} is in neither list of "
                     "linear_attn_config")


def layer(x: jnp.ndarray, p: Dict[str, jnp.ndarray], number: int,
          config: Dict[str, Any]) -> jnp.ndarray:
    eps = float(config["rms_norm_eps"])
    mixer = (delta_attention if mixer_of(number, config) == "kda"
             else latent_attention)
    x = x + mixer(rms_norm(x, p["attn_norm"], eps), p, config)
    h = rms_norm(x, p["mlp_norm"], eps)
    if number <= int(config["first_k_dense_replace"]):
        return x + gated_mlp(h, p["w_gate"], p["w_up"], p["w_down"])
    b, seq, d = x.shape
    tokens = h.reshape(b * seq, d)
    y = routed_experts(tokens, p, config)
    if int(config.get("num_shared_experts", 0)):
        y = y + shared_expert(tokens, p)
    return x + y.reshape(b, seq, d)


def _layer(x, p, number, config):
    return jax.checkpoint(lambda x, p: layer(x, p, number, config))(x, p)


def head_nll(x: jnp.ndarray, norm: jnp.ndarray, head: jnp.ndarray,
             targets: jnp.ndarray, eps: float) -> jnp.ndarray:
    """``(batch, seq)``: the negative log-likelihood of ``targets``."""
    logp = jax.nn.log_softmax(rms_norm(x, norm, eps) @ head, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def loss(params: Dict[str, Any], tokens: jnp.ndarray,
         config: Dict[str, Any]) -> jnp.ndarray:
    """Mean next-token NLL over a packed grid ``(batch, seq + 1)``:
    every cell is a target, no padding."""
    eps = float(config["rms_norm_eps"])
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = params["embed"][inputs]
    for i in range(int(config["num_hidden_layers"])):
        x = _layer(x, params[f"KimiBlock_{i}"], i + 1, config)
    nll = jax.checkpoint(lambda x, n, hd: head_nll(x, n, hd, targets, eps))
    return jnp.mean(nll(x, params["final_norm"], params["head"]))


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
