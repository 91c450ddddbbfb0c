"""The plain reference of the Qwen3-Next block: what a configuration
with ``"reference": "qwen3next_plain"`` is held to.  Forward pass, loss
and gradient in straightforward ``jax.numpy``, float32, every matrix
product at ``default_matmul_precision("highest")``.  The gated delta
rule's state **a position at a time** (one ``lax.scan`` step a position:
the definition, ``linear_num_key_heads`` key heads, one scalar decay a
value head, no chunks, no triangular solve, no key repeated and no decay
broadcast), the convolution as shifted products, the attention over a
materialised causal mask in blocks of heads with the rotation written
out over the first dimensions of a head, the experts an expert at a time
over the range held (every token through each, masked by its router
weight); no kernel, no sort, no grouped product, no parameter server.
It imports nothing of the program and exists once: the CPU tests
(``tests/test_qwen3next.py``) hold the program to this very module.
``chipbench/spec.py`` finds it by the configuration's key and has the
contract of such a module (``loss_and_grad_flat``, ``LOSS_TOL_NATS``,
``GRAD_REL_TOL``); ``chipbench/compare.py`` is the comparison every
reference is held by.

The block (Qwen3-Next-80B-A3B, Qwen; ``model_type`` ``qwen3_next``; the
configuration's keys are those of its ``config.json``, the equations the
public ``qwen3_next`` module's).  ``N(x; w) = x / sqrt(mean x^2 + eps)
(1 + w)``: **every norm on the stream and on the attention's heads
stores its weight as an offset from one**.  Layer ``l`` (from 0) is ``u
= u + Mixer_l(N(u))``, ``u = u + MoE_l(N(u))``; its mixer is
``full_attention`` where ``(l + 1) mod full_attention_interval = 0`` and
``linear_attention`` otherwise.

``linear_attention`` (Gated DeltaNet; ``H_k`` key heads of ``d_k``,
``H_v = r H_k`` value heads of ``d_v``)::

    [q | k | v | z] = h W_qkvz,   [b | a] = h W_ba
    [q | k | v] = SiLU(Conv([q | k | v]))   # depthwise causal, no bias,
                  # linear_conv_kernel_dim taps, the last on position t
    q_t = q_t / sqrt(sum_head q_t^2 + 1e-6) / sqrt(d_k)
    k_t = k_t / sqrt(sum_head k_t^2 + 1e-6)
    beta_t = sigmoid(b_t)                                   # H_v
    g_t = -exp(A_log) softplus(a_t + dt_bias)               # H_v
    S_t = (I - beta_t k_t k_t^T) exp(g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t        # a value head's state, d_k x d_v, zero at the
                           # start of a row; value head i reads key head
                           # i // r
    y_t = [RMSNorm_head(o_t; w_o) * SiLU(z_t)] W_out
                           # a plain weight (not 1 + w), norm THEN gate

``full_attention``: ``num_attention_heads`` query heads of ``head_dim``
over ``num_key_value_heads`` key/value heads (query head ``i`` on head
``i // group``); ``q = N_head(h W_q; w_q)``, ``k = N_head(h W_k; w_k)``;
**the first ``partial_rotary_factor x head_dim`` dimensions of every
query and key head rotated** (the half-split pairing over those
dimensions: pair ``j`` is dimensions ``j`` and ``j + rot / 2``, angle
``t rope_theta^(-2j / rot)``), the rest pass; scores ``q . k /
sqrt(head_dim)``, causal, softmax; the heads' output times ``sigmoid(h
W_g)`` elementwise; ``W_o``.

The sparse MLP::

    p = softmax(h W_r)                          # over all the experts
    chosen: the num_experts_per_tok largest of p (ties: the lower index)
    w_e = p_e / sum_chosen p                    # norm_topk_prob
    y = sum_{e chosen and held} w_e E_e(h) + sigmoid(h w_s) S(h)
              # E_e, S: SiLU-gated; S the shared expert, every token

Then ``N`` and an untied head; the loss is the mean next-token NLL.

**The share.**  ``router_experts`` is the router's width (the published
``num_experts``); the held experts' count is the leading axis of the
experts' leaves, the contiguous range from ``experts_first``.  The
softmax, the choice and the normalisation run over all
``router_experts``; the routed sum runs over the held ones only; the
shared expert and its gate are whole.

Memory.  Each layer is under ``jax.checkpoint``.  The recurrence is
walked in blocks of :data:`SCAN_BLOCK` positions, each under
``jax.checkpoint``: the backward pass keeps the state at a block's
start and walks the block again, never ``L`` states.  The attention is
walked in blocks of :data:`HEAD_BLOCK` query heads inside a ``lax.map``,
each under ``jax.checkpoint``.  Recomputation changes no number.

Parameters come as the program's own pytree (the ``unravel`` of the flat
vector), read by the names ``models/transformer.py`` gave them.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

HEAD_BLOCK = 2
SCAN_BLOCK = 128
L2_EPS = 1e-6

# Tolerances: ``probe_qwen3next.py`` beside this file and the cell's own
# runs made every reading (PERF.md section 6, PR 61, has them with their
# origin), on the v5e at the published widths of
# ``qwen3next-l4e32-local``, one seeded sequence of 8192 a seed.  The
# gradient's limit lies between two readings: the system's largest
# (float32 in memory, one bf16 pass a product, the router's product and
# the chunk's triangular solve at full precision, log-decays summed in
# float32: 0.1584-0.1598% of the gradient's norm on the probe's three
# seeds) and this file's own arithmetic with parameters and activations
# held in bf16, the nearest precision below the configuration's
# (0.3352, 0.3381 and 0.3399% on the same seeds): 0.240% lies 1.5 times
# above the one and 1.4 times under the other.  What it cannot refuse,
# read by the same probe: the system with the router's product at one
# bf16 pass (0.1593-0.1611%: ten of 512 probabilities flip in too few
# rows to move the gradient's 2-norm, as in every cell) and the system
# with the scan's summed log-decays held in bf16 (0.1583-0.1598%: at
# the seeded weights the whole gradient hardly feels a delta layer's
# decays); ``tests/test_qwen3next.py`` holds the sums' dtype and the
# router's rule on the CPU, where nothing else rounds.  The loss cannot
# tell any of them apart (the system 2.6e-5 to 7.1e-5 nats off, the
# bf16 reference 1.1e-5 to 4.7e-5) and its limit is the accepted sparse
# cells', a guard against a wrong loss (a plain weight where the offset
# norm belongs, the gate before the head norm, the whole head rotated,
# the shared expert ungated are each refused at the tiny size:
# ``tests/test_qwen3next.py``).
LOSS_TOL_NATS = 1.0e-3
GRAD_REL_TOL = 2.4e-3


def offset_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float) -> jnp.ndarray:
    """``x / sqrt(mean x^2 + eps) (1 + w)``."""
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


def mixer_of(layer: int, config: Dict[str, Any]) -> str:
    """The mixer of the published model's layer ``layer``, from 0."""
    every = int(config["full_attention_interval"])
    return "full_attention" if (layer + 1) % every == 0 \
        else "linear_attention"


# -- Gated DeltaNet ------------------------------------------------------------


def conv_silu(u: jnp.ndarray, taps: jnp.ndarray) -> jnp.ndarray:
    """``SiLU(sum_j taps[j] u[t - (K - 1) + j])`` a channel, ``u`` zero
    before the row: ``K`` shifted products, no bias."""
    k, seq = taps.shape[0], u.shape[1]
    total = jnp.zeros_like(u)
    for j in range(k):
        back = k - 1 - j                       # positions behind t
        shifted = jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :seq]
        total = total + taps[j] * shifted
    return jax.nn.silu(total)


def delta_rule(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
               g: jnp.ndarray, beta: jnp.ndarray) -> jnp.ndarray:
    """Equation 5 as written, one position a ``lax.scan`` step: ``q, k
    (batch, seq, H_k, d_k)``, ``v (batch, seq, H_v, d_v)``, ``g, beta
    (batch, seq, H_v)``.  The state is ``(batch, H_k, r, d_k, d_v)``: a
    key head's ``r`` value heads side by side, so no key is repeated."""
    batch, seq, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r = hv // hk

    def one(state, at):
        q_t, k_t, v_t, g_t, beta_t = at
        state = jnp.exp(g_t)[..., None, None] * state
        seen = jnp.einsum("bjk,bjrkv->bjrv", k_t, state)
        state = state + jnp.einsum(
            "bjk,bjrv->bjrkv", k_t, beta_t[..., None] * (v_t - seen))
        return state, jnp.einsum("bjk,bjrkv->bjrv", q_t, state)

    @jax.checkpoint
    def block(state, chunk):
        return jax.lax.scan(one, state, chunk)

    short = -seq % SCAN_BLOCK
    along = []
    for m, shape in ((q, (hk, dk)), (k, (hk, dk)), (v, (hk, r, dv)),
                     (g, (hk, r)), (beta, (hk, r))):
        # a filled position neither decays (g 0) nor writes (beta 0, k
        # 0), and is cut off
        m = jnp.pad(m, ((0, 0), (0, short)) + ((0, 0),) * (m.ndim - 2))
        m = jnp.moveaxis(m, 1, 0).reshape((-1, SCAN_BLOCK, batch) + shape)
        along.append(m)
    state = jnp.zeros((batch, hk, r, dk, dv), jnp.float32)
    _, o = jax.lax.scan(block, state, tuple(along))
    o = o.reshape((-1, batch, hv, dv))
    return jnp.moveaxis(o, 0, 1)[:, :seq]


def gated_delta_net(h: jnp.ndarray, p: Dict[str, jnp.ndarray],
                    config: Dict[str, Any]) -> jnp.ndarray:
    """The mixer on the normed input ``h (batch, seq, d)``."""
    hk, hv = int(config["linear_num_key_heads"]), int(
        config["linear_num_value_heads"])
    dk, dv = int(config["linear_key_head_dim"]), int(
        config["linear_value_head_dim"])
    batch, seq, _ = h.shape
    keys, values = hk * dk, hv * dv
    qkvz, ba = h @ p["w_qkvz"], h @ p["w_ba"]
    qkv = conv_silu(qkvz[..., :2 * keys + values], p["conv"])
    z = qkvz[..., 2 * keys + values:].reshape(batch, seq, hv, dv)

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)

    q = unit(qkv[..., :keys].reshape(batch, seq, hk, dk)) / math.sqrt(dk)
    k = unit(qkv[..., keys:2 * keys].reshape(batch, seq, hk, dk))
    v = qkv[..., 2 * keys:].reshape(batch, seq, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])
    o = delta_rule(q, k, v, g, beta)
    # the head norm's weight is plain, and the norm comes before the gate
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                     + float(config["rms_norm_eps"])) * p["o_norm"]
    return (o * jax.nn.silu(z)).reshape(batch, seq, values) @ p["wo"]


# -- gated attention with a partly rotated head --------------------------------


def rotate_first(x: jnp.ndarray, rot: int, theta: float) -> jnp.ndarray:
    """The first ``rot`` dimensions of ``x (batch, seq, heads, head)``
    rotated, written out: dimension ``j < rot / 2`` pairs with ``j + rot
    / 2`` at the angle ``t theta^(-2j / rot)``; dimensions ``rot ..``
    pass."""
    seq, half = x.shape[1], rot // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = (t[None, :, None, :] for t in (jnp.cos(angle), jnp.sin(angle)))
    lo, hi, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate(
        [lo * cos - hi * sin, hi * cos + lo * sin, rest], axis=-1)


@jax.checkpoint
def _heads(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Causal softmax attention of ``q (batch, heads, seq, head)`` over
    one KV head ``k, v (batch, seq, head)``."""
    seq = q.shape[2]
    scores = jnp.einsum("bhqd,bkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    live = jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None]
    scores = jnp.where(live[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkd->bhqd", jax.nn.softmax(scores, axis=-1), v)


def attention(h: jnp.ndarray, p: Dict[str, jnp.ndarray],
              config: Dict[str, Any]) -> jnp.ndarray:
    """The gated grouped attention on the normed input ``h``."""
    n_head = int(config["num_attention_heads"])
    n_kv = int(config["num_key_value_heads"])
    head = int(config["head_dim"])
    rot = int(head * float(config["partial_rotary_factor"]))
    theta, eps = float(config["rope_theta"]), float(config["rms_norm_eps"])
    b, seq, _ = h.shape
    q = offset_norm((h @ p["wq"]).reshape(b, seq, n_head, head),
                    p["q_norm"], eps)
    k = offset_norm((h @ p["wk"]).reshape(b, seq, n_kv, head),
                    p["k_norm"], eps)
    q = rotate_first(q, rot, theta).transpose(0, 2, 1, 3)
    k = rotate_first(k, rot, theta).transpose(2, 0, 1, 3)
    v = (h @ p["wv"]).reshape(b, seq, n_kv, head).transpose(2, 0, 1, 3)
    group = n_head // n_kv
    step = min(HEAD_BLOCK, group)
    blocks = n_head // step
    kv_of = jnp.arange(blocks) * step // group
    out = jax.lax.map(
        lambda block: _heads(*block),
        (q.reshape(b, blocks, step, seq, head).transpose(1, 0, 2, 3, 4),
         k[kv_of], v[kv_of]))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, n_head, seq, head)
    out = out.transpose(0, 2, 1, 3).reshape(b, seq, n_head * head)
    return (out * jax.nn.sigmoid(h @ p["wg"])) @ p["wo"]


# -- the sparse MLP ------------------------------------------------------------


def gated_mlp(h: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
              w_down: jnp.ndarray) -> jnp.ndarray:
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def router_gates(h: jnp.ndarray, router: jnp.ndarray,
                 config: Dict[str, Any]) -> jnp.ndarray:
    """``gates (T, E)`` over all the router's experts: the softmax
    probabilities of the ``num_experts_per_tok`` largest (an expert's
    rank is its place in a stable descending sort: of two equal ones the
    lower index comes first), divided by their sum (``norm_topk_prob``);
    the rest zero.  No bias, no scale."""
    probs = jax.nn.softmax(h @ router, axis=-1)
    order = jnp.argsort(-probs, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    gates = jnp.where(rank < int(config["num_experts_per_tok"]), probs, 0.0)
    if bool(config["norm_topk_prob"]):
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates


@jax.checkpoint
def _one_expert(h: jnp.ndarray, gate: jnp.ndarray, w_gate: jnp.ndarray,
                w_up: jnp.ndarray, w_down: jnp.ndarray) -> jnp.ndarray:
    """Every token through one expert, masked by its gate (zero where
    the token did not choose it)."""
    return gate[:, None] * gated_mlp(h, w_gate, w_up, w_down)


def sparse_mlp(h: jnp.ndarray, p: Dict[str, jnp.ndarray],
               config: Dict[str, Any]) -> jnp.ndarray:
    """The sparse layer on tokens ``h (T, d)``: the held experts' part
    of the routed sum, an expert at a time, plus the shared expert times
    its own gate."""
    gates = router_gates(h, p["router"], config)
    first, held = int(config.get("experts_first", 0)), p[
        "experts_up"].shape[0]
    y = jnp.zeros_like(h)
    for e in range(held):
        y = y + _one_expert(h, gates[:, first + e], p["experts_gate"][e],
                            p["experts_up"][e], p["experts_down"][e])
    shared = gated_mlp(h, p["shared_gate"], p["shared_up"], p["shared_down"])
    return y + jax.nn.sigmoid(h @ p["shared_expert_gate"]) * shared


# -- the model -----------------------------------------------------------------


def layer(u: jnp.ndarray, p: Dict[str, jnp.ndarray], kind: str,
          config: Dict[str, Any]) -> jnp.ndarray:
    """One layer on the stream ``u (batch, seq, d)``: its mixer, then
    its sparse MLP."""
    eps = float(config["rms_norm_eps"])
    mixer = gated_delta_net if kind == "linear_attention" else attention
    u = u + mixer(offset_norm(u, p["attn_norm"], eps), p, config)
    b, seq, d = u.shape
    h = offset_norm(u, p["mlp_norm"], eps).reshape(b * seq, d)
    return u + sparse_mlp(h, p, config).reshape(b, seq, d)


def block_names(config: Dict[str, Any]) -> List[str]:
    return [f"Qwen3NextBlock_{i}"
            for i in range(int(config["num_hidden_layers"]))]


def loss(params: Dict[str, Any], tokens: jnp.ndarray,
         config: Dict[str, Any]) -> jnp.ndarray:
    """Mean next-token negative log-likelihood over a packed grid
    ``(batch, seq + 1)``: the published model's layers ``0 ..
    num_hidden_layers - 1``."""
    u = params["embed"][tokens[:, :-1]]
    for number, name in enumerate(block_names(config)):
        kind = mixer_of(number, config)
        u = jax.checkpoint(
            lambda u, p, kind=kind: layer(u, p, kind, config))(
                u, params[name])
    x = offset_norm(u, params["final_norm"], float(config["rms_norm_eps"]))
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
    def fn(flat, tok):
        nll, grads = jax.value_and_grad(loss)(unravel(flat), tok, config)
        return nll, jnp.concatenate(
            [leaf.reshape(-1) for leaf in jax.tree_util.tree_leaves(grads)])

    with jax.default_matmul_precision("highest"):
        return jax.jit(fn)(w, tokens)
