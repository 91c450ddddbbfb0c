"""The plain reference of the dense state-space hybrid: what a
configuration with ``"reference": "granite_plain"`` is held to.  Forward
pass, loss and gradient in straightforward ``jax.numpy``, float32, every
matrix product at ``default_matmul_precision("highest")``.  **The state
is stepped a position at a time** (``lax.scan`` over the sequence, the
recurrence as it is written, no chunks); the convolution is four shifted
products, the attention a materialised mask and a softmax over the whole
row, the head the table's transpose; no kernel, no checkpoint policy by
name, no custom derivative, no parameter server.  It imports nothing of
the program.  ``chipbench/spec.py`` finds it by the configuration's key
and has the contract of such a module (``loss_and_grad_flat``,
``LOSS_TOL_NATS``, ``GRAD_REL_TOL``); ``chipbench/compare.py`` is the
comparison every reference is held by.

The block (IBM Granite-4.0-H-Micro, ``model_type`` ``granitemoehybrid``,
``num_local_experts`` 0; the configuration's keys are those of its
``config.json``; what they do not carry is listed under ``assumed`` in
the configuration's file).  With ``e = embedding_multiplier``, ``r =
residual_multiplier``, ``m = attention_multiplier``, ``s =
logits_scaling``, ``T`` the token table ``(vocab_size, hidden_size)``
and weight-only RMSNorms at ``rms_norm_eps``::

    u = e T[tokens]
    layer l, layer_types[l] in {mamba, attention}:
      u = u + r Mixer_l(RMSNorm(u; w_l))
      u = u + r MLP(RMSNorm(u; w'_l))
      MLP(h) = (SiLU(h W_a) * (h W_b)) W_o    [W_a | W_b] one matrix,
                                              hidden x 2 shared_intermediate_size

    mamba(h):  H = mamba_n_heads, P = mamba_d_head, G = mamba_n_groups,
               N = mamba_d_state
      [z | xBC | dt] = h W_in          widths H P | H P + 2 G N | H
      xBC = SiLU(conv(xBC) + b)        causal, depthwise, mamba_d_conv
                                       taps a channel; the last tap on
                                       the current position
      x (H x P), B, C (G x N each) = split(xBC)
      D_t = softplus(dt_t + dt_bias)   a head, no clamp
      a_t = exp(D_t A),  A = -exp(A_log)      one scalar a head
      head h of group g = h // (H / G)  (published: ONE group, B and C
                                         shared by all 64 heads):
        S_t = a_t S_{t-1} + D_t x_t (x) B_t   (P x N; S = 0 at the
                                              start of a row, nowhere
                                              else)
        y_t = S_t C_t + D_h x_t
      y = RMSNormGroup(y * SiLU(z); w)  the gate BEFORE the norm; the
                                        mean square over each group's
                                        H P / G channels (published: all
                                        4096), one weight a channel
      mamba = y W_out

    attention(h): q = h Wq (num_attention_heads x head), k = h Wk,
            v = h Wv (num_key_value_heads x head), head = hidden_size /
            num_attention_heads; no positional term, no norm, no gate,
            no bias; query head i on KV head i // (heads / kv heads);
            p = softmax over j <= i of  m q_i . k_j  (NOT / sqrt(head));
            attention = (sum_j p v_j) Wo

    logits = RMSNorm(u; w_f) T^T / s

The loss is the mean next-token negative log-likelihood over a packed
grid.  ``T`` is one leaf: its gradient is the look-up's plus the head's.

Memory.  The recurrence is walked in blocks of :data:`SCAN_BLOCK`
positions, each under ``jax.checkpoint``: the backward pass keeps a
state a block (2 MB a row at the published sizes) and one block's
states, not 4096.  The attention is walked in blocks of
:data:`HEAD_BLOCK` query heads inside a ``lax.map``, each under
``jax.checkpoint``, and so is each layer as a whole.  Recomputation
changes no number.

Parameters come as the program's own pytree (the ``unravel`` of the flat
vector), read by the names ``models/transformer.py`` gave them.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

HEAD_BLOCK = 2
SCAN_BLOCK = 128
KINDS = ("mamba", "attention")

# Tolerances: ``probe_granite.py`` beside this file and the cell's own
# runs made every reading (PERF.md section 6, PR 65, has them with their
# origin), on the v5e at the published widths of ``granite4h-l10-local``,
# one seeded sequence of 4096 a seed; the configuration's file has each
# limit with its reason (``limits``).  The gradient's limit lies between
# two readings: the system's (float32 in memory, one bf16 pass a
# product, the carry and the log-decays' sums float32: 1.669-1.687% of
# the gradient's norm on the probe's three seeds and in the cell's own
# ten runs, thirteen seeds) and this file's own arithmetic with parameters and
# activations held in bf16, the nearest precision below the
# configuration's (2.280%).  The system reads five times Nemotron's
# 0.3% because the gradient of the stream crosses twenty branches, two
# products each, and here the branches, not the looked-up rows, make
# the stream (``stream_rms`` 1.07 at the seed where the rows enter at
# 0.24): every product's rounding reaches the table's gradient whole,
# as Ouro's twenty-four layer passes read 1.0-1.4%.  1.95% lies 15.6%
# over the one and the bf16 reference 17% over it.  It refuses the
# scan's sums in bf16 (5.56%), ``attention_multiplier`` read as 1/8
# (2.50%), the other three multipliers read as 1 (146-773%) and the
# head untied (15.7%); it cannot refuse the state rounded to bf16
# between chunks (1.686%: what that adds, 0.11%, is under the system's
# own spread), which ``tests/test_granite.py`` holds on the CPU.  The
# loss cannot tell the lowered precisions apart (the system 4e-6 to
# 7.7e-5 nats off over the thirteen seeds, the lowered ones 7e-6 to
# 4e-5) and its limit is the accepted cells', thirteen times the
# system's largest: a guard against a
# wrong loss (``residual_multiplier`` as 1 reads 1.5e-3,
# ``logits_scaling`` as 1 0.86).
LOSS_TOL_NATS = 1.0e-3
GRAD_REL_TOL = 1.95e-2


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def layer_kinds(config: Dict[str, Any]) -> List[str]:
    kinds = [str(kind) for kind in config["layer_types"]]
    if len(kinds) != int(config["num_hidden_layers"]) or set(kinds) - set(
            KINDS):
        raise ValueError(f"layer_types {kinds} names {len(kinds)} layers of "
                         f"{KINDS}, num_hidden_layers is "
                         f"{config['num_hidden_layers']}")
    return kinds


def conv_silu(u: jnp.ndarray, taps: jnp.ndarray,
              bias: jnp.ndarray) -> jnp.ndarray:
    """``SiLU(sum_j taps[j] u[t - (K - 1) + j] + bias)`` a channel, ``u``
    zero before the row: ``K`` shifted products."""
    k, seq = taps.shape[0], u.shape[1]
    total = bias
    for j in range(k):
        back = k - 1 - j                       # positions behind t
        shifted = jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :seq]
        total = total + taps[j] * shifted
    return jax.nn.silu(total)


def recurrence(x: jnp.ndarray, step: jnp.ndarray, rate: jnp.ndarray,
               b: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """``y_t = S_t C_t`` with ``S_t = exp(step_t rate) S_{t-1} + step_t
    x_t (x) B_t``, one position a ``lax.scan`` step.  ``x (batch, seq,
    H, P)``, ``step (batch, seq, H)``, ``rate (H,)``, ``b, c (batch,
    seq, G, N)``."""
    batch, seq, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    per = heads // groups

    def one(state, at):
        x_t, step_t, b_t, c_t = at
        b_t = jnp.repeat(b_t, per, axis=1)             # (batch, H, N)
        c_t = jnp.repeat(c_t, per, axis=1)
        state = (jnp.exp(step_t * rate)[..., None, None] * state
                 + (step_t[..., None] * x_t)[..., :, None]
                 * b_t[..., None, :])
        return state, jnp.sum(state * c_t[..., None, :], axis=-1)

    @jax.checkpoint
    def block(state, chunk):
        return jax.lax.scan(one, state, chunk)

    short = -seq % SCAN_BLOCK
    along = []
    for m in (x, step, b, c):     # a filled position neither decays nor
        m = jnp.pad(m, ((0, 0), (0, short)) + ((0, 0),) * (m.ndim - 2))
        m = jnp.moveaxis(m, 1, 0)  # writes (step 0), and is cut off
        along.append(m.reshape((-1, SCAN_BLOCK) + m.shape[1:]))
    state = jnp.zeros((batch, heads, p, n), jnp.float32)
    _, y = jax.lax.scan(block, state, tuple(along))
    return jnp.moveaxis(y.reshape((-1,) + y.shape[2:]), 0, 1)[:, :seq]


def mamba(h: jnp.ndarray, p: Dict[str, jnp.ndarray],
          config: Dict[str, Any]) -> jnp.ndarray:
    """The Mamba-2 mixer on the normed input ``h (batch, seq, d)``."""
    heads, width = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    groups, n = int(config["mamba_n_groups"]), int(config["mamba_d_state"])
    batch, seq, _ = h.shape
    inner, shared = heads * width, groups * n
    projected = h @ p["w_in"]
    z = projected[..., :inner]
    xbc = conv_silu(projected[..., inner:2 * inner + 2 * shared],
                    p["conv_w"], p["conv_b"])
    dt = projected[..., 2 * inner + 2 * shared:]
    x = xbc[..., :inner].reshape(batch, seq, heads, width)
    b = xbc[..., inner:inner + shared].reshape(batch, seq, groups, n)
    c = xbc[..., inner + shared:].reshape(batch, seq, groups, n)
    step = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(x, step, -jnp.exp(p["a_log"]), b, c) \
        + p["d_skip"][:, None] * x
    y = y.reshape(batch, seq, inner) * jax.nn.silu(z)
    y = y.reshape(batch, seq, groups, inner // groups)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                     + float(config["rms_norm_eps"]))
    return (y.reshape(batch, seq, inner) * p["ssm_norm"]) @ p["w_out"]


@jax.checkpoint
def _heads(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
           multiplier: jnp.ndarray) -> jnp.ndarray:
    """Causal softmax attention of ``q (batch, heads, seq, head)`` over
    one KV head ``k, v (batch, seq, head)``, the scores multiplied by
    ``multiplier``."""
    seq = q.shape[2]
    scores = jnp.einsum("bhqd,bkd->bhqk", q, k) * multiplier
    live = jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None]
    scores = jnp.where(live[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkd->bhqd", jax.nn.softmax(scores, axis=-1), v)


def attention(h: jnp.ndarray, p: Dict[str, jnp.ndarray],
              config: Dict[str, Any]) -> jnp.ndarray:
    """The grouped attention on the normed input ``h``: no positional
    term, the scores times ``attention_multiplier``."""
    n_head = int(config["num_attention_heads"])
    n_kv = int(config["num_key_value_heads"])
    head = int(config["hidden_size"]) // n_head
    b, seq, _ = h.shape
    q = (h @ p["wq"]).reshape(b, seq, n_head, head).transpose(0, 2, 1, 3)
    k = (h @ p["wk"]).reshape(b, seq, n_kv, head).transpose(2, 0, 1, 3)
    v = (h @ p["wv"]).reshape(b, seq, n_kv, head).transpose(2, 0, 1, 3)
    group = n_head // n_kv
    step = min(HEAD_BLOCK, group)
    blocks = n_head // step
    kv_of = jnp.arange(blocks) * step // group
    multiplier = jnp.float32(config["attention_multiplier"])
    out = jax.lax.map(
        lambda block: _heads(*block, multiplier),
        (q.reshape(b, blocks, step, seq, head).transpose(1, 0, 2, 3, 4),
         k[kv_of], v[kv_of]))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, n_head, seq, head)
    return out.transpose(0, 2, 1, 3).reshape(b, seq, n_head * head) @ p["wo"]


def gated_mlp(h: jnp.ndarray, p: Dict[str, jnp.ndarray],
              config: Dict[str, Any]) -> jnp.ndarray:
    """``(SiLU(h W_a) * (h W_b)) W_o`` with ``[W_a | W_b]`` one matrix."""
    width = int(config["shared_intermediate_size"])
    both = h @ p["mlp_in"]
    return (jax.nn.silu(both[..., :width]) * both[..., width:]) @ p["mlp_out"]


def layer(u: jnp.ndarray, p: Dict[str, jnp.ndarray], kind: str,
          config: Dict[str, Any]) -> jnp.ndarray:
    """One layer on the stream ``u (batch, seq, d)``: the mixer, then the
    MLP, each times the residual multiplier."""
    eps, r = float(config["rms_norm_eps"]), float(
        config["residual_multiplier"])
    mixer = mamba if kind == "mamba" else attention
    u = u + r * mixer(rms_norm(u, p["norm"], eps), p, config)
    return u + r * gated_mlp(rms_norm(u, p["mlp_norm"], eps), p, config)


def block_names(config: Dict[str, Any]) -> List[str]:
    return [f"GraniteBlock_{i}"
            for i in range(int(config["num_hidden_layers"]))]


def loss(params: Dict[str, Any], tokens: jnp.ndarray,
         config: Dict[str, Any], head: Any = None) -> jnp.ndarray:
    """Mean next-token negative log-likelihood over a packed grid
    ``(batch, seq + 1)``; the head is the token table transposed.
    ``head``: a matrix ``(vocab, hidden)`` the logits are taken against
    in the table's place, for a caller that tells the table's two uses
    apart (the probe's and the tests' untied head); None: the table."""
    table = params["embed"]
    u = float(config["embedding_multiplier"]) * table[tokens[:, :-1]]
    for name, kind in zip(block_names(config), layer_kinds(config)):
        u = jax.checkpoint(
            lambda u, p, kind=kind: layer(u, p, kind, config))(
                u, params[name])
    x = rms_norm(u, params["final_norm"], float(config["rms_norm_eps"]))
    logits = x @ (table if head is None else head).T / float(
        config["logits_scaling"])
    logp = jax.nn.log_softmax(logits, axis=-1)
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
