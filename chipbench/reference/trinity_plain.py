"""The plain reference of the Trinity block: what a configuration with
``"reference": "trinity_plain"`` is held to.  Forward pass, loss,
gradient and **the balancing rule's step** in straightforward
``jax.numpy``, float32, every matrix product at
``default_matmul_precision("highest")``.  Materialised masks, a softmax
over the whole row, dense over the experts held, the rule written out
here; no kernel, no sort of tokens, no grouped product, no checkpoint
policy, no custom derivative, no parameter server.  It imports nothing
of the program.  ``chipbench/spec.py`` finds it by the configuration's
key and has the contract of such a module (``loss_and_grad_flat``,
``LOSS_TOL_NATS``, ``GRAD_REL_TOL``); ``chipbench/compare.py`` is the
comparison every reference is held by.

The block (Trinity-Mini, Arcee, ``model_type`` ``afmoe``; the
configuration's keys are those of its ``config.json``; what they do not
carry is listed under ``assumed`` in the configuration's file).  The
input is ``u = table[ids] * sqrt(hidden_size)`` (``mup_enabled``).  For
hidden ``u`` of width ``hidden_size``, position ``i`` of a sequence, in
every layer, with four RMSNorms (weight only, ``rms_norm_eps``)::

    u = u + N2(Attn(N1(u)))
    u = u + N4(Mlp(N3(u)))

    Attn(x):
      q = RMSNorm_h(x Wq)   (num_attention_heads x head_dim)
      k = RMSNorm_h(x Wk),  v = x Wv   (num_key_value_heads x head_dim)
      g = x Wg              (num_attention_heads x head_dim)
          # RMSNorm_h: over each head's width, one weight of head_dim
          # for the queries and one for the keys; no bias anywhere
      layer_types[l] == "sliding_attention":
          q, k = rope(q), rope(k)     # rotate-half, rope_theta, all of
                                      # head_dim; keys i - W < j <= i,
                                      # W = sliding_window
      layer_types[l] == "full_attention":
          no positional term; keys j <= i
      query head h attends KV head h // (heads / kv heads):
          p[i, j] = softmax over the layer's keys of q_i . k_j / sqrt(head_dim)
      Attn = ((sum_j p[i, j] v_j) * sigmoid(g_i)) Wo

    Mlp(x), layer index below num_dense_layers:
      (SiLU(x W_gate) * (x W_up)) W_down          (intermediate_size)
    Mlp(x), else:
      s = sigmoid(x Wr)                           # over all the experts
      chosen: the num_experts_per_tok largest of s + b (a tie to the
              lower index); b is the selection bias
      w_e = s_e / (sum of the chosen s + 1e-20) * route_scale
      sum_{e chosen and held} w_e (SiLU(x Wg_e) * (x Wu_e)) Wd_e
        + (SiLU(x Sg) * (x Su)) Sd                # the shared expert

Then a final RMSNorm and an untied head; the loss is the mean next-token
negative log-likelihood over a packed grid.

**The rule** (``load_balance_coeff`` ``c``; Wang et al.,
arXiv:2408.15664).  No gradient reaches ``b``.  After the forward pass,
for every sparse layer, ``n_e`` = how many of the grid's ``rows x k``
choices fell on expert ``e``, over all the router's experts;
:func:`balance_step`: ``s_e = sign(mean(n) - n_e)``, ``d = c (s -
mean(s))``.  The rule asks ``b <- b + d``; the flat gradient this module
returns holds ``-d`` in the slots of every ``router_bias`` leaf (the
step written as a gradient of rate 1), which is what the program's
gradient holds there, so the one comparison covers the rule's output.

**The share.**  As ``joyai_plain.py``: ``router_experts`` is the
router's width, ``num_experts`` the experts held from ``experts_first``;
the selection and its normalisation run over all, the sum over the
held, the counts over all.

Memory.  The attention is walked in blocks of :data:`HEAD_BLOCK` query
heads inside a ``lax.map`` (a Python loop lets the TPU compiler lay
every block's ``L x L`` scores side by side: Keye's lesson), each under
``jax.checkpoint``, and so is each layer as a whole: the backward pass
holds one layer's activations and one block's scores at a time.
Recomputation changes no number.

Parameters come as the program's own pytree (the ``unravel`` of the flat
vector), read by the names ``models/transformer.py`` gave them.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HEAD_BLOCK = 2
ROUTE_EPS = 1e-20
BIAS_LEAF = "router_bias"

# Tolerances: ``probe_trinity.py`` beside this file and the cell's own
# runs made every reading (PERF.md section 6, PR 53, has them with their
# origin), on the v5e at the published widths of ``trinity-l5e8-local``,
# one seeded sequence of 8192 a seed.  The gradient's limit lies between
# two readings: the system's largest (float32 in memory, one bf16 pass a
# product, the router's at full precision: 0.344-0.414% of the
# gradient's norm over twenty-six seeds, mean 0.376, deviation 0.017)
# and the smallest of this file's own arithmetic with parameters and
# activations held in bf16, the nearest precision below the
# configuration's (0.477, 0.478, 0.496, 0.505 and 0.508% on five seeds).
# 0.450% lies 8.8% over the one and 5.7% under the other, 4.3 deviations
# over the system's mean: a limit that no run of the benchmark may fail
# by its seed lies nearer the side that only the probe reads.  The two
# lie close, as JoyAI's do and for its reason: a product of one bf16
# pass already rounds both operands, and what the lower precision adds
# is the rounding of what is stored between products.  Every leaf of
# the system's gradient is off by about 0.4% of its own norm and the
# head by 0.23% (a leaf-by-leaf reading on one seed: PERF.md): the level
# of one bf16 pass, and higher than Mellum's or SDAR's 0.08-0.09%
# because the out-norms make every branch's output as large as a normed
# stream's, so its rounding is no longer small beside the table's row;
# the held experts' leaves are off by 2%, where rows near a tie fall on
# another expert than the reference's.  Between the two lies the system
# with the router's product at one bf16 pass (0.380-0.424% on the five
# seeds where the system read 0.358-0.393%): a seed's own spread is as
# wide, so the gradient's 2-norm cannot refuse it; the CPU tests do
# (``tests/test_trinity.py``).  The loss cannot tell any of them apart
# (the system 2e-6 to 1.8e-4 nats off, the bf16 reference 1.4e-5 to
# 2.9e-4) and its limit is the accepted sparse cells', a guard against
# a wrong loss only (the input left unscaled is 2.6e-2 off at the tiny
# size, the bias left out of the selection 2e-3).
# The rule's slots are ``O(1e-3)`` beside a gradient of norm 2.0-2.1
# (their own norm 0.022), so the 2-norm cannot refuse a wrong rule: the
# probe prints, and ``tests/test_trinity.py`` holds, the rule's own
# comparison (:func:`rule_agrees`).
LOSS_TOL_NATS = 1.0e-3
GRAD_REL_TOL = 4.5e-3


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


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


def live_pairs(seq: int, window: int) -> jnp.ndarray:
    """``(seq, seq)`` bool: query ``i`` sees key ``j`` iff ``j <= i`` and,
    with a window, ``i - window < j``."""
    i = jnp.arange(seq)[:, None]
    j = jnp.arange(seq)[None, :]
    mask = j <= i
    if window:
        mask = mask & (j > i - window)
    return mask


@jax.checkpoint
def _heads(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
           mask: jnp.ndarray) -> jnp.ndarray:
    """Masked softmax attention of ``q (batch, heads, seq, head)`` over
    one KV head ``k, v (batch, seq, head)``; ``mask (seq, seq)``."""
    scores = jnp.einsum("bhqd,bkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkd->bhqd", jax.nn.softmax(scores, axis=-1), v)


def attention(x: jnp.ndarray, p: Dict[str, jnp.ndarray], sliding: bool,
              config: Dict[str, Any]) -> jnp.ndarray:
    """The gated grouped attention on the normed input ``x``."""
    n_head = int(config["num_attention_heads"])
    n_kv = int(config["num_key_value_heads"])
    head, eps = int(config["head_dim"]), float(config["rms_norm_eps"])
    b, seq, _ = x.shape
    q = rms_norm((x @ p["wq"]).reshape(b, seq, n_head, head), p["q_norm"],
                 eps)
    k = rms_norm((x @ p["wk"]).reshape(b, seq, n_kv, head), p["k_norm"], eps)
    if sliding:  # a full layer has no positional term
        theta = float(config["rope_theta"])
        q, k = rotate(q, theta), rotate(k, theta)
    q, k = q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3)
    v = (x @ p["wv"]).reshape(b, seq, n_kv, head).transpose(0, 2, 1, 3)
    mask = live_pairs(seq, int(config["sliding_window"]) if sliding else 0)
    group = n_head // n_kv
    step = min(HEAD_BLOCK, group)
    blocks = n_head // step
    kv_of = jnp.arange(blocks) * step // group
    out = jax.lax.map(
        lambda block: _heads(block[0], block[1], block[2], mask),
        (q.reshape(b, blocks, step, seq, head).transpose(1, 0, 2, 3, 4),
         k.transpose(1, 0, 2, 3)[kv_of], v.transpose(1, 0, 2, 3)[kv_of]))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, n_head, seq, head)
    out = out.transpose(0, 2, 1, 3).reshape(b, seq, n_head * head)
    return (out * jax.nn.sigmoid(x @ p["wg"])) @ p["wo"]


def swiglu(h: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
           w_down: jnp.ndarray) -> jnp.ndarray:
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def router_gates(h: jnp.ndarray, router: jnp.ndarray, bias: jnp.ndarray,
                 config: Dict[str, Any]
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(gates (T, E), chosen (T, E) bool)`` over all the router's
    experts: the sigmoid scores of the ``num_experts_per_tok`` that rank
    highest by score plus bias (of two equal ones the lower index wins),
    divided by their sum plus 1e-20 (``route_norm``) and multiplied by
    ``route_scale``; the rest zero.  The bias ranks and does nothing
    else."""
    scores = jax.nn.sigmoid(h @ router)
    ranked = scores + jax.lax.stop_gradient(bias)
    index = jnp.arange(scores.shape[-1])
    other, mine = ranked[:, None, :], ranked[:, :, None]
    beats = (other > mine) | ((other == mine)
                              & (index[None, None, :] < index[None, :, None]))
    chosen = jnp.sum(beats, axis=-1) < int(config["num_experts_per_tok"])
    gates = jnp.where(chosen, scores, 0.0)
    if bool(config["route_norm"]):
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + ROUTE_EPS)
    return gates * float(config["route_scale"]), chosen


@jax.checkpoint
def _expert_block(h: jnp.ndarray, gates: jnp.ndarray, wg: jnp.ndarray,
                  wu: jnp.ndarray, wd: jnp.ndarray) -> jnp.ndarray:
    """``sum_e gates[:, e] (SiLU(h Wg_e) * (h Wu_e)) Wd_e`` over the
    experts given: every token through every one of them."""
    hidden = jax.nn.silu(jnp.einsum("td,edf->etf", h, wg)) \
        * jnp.einsum("td,edf->etf", h, wu)
    return jnp.einsum("etd,te->td", jnp.einsum("etf,efd->etd", hidden, wd),
                      gates)


def sparse_mlp(h: jnp.ndarray, p: Dict[str, jnp.ndarray],
               config: Dict[str, Any]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The sparse layer on tokens ``h (T, d)``: the held experts' part
    of the routed sum plus the shared expert, and the counts ``(E,)``
    int32 of the choices over all the router's experts."""
    gates, chosen = router_gates(h, p["router"], p[BIAS_LEAF], config)
    first, held = int(config.get("experts_first", 0)), p[
        "experts_gate"].shape[0]
    y = _expert_block(h, gates[:, first:first + held], p["experts_gate"],
                      p["experts_up"], p["experts_down"])
    if int(config["num_shared_experts"]):
        y = y + swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"])
    return y, jnp.sum(chosen, axis=0, dtype=jnp.int32)


def layer(u: jnp.ndarray, p: Dict[str, jnp.ndarray], kind: str, dense: bool,
          config: Dict[str, Any]):
    """One layer on the stream ``u (batch, seq, d)``: ``(the stream, the
    sparse branch's counts or None)``."""
    eps = float(config["rms_norm_eps"])
    b, seq, d = u.shape
    a = attention(rms_norm(u, p["attn_norm"], eps), p,
                  kind == "sliding_attention", config)
    u = u + rms_norm(a, p["attn_out_norm"], eps)
    h = rms_norm(u, p["mlp_norm"], eps)
    counts = None
    if dense:
        y = swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
    else:
        y, counts = sparse_mlp(h.reshape(b * seq, d), p, config)
        y = y.reshape(b, seq, d)
    return u + rms_norm(y, p["mlp_out_norm"], eps), counts


def block_names(config: Dict[str, Any]) -> List[str]:
    return [f"TrinityBlock_{i}"
            for i in range(int(config["num_hidden_layers"]))]


def layers(params: Dict[str, Any], inputs: jnp.ndarray,
           config: Dict[str, Any]):
    """``(the stream after the last layer, {block: counts} of the sparse
    layers)``."""
    kinds = list(config["layer_types"])
    scale = math.sqrt(float(config["hidden_size"])) if config[
        "mup_enabled"] else 1.0
    u = params["embed"][inputs] * scale
    counted = {}
    for i, name in enumerate(block_names(config)):
        if kinds[i] not in ("sliding_attention", "full_attention"):
            raise ValueError(f"layer type {kinds[i]!r}")
        dense = i < int(config["num_dense_layers"])
        run = jax.checkpoint(
            lambda u, p, kind=kinds[i], dense=dense: layer(
                u, p, kind, dense, config))
        u, counts = run(u, params[name])
        if counts is not None:
            counted[name] = counts
    return u, counted


def loss(params: Dict[str, Any], tokens: jnp.ndarray,
         config: Dict[str, Any]):
    """``(mean next-token negative log-likelihood over a packed grid
    (batch, seq + 1), the sparse layers' counts)``."""
    u, counted = layers(params, tokens[:, :-1], config)
    x = rms_norm(u, params["final_norm"], float(config["rms_norm_eps"]))
    logp = jax.nn.log_softmax(x @ params["head"], axis=-1)
    nll = -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))
    return nll, counted


def balance_step(counts: Any, rate: float, xp: Any = np) -> Any:
    """The rule's step ``d (E,)`` float32 for one pass's ``counts (E,)``
    int32, in ``xp`` (numpy, or ``jax.numpy`` inside the jitted
    reference): ``s = sign(mean(counts) - counts)`` taken in integers as
    ``sign(sum(counts) - E counts)``; ``d = rate (s - mean(s))``."""
    e = counts.shape[-1]
    sign = xp.sign(xp.sum(counts) - e * counts)
    mean = xp.sum(sign).astype(xp.float32) / xp.float32(e)
    return xp.float32(rate) * (sign.astype(xp.float32) - mean)


def loss_grads_counts(params: Dict[str, Any], tokens: jnp.ndarray,
                      config: Dict[str, Any]):
    """``(loss, gradient pytree with -d in every bias leaf, {block:
    counts})``."""
    (nll, counted), grads = jax.value_and_grad(loss, has_aux=True)(
        params, tokens, config)
    rate = float(config["load_balance_coeff"])
    grads = {name: ({**leaves, BIAS_LEAF: -balance_step(
        counted[name], rate, jnp)} if name in counted else leaves)
        for name, leaves in grads.items()}
    return nll, grads, counted


def rule_agrees(counts_sys: Any, counts_ref: Any, step_sys: Any,
                rate: float, near_ties: int) -> Dict[str, Any]:
    """The rule's own comparison for one sparse layer: the system's
    counts against the reference's within ``near_ties`` (the rows whose
    selection is a near tie in float32 may fall either way on two
    streams that differ by the program's rounding), and the system's
    step (its gradient slots, negated) against :func:`balance_step` of
    its own counts exactly, and against the reference's wherever
    ``|n_e - mean|`` exceeds ``near_ties``."""
    counts_sys, counts_ref = np.asarray(counts_sys), np.asarray(counts_ref)
    step_sys = np.asarray(step_sys, np.float32)
    off = np.abs(counts_sys.astype(np.int64) - counts_ref)
    e = counts_ref.shape[-1]
    clear = np.abs(e * counts_ref.astype(np.int64)
                   - counts_ref.sum()) > e * near_ties
    sign_ref = np.sign(counts_ref.sum() - e * counts_ref.astype(np.int64))
    sign_sys = np.sign(counts_sys.sum() - e * counts_sys.astype(np.int64))
    own = balance_step(counts_sys, rate)
    return {"counts_max_off": int(off.max()),
            "counts_ok": bool(off.max() <= near_ties),
            "own_step_exact": bool(np.array_equal(own, step_sys)),
            "clear_experts": int(clear.sum()),
            "clear_signs_ok": bool(np.array_equal(sign_ref[clear],
                                                  sign_sys[clear]))}


def loss_and_grad_flat(w: jnp.ndarray, unravel: Any, tokens: jnp.ndarray,
                       config: Dict[str, Any]
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """From the program's flat vector to the loss and a flat gradient
    with ``-d`` in the bias slots, in one jitted program, so that no
    pytree of the model's size outlives it; ``config`` holds the model's
    own keys (the module's docstring names each).  The tokens are an
    argument, never a constant of the program."""
    def fn(flat, tok):
        nll, grads, _ = loss_grads_counts(unravel(flat), tok, config)
        return nll, jnp.concatenate(
            [leaf.reshape(-1) for leaf in jax.tree_util.tree_leaves(grads)])

    with jax.default_matmul_precision("highest"):
        return jax.jit(fn)(w, tokens)
