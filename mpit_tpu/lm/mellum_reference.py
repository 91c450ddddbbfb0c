"""The plain reference of the Mellum block: forward pass, loss and
gradient in straightforward ``jax.numpy``, float32, every matrix product
at ``default_matmul_precision("highest")``.  Dense over the experts held
(every token through each of them, masked by its renormalised top-k
router weights), a materialised ``L x L`` mask, no kernel, no sort, no
grouped product, no parameter server.  It shares no code with
``models/transformer.py``, ``ops/flash_attention.py`` or
``parallel/moe.py``: the tests hold the program's block to it
(``tests/test_mellum.py``), and ``chipbench/reference/mellum_plain.py``
is the benchmark's own copy, held to the program on the chip at
published widths in every run.

The block (Mellum 2, JetBrains, ``model_type`` ``mellum``; the
configuration's keys are those of its ``config.json``).  For hidden
``x`` of width ``hidden_size``, in layer ``i``::

    h = RMSNorm(x)                                # weight only
    q = h Wq   (num_attention_heads x head_dim)   # no bias
    k, v = h Wk, h Wv  (num_key_value_heads x head_dim)
    q, k = RoPE_i(q), RoPE_i(k)       # rotate-half over the head's width
    query head g attends KV head g // (heads / kv heads), causally,
        scores q k^T / sqrt(head_dim); where layer_types[i] is
        sliding_attention key j is visible to query t iff
        0 <= t - j < sliding_window
    x = x + Attention Wo
    h = RMSNorm(x)
    p = softmax(h Wr)                             # over all the experts
    the num_experts_per_tok largest p_e, divided by their sum
    x = x + sum_{e held} p_e (SiLU(h Wg_e) * (h Wu_e)) Wd_e

``RoPE_i`` by ``rope_parameters[layer_types[i]]``: ``default`` is the
plain table ``theta^(-2j/head_dim)``; ``yarn`` blends that with the
same divided by ``factor`` (see :func:`yarn_frequencies`) and multiplies
``cos`` and ``sin`` by ``attention_factor``.  Then a final RMSNorm and
an untied head; the loss is the mean next-token negative log-likelihood
over a packed grid.

**The share.**  ``router_experts`` is the router's width (the published
``num_experts``); ``num_experts`` counts the experts held here, the
contiguous range from ``experts_first``.  The top-k and its
renormalisation run over all ``router_experts``; the sum runs over the
held ones only.  What the absent experts would add is left out, and that
partial result goes on to the next layer (model-configs guide, section
4).  With ``num_experts == router_experts`` this is the whole layer.

Departures from the published model and its recipe, each also a line of
``assumed`` in ``chipbench/configs/mellum2-12b-l4e8.json``:

- no per-head query/key norm (the config has no key for one), no MTP
  head, no auxiliary routing loss (no coefficient in the config): the
  training loss is the NLL alone;
- the window's convention: the query's own position and the
  ``sliding_window - 1`` before it;
- no token is dropped; ties in the top-k go to the lower expert index;
- weights are the program's seeded initialisation, not the checkpoint.

Memory.  The attention is walked in blocks of :data:`HEAD_BLOCK` query
heads and the experts one block, each under ``jax.checkpoint``, so that
the backward pass holds one block's ``L x L`` scores at a time: at the
published widths and 8192 positions (32 heads of 8192 x 8192 scores)
that is what lets the reference run beside the system's own operands on
a 16 GB chip.  Recomputation changes no number.

Parameters come as the program's own pytree (the ``unravel`` of the flat
vector), read by the names ``models/transformer.py`` gave them.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

HEAD_BLOCK = 2


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def yarn_frequencies(head: int, rope: Dict[str, Any]) -> jnp.ndarray:
    """Per-pair inverse frequencies under YaRN.  With ``c(n) = head
    ln(original / (2 pi n)) / (2 ln theta)`` the pair that makes ``n``
    turns over the original context, ``low = floor(c(beta_fast))``,
    ``high = ceil(c(beta_slow))`` (clipped to ``[0, head - 1]``) and
    ``r_j = clip((j - low) / (high - low), 0, 1)``: pair ``j`` gets ``(1
    - r_j) theta^(-2j/head) + r_j theta^(-2j/head) / factor``."""
    theta, original = float(rope["rope_theta"]), float(
        rope["original_max_position_embeddings"])

    def c(turns: float) -> float:
        return head * math.log(original / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    low = max(math.floor(c(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(c(float(rope["beta_slow"]))), head - 1)
    if high == low:
        high = low + 0.001
    j = jnp.arange(head // 2, dtype=jnp.float32)
    r = jnp.clip((j - low) / (high - low), 0.0, 1.0)
    plain = theta ** (-2.0 * j / head)
    return (1.0 - r) * plain + r * plain / float(rope["factor"])


def rotary_table(seq: int, head: int, rope: Dict[str, Any]
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``cos, sin (seq, head)`` of one layer type's section of
    ``rope_parameters``."""
    if rope["rope_type"] == "yarn":
        freq, scale = yarn_frequencies(head, rope), float(
            rope["attention_factor"])
    elif rope["rope_type"] == "default":
        freq = float(rope["rope_theta"]) ** (
            -jnp.arange(0, head, 2, dtype=jnp.float32) / head)
        scale = 1.0
    else:
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)       # (seq, head)
    return jnp.cos(angle) * scale, jnp.sin(angle) * scale


def rotate(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Rotary embedding of ``x (batch, heads, seq, head)`` in the
    rotate-half convention: ``x cos + rotate_half(x) sin`` with
    ``rotate_half((a, b)) = (-b, a)`` over the head's two halves."""
    head = x.shape[-1]
    a, b = x[..., : head // 2], x[..., head // 2:]
    return x * cos + jnp.concatenate([-b, a], axis=-1) * sin


def visible(seq: int, window: int) -> jnp.ndarray:
    """``(seq, seq)`` bool: query ``t`` (row) sees key ``j`` (column)
    iff ``j <= t`` and, with a ``window`` > 0, ``t - j < window``."""
    t = jnp.arange(seq)[:, None]
    j = jnp.arange(seq)[None, :]
    mask = j <= t
    if window:
        mask = mask & (t - j < window)
    return mask


@jax.checkpoint
def _heads(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
           mask: jnp.ndarray) -> jnp.ndarray:
    """Masked softmax attention of ``q (batch, heads, seq, head)`` over
    one KV head ``k, v (batch, seq, head)``."""
    scores = jnp.einsum("bhqd,bkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    scores = jnp.where(mask, scores, -jnp.inf)
    return jnp.einsum("bhqk,bkd->bhqd", jax.nn.softmax(scores, axis=-1), v)


def attention(x: jnp.ndarray, p: Dict[str, jnp.ndarray], n_head: int,
              n_kv: int, head: int, window: int, rope: Dict[str, Any]
              ) -> jnp.ndarray:
    b, seq, _ = x.shape
    q = (x @ p["wq"]).reshape(b, seq, n_head, head).transpose(0, 2, 1, 3)
    k = (x @ p["wk"]).reshape(b, seq, n_kv, head).transpose(0, 2, 1, 3)
    v = (x @ p["wv"]).reshape(b, seq, n_kv, head).transpose(0, 2, 1, 3)
    cos, sin = rotary_table(seq, head, rope)
    q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    mask = visible(seq, window)
    group = n_head // n_kv
    step = min(HEAD_BLOCK, group)
    out = [_heads(q[:, lo:lo + step], k[:, lo // group], v[:, lo // group],
                  mask)
           for lo in range(0, n_head, step)]
    out = jnp.concatenate(out, axis=1)
    return out.transpose(0, 2, 1, 3).reshape(b, seq, n_head * head) @ p["wo"]


def router_gates(h: jnp.ndarray, router: jnp.ndarray, top_k: int,
                 renormalise: bool) -> jnp.ndarray:
    """``(T, E)`` over all the router's experts: its softmax, the
    ``top_k`` largest of each row kept and (``renormalise``) divided by
    their sum, the rest zero.  An expert is kept if fewer than ``top_k``
    others beat it; of two equal ones the lower index beats the
    higher."""
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


def experts(h: jnp.ndarray, p: Dict[str, jnp.ndarray], top_k: int,
            renormalise: bool, first: int) -> jnp.ndarray:
    """The sparse-expert layer on tokens ``h (T, d)``, densely, over the
    experts whose matrices ``p`` holds: the router's experts ``first ..
    first + held - 1``."""
    gates = router_gates(h, p["router"], top_k, renormalise)
    held = p["experts_gate"].shape[0]
    return _expert_block(h, gates[:, first:first + held], p["experts_gate"],
                         p["experts_up"], p["experts_down"])


def forward(params: Dict[str, Any], inputs: jnp.ndarray,
            config: Dict[str, Any]) -> jnp.ndarray:
    """Log-probabilities ``(batch, seq, vocab)`` for int32 ``inputs``."""
    n_head = int(config["num_attention_heads"])
    n_kv = int(config["num_key_value_heads"])
    head = int(config["head_dim"])
    top_k = int(config["num_experts_per_tok"])
    eps = float(config["rms_norm_eps"])
    renormalise = bool(config["norm_topk_prob"])
    first = int(config.get("experts_first", 0))
    x = params["embed"][inputs]
    b, seq, d = x.shape
    for i in range(int(config["num_hidden_layers"])):
        p = params[f"MellumBlock_{i}"]
        kind = config["layer_types"][i]
        window = int(config["sliding_window"]) if kind == "sliding_attention" \
            else 0
        x = x + attention(rms_norm(x, p["attn_norm"], eps), p, n_head, n_kv,
                          head, window, config["rope_parameters"][kind])
        h = rms_norm(x, p["mlp_norm"], eps).reshape(b * seq, d)
        x = x + experts(h, p, top_k, renormalise, first).reshape(b, seq, d)
    x = rms_norm(x, params["final_norm"], eps)
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
    outlives it; ``config`` holds Mellum's own keys (the module's
    docstring names each).  The tokens are an argument, never a constant
    of the program."""
    fn = jax.jit(jax.value_and_grad(
        lambda flat, tok: loss(unravel(flat), tok, config)))
    with jax.default_matmul_precision("highest"):
        return fn(w, tokens)
