"""The plain reference of the SDAR block: what a configuration with
``"reference": "sdar_plain"`` is held to.  The noise in numpy, the
forward pass, loss and gradient in straightforward ``jax.numpy``,
float32, every matrix product at ``default_matmul_precision("highest")``.
The mask materialised as a whole ``(2 L, 2 L)`` square, the attention a
masked softmax over the whole row, dense over the experts held; no
kernel, no walk over tiles, no sort of tokens, no grouped product, no
checkpoint policy, no parameter server.  It imports nothing of the
program.  ``chipbench/spec.py`` finds it by the configuration's key and
has the contract of such a module (``loss_and_grad_flat``,
``LOSS_TOL_NATS``, ``GRAD_REL_TOL``); ``chipbench/compare.py`` is the
comparison every reference is held by.

The function (SDAR-30B-A3B-Chat, JetLM, ``model_type`` ``sdar_moe``:
Qwen3-MoE's layer trained by block diffusion; the configuration's keys
are those of its ``config.json`` and, beside them, ``train_seq``,
``block_length``, ``mask_token_id`` and ``noise_seed``).  Sizes: ``L =
train_seq``, ``B = block_length``, ``n = L / B`` blocks, ``blk(i) = i //
B``.

*Input.*  ``x0``: the row's first ``L`` ids (the grid's last id is read
by nothing).  *Noise* (:func:`noise`), a pure function of ``noise_seed``
and the row's ``L`` ids in wrapping 32-bit integer arithmetic: a
checksum ``h = mix(seed ^ sum_i mix(id_i + 0x9E3779B9 (i + 1)))``;
block ``b``'s key ``mix(h ^ mix(b ^ 0x85EBCA6B))`` and count ``c_b = 1 +
rank_b mod B``, ``rank_b`` the block's place among the row's blocks by
key, a tie to the lower block (a seeded permutation: where ``B`` divides
``n`` exactly ``n / B`` blocks get each count); position ``i``'s key
``mix(h ^ mix(i ^ 0xC2B2AE35))``, and ``i`` is in its block's set
``S_b`` iff fewer than ``c_b`` of the block's positions come before it
by key, a tie to the lower position (the ``c_b`` smallest of ``B``
hashed keys: a uniform set of that size).  ``xt[i] = mask_token_id``
where ``i`` is in its block's set, else ``x0[i]``.  Every row masks
exactly ``L (B + 1) / (2 B)`` positions.

*Sequence.*  ``z = [xt ; x0]``, ``2 L`` rows; row ``r`` has rotary
position ``r mod L``.  The table, then in every layer on all ``2 L``
rows::

    x = RMSNorm(u)                                   # weight only
    q = rope(RMSNorm_h(x Wq))   (num_attention_heads x head_dim)
    k = rope(RMSNorm_h(x Wk)),  v = x Wv   (num_key_value_heads x head_dim)
        # RMSNorm_h: over each head's width, one weight of head_dim for
        # the queries and one for the keys, before the rotation; no bias
    query head g attends KV head g // (heads / kv heads):
        p[r, c] = softmax over {c : M(r, c)} of q_r . k_c / sqrt(head_dim)
    u = u + (sum_c p[r, c] v_c) Wo
    h = RMSNorm(u)
    g = softmax(h Wr)                                # over all the experts
    the num_experts_per_tok largest g_e, divided by their sum
    u = u + sum_{e held} g_e (SiLU(h Wg_e) * (h Wu_e)) Wd_e

``M(r, c)`` for a noised row ``r < L``: ``c < L and blk(c) == blk(r)``,
or ``c >= L and blk(c - L) < blk(r)``.  For a clean row ``r >= L``: ``c
>= L and blk(c - L) <= blk(r - L)``.  Nothing else: no clean row sees a
noised one, and a noised block never sees its own clean copy.  ``rope``
is the rotate-half rotation at ``rope_theta`` over all of a head's
width.

*Head and loss.*  The final RMSNorm and the untied head on the noised
half alone; ``loss = (1 / n) sum_b (1 / c_b) sum_{i in S_b} -log
softmax(z_i)[x0[i]]``, the mean over the batch.  A masked position
predicts its own id: no shift.

**Why this is the bound.**  Block diffusion under the linear schedule
masks every position of a block independently with probability ``t``,
``t`` uniform in (0, 1], and weights the masked positions'
cross-entropy by ``1 / t`` (LLaDA, arXiv:2502.09992; Ou et al.,
arXiv:2406.03736).  A given set of size ``c`` of ``B`` then has the
weight ``int_0^1 t^(c-1) (1 - t)^(B-c) dt = 1 / (c C(B, c))``
(:func:`count_weight`), which is what drawing ``c`` uniformly in
``1..B``, the set uniformly among the ``C(B, c)`` of that size and the
weight ``1 / c`` gives, ``B`` times over: the same expectation, and the
count form's weight times count is 1 in every block.  The counts are
stratified (a permutation, not draws), so every row masks the same
number of positions.

**The share.**  As ``mellum_plain.py``: ``router_experts`` is the
router's width, ``num_experts`` the experts held from ``experts_first``;
the top-k and its renormalisation run over all, the sum over the held.

Memory.  As ``keye_plain.py``: the attention in blocks of
:data:`HEAD_BLOCK` query heads, one after another inside a ``lax.map``
(as a Python loop the TPU compiler laid every block's ``2 L x 2 L``
scores side by side), the experts one block, each under
``jax.checkpoint``, and so is each layer as a whole.  Recomputation
changes no number.

Parameters come as the program's own pytree (the ``unravel`` of the flat
vector), read by the names ``models/transformer.py`` gave them.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HEAD_BLOCK = 2

# Tolerances, from the v5e at the published widths of ``sdar-l6e8-local``
# (PERF.md section 6, PR 51; ``probe_sdar.py`` beside this file and the
# cell's own runs made every reading, one seeded sequence of 4096 a
# seed, 8192 rows a layer).  A limit lies between two readings.  The
# system's (float32 in memory, one bf16 pass a product, the router's at
# full precision): 0.0847..0.0897% of the gradient's norm over fifteen
# seeds (mean 0.0867, deviation 0.0014) and 0.3e-5..2.3e-4 nats.  What
# the limits must refuse, the reference's own arithmetic with parameters
# and activations in bf16, the nearest precision below the
# configuration's: 0.251 and 0.263% on two seeds, and 1.0e-4 nats (the
# loss cannot tell it from the system: the gradient's limit refuses it,
# 2.5 times over).  One product's precision lowered: the system with the
# router's product at one bf16 pass reads 0.1018% on one seed (the
# system 0.0847 there) and 0.0873% on another (0.0874: where no row's
# top-8 changes hands the product's precision changes nothing), so the
# gradient's limit is set at 0.100%: 1.11 times the system's largest
# reading, nine of its deviations above its mean (the largest of the
# fifteen lies 2.1 above, as a normal spread's would), 1.8% under the
# lowered build's on the seed where it differs at all.  The reference
# with its logits, log-softmax and loss in bf16 reads 0.0179 and 0.0051
# nats off (a loss near 10.25 rounds to a grid of 0.0625) and 0.059% in
# the gradient: the loss's limit refuses it, 4.3 times the system's
# largest reading and a fifth of the smaller of the two.  **Not refused,
# and not
# refusable beside the system's own reading**: the reference with its
# attention's softmax in bf16, 0.0073 and 0.0067% of the gradient and
# exactly the loss (at the seeded weights the attention is nearly
# uniform over a row's live keys, and the probabilities' rounding
# averages out over hundreds of them).  Also refused, each the
# reference with one thing wrong: a noised block that sees its own
# clean copy (0.48%), plain causal attention over the 2 L rows (36%),
# the loss without its 1 / c (16%), the targets shifted by one (37%).
LOSS_TOL_NATS = 1.0e-3
GRAD_REL_TOL = 1.0e-3

_GOLDEN, _MIX_A, _MIX_B = 0x9E3779B9, 0x7FEB352D, 0x846CA68B
_BLOCK_SALT, _SPOT_SALT = 0x85EBCA6B, 0xC2B2AE35


def mix(x: np.ndarray) -> np.ndarray:
    """The 32-bit mix of the noise, on a ``uint32`` array, wrapping."""
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(_MIX_A)
    x = x ^ (x >> np.uint32(15))
    x = x * np.uint32(_MIX_B)
    return x ^ (x >> np.uint32(16))


def noise(ids: np.ndarray, seed: int, block: int
          ) -> Tuple[np.ndarray, np.ndarray]:
    """``(masked (rows, L) bool, count (rows, L) int32)`` of ``ids
    (rows, L)`` by the module's recipe, in numpy: the positions that are
    noised, and at every position its block's count ``c_b``."""
    ids = np.asarray(ids)
    rows, seq = ids.shape
    n = seq // block
    at = np.arange(seq, dtype=np.uint32)
    masked = np.zeros((rows, seq), bool)
    count = np.zeros((rows, seq), np.int32)
    for r in range(rows):
        terms = mix(ids[r].astype(np.uint32)
                    + np.uint32(_GOLDEN) * (at + np.uint32(1)))
        h = mix(np.asarray([np.uint32(seed)
                            ^ np.sum(terms, dtype=np.uint32)], np.uint32))[0]
        block_key = mix(h ^ mix(np.arange(n, dtype=np.uint32)
                                ^ np.uint32(_BLOCK_SALT)))
        rank = np.empty(n, np.int64)
        rank[np.argsort(block_key, kind="stable")] = np.arange(n)
        c = 1 + rank % block
        key = mix(h ^ mix(at ^ np.uint32(_SPOT_SALT)))
        for b in range(n):
            spots = np.argsort(key[b * block:(b + 1) * block], kind="stable")
            masked[r, b * block + spots[:c[b]]] = True
        count[r] = np.repeat(c, block)
    return masked, count


def count_weight(block: int, c: int) -> float:
    """``1 / (c C(block, c))``: what the ``1 / t``-weighted linear
    schedule gives one set of ``c`` of a block's positions (the module's
    docstring)."""
    return 1.0 / (c * math.comb(block, c))


def visible(seq: int, block: int) -> jnp.ndarray:
    """``M (2 seq, 2 seq)`` bool, written from its definition."""
    r = jnp.arange(2 * seq)[:, None]
    c = jnp.arange(2 * seq)[None, :]
    own = (c < seq) & (c // block == r // block)
    past = (c >= seq) & ((c - seq) // block < r // block)
    clean = (c >= seq) & ((c - seq) // block <= (r - seq) // block)
    return jnp.where(r < seq, own | past, clean)


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def head_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    """The RMSNorm over each head's width of ``x (..., head)``."""
    return rms_norm(x, weight, eps)


def rotate(x: jnp.ndarray, theta: float, period: int) -> jnp.ndarray:
    """Rotary embedding of ``x (batch, rows, heads, head)`` over the
    whole head, rotate-half, row ``r`` at position ``r mod period``: ``x
    cos + rotate_half(x) sin`` with ``rotate_half((a, b)) = (-b, a)`` and
    angles ``t theta^(-2i/head)``."""
    rows, head = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, head, 2, dtype=jnp.float32) / head)
    at = (jnp.arange(rows) % period).astype(jnp.float32)
    angle = at[:, None] * freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    a, b = x[..., : head // 2], x[..., head // 2:]
    return x * jnp.cos(angle) + jnp.concatenate([-b, a], axis=-1) * jnp.sin(
        angle)


@jax.checkpoint
def _heads(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
           mask: jnp.ndarray) -> jnp.ndarray:
    """Masked softmax attention of ``q (batch, heads, rows, head)`` over
    one KV head ``k, v (batch, rows, head)``; ``mask (rows, rows)``."""
    scores = jnp.einsum("bhqd,bkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkd->bhqd", jax.nn.softmax(scores, axis=-1), v)


def attention(x: jnp.ndarray, p: Dict[str, jnp.ndarray], mask: jnp.ndarray,
              config: Dict[str, Any]) -> jnp.ndarray:
    """Grouped attention on the normed input ``x (batch, 2 L, d)`` over
    the pairs of ``mask``."""
    n_head = int(config["num_attention_heads"])
    n_kv = int(config["num_key_value_heads"])
    head, eps = int(config["head_dim"]), float(config["rms_norm_eps"])
    theta = float(config["rope_theta"])
    b, rows, _ = x.shape
    q = rotate(head_norm((x @ p["wq"]).reshape(b, rows, n_head, head),
                         p["q_norm"], eps), theta, rows // 2
               ).transpose(0, 2, 1, 3)
    k = rotate(head_norm((x @ p["wk"]).reshape(b, rows, n_kv, head),
                         p["k_norm"], eps), theta, rows // 2
               ).transpose(0, 2, 1, 3)
    v = (x @ p["wv"]).reshape(b, rows, n_kv, head).transpose(0, 2, 1, 3)
    group = n_head // n_kv
    step = min(HEAD_BLOCK, group)
    # a block of query heads at a time over the KV head they share, one
    # after another (a ``lax.map``: as a Python loop the compiler laid
    # the blocks' scores side by side, 537 MB each)
    blocks = n_head // step
    kv_of = jnp.arange(blocks) * step // group
    out = jax.lax.map(
        lambda block: _heads(block[0], block[1], block[2], mask),
        (q.reshape(b, blocks, step, rows, head).transpose(1, 0, 2, 3, 4),
         k.transpose(1, 0, 2, 3)[kv_of], v.transpose(1, 0, 2, 3)[kv_of]))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, n_head, rows, head)
    return out.transpose(0, 2, 1, 3).reshape(b, rows, n_head * head) @ p["wo"]


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
    """One layer on the stream ``u (batch, 2 L, d)`` over the pairs of
    ``mask``: the attention and the sparse MLP, each added to the
    stream."""
    eps = float(config["rms_norm_eps"])
    b, rows, d = u.shape
    u = u + attention(rms_norm(u, p["attn_norm"], eps), p, mask, config)
    h = rms_norm(u, p["mlp_norm"], eps).reshape(b * rows, d)
    return u + experts(h, p, config).reshape(b, rows, d)


def logits(params: Dict[str, Any], noised: jnp.ndarray, ids: jnp.ndarray,
           config: Dict[str, Any]) -> jnp.ndarray:
    """The head's logits ``(batch, L, vocabulary)`` at the noised half's
    rows, from the noised copy ``noised`` and the clean one ``ids``."""
    seq = ids.shape[1]
    mask = visible(seq, int(config["block_length"]))
    u = params["embed"][jnp.concatenate([noised, ids], axis=1)]
    run = jax.checkpoint(lambda u, p: layer(u, p, mask, config))
    for i in range(int(config["num_hidden_layers"])):
        u = run(u, params[f"SdarBlock_{i}"])
    x = rms_norm(u[:, :seq], params["final_norm"],
                 float(config["rms_norm_eps"]))
    return x @ params["head"]


def loss(params: Dict[str, Any], ids: jnp.ndarray, masked: jnp.ndarray,
         count: jnp.ndarray, config: Dict[str, Any]) -> jnp.ndarray:
    """The block-diffusion loss of the rows ``ids (batch, L)`` under the
    noise ``masked``, ``count`` (:func:`noise`)."""
    block = int(config["block_length"])
    noised = jnp.where(masked, int(config["mask_token_id"]), ids)
    logp = jax.nn.log_softmax(
        logits(params, noised, ids, config).astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, ids[..., None], axis=-1)[..., 0]
    per_row = jnp.sum(jnp.where(masked, nll / count, 0.0), axis=1)
    return jnp.mean(per_row) / (ids.shape[1] // block)


def loss_and_grad_flat(w: jnp.ndarray, unravel: Any, tokens: jnp.ndarray,
                       config: Dict[str, Any]
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """From the program's flat vector to the loss and a flat gradient,
    in one jitted program, so that no pytree of the model's size
    outlives it; ``config`` holds the model's own keys (the module's
    docstring names each).  ``tokens (batch, L + 1)`` is the packed
    grid: its first ``L`` ids are the rows.  The noise is made here in
    numpy from the ids; ids and noise are arguments, never constants of
    the program."""
    ids = np.asarray(tokens)[:, :-1]
    masked, count = noise(ids, int(config["noise_seed"]),
                          int(config["block_length"]))
    fn = jax.jit(jax.value_and_grad(
        lambda flat, ids, masked, count: loss(unravel(flat), ids, masked,
                                              count, config)))
    with jax.default_matmul_precision("highest"):
        return fn(w, jnp.asarray(ids), jnp.asarray(masked),
                  jnp.asarray(count))
