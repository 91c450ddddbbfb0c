"""The plain reference of the OLMoE block: what a configuration with
``"reference": "olmoe_plain"`` is held to.  Forward pass, loss and
gradient in straightforward ``jax.numpy``, float32, every matrix product
at ``default_matmul_precision("highest")``.  Dense over experts (every
token through every expert, masked by its top-k router weights), dense
masked attention, no kernel, no sort, no grouped product, no parameter
server.  The benchmark's own copy of ``mpit_tpu/lm/olmoe_reference.py``
(as ``gpt_plain.py`` is of its block: a change to the program's file
cannot move what the benchmark holds the program to); it imports
nothing of the program.  ``chipbench/spec.py`` finds it by the
configuration's key and has the contract of such a module
(``loss_and_grad_flat``, ``LOSS_TOL_NATS``, ``GRAD_REL_TOL``);
``chipbench/compare.py`` is the comparison every reference is held by.

The block (OLMoE: Muennighoff et al., arXiv:2409.02060; Hugging Face
``modeling_olmoe.py``; the configuration's keys are those of
``config.json``).  For hidden ``x`` of width ``hidden_size``, per layer::

    h = RMSNorm(x)
    q, k, v = h Wq, h Wk, h Wv                    # no bias
    q, k = RMSNorm(q), RMSNorm(k)                 # over the whole width
    q, k = RoPE(q), RoPE(k)                       # per head, rotate-half
    x = x + CausalAttention(q, k, v) Wo           # scale 1/sqrt(head)
    h = RMSNorm(x)
    p = softmax(h Wr)                             # float32, all experts
    the num_experts_per_tok largest p_e, NOT renormalised
    x = x + sum_e p_e (SiLU(h Wg_e) * (h Wu_e)) Wd_e

then a final RMSNorm and an untied head; the loss is the mean next-token
negative log-likelihood over a packed grid.

Departures from the published model and its recipe, each also a line of
``assumed`` in ``chipbench/configs/olmoe-1b-7b-l1.json``:

- the query/key RMSNorm is not among ``config.json``'s keys; it is in
  the paper and in ``modeling_olmoe.py`` (``q_norm``, ``k_norm`` of
  width ``hidden_size``, applied before the split into heads);
- the training loss is the NLL alone: the load-balancing loss and the
  router z-loss of OLMoE's recipe are left out;
- no token is dropped: every token keeps all its experts (the released
  model is dropless too);
- ties in the top-k go to the lower expert index;
- weights are the program's seeded initialisation, not the checkpoint.

Memory.  The experts are walked in blocks of :data:`EXPERT_BLOCK` and
the attention by heads, each under ``jax.checkpoint``, so that the
backward pass holds one block's activations at a time: at the published
widths (64 experts of 1024 over 4096 tokens, 16 heads of 4096 x 4096
scores) that is what lets the reference run beside the system's own
operands on a 16 GB chip.  Recomputation changes no number.

Parameters come as the program's own pytree (the ``unravel`` of the flat
vector), read by the names ``models/transformer.py`` gave them.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

EXPERT_BLOCK = 8

# How far the system may be from this reference, and why (my chip runs,
# PR 26: chipbench/reference/probe_olmoe.py at published widths, 15
# seeds, PERF.md section 6).  The reference multiplies at full float32
# precision.  The system's matrices are float32 in memory and its
# products one bf16 MXU pass with float32 accumulation, except the path
# into the router (attention projections three passes, the flash
# kernel's products on float32 inputs, the router's product full
# float32: models/transformer.py says why).  It read 0.229..0.325% of
# the gradient's norm and 0.2e-4..1.8e-4 nats.  The limits are 1.85 and
# 5 times the largest readings.  What they refuse, each read in the same
# probe: the reference's own arithmetic with parameters and activations
# in bf16, the nearest precision below the configuration's
# (1.81..2.22%); the system with its router's product in one bf16 pass
# (1.08%: top-8 membership flips) or its attention path in one pass
# (1.37..1.78%); a top-8 renormalised (43..46%) or unweighted (47..49%),
# a mask one key ahead (3.4..3.8%), the interleaved rotary convention
# (57..62%).  The loss alone tells none of these apart (the bf16
# arithmetic reads 1e-6..1e-3 nats); the gradient does.
LOSS_TOL_NATS = 1.0e-3
GRAD_REL_TOL = 6.0e-3


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rotate(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embedding of ``x (batch, heads, seq, head)`` in the
    rotate-half convention: ``x cos + rotate_half(x) sin`` with
    ``rotate_half((a, b)) = (-b, a)`` over the head's two halves and the
    angle of position ``t``, pair ``i``: ``t / theta^(2 i / head)``."""
    seq, head = x.shape[-2], x.shape[-1]
    freq = theta ** (-jnp.arange(0, head, 2, dtype=jnp.float32) / head)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)      # (seq, head)
    a, b = x[..., : head // 2], x[..., head // 2:]
    rotated = jnp.concatenate([-b, a], axis=-1)
    return x * jnp.cos(angle) + rotated * jnp.sin(angle)


@jax.checkpoint
def _heads(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Causal softmax attention, all of ``(batch, heads, seq, head)``."""
    seq, head = q.shape[-2], q.shape[-1]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(head)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)


def attention(x: jnp.ndarray, p: Dict[str, jnp.ndarray], n_head: int,
              theta: float, eps: float) -> jnp.ndarray:
    b, seq, d = x.shape
    q = rms_norm(x @ p["wq"], p["q_norm"], eps)
    k = rms_norm(x @ p["wk"], p["k_norm"], eps)
    v = x @ p["wv"]
    q, k, v = (t.reshape(b, seq, n_head, d // n_head).transpose(0, 2, 1, 3)
               for t in (q, k, v))
    out = _heads(rotate(q, theta), rotate(k, theta), v)
    return out.transpose(0, 2, 1, 3).reshape(b, seq, d) @ p["wo"]


@jax.checkpoint
def _expert_block(h: jnp.ndarray, gates: jnp.ndarray, wg: jnp.ndarray,
                  wu: jnp.ndarray, wd: jnp.ndarray) -> jnp.ndarray:
    """``sum_e gates[:, e] (SiLU(h Wg_e) * (h Wu_e)) Wd_e`` over the
    experts of one block: every token through every one of them."""
    hidden = jax.nn.silu(jnp.einsum("td,edf->etf", h, wg)) \
        * jnp.einsum("td,edf->etf", h, wu)
    return jnp.einsum("etd,te->td", jnp.einsum("etf,efd->etd", hidden, wd),
                      gates)


def router_gates(h: jnp.ndarray, router: jnp.ndarray, top_k: int
                 ) -> jnp.ndarray:
    """``(T, E)``: the router's softmax over all experts, the ``top_k``
    largest of each row kept at their own value (not renormalised), the
    rest zero.  An expert is kept if fewer than ``top_k`` others beat
    it; of two equal ones the lower index beats the higher."""
    probs = jax.nn.softmax(h @ router, axis=-1)
    index = jnp.arange(probs.shape[-1])
    other, mine = probs[:, None, :], probs[:, :, None]
    beats = (other > mine) | ((other == mine)
                              & (index[None, None, :] < index[None, :, None]))
    return jnp.where(jnp.sum(beats, axis=-1) < top_k, probs, 0.0)


def experts(h: jnp.ndarray, p: Dict[str, jnp.ndarray], top_k: int
            ) -> jnp.ndarray:
    """The sparse-expert layer on tokens ``h (T, d)``, densely: every
    expert on every token, weighted by :func:`router_gates`."""
    gates = router_gates(h, p["router"], top_k)
    out = jnp.zeros_like(h)
    for lo in range(0, gates.shape[-1], EXPERT_BLOCK):
        hi = lo + EXPERT_BLOCK
        out = out + _expert_block(
            h, gates[:, lo:hi], p["experts_gate"][lo:hi],
            p["experts_up"][lo:hi], p["experts_down"][lo:hi])
    return out


def forward(params: Dict[str, Any], inputs: jnp.ndarray,
            config: Dict[str, Any]) -> jnp.ndarray:
    """Log-probabilities ``(batch, seq, vocab)`` for int32 ``inputs``."""
    n_head = int(config["num_attention_heads"])
    top_k = int(config["num_experts_per_tok"])
    theta = float(config["rope_theta"])
    eps = float(config["rms_norm_eps"])
    x = params["embed"][inputs]
    b, seq, d = x.shape
    for i in range(int(config["num_hidden_layers"])):
        p = params[f"OlmoeBlock_{i}"]
        x = x + attention(rms_norm(x, p["attn_norm"], eps), p, n_head,
                          theta, eps)
        h = rms_norm(x, p["mlp_norm"], eps).reshape(b * seq, d)
        x = x + experts(h, p, top_k).reshape(b, seq, d)
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
    outlives it; ``config`` holds OLMoE's own keys
    (``num_attention_heads``, ``num_hidden_layers``,
    ``num_experts_per_tok``, ``rope_theta``, ``rms_norm_eps``).  The
    tokens are an argument, never a constant of the program."""
    fn = jax.jit(jax.value_and_grad(
        lambda flat, tok: loss(unravel(flat), tok, config)))
    with jax.default_matmul_precision("highest"):
        return fn(w, tokens)
