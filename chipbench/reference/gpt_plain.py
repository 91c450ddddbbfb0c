"""The plain reference of the GPT-2 block: what a configuration with
``"reference": "gpt_plain"`` is held to.  Forward, loss and gradient in
straightforward ``jax.numpy``, float32, every matrix product at
``default_matmul_precision("highest")``, dense masked attention, no
kernel, no parameter server.  ``chipbench/spec.py`` finds it by the
configuration's key and has the contract of such a module
(``loss_and_grad_flat``, ``LOSS_TOL_NATS``, ``GRAD_REL_TOL``);
``chipbench/compare.py`` is the comparison every reference is held by,
and decides the reference part of ``correct``.  The sizes it needs
(``n_head``, ``n_layer``) it reads from the configuration's file, which
it is handed as a dict.

The block is GPT-2's, which Cerebras-GPT (arXiv:2304.03208, section 2.1;
``model_type`` ``gpt2``) uses unchanged: token plus learned position
embeddings, ``n_layer`` pre-LayerNorm blocks of causal multi-head
attention and a GELU MLP of ``n_inner`` = 4 x ``n_embd``, a final
LayerNorm and a linear head, next-token cross-entropy averaged over all
positions.

Departures from the published block, all of them the program's
(``mpit_tpu/models/transformer.py``), followed here so that the two can
agree:

- no bias on the QKV and output projections (GPT-2 has them);
- the output head is a matrix of its own, not the token table
  transposed (GPT-2 ties them);
- LayerNorm epsilon 1e-6 (flax's default; GPT-2's ``config.json`` says
  1e-5);
- GELU in its tanh form (flax's default; GPT-2's ``gelu_new`` is the
  same form, Cerebras-GPT's ``gelu`` is the erf form);
- attention scores scaled by 1/sqrt(head width), no dropout;
- weights are the program's seeded initialisation, not GPT-2's.

Parameters come as the program's own pytree (the ``unravel`` of the flat
vector), read by the names flax gave them.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

LN_EPS = 1e-6

# How far the system may be from this reference, and why (my chip run,
# PR 22: chipbench/reference/probe_tolerance.py, three seeds at each
# configuration's widths, PERF.md section 6).  The reference multiplies
# at full float32 precision.  The program's matrices are float32 in
# memory, but its products run on the MXU at XLA's default precision for
# the TPU: one bf16 pass with float32 accumulation (the compiled step
# converts the weights to bf16, as the device trace shows).  So the
# system differs from the reference by the rounding of every product's
# inputs to 8 bits of mantissa: it measured 0.4e-4..4.8e-4 nats in the
# loss and 0.73..0.86% of the gradient's norm at vocabulary 256; at the
# full vocabulary the eight benchmark runs of PR 22 read 0.2e-4..2.9e-4
# nats and 0.58..0.68%.  The bounds are three times and one and a half
# times the largest of the first readings.  They refuse a
# wrong mask, a dropped term and anything coarser than one bf16 pass.
# They cannot refuse activations or parameters kept in bf16 as well: in
# the same probe that measured 0.83..1.0% of the gradient's norm, next
# to the system's own, because the system's products already are bf16.
LOSS_TOL_NATS = 1.5e-3
GRAD_REL_TOL = 1.3e-2


def layer_norm(x: jnp.ndarray, p: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def gelu_tanh(x: jnp.ndarray) -> jnp.ndarray:
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def attention(x: jnp.ndarray, p: Dict[str, Any], n_head: int) -> jnp.ndarray:
    b, seq, d = x.shape
    head = d // n_head
    qkv = x @ p["Dense_0"]["kernel"]
    q, k, v = (t.reshape(b, seq, n_head, head).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(head)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)
    return out.transpose(0, 2, 1, 3).reshape(b, seq, d) @ p["Dense_1"]["kernel"]


def block(x: jnp.ndarray, p: Dict[str, Any], n_head: int) -> jnp.ndarray:
    x = x + attention(layer_norm(x, p["LayerNorm_0"]), p, n_head)
    h = layer_norm(x, p["LayerNorm_1"])
    h = gelu_tanh(h @ p["Dense_2"]["kernel"] + p["Dense_2"]["bias"])
    return x + h @ p["Dense_3"]["kernel"] + p["Dense_3"]["bias"]


def forward(params: Dict[str, Any], inputs: jnp.ndarray, n_head: int,
            n_layer: int) -> jnp.ndarray:
    """Log-probabilities ``(batch, seq, vocab)`` for int32 ``inputs``."""
    seq = inputs.shape[1]
    x = params["Embed_0"]["embedding"][inputs]
    x = x + params["Embed_1"]["embedding"][jnp.arange(seq)][None]
    for i in range(n_layer):
        x = block(x, params[f"DecoderBlock_{i}"], n_head)
    x = layer_norm(x, params["LayerNorm_0"])
    return jax.nn.log_softmax(x @ params["Dense_0"]["kernel"], axis=-1)


def loss(params: Dict[str, Any], tokens: jnp.ndarray, n_head: int,
         n_layer: int) -> jnp.ndarray:
    """Mean next-token negative log-likelihood over a packed grid
    ``(batch, seq + 1)``: every cell is a target."""
    logp = forward(params, tokens[:, :-1], n_head, n_layer)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


def loss_and_grad_flat(w: jnp.ndarray, unravel: Any, tokens: jnp.ndarray,
                       config: Dict[str, Any]
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The contract's entry: from the program's flat vector to the loss
    and a flat gradient, in one jitted program, so that no pytree of the
    model's size outlives it; ``config`` is the configuration's file.
    The tokens are an argument, never a constant of the program: a
    constant would make every seed a new program for the compile cache
    (85 s of every set-up at 111m, my chip run, PR 22)."""
    n_head, n_layer = int(config["n_head"]), int(config["n_layer"])
    fn = jax.jit(jax.value_and_grad(
        lambda flat, tok: loss(unravel(flat), tok, n_head, n_layer)))
    with jax.default_matmul_precision("highest"):
        return fn(w, tokens)
