"""The plain reference of the looped block: what a configuration with
``"reference": "ouro_plain"`` is held to.  Forward pass, loss and
gradient in straightforward ``jax.numpy``, float32, every matrix product
at ``default_matmul_precision("highest")``, taken at the weights as the
chip's products read them (:func:`as_the_products_see`: the tolerances'
comment says why).  A Python loop over the
passes and, inside it, over the layers; attention by materialised
scores, a block of query rows at a time; no kernel, no scan, no
parameter server.  It imports nothing of the program and exists once:
the CPU tests (``tests/test_ouro.py``) hold the program to this very
module.  ``chipbench/spec.py`` finds it by the configuration's key and
has the contract of such a module (``loss_and_grad_flat``,
``LOSS_TOL_NATS``, ``GRAD_REL_TOL``); ``chipbench/compare.py`` is the
comparison every reference is held by.

The block (Ouro, ByteDance, ``model_type`` ``ouro``; the configuration's
keys are those of its ``config.json``).  With ``R = total_ut_steps``
passes over ``L = num_hidden_layers`` layers, per position::

    h^0 = E[x]
    pass t = 1..R, u = h^(t-1); for layer l = 1..L, the same weights in every t:
        a = rms(u; g1_l);  q, k, v = a Wq, a Wk, a Wv
                  # num_attention_heads heads of head_dim, as many KV
                  # heads, no bias; rotate-half RoPE, rope_theta, on q, k
        u = u + rms( softmax_causal(q k^T / sqrt head_dim) v  Wo ; g2_l )
        b = rms(u; g3_l)
        u = u + rms( (silu(b Wgate) * (b Wup)) Wdown ; g4_l )
    h^t     = rms(u; g_f)              # one final norm, shared by the passes
    nll^t   = -log softmax(h^t W_head)[next token]     # one head, R times
    lam_t   = sigmoid(h^t . w_g + b_g)   for t < R     # the exit gate
    p_1 = lam_1;  p_t = lam_t prod_{j<t}(1 - lam_j), 1 < t < R
    p_R = prod_{j<R}(1 - lam_j)
    loss    = mean over positions of [ sum_t p_t nll^t - beta H(p) ]
    H(p)    = -sum_t p_t log p_t

``rms(x; g) = x / sqrt(mean(x^2) + rms_norm_eps) * g``; ``beta`` is the
file's ``exit_entropy_beta``.  The norm before **and after** each
sublayer is the sandwich; the pass's normed output ``h^t`` is what goes
to the head, to the gate and into the next pass.

What the published config does not give, each also a line of ``assumed``
in ``chipbench/configs/ouro-2.6b-l6.json``: where the four norms of a
layer and the final norm sit (as above), the gate's bias, ``beta`` 0.1,
the loss taken per position; ``early_exit_threshold`` plays no part in
training; weights are the program's seeded initialisation, not the
checkpoint.

Memory.  Each layer application and each pass's norm, head and loss is
under ``jax.checkpoint`` (the backward pass keeps its input and computes
it again), and inside a layer the attention is walked in blocks of
:data:`ROW_BLOCK` query rows, each under ``jax.checkpoint``, so that one
block's scores live at a time: at the published widths and 4096
positions that is what lets the reference run beside the system's own
vectors on a 16 GB chip.  Recomputation changes no number.

Parameters come as the program's own pytree (the ``unravel`` of the flat
vector), read by the names ``models/transformer.py`` gave them.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

ROW_BLOCK = 1024

# Tolerances, from the v5e at the published widths of ``ouro-l6-local``
# (PERF.md section 6, PR 36; ``probe_ouro.py`` beside this file and the
# cell's own runs made every reading, one seeded sequence of 4096 a
# seed, the gate's bias seeded at -4 as the configuration has it, so
# that the last pass carries 0.95 of the loss and the gradient runs
# through all 24 layer applications).
#
# Against the exact float32 reference *at the float32 weights* the
# system (float32 in memory, one bf16 pass a product) read 1.06..2.28%
# of the gradient's norm over nineteen seeds, and this module's own
# arithmetic with parameters and activations in bf16, the nearest
# precision below the configuration's, 1.13..1.28 times that on the same
# seeds: no limit lay between them, and the first version of this file
# passed bf16.  Most of both errors is one and the same thing: a product
# multiplies by ``bf16(W)``, a fixed perturbation of every matrix, the
# same in all four passes and at every position, which a sum over 4096
# positions does not average away, through 24 layer applications whose
# outputs the sandwich's norms bring back to full size.  Read on the
# chip, at the cell's size: the exact reference at the float32 weights
# against the exact reference at ``bf16(W)`` is 1.22, 1.87 and 2.22% of
# the gradient's norm with the bias at 0 and 2.15% with it at -4
# (``probe_ouro.py --exact``).  That part is a choice of the point at
# which the gradient is taken, it is the configuration's stated
# precision and not an error of the program's, and bf16 in memory
# shares it; so the reference takes the system's point
# (:func:`as_the_products_see`), and what is left is what the two sides
# do differently:
#
#                                  gradient, % of norm        loss, nats
#   system, thirteen seeds         1.04..1.42 (mean 1.27)     0.6e-4..9.8e-4
#   bf16 in memory, six of them    1.64..2.05 (mean 1.87)     0.2e-4..1.1e-3
#
# (six seeds by the probe, both readings: 1.17/1.80, 1.19/1.64,
# 1.21/1.72, 1.31/2.01, 1.32/1.88, 1.41/2.05: each seed's bf16 reading
# is 1.38..1.54 times its system reading, since the products' inputs are
# rounded on both sides and bf16 in memory rounds the stream, the norms
# and the softmax besides; seven more system readings by the cell's own
# runs: 1.04, 1.12, 1.21, 1.28, 1.32, 1.35, 1.42).  GRAD_REL_TOL lies
# between the system's largest and the bf16 reference's smallest, nearer
# the latter because a run that fails an honest system is the costlier
# fault: 1.6%, 2.9 of the thirteen readings' standard deviations (0.115)
# above their mean and 12% above the largest, 2.4% under the smallest
# bf16 reading, which fails it on all six seeds read.  The room is what
# the precisions leave: they differ by a factor of 1.45 here where
# Mellum's and LFM2's differ by 2.4 to 3.3, and a fresh seed's bf16
# reading can come under the limit (1.8 of their deviations away).
#
# The limit also refuses the reference with one thing wrong, each on the
# chip at the committed configuration (seed 2147361441, where the system
# reads 1.19% and bf16 1.64%): no entropy term 10.9%, rotary pairs
# interleaved 57%, one pass fewer 78%, attention one key into the future
# 83%, the next pass fed the stream before the final norm 106%, no norm
# on the sublayers' outputs 117%.
#
# The loss cannot tell the precisions apart (the table).  The harness's
# 1.0e-3 of the other references is the system's largest reading of
# thirteen (9.8e-4), and the comparison does not let a reference leave
# the loss out: 2.0e-3, twice the largest reading and 4.5 of their
# root-mean-squares (4.4e-4), is a guard against a wrong loss and no
# more (``rotary pairs interleaved`` reads 2.4e-3, ``one pass fewer``
# 5.1e-3, ``no entropy term`` 2.0e-2; PERF.md section 7 asks for the
# repair).
LOSS_TOL_NATS = 2.0e-3
GRAD_REL_TOL = 1.6e-2


def rms(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


# -- attention ---------------------------------------------------------------------


def rotary_table(seq: int, head: int, theta: float
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``cos, sin (seq, head)``: pair ``j`` of ``head / 2`` turns by
    ``position * theta^(-2j/head)``."""
    freq = theta ** (-jnp.arange(0, head, 2, dtype=jnp.float32) / head)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)
    return jnp.cos(angle), jnp.sin(angle)


def rotate(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Rotary embedding of ``x (batch, heads, seq, head)``, rotate-half:
    ``x cos + rotate_half(x) sin`` with ``rotate_half((a, b)) = (-b,
    a)`` over the head's two halves."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return x * cos + jnp.concatenate([-b, a], axis=-1) * sin


@jax.checkpoint
def _rows(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
          first: jnp.ndarray) -> jnp.ndarray:
    """Causal softmax attention of the query rows ``q (batch, heads,
    rows, head)``, which are positions ``first ..``, over all of ``k, v
    (batch, heads, seq, head)``."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    at = first + jnp.arange(q.shape[2])
    seen = jnp.arange(k.shape[2])[None, :] <= at[:, None]
    scores = jnp.where(seen, scores, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)


def attention(a: jnp.ndarray, p: Dict[str, jnp.ndarray], heads: int,
              head: int, theta: float) -> jnp.ndarray:
    batch, seq, _ = a.shape

    def split(x):
        return x.reshape(batch, seq, heads, head).transpose(0, 2, 1, 3)

    q, k, v = split(a @ p["wq"]), split(a @ p["wk"]), split(a @ p["wv"])
    cos, sin = rotary_table(seq, head, theta)
    q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    out = jnp.concatenate(
        [_rows(q[:, :, lo:lo + ROW_BLOCK], k, v, jnp.asarray(lo))
         for lo in range(0, seq, ROW_BLOCK)], axis=2)
    return out.transpose(0, 2, 1, 3).reshape(batch, seq, heads * head) \
        @ p["wo"]


# -- the model ---------------------------------------------------------------------


def layer(u: jnp.ndarray, p: Dict[str, jnp.ndarray],
          config: Dict[str, Any]) -> jnp.ndarray:
    eps = float(config["rms_norm_eps"])
    if int(config["num_key_value_heads"]) != int(
            config["num_attention_heads"]):
        raise ValueError("ouro_plain: as many KV heads as query heads")
    a = rms(u, p["attn_norm"], eps)
    u = u + rms(attention(a, p, int(config["num_attention_heads"]),
                          int(config["head_dim"]),
                          float(config["rope_theta"])),
                p["attn_out_norm"], eps)
    b = rms(u, p["mlp_norm"], eps)
    mlp = (jax.nn.silu(b @ p["w_gate"]) * (b @ p["w_up"])) @ p["w_down"]
    return u + rms(mlp, p["mlp_out_norm"], eps)


def pass_end(u: jnp.ndarray, params: Dict[str, Any], targets: jnp.ndarray,
             eps: float) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The pass's normed output, its next-token NLL ``(batch, seq)`` and
    its gate ``(batch, seq)``."""
    h = rms(u, params["final_norm"], eps)
    logp = jax.nn.log_softmax(h @ params["head"], axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    lam = jax.nn.sigmoid(h @ params["loop_gate"] + params["loop_gate_bias"])
    return h, nll, lam


def passes(params: Dict[str, Any], inputs: jnp.ndarray, targets: jnp.ndarray,
           config: Dict[str, Any]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``nll (R, batch, seq)`` of every pass and ``p (R, batch, seq)``,
    the probability of leaving at each."""
    eps = float(config["rms_norm_eps"])
    steps = int(config["total_ut_steps"])
    h = params["embed"][inputs]
    nll, lam = [], []
    for _ in range(steps):
        u = h
        for i in range(int(config["num_hidden_layers"])):
            u = jax.checkpoint(lambda u, p: layer(u, p, config))(
                u, params[f"OuroBlock_{i}"])
        h, nll_t, lam_t = jax.checkpoint(
            lambda u, ps: pass_end(u, ps, targets, eps))(
                u, {k: params[k] for k in ("final_norm", "head", "loop_gate",
                                           "loop_gate_bias")})
        nll.append(nll_t)
        lam.append(lam_t)
    p, stay = [], jnp.ones_like(nll[0])
    for t in range(steps - 1):
        p.append(lam[t] * stay)
        stay = stay * (1.0 - lam[t])
    p.append(stay)
    return jnp.stack(nll), jnp.stack(p)


def loss(params: Dict[str, Any], tokens: jnp.ndarray,
         config: Dict[str, Any]) -> jnp.ndarray:
    """The training loss over a packed grid ``(batch, seq + 1)``, every
    cell a target: the mean over positions of the passes' NLLs weighted
    by the exit distribution, less ``exit_entropy_beta`` times its
    entropy."""
    nll, p = passes(params, tokens[:, :-1], tokens[:, 1:], config)
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)),
                                 0.0), axis=0)
    return jnp.mean(jnp.sum(p * nll, axis=0)
                    - float(config["exit_entropy_beta"]) * entropy)


# The matrices that enter a product, by the names ``models/transformer.py``
# gave them.  Not among them: the token table (a lookup), the norms'
# weights and the gate (elementwise in float32 in the program too).
PRODUCT_WEIGHTS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "head")


def as_the_products_see(params: Dict[str, Any]) -> Dict[str, Any]:
    """``params`` with every matrix of :data:`PRODUCT_WEIGHTS` at the
    value the program's products read it at on this backend.  The
    configuration states float32 in memory and one bf16 pass a product:
    on a TPU a product rounds both of its inputs to bf16 (to nearest
    even), so the *weights* it multiplies by are ``bf16(W)``, one fixed
    matrix that is the same in all four passes, at every position, in
    the forward and the backward pass.  The reference is the exact
    float32 loss and gradient **at those weights** (the gradient passes
    the rounding as the identity, as the program's does), so that what
    is compared is the rounding of the activations and the accumulation,
    which is the program's arithmetic, and not the choice of the point:
    see the tolerances' comment.  Elsewhere (the CPU of the tests and of
    the self-check) a product reads float32 and this is the identity."""
    if jax.default_backend() != "tpu":
        return params

    def seen(name: str, leaf: Any) -> Any:
        if hasattr(leaf, "items"):
            return {k: seen(k, v) for k, v in leaf.items()}
        if name not in PRODUCT_WEIGHTS:
            return leaf
        # bf16's 8 exponent and 7 mantissa bits, to nearest even; not a
        # pair of casts, which the compiler may take out as excess
        # precision (it did, on the chip)
        rounded = jax.lax.reduce_precision(leaf, exponent_bits=8,
                                           mantissa_bits=7)
        return leaf + jax.lax.stop_gradient(rounded - leaf)

    return seen("", params)


def loss_and_grad_flat(w: jnp.ndarray, unravel: Any, tokens: jnp.ndarray,
                       config: Dict[str, Any]
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """From the program's flat vector to the loss and a flat gradient,
    in one jitted program, so that no pytree of the model's size
    outlives it; ``config`` holds Ouro's own keys (the module's
    docstring names each).  The tokens are an argument, never a constant
    of the program.  The weights are :func:`as_the_products_see` them."""
    fn = jax.jit(jax.value_and_grad(
        lambda flat, tok: loss(as_the_products_see(unravel(flat)), tok,
                               config)))
    with jax.default_matmul_precision("highest"):
        return fn(w, tokens)
