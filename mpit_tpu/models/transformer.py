"""Decoder-only transformer with pluggable attention — the long-context
workload.

The reference's model zoo stops at conv/pool nets (SURVEY.md §5: no
attention, no sequence machinery); this model is the TPU-native
long-context showcase built on the framework's own kernels:

- attention is injected as ``attn_fn(q, k, v) -> out`` over
  ``(B, L, H, D)``, so the same module runs single-device with
  :func:`mpit_tpu.ops.flash_attention` (the default) or
  sequence-parallel with
  :func:`mpit_tpu.parallel.ring_attention.ring_attention` — the module
  never knows about meshes;
- MXU-friendly sizing: model/head dims in multiples of 8, all matmuls
  batched over (B, L);
- pre-LN blocks, learned positional embeddings, causal by default.
"""

from __future__ import annotations

from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from mpit_tpu.ops.flash_attention import attention_reference, flash_attention

AttnFn = Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray], jnp.ndarray]


def default_attn(causal: bool = True, use_flash: bool = True,
                 interpret: Optional[bool] = None) -> AttnFn:
    """Single-device attention over (B, L, H, D): flash kernel or the jnp
    reference (the latter differentiates without a recompute pass).
    ``interpret`` reaches ``pallas_call``: None interprets everywhere
    but on a TPU (ops/tiles.py), False pins the Mosaic-compiled kernel."""

    def fn(q, k, v):
        qh, kh, vh = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
        if use_flash:
            out = flash_attention(qh, kh, vh, causal=causal,
                                  interpret=interpret)
        else:
            out = attention_reference(qh, kh, vh, causal=causal)
        return out.transpose(0, 2, 1, 3)

    return fn


class DecoderBlock(nn.Module):
    d_model: int
    n_heads: int
    mlp_ratio: int = 4
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        b, l, _ = x.shape
        head = self.d_model // self.n_heads
        attn = self.attn_fn if self.attn_fn is not None else default_attn()

        # The scopes name the model's layers on the device operations
        # of a profiler trace (forward and backward alike); they are
        # metadata and change no program.
        with jax.named_scope("attn"):
            h = nn.LayerNorm()(x)
            qkv = nn.Dense(3 * self.d_model, use_bias=False)(h)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(b, l, self.n_heads, head)
            k = k.reshape(b, l, self.n_heads, head)
            v = v.reshape(b, l, self.n_heads, head)
            x = x + nn.Dense(self.d_model, use_bias=False)(
                attn(q, k, v).reshape(b, l, self.d_model)
            )

        with jax.named_scope("mlp"):
            h = nn.LayerNorm()(x)
            h = nn.gelu(nn.Dense(self.mlp_ratio * self.d_model)(h))
            return x + nn.Dense(self.d_model)(h)


class TinyDecoder(nn.Module):
    """Small causal LM: token + learned position embeddings, N pre-LN
    blocks, tied-free output head.  ``attn_fn`` switches between local
    flash attention and mesh ring attention without touching params —
    the two variants are numerically identical, which the tests pin."""

    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 2
    max_len: int = 1024
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, tokens: jnp.ndarray) -> jnp.ndarray:
        b, l = tokens.shape
        if l > self.max_len:
            # Fail at trace time: out-of-range position gathers clamp
            # under jit and would silently reuse the last embedding row.
            raise ValueError(f"sequence length {l} > max_len {self.max_len}")
        with jax.named_scope("embed"):
            x = nn.Embed(self.vocab, self.d_model)(tokens)
            pos = nn.Embed(self.max_len, self.d_model)(jnp.arange(l))
            x = x + pos[None, :, :]
        for _ in range(self.n_layers):
            x = DecoderBlock(
                d_model=self.d_model, n_heads=self.n_heads,
                attn_fn=self.attn_fn,
            )(x)
        with jax.named_scope("head_loss"):  # lm/model.py's NLL joins it
            x = nn.LayerNorm()(x)
            logits = nn.Dense(self.vocab, use_bias=False)(x)
            return nn.log_softmax(logits)
