"""Transformer-LM TrainState for the PS stack.

Assembles :class:`mpit_tpu.models.transformer.TinyDecoder` (whose
attention is the ``ops/`` flash kernel on TPU and the jnp reference —
which differentiates without a recompute pass — elsewhere) into the
flat-vector calling convention the parameter server shards: a
:class:`~mpit_tpu.models.flat.FlatModel` plus a next-token NLL over
packed token grids, and the params+optimizer pytree
(:func:`train_state_tree`) that :mod:`mpit_tpu.lm.plan` drives the
partition rules over.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from mpit_tpu.models.flat import FlatModel, flatten_module
from mpit_tpu.models.transformer import TinyDecoder, default_attn


class LmModel(NamedTuple):
    """A built LM: the module, its flat view, and the loss closures."""

    module: Any
    flat: FlatModel
    loss: Callable[..., jnp.ndarray]          # (w, tokens) -> scalar NLL
    value_and_grad: Callable[..., Any]        # (w, tokens) -> (loss, grad)
    seq_len: int
    vocab: int


def _resolve_attn(use_flash: Optional[bool]):
    """``use_flash`` None: the pallas kernel on TPU, the jnp reference
    elsewhere (the reference path differentiates without a recompute
    pass, which is the right trade on CPU gangs like the CI smoke).
    True pins the kernel AND pins it compiled (``interpret=False``): a
    run that asked for flash gets Mosaic's kernel or an error at
    lowering, never the interpreter because the process quietly came up
    on another backend.  False pins the reference."""
    if use_flash is None:
        return default_attn(causal=True,
                            use_flash=jax.default_backend() == "tpu")
    return default_attn(causal=True, use_flash=bool(use_flash),
                        interpret=False if use_flash else None)


def vocab_kw(vocab: int) -> dict:
    """``build``'s vocabulary keyword for a ``--lm_vocab`` value: 0
    leaves ``build``'s own keyword default in force."""
    return {"vocab": int(vocab)} if vocab else {}


def build(*, vocab: int = 256, d_model: int = 64, n_heads: int = 4,
          n_layers: int = 2, seq_len: int = 128, seed: int = 0,
          use_flash: Optional[bool] = None) -> LmModel:
    """Build the decoder, flatten its params, and close over the
    next-token NLL.  ``max_len`` is pinned to ``seq_len`` — the packed
    stream always fills full sequences, and an exact fit keeps the
    position table out of the sharding slack."""
    module = TinyDecoder(
        vocab=vocab, d_model=d_model, n_heads=n_heads, n_layers=n_layers,
        max_len=seq_len,
        attn_fn=_resolve_attn(use_flash),
    )
    sample = jnp.zeros((1, seq_len), jnp.int32)
    fm = flatten_module(module, jax.random.PRNGKey(seed), sample)

    def loss(w, tokens):
        # tokens: (B, seq_len + 1) int32 — packed, every cell real.
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        logp = fm.apply_flat(w, inputs)  # (B, L, V) log-probs
        with jax.named_scope("head_loss"):
            nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
            return jnp.mean(nll)

    return LmModel(module=module, flat=fm, loss=loss,
                   value_and_grad=jax.value_and_grad(loss),
                   seq_len=seq_len, vocab=vocab)


def train_state_tree(params: Any, rule_name: str = "adam") -> Any:
    """The params+optimizer pytree the shard plan is computed over: a
    TrainState-shaped dict whose ``opt_state`` mirrors ``params`` with
    one :mod:`mpit_tpu.optim.rules` state dict per parameter (the
    per-parameter optimizer slots the servers allocate beside their
    shard).  Rule inits share one ``zeros_like`` across their state
    entries (e.g. adam's m and v), so the returned tree contains the
    aliasing that ``hbm.dedupe_state`` exists to break — tests pin that
    the two compose."""
    from mpit_tpu.optim import rules as _rules

    rule = _rules.make(rule_name)
    opt_state = jax.tree_util.tree_map(rule.init, params)
    return {"params": params, "opt_state": opt_state}
