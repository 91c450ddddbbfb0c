"""Transformer-LM TrainState for the PS stack.

Assembles one of the decoders of :mod:`mpit_tpu.models.transformer`
(``arch``: ``gpt2`` is :class:`TinyDecoder`, ``olmoe``
:class:`OlmoeDecoder`; either's attention is the ``ops/`` flash kernel
on TPU and the jnp reference — which differentiates without a recompute
pass — elsewhere) into the flat-vector calling convention the parameter server shards: a
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
from mpit_tpu.models import transformer
from mpit_tpu.models.transformer import (
    OlmoeDecoder,
    TinyDecoder,
    default_attn,
)

ARCHS = ("gpt2", "olmoe")


class LmModel(NamedTuple):
    """A built LM: the module, its flat view, and the loss closures."""

    module: Any
    flat: FlatModel
    loss: Callable[..., jnp.ndarray]          # (w, tokens) -> scalar NLL
    value_and_grad: Callable[..., Any]        # (w, tokens) -> (loss, grad)
    seq_len: int
    vocab: int
    #: olmoe: (w, tokens) -> ((loss, {name: device array}), grad), the
    #: same step with the block's own telemetry as an auxiliary output
    #: (``moe_load_max_over_mean``, one number a layer), which the shell
    #: fetches only while obs is on; None for a block that has none
    value_grad_stats: Optional[Callable[..., Any]] = None


def _resolve_attn(use_flash: Optional[bool],
                  precision: Optional[str] = None):
    """``use_flash`` None: the pallas kernel on TPU, the jnp reference
    elsewhere (the reference path differentiates without a recompute
    pass, which is the right trade on CPU gangs like the CI smoke).
    True pins the kernel AND pins it compiled (``interpret=False``): a
    run that asked for flash gets Mosaic's kernel or an error at
    lowering, never the interpreter because the process quietly came up
    on another backend.  False pins the reference.  ``precision`` is the
    attention products' (``default_attn``)."""
    if use_flash is None:
        return default_attn(causal=True, precision=precision,
                            use_flash=jax.default_backend() == "tpu")
    return default_attn(causal=True, use_flash=bool(use_flash),
                        interpret=False if use_flash else None,
                        precision=precision)


def build_kw(cfg: Any) -> dict:
    """``build``'s keywords for a trainer config (``LM_DEFAULTS``'
    names): every size of either block and the seed; the attention is
    the caller's to choose.  ``vocab`` 0 leaves ``build``'s own default
    in force (the byte stream's 256)."""
    kw = {key: cfg[key] for key in (
        "arch", "d_model", "n_heads", "n_layers", "seq_len", "seed",
        "n_experts", "experts_per_tok", "expert_width", "rope_theta",
        "norm_eps")}
    if int(cfg.vocab):
        kw["vocab"] = int(cfg.vocab)
    return kw


def build(*, arch: str = "gpt2", vocab: int = 256, d_model: int = 64,
          n_heads: int = 4, n_layers: int = 2, seq_len: int = 128,
          seed: int = 0, use_flash: Optional[bool] = None,
          n_experts: int = 8, experts_per_tok: int = 2,
          expert_width: int = 32, rope_theta: float = 10000.0,
          norm_eps: float = 1e-5) -> LmModel:
    """Build the decoder, flatten its params, and close over the
    next-token NLL.  ``arch`` chooses the block; the expert, rotary and
    norm sizes are ``olmoe``'s alone.  For ``gpt2`` ``max_len`` is
    pinned to ``seq_len`` — the packed stream always fills full
    sequences, and an exact fit keeps the position table out of the
    sharding slack (``olmoe``'s positions are rotary: no table)."""
    if arch not in ARCHS:
        raise ValueError(f"unknown LM arch {arch!r}; have {ARCHS}")
    if arch == "olmoe":
        # read at build time: the probe of the reference's tolerances
        # tries other precisions (chipbench/reference/probe_olmoe.py)
        attn_fn = _resolve_attn(use_flash, transformer.ATTN_KERNEL_PRECISION)
        module: Any = OlmoeDecoder(
            vocab=vocab, d_model=d_model, n_heads=n_heads,
            n_layers=n_layers, n_experts=n_experts,
            experts_per_tok=experts_per_tok, expert_width=expert_width,
            rope_theta=rope_theta, norm_eps=norm_eps, attn_fn=attn_fn)
    else:
        module = TinyDecoder(
            vocab=vocab, d_model=d_model, n_heads=n_heads,
            n_layers=n_layers, max_len=seq_len,
            attn_fn=_resolve_attn(use_flash))
    sample = jnp.zeros((1, seq_len), jnp.int32)
    fm = flatten_module(module, jax.random.PRNGKey(seed), sample)

    def mean_nll(logp, targets):
        with jax.named_scope("head_loss"):
            nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
            return jnp.mean(nll)

    def loss(w, tokens):
        # tokens: (B, seq_len + 1) int32 — packed, every cell real.
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        logp = fm.apply_flat(w, inputs)  # (B, L, V) log-probs
        return mean_nll(logp, targets)

    value_grad_stats = None
    if arch == "olmoe":
        def loss_and_load(w, tokens):
            # the same loss with ``intermediates`` collected: the
            # routers' loads, which the forward pass has already counted
            logp, state = fm.apply_flat(w, tokens[:, :-1],
                                        mutable=["intermediates"])
            loads = jax.tree_util.tree_leaves(state["intermediates"])
            return mean_nll(logp, tokens[:, 1:]), {
                "moe_load_max_over_mean": jnp.stack(loads)}

        value_grad_stats = jax.value_and_grad(loss_and_load, has_aux=True)

    return LmModel(module=module, flat=fm, loss=loss,
                   value_and_grad=jax.value_and_grad(loss),
                   seq_len=seq_len, vocab=vocab,
                   value_grad_stats=value_grad_stats)


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
