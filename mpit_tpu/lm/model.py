"""Transformer-LM TrainState for the PS stack.

Assembles one of the decoders of :mod:`mpit_tpu.models.transformer`
(``arch``: ``gpt2`` is :class:`TinyDecoder`, ``olmoe``
:class:`OlmoeDecoder`, ``mellum`` :class:`MellumDecoder`, ``lfm2``
:class:`Lfm2Decoder`, ``ouro`` :class:`OuroDecoder`, ``joyai``
:class:`JoyaiDecoder`; each one's
attention is the ``ops/`` flash kernel
on TPU and the jnp reference — which differentiates without a recompute
pass — elsewhere) into the flat-vector calling convention the parameter server shards: a
:class:`~mpit_tpu.models.flat.FlatModel` plus a next-token NLL over
packed token grids (``ouro`` closes its own loss over its passes' heads
and exit gates, ``joyai`` over its main and its multi-token-prediction
head), and the params+optimizer pytree
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
    JoyaiDecoder,
    Lfm2Decoder,
    MellumDecoder,
    OlmoeDecoder,
    OuroDecoder,
    TinyDecoder,
    default_attn,
)

ARCHS = ("gpt2", "olmoe", "mellum", "lfm2", "ouro", "joyai")
# what a sparse layer ``sow``s, and the name of each in the step's
# telemetry (``value_grad_stats``), on the round span and as the gauge
# ``mpit_<name>``
MOE_STATS = {"moe_load": "moe_load_max_over_mean",
             "moe_held": "moe_held_rows_share",
             "moe_compact": "moe_compact_share",
             "moe_flips": "moe_bias_flips_share"}


class LmModel(NamedTuple):
    """A built LM: the module, its flat view, and the loss closures."""

    module: Any
    flat: FlatModel
    loss: Callable[..., jnp.ndarray]          # (w, tokens) -> scalar NLL
    value_and_grad: Callable[..., Any]        # (w, tokens) -> (loss, grad)
    seq_len: int
    vocab: int
    #: olmoe, mellum, lfm2: (w, tokens) -> ((loss, {name: device
    #: array}), grad), the same step with the block's own telemetry as
    #: an auxiliary output (``moe_load_max_over_mean``; from a block
    #: that holds a share of its experts ``moe_held_rows_share`` and
    #: ``moe_compact_share``; from
    #: one whose router has a selection bias ``moe_bias_flips_share``;
    #: one number a sparse layer each), which the optimizer fetches only
    #: while obs is on; ouro: the loop's three counters
    #: (``loop_exit_step_mean``, ``loop_loss_drop``,
    #: ``loop_exit_entropy``, one number each); joyai: its two heads'
    #: NLLs (``lm_main_nll``, ``lm_mtp_nll``) and the four routing
    #: counters, one entry a sparse layer, the MTP module's last; None
    #: for a block that has none
    value_grad_stats: Optional[Callable[..., Any]] = None
    #: ouro: the bytes a sequence that the decoder's checkpoints keep by
    #: name for the backward pass (``OuroDecoder.kept_residual_bytes``);
    #: 0 for a decoder that names none
    kept_residual_bytes: int = 0


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
    return default_attn(causal=True, use_flash=_uses_flash(use_flash),
                        interpret=False if use_flash else None,
                        precision=precision)


def _uses_flash(use_flash: Optional[bool]) -> bool:
    """Whether :func:`_resolve_attn` gives the kernel."""
    return (jax.default_backend() == "tpu" if use_flash is None
            else bool(use_flash))


# mellum's own sizes (``build``'s keywords, ``LM_DEFAULTS``' names);
# lfm2 shares the first four
MELLUM_KEYS = ("kv_heads", "head_dim", "experts_first", "experts_held",
               "window", "full_every", "yarn_factor", "yarn_orig",
               "yarn_beta_fast", "yarn_beta_slow", "yarn_attn_factor")
# lfm2's own
LFM2_KEYS = ("layer_types", "dense_layers", "dense_width", "conv_kernel",
             "route_scale")
# ouro's own (it takes ``kv_heads``, ``head_dim`` and ``dense_width``
# too)
OURO_KEYS = ("loop_steps", "exit_beta", "exit_bias")
# joyai's own (it takes the share's two, ``dense_layers``,
# ``dense_width`` and ``route_scale`` too)
JOYAI_KEYS = ("q_rank", "kv_rank", "qk_nope", "qk_rope", "v_head",
              "shared_experts", "mtp_layers", "mtp_weight")


def build_kw(cfg: Any) -> dict:
    """``build``'s keywords for a trainer config (``LM_DEFAULTS``'
    names): every size of every block and the seed; the attention is
    the caller's to choose.  ``vocab`` 0 leaves ``build``'s own default
    in force (the byte stream's 256)."""
    kw = {key: cfg[key] for key in (
        "arch", "d_model", "n_heads", "n_layers", "seq_len", "seed",
        "n_experts", "experts_per_tok", "expert_width", "rope_theta",
        "norm_eps", *MELLUM_KEYS, *LFM2_KEYS, *OURO_KEYS, *JOYAI_KEYS)}
    if int(cfg.vocab):
        kw["vocab"] = int(cfg.vocab)
    return kw


def build(*, arch: str = "gpt2", vocab: int = 256, d_model: int = 64,
          n_heads: int = 4, n_layers: int = 2, seq_len: int = 128,
          seed: int = 0, use_flash: Optional[bool] = None,
          n_experts: int = 8, experts_per_tok: int = 2,
          expert_width: int = 32, rope_theta: float = 10000.0,
          norm_eps: float = 1e-5, kv_heads: int = 0, head_dim: int = 0,
          experts_first: int = 0, experts_held: int = 0, window: int = 0,
          full_every: int = 4, yarn_factor: float = 0.0,
          yarn_orig: int = 0, yarn_beta_fast: float = 32.0,
          yarn_beta_slow: float = 1.0,
          yarn_attn_factor: float = 1.0, layer_types: str = "",
          dense_layers: int = 0, dense_width: int = 0,
          conv_kernel: int = 3, route_scale: float = 1.0,
          loop_steps: int = 4, exit_beta: float = 0.1,
          exit_bias: float = 0.0, q_rank: int = 0, kv_rank: int = 0,
          qk_nope: int = 0, qk_rope: int = 0, v_head: int = 0,
          shared_experts: int = 1, mtp_layers: int = 1,
          mtp_weight: float = 0.3) -> LmModel:
    """Build the decoder, flatten its params, and close over the
    next-token NLL.  ``arch`` chooses the block; the expert, rotary and
    norm sizes are the sparse blocks' alone, and those from
    ``kv_heads`` on ``mellum``'s (``kv_heads`` 0: as many as query
    heads; ``head_dim`` 0: ``d_model / n_heads``; ``experts_held`` 0:
    all ``n_experts``, else the contiguous share from ``experts_first``
    that this chip holds of a router ``n_experts`` wide; ``window`` 0:
    every layer full; ``yarn_factor`` 0: the plain rotary table on the
    full layers too).  ``lfm2`` takes ``kv_heads``, ``head_dim`` and the
    share as ``mellum`` does, and from ``layer_types`` on its own: the
    token mixer of each of the ``n_layers`` layers held here, ``conv``
    or ``full_attention``, comma-separated; how many of them, the
    first, have the dense MLP of ``dense_width`` and not the sparse one;
    the short convolution's taps; the router's
    ``routed_scaling_factor``.  ``ouro`` takes ``kv_heads``,
    ``head_dim`` and ``dense_width`` (its one MLP's) as they do, and on
    its own ``loop_steps``, how often the ``n_layers`` layers are applied
    with the same weights, ``exit_beta``, the weight of the exit
    distribution's entropy in its loss, and ``exit_bias``, the value the
    exit gate's bias is seeded at (0: a gate of a half; negative: the
    loop starts nearer to running every pass); the loss is the block's own
    (:class:`OuroDecoder`), not the next-token NLL of one head.
    ``joyai`` takes the share, ``dense_layers``, ``dense_width`` and
    ``route_scale`` as ``lfm2`` does, and on its own the latent
    attention's sizes (``q_rank`` and ``kv_rank``, the low-rank
    products' inner widths; ``qk_nope`` and ``qk_rope``, the two parts
    of a head's query and key; ``v_head``, a head's value), how many
    ``shared_experts`` of ``expert_width`` every token takes beside the
    routed ones, ``mtp_layers`` (0 or 1: the multi-token-prediction
    module) and ``mtp_weight``, its loss's weight in the block's own
    objective (:class:`JoyaiDecoder`).  For
    ``gpt2`` ``max_len`` is pinned to ``seq_len``
    — the packed stream always fills full sequences, and an exact fit
    keeps the position table out of the sharding slack (the other
    blocks' positions are rotary: no table)."""
    if arch not in ARCHS:
        raise ValueError(f"unknown LM arch {arch!r}; have {ARCHS}")
    if arch == "ouro":
        if loop_steps < 1:
            raise ValueError(f"loop_steps {loop_steps}: at least one pass")
        module: Any = OuroDecoder(
            vocab=vocab, d_model=d_model, n_heads=n_heads,
            kv_heads=kv_heads or n_heads,
            head_dim=head_dim or d_model // n_heads, n_layers=n_layers,
            dense_width=dense_width, loop_steps=loop_steps,
            exit_beta=float(exit_beta), exit_bias=float(exit_bias),
            rope_theta=rope_theta,
            norm_eps=norm_eps, attn_fn=_resolve_attn(use_flash))
        return _own_loss(module, seed, seq_len, vocab)._replace(
            kept_residual_bytes=module.kept_residual_bytes(
                seq_len, _uses_flash(use_flash)))
    if arch in ("mellum", "lfm2", "joyai"):
        held = experts_held or n_experts
        if experts_first + held > n_experts:
            raise ValueError(f"experts {experts_first}.."
                             f"{experts_first + held - 1} held of {n_experts}")
        attn_fn = _resolve_attn(use_flash)
    if arch == "joyai":
        if min(q_rank, kv_rank, qk_nope, qk_rope, v_head) < 1 or qk_rope % 2:
            raise ValueError(
                f"joyai needs q_rank, kv_rank, qk_nope, qk_rope (even) and "
                f"v_head: {(q_rank, kv_rank, qk_nope, qk_rope, v_head)}")
        module = JoyaiDecoder(
            vocab=vocab, d_model=d_model, n_heads=n_heads, q_rank=q_rank,
            kv_rank=kv_rank, qk_nope=qk_nope, qk_rope=qk_rope,
            v_head=v_head, n_layers=n_layers, dense_layers=dense_layers,
            dense_width=dense_width, n_experts=n_experts,
            experts_per_tok=experts_per_tok, expert_width=expert_width,
            experts_first=experts_first, experts_held=experts_held,
            shared_experts=shared_experts, route_scale=float(route_scale),
            mtp_layers=mtp_layers, mtp_weight=float(mtp_weight),
            rope_theta=rope_theta, norm_eps=norm_eps, attn_fn=attn_fn)
        return _own_loss(module, seed, seq_len, vocab)
    if arch == "lfm2":
        kinds = tuple(kind.strip() for kind in layer_types.split(",")
                      if kind.strip())
        if len(kinds) != n_layers:
            raise ValueError(f"layer_types names {len(kinds)} layers "
                             f"({layer_types!r}), n_layers is {n_layers}")
        module = Lfm2Decoder(
            vocab=vocab, d_model=d_model, n_heads=n_heads,
            kv_heads=kv_heads or n_heads,
            head_dim=head_dim or d_model // n_heads, layer_types=kinds,
            dense_layers=dense_layers, dense_width=dense_width,
            n_experts=n_experts, experts_per_tok=experts_per_tok,
            expert_width=expert_width, experts_first=experts_first,
            experts_held=experts_held, conv_kernel=conv_kernel,
            route_scale=float(route_scale), rope_theta=rope_theta,
            norm_eps=norm_eps, attn_fn=attn_fn)
    elif arch == "mellum":
        yarn = (float(yarn_factor), int(yarn_orig), float(yarn_beta_fast),
                float(yarn_beta_slow), float(yarn_attn_factor)
                ) if yarn_factor else None
        module = MellumDecoder(
            vocab=vocab, d_model=d_model, n_heads=n_heads,
            kv_heads=kv_heads or n_heads,
            head_dim=head_dim or d_model // n_heads, n_layers=n_layers,
            n_experts=n_experts, experts_per_tok=experts_per_tok,
            expert_width=expert_width, experts_first=experts_first,
            experts_held=experts_held, window=window,
            full_every=full_every, rope_theta=rope_theta, yarn=yarn,
            norm_eps=norm_eps, attn_fn=attn_fn)
    elif arch == "olmoe":
        # read at build time: the probe of the reference's tolerances
        # tries other precisions (chipbench/reference/probe_olmoe.py)
        attn_fn = _resolve_attn(use_flash, transformer.ATTN_KERNEL_PRECISION)
        module = OlmoeDecoder(
            vocab=vocab, d_model=d_model, n_heads=n_heads,
            n_layers=n_layers, n_experts=n_experts,
            experts_per_tok=experts_per_tok, expert_width=expert_width,
            rope_theta=rope_theta, norm_eps=norm_eps, attn_fn=attn_fn)
    else:
        module = TinyDecoder(
            vocab=vocab, d_model=d_model, n_heads=n_heads,
            n_layers=n_layers, max_len=seq_len,
            attn_fn=_resolve_attn(use_flash))
    # Initialisation runs the model on the sample.  No parameter's shape
    # or value depends on the sample's length (rotary positions, a key
    # per parameter's path), and mellum trains at sequences at which a
    # host role's forward pass with the materialised reference attention
    # takes minutes and tens of GB (lm_layout on a server rank), so its
    # sample is short, and lfm2's with it; the older blocks keep the
    # sample they had.
    short = arch in ("mellum", "lfm2")
    sample = jnp.zeros((1, 16 if short else seq_len), jnp.int32)
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
    if arch in ("olmoe", "mellum", "lfm2"):
        def loss_and_load(w, tokens):
            # the same loss with ``intermediates`` collected: the
            # routers' counts, which the forward pass has already made
            # (a layer with a dense MLP sows none and is not among them)
            logp, state = fm.apply_flat(w, tokens[:, :-1],
                                        mutable=["intermediates"])
            blocks = state["intermediates"]
            layers = sorted(blocks, key=lambda name: int(
                name.rsplit("_", 1)[1]))
            return mean_nll(logp, tokens[:, 1:]), {
                name: jnp.stack([blocks[layer][sown][0] for layer in layers])
                for sown, name in MOE_STATS.items()
                if sown in blocks[layers[0]]}

        value_grad_stats = jax.value_and_grad(loss_and_load, has_aux=True)

    return LmModel(module=module, flat=fm, loss=loss,
                   value_and_grad=jax.value_and_grad(loss),
                   seq_len=seq_len, vocab=vocab,
                   value_grad_stats=value_grad_stats)


def _own_loss(module: Any, seed: int, seq_len: int, vocab: int) -> LmModel:
    """The :class:`LmModel` of a decoder that closes its own loss:
    ``module(inputs, targets) -> (loss, {name: device scalar})``.  The
    statistics are the step's telemetry as they come; initialised on 16
    positions, as the other rotary blocks."""
    sample = jnp.zeros((1, 16), jnp.int32)
    fm = FlatModel(module, module.init(jax.random.PRNGKey(seed), sample,
                                       sample)["params"])

    def loss_and_stats(w, tokens):
        # tokens: (B, seq_len + 1) int32 — packed, every cell real.
        return fm.apply_flat(w, tokens[:, :-1], tokens[:, 1:])

    def loss(w, tokens):
        return loss_and_stats(w, tokens)[0]

    return LmModel(module=module, flat=fm, loss=loss,
                   value_and_grad=jax.value_and_grad(loss),
                   seq_len=seq_len, vocab=vocab,
                   value_grad_stats=jax.value_and_grad(loss_and_stats,
                                                       has_aux=True))


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
