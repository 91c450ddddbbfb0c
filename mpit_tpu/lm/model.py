"""Transformer-LM TrainState for the PS stack.

Assembles one of the decoders of :mod:`mpit_tpu.models.transformer`,
chosen by ``arch`` from the table of blocks (:mod:`mpit_tpu.lm.archs`:
what sizes each takes and how it is made from them; each one's attention
is the ``ops/`` flash kernel on TPU and the jnp reference — which
differentiates without a recompute pass — elsewhere), into the
flat-vector calling convention the parameter server shards: a
:class:`~mpit_tpu.models.flat.FlatModel` plus a loss over packed token
grids (the head's next-token NLL, or the block's own, which need be no
NLL of the next token: sdar's is the block-diffusion bound over a noised
and a clean copy of the grid's inputs, and ``seq_len`` counts the tokens
of a sequence, not the rows the layers see), and the
params+optimizer pytree
(:func:`train_state_tree`) that :mod:`mpit_tpu.lm.plan` drives the
partition rules over.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from mpit_tpu.lm import archs
from mpit_tpu.models.flat import FlatModel, flatten_module, plain_ranges
from mpit_tpu.models.transformer import default_attn

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
    #: (w, tokens) -> ((loss, {name: device array}), grad): the same
    #: step with the block's own telemetry as an auxiliary output (what
    #: its sparse layers sow, ``MOE_STATS``, one entry a sparse layer;
    #: or what a decoder with its own loss returns beside it: its
    #: docstring names them), which the optimizer fetches only while obs
    #: is on; None for a block that has none
    value_grad_stats: Optional[Callable[..., Any]] = None
    #: the bytes a sequence that the decoder's checkpoints keep by name
    #: for the backward pass; 0 for a decoder that names none
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


def build_kw(cfg: Any) -> dict:
    """``build``'s keywords for a trainer config (``LM_DEFAULTS``'
    names): the block, the seed, the sizes every block takes and the
    chosen block's own, no other's; the attention is the caller's to
    choose."""
    return {"arch": cfg.arch, "seed": cfg.seed,
            **{name: cfg[name] for name in archs.sizes_of(cfg.arch)}}


def build(*, arch: str = "gpt2", use_flash: Optional[bool] = None,
          seed: int = 0, **sizes: Any) -> LmModel:
    """Build the decoder, flatten its params seeded from ``seed``, and
    close over its loss.  ``arch`` chooses the block's entry in
    :mod:`mpit_tpu.lm.archs`, which says what ``sizes`` it takes (a size
    of another block is refused), what each means and defaults to, how
    the module is made from them, and which loss convention it has."""
    block = archs.block(arch)
    sizes = archs.resolve(arch, sizes)
    module = block.make(sizes, partial(_resolve_attn, use_flash))
    close = _own_loss if block.loss == archs.OWN_LOSS else partial(
        _head_nll, sown_stats=block.sown_stats)
    sample = jnp.zeros((1, block.sample_len or sizes["seq_len"]), jnp.int32)
    fm, loss, value_grad_stats = close(module, jax.random.PRNGKey(seed),
                                       sample)
    kept = module.kept_residual_bytes(
        sizes["seq_len"], _uses_flash(use_flash)) if block.kept_residuals else 0
    value_and_grad = jax.value_and_grad(loss)
    if sizes.get("bias_rate", 0.0) > 0:
        # the block moves its routers' biases by a rule of its own: the
        # vector has plain ranges, and every step handed out says where
        # (models/flat.py plain_ranges; optim/rules.py plain_of)
        fm.plain = plain_ranges(jax.eval_shape(fm.unravel, fm.w0))
        for step in (value_and_grad, value_grad_stats):
            step.plain = fm.plain
    return LmModel(
        module=module, flat=fm, loss=loss, value_and_grad=value_and_grad,
        seq_len=sizes["seq_len"], vocab=sizes["vocab"],
        value_grad_stats=value_grad_stats, kept_residual_bytes=kept)


#: the flat model, ``loss(w, tokens)``, ``LmModel.value_grad_stats``
Closed = Tuple[FlatModel, Callable[..., Any], Optional[Callable[..., Any]]]


def _head_nll(module: Any, key: Any, sample: Any, sown_stats: bool) -> Closed:
    """A decoder that returns log-probs: the head's next-token NLL, and
    with ``sown_stats`` the same step with what the sparse layers
    ``sow`` beside the loss."""
    fm = flatten_module(module, key, sample)

    def mean_nll(logp, targets):
        with jax.named_scope("head_loss"):
            nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
            return jnp.mean(nll)

    def loss(w, tokens):
        # tokens: (B, seq_len + 1) int32 — packed, every cell real.
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        logp = fm.apply_flat(w, inputs)  # (B, L, V) log-probs
        return mean_nll(logp, targets)

    if not sown_stats:
        return fm, loss, None

    def loss_and_load(w, tokens):
        # the same loss with ``intermediates`` collected: the routers'
        # counts, which the forward pass has already made (a layer with
        # a dense MLP sows none and is not among them)
        logp, state = fm.apply_flat(w, tokens[:, :-1],
                                    mutable=["intermediates"])
        blocks = state["intermediates"]
        layers = sorted(blocks, key=lambda name: int(
            name.rsplit("_", 1)[1]))
        return mean_nll(logp, tokens[:, 1:]), {
            name: jnp.stack([blocks[layer][sown][0] for layer in layers])
            for sown, name in MOE_STATS.items()
            if sown in blocks[layers[0]]}

    return fm, loss, jax.value_and_grad(loss_and_load, has_aux=True)


def _own_loss(module: Any, key: Any, sample: Any) -> Closed:
    """A decoder that closes its own loss: ``module(inputs, targets) ->
    (loss, {name: device scalar})``.  The statistics are the step's
    telemetry as they come.  The seeding is one compiled program: run
    eagerly, ``init`` compiles every operation of the forward pass on
    its own, 350 of them and two minutes on a chip for a delta-attention
    block (ROADMAP S7), to throw the result away."""
    fm = FlatModel(
        module, jax.jit(module.init)(key, sample, sample)["params"])

    def loss_and_stats(w, tokens):
        # tokens: (B, seq_len + 1) int32 — packed, every cell real.
        return fm.apply_flat(w, tokens[:, :-1], tokens[:, 1:])

    def loss(w, tokens):
        return loss_and_stats(w, tokens)[0]

    return fm, loss, jax.value_and_grad(loss_and_stats, has_aux=True)


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
