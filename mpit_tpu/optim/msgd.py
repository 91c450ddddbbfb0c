"""Nesterov momentum SGD ("msgd") — the reference's local update rule.

Semantics follow reference asyncsgd/optim-msgd.lua exactly:

1. optional momentum ramp: ``mom_k = min(mommax, 1 - 0.5/(1 + k/momdecay))``
   (reference :21-23);
2. Sutskever-formulation lookahead: ``vt *= mom_k; w += vt`` *before* the
   gradient is evaluated (reference :24-29) — so the gradient is taken at
   the displaced point;
3. L2 term added to the gradient at the displaced point (reference :31);
4. lr decay ``clr = lr/(1 + k*lrd)^lrp`` (reference :33-35);
5. ``w -= clr*g; vt -= clr*g`` (reference :36-39), step counter ``k += 1``.

TPU-native shape: the whole step is one pure function suitable for
``jax.jit`` and ``lax.scan`` over minibatches.  On a flat vector under
the fused build it sweeps the vector once: :func:`msgd_step` does step
k's 3-5 and then step k+1's 2 on the block the commit kernel holds (with
``mom_{k+1}``: the ramp keeps its schedule), so between steps ``w`` is
the *displaced* point ``w + mom*vt`` and ``state["vt"]`` the scaled
velocity ``mom*vt``.  From :func:`msgd_init`'s zero velocity the
displaced point is the seeded vector, so the first step is no special
case.  Anything else (a pytree, the unfused build, ``mom <= 0``) runs
the five in the reference's order and keeps the reference's pair.  The
losses are taken where the reference takes them either way, and what
the pair holds between steps is the step's own business:
:func:`msgd_params` reads the committed vector out of it for whoever
evaluates, saves or ships it.

The lookahead/commit halves are exported separately because the
EASGD/EAMSGD wrapper and the mesh trainers interleave an exchange
between them (reference optim-eamsgd.lua:24-45 embeds the same local
update).  Their state is the reference's: ``w`` committed and ``vt``
unscaled between steps.

**The vector's plain ranges** (``models/flat.py`` ``plain_ranges``,
read off the step's function by ``optim/rules.py`` ``plain_of``) are
not this rule's: their slots of the gradient hold a step the model has
worked out itself, and whichever form commits the rest, those elements
move by exactly minus what lies there, at no rate, with no momentum
(their velocity stays zero, so the displaced point is the committed one
there) and no decay (:func:`plain_commit`: a few slices taken before the
commit and written over its results where they lie; without ranges
nothing is traced).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from mpit_tpu.obs import get_recorder
from mpit_tpu.ops.fused_update import (
    fused_enabled as _fused_enabled,
    fused_nesterov_commit,
)
from mpit_tpu.optim.rules import Ranges, plain_of


class MSGDConfig(NamedTuple):
    lr: float = 0.0
    lrd: float = 0.0  # lr decay
    lrp: float = 0.0  # lr decay power
    mom: float = 0.0
    mommax: float = 1.0
    momdecay: float = 0.0
    l2wd: float = 0.0
    # Reference msgd enables decay only when lrd>0 AND lrp>0
    # (optim-msgd.lua:33); eamsgd's embedded copy uses lrd!=0 AND lrp>0
    # (optim-eamsgd.lua:40) — identical for the sane lrd>=0 regime.
    use_fused: bool | None = None  # pallas commit sweep (on-TPU default)


def msgd_init(w: Any) -> dict:
    """Step counter and zero velocity: the start of either form (the
    module text: on the kernel's path :func:`msgd_step` keeps ``vt``
    scaled by the coming step's momentum, :func:`msgd_lookahead` /
    :func:`msgd_commit` keep it unscaled)."""
    return {
        "k": jnp.zeros((), jnp.int32),
        "vt": jax.tree_util.tree_map(jnp.zeros_like, w),
    }


def _effective_momentum(cfg: MSGDConfig, k: jnp.ndarray) -> jnp.ndarray:
    mom = jnp.asarray(cfg.mom, jnp.float32)
    if cfg.mom > 0 and cfg.momdecay > 0:
        mom = jnp.minimum(
            cfg.mommax, 1.0 - 0.5 / (1.0 + k.astype(jnp.float32) / cfg.momdecay)
        )
    return mom


def _effective_lr(cfg: MSGDConfig, k: jnp.ndarray) -> jnp.ndarray:
    clr = jnp.asarray(cfg.lr, jnp.float32)
    if cfg.lrd > 0 and cfg.lrp > 0:
        clr = cfg.lr / jnp.power(1.0 + k.astype(jnp.float32) * cfg.lrd, cfg.lrp)
    return clr


def msgd_lookahead(w: Any, state: dict, cfg: MSGDConfig) -> Tuple[Any, dict]:
    """Phase 1: scale velocity and displace w (reference :24-29)."""
    if cfg.mom <= 0:
        return w, state
    mom = _effective_momentum(cfg, state["k"])
    vt = jax.tree_util.tree_map(lambda v: mom * v, state["vt"])
    w = jax.tree_util.tree_map(jnp.add, w, vt)
    return w, {"k": state["k"], "vt": vt}


def _takes_kernel(w: Any, cfg: MSGDConfig) -> bool:
    """Flat 1-D params with momentum take the fused pallas sweep when
    enabled (on a TPU, unless ``cfg.use_fused`` says otherwise)."""
    return (cfg.mom > 0 and isinstance(w, jnp.ndarray) and w.ndim == 1
            and _fused_enabled(cfg.use_fused))


def plain_commit(plain: Ranges, w: Any, grad: Any,
                 commit: Callable[[Any], Tuple[Any, Any]]) -> Tuple[Any, Any]:
    """``commit(w) -> (w_new, vt)`` with the plain ranges as their own
    rule leaves them (the module text): ``w_new`` there is ``w`` less
    the gradient as it is, ``vt`` zero.  ``w`` is the point the gradient
    was taken at.  Without ranges this is ``commit(w)`` and nothing else
    is traced.

    The ranges' next values are sliced out **before** the commit, behind
    a barrier that the commit's ``w`` comes through: the commit kernel
    overwrites ``w`` where it lies, and a slice of ``w`` that may be
    read after it makes XLA copy the whole vector before the kernel and
    again after the writes (two sweeps of a 2 GB vector, 12 ms of a
    339 ms step, and a vector more of temporaries: PERF.md section 6,
    PR 53)."""
    if not plain:
        return commit(w)
    if not (isinstance(w, jnp.ndarray) and w.ndim == 1):
        raise TypeError("plain ranges are extents of a flat vector")
    with jax.named_scope("bias_rule"):
        moved = [w[start:stop] - grad[start:stop] for start, stop in plain]
        w, moved = jax.lax.optimization_barrier((w, moved))
    w_new, vt = commit(w)
    with jax.named_scope("bias_rule"):
        for (start, stop), piece in zip(plain, moved):
            w_new = w_new.at[start:stop].set(piece)
            vt = vt.at[start:stop].set(0.0)
    return w_new, vt


def msgd_commit(w: Any, grad: Any, state: dict, cfg: MSGDConfig,
                plain: Ranges = ()) -> Tuple[Any, dict]:
    """Phase 2: weight-decay, decayed-lr descent, velocity update (:31-40).

    Flat 1-D params with momentum take the fused pallas sweep
    (:func:`mpit_tpu.ops.fused_update.fused_nesterov_commit`) when enabled
    — one HBM read/write of (w, vt, g) instead of several.  ``plain``:
    the vector's plain ranges (:func:`plain_commit`)."""
    clr = _effective_lr(cfg, state["k"])
    if _takes_kernel(w, cfg):
        w_new, vt = plain_commit(
            plain, w, grad, lambda w: fused_nesterov_commit(
                w, state["vt"], grad, clr, l2wd=float(cfg.l2wd)))
        return w_new, {"k": state["k"] + 1, "vt": vt}

    def two_passes(w):
        g = grad
        if cfg.l2wd != 0:
            g = jax.tree_util.tree_map(lambda g, p: g + cfg.l2wd * p, g, w)
        w_new = jax.tree_util.tree_map(lambda p, g: p - clr * g, w, g)
        vt = state["vt"]
        if cfg.mom > 0:
            vt = jax.tree_util.tree_map(lambda v, g: v - clr * g, vt, g)
        return w_new, vt

    w_new, vt = plain_commit(plain, w, grad, two_passes)
    return w_new, {"k": state["k"] + 1, "vt": vt}


def msgd_step(
    value_and_grad_fn: Callable[..., Tuple[jnp.ndarray, Any]],
    w: Any,
    state: dict,
    cfg: MSGDConfig,
    *fn_args: Any,
) -> Tuple[Any, dict, jnp.ndarray]:
    """One full msgd step: lookahead -> grad at displaced w -> commit.

    What it returns is what the next call takes, and no more is promised
    of the pair; :func:`msgd_params` reads the committed vector out of
    it.  A flat vector that takes the commit kernel (``_takes_kernel``:
    ``w.ndim``, ``mom`` and the backend, as :func:`msgd_commit` chooses)
    is swept once: the gradient is taken at ``w`` as handed in, which is
    the displaced point, and the kernel commits and then writes the next
    step's lookahead over its operands (the module text).  Anything else
    runs the two phases round the gradient and keeps their pair.

    ``value_and_grad_fn(w, *fn_args) -> (loss, grad)`` is the feval closure
    analog (reference goot.lua:101-126).  Pure; jit the caller
    (:class:`MSGD` does, and donates ``w`` and ``state`` to the jitted
    step: there the caller's arrays are consumed).
    """
    plain = plain_of(value_and_grad_fn)
    if not _takes_kernel(w, cfg):
        with jax.named_scope("update"):
            w_la, state = msgd_lookahead(w, state, cfg)
        loss, grad = value_and_grad_fn(w_la, *fn_args)
        with jax.named_scope("update"):
            w_new, state = msgd_commit(w_la, grad, state, cfg, plain)
        return w_new, state, loss
    loss, grad = value_and_grad_fn(w, *fn_args)
    k = state["k"]
    with jax.named_scope("update"):
        w_new, vt = plain_commit(
            plain, w, grad, lambda w: fused_nesterov_commit(
                w, state["vt"], grad, _effective_lr(cfg, k),
                l2wd=float(cfg.l2wd),
                mom_next=_effective_momentum(cfg, k + 1)))
    return w_new, {"k": k + 1, "vt": vt}, loss


def msgd_params(w: Any, state: dict, cfg: MSGDConfig) -> Any:
    """The committed vector behind :func:`msgd_step`'s pair: what to
    evaluate, save or ship.  On the kernel's path that is ``w - vt``,
    within one rounding of the vector the two phases would have
    committed (``(w + vt) - vt``) and ``w`` itself while the velocity is
    zero (no step yet); on the phases' path it is ``w``."""
    return w - state["vt"] if _takes_kernel(w, cfg) else w


def committed(trainer: Any) -> Any:
    """The vector a trainer evaluates or saves: its optimizer's
    ``params`` of its ``w`` (every optimizer of this package has one,
    the identity but for :func:`msgd_step`'s), and ``w`` itself while
    the trainer's ``optimizer``, a cached property, is not built: an
    eval-only role never builds one."""
    opt = vars(trainer).get("optimizer")
    return trainer.w if opt is None else opt.params(trainer.w)


class MSGD:
    """Object wrapper with the same lifecycle as the comm-aware optimizers,
    for uniform dispatch in trainers (reference goot.lua:66-89 dispatch)."""

    def __init__(self, cfg: MSGDConfig,
                 value_and_grad_fn: Callable[..., Tuple[jnp.ndarray, Any]],
                 has_aux: bool = False):
        """``has_aux``: the step returns ``((loss, stats), grad)``,
        ``stats`` the model's own {name: device array with one entry a
        layer} (lm/model.py ``value_grad_stats``), as the parameter
        server's shells take it (optim/shells.py).  Each step is then a
        ``round`` span while obs records (phases ``step`` and
        ``telemetry``), with the statistics noted on it, set on their
        gauges and kept as ``stats_last``; the first of them also says
        what every step does with the vector, a constant of the run
        (``commit``: ``kernel`` or ``xla``; ``lookahead``: ``folded``
        into the commit's sweep, a ``pass`` of its own, or ``none`` at
        ``mom <= 0``); with obs off they are never fetched and no span
        exists."""
        self.cfg = cfg
        # ``w`` and ``state`` are donated: the step writes the new
        # vector and momentum where the old ones lay, so it holds each
        # once and not in and out, and the ``w`` handed to :meth:`step`
        # is consumed.  Not the first call's: see :meth:`step`.
        self._step = jax.jit(
            lambda w, state, *args: msgd_step(value_and_grad_fn, w, state, cfg, *args),
            donate_argnums=(0, 1),
        )
        self.state: dict | None = None
        self._has_aux = has_aux
        self._spans = get_recorder()
        self.rounds = 0  # steps done: the ``round`` of the spans
        self.stats_last: dict = {}  # name -> the last recorded step's values
        self._sweep_noted = False

    def params(self, w: Any) -> Any:
        """The committed vector behind the ``w`` that :meth:`step`
        returned (:func:`msgd_params`): what to evaluate or save."""
        return w if self.state is None else msgd_params(w, self.state, self.cfg)

    def step(self, w: Any, *fn_args: Any) -> Tuple[Any, jnp.ndarray]:
        """One step; returns the new ``w`` and the loss.  ``w`` is
        :func:`msgd_step`'s, to be handed back as it is: on the kernel's
        path the point the next step's gradient is taken at
        (:meth:`params` reads the committed vector).  The ``w`` passed
        in is donated to the step
        and unreadable afterwards: keep only what this returns.  The
        first call's is the exception: a trainer starts from its model's
        seeded vector (``flat.w0``, which ``LmTrainer.w`` aliases and
        the benchmark reads again after warm-up), so that call steps on
        a copy."""
        if self.state is None:
            self.state = msgd_init(w)
            w = jax.tree_util.tree_map(jnp.copy, w)
        if not self._has_aux:
            w, self.state, loss = self._step(w, self.state, *fn_args)
            return w, loss
        rec = self._spans
        span = rec.round(self.rounds, "step")
        w, self.state, (loss, stats) = self._step(w, self.state, *fn_args)
        if rec.enabled:
            from mpit_tpu.optim.sync import note_stats

            jax.block_until_ready(w)
            span.mark("telemetry")
            note_stats(self, span, stats)
            if not self._sweep_noted:
                kernel = _takes_kernel(w, self.cfg)
                span.note(
                    commit="kernel" if kernel else "xla",
                    lookahead="folded" if kernel
                    else "pass" if self.cfg.mom > 0 else "none")
                self._sweep_noted = True
        span.end()
        self.rounds += 1
        return w, loss
