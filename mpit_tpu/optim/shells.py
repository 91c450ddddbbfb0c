"""Client-side shells for server-stateful rules, and single-worker mode.

**RuleShell** (reference BiCNN/optim-{rmsprop,adam,adamax,adagrad,
adadelta}.lua): in 'global' mode the client ships *raw* gradients — every
step when su==1, else accumulated and shipped on every su-th step — and the
server applies the actual optimizer rule to its shard
(mpit_tpu.optim.rules / reference BiCNN/pserver.lua:123-197).  Between syncs
the local params do not move (reference optim-adam.lua:41 "do nothing
here").  RMSProp additionally has a 'local' mode where the client applies
centered-RMSProp itself and ships the *update* for the server to plain-add
(reference optim-rmsprop.lua:48-65,76-92).

**SingleWorker** (reference BiCNN/optim-*-single.lua, BiCNN/optim-msgd.lua):
one worker runs the full optimizer locally — the same
:mod:`mpit_tpu.optim.rules` math with plain bias correction — then pushes
the whole parameter vector so the server acts as a parameter mirror for the
tester rank (reference optim-adam-single.lua:35-36).

Wire codecs (``MPIT_PS_CODEC``): both shells stay codec-oblivious — they
write fp32 into the client's ``grad`` mirror and the ParamClient
encodes/decodes at the wire.  Error feedback note for ``int8``: in
'global' mode the *raw* gradient stream is what the residual corrects,
which composes with su>1 accumulation (the accumulated delta is shipped
as one frame, its quantization error rides into the next sync).
SingleWorker's whole-param PARAM_PUSH mirror is a state transfer, not an
accumulating signal — it ships without residual, so a lossy codec makes
the mirror (and the tester reading it) approximate to one quantization
step; pick ``none``/``bf16`` there if the tester must match exactly.
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from mpit_tpu.obs import get_recorder, get_registry
from mpit_tpu.optim import rules as rules_mod
from mpit_tpu.optim.client_api import ParamClientAPI
from mpit_tpu.optim.msgd import MSGDConfig, msgd_init, msgd_params, msgd_step
from mpit_tpu.optim.sync import attach, push_pull


def host_mirror(w: jnp.ndarray) -> np.ndarray:
    """A writable, dtype-preserving host copy of ``w`` that leaves no
    second one behind: ``np.array(w)`` alone would cache a read-only
    host copy on ``w`` itself for as long as ``w`` lives (the seeded
    vector lives as long as the model object: a dead whole vector of
    host memory).  The cache lands on a device copy that dies here."""
    return np.array(jnp.copy(w))


class RuleShell:
    """Accumulate-and-ship client for server-side optimizer rules."""

    def __init__(
        self,
        value_and_grad_fn: Callable[..., Tuple[jnp.ndarray, jnp.ndarray]],
        pclient: ParamClientAPI,
        *,
        su: int = 1,
        mode: str = "global",
        # global mode: the step returns ``((loss, stats), grad)``,
        # ``stats`` the model's own {name: device array with one entry a
        # layer} (lm/model.py ``value_grad_stats``): fetched on sync
        # rounds while obs records, never with obs off (optim/sync.py)
        has_aux: bool = False,
        # 'local'-mode RMSProp hyperparameters (reference optim-rmsprop.lua):
        lr: float = 1e-2,
        decay: float = 0.95,
        momentum: float = 0.9,
        epsilon: float = 1e-4,
    ):
        if su < 1:
            raise ValueError("su must be >= 1")
        if mode not in ("global", "local"):
            raise ValueError(f"mode must be 'global' or 'local', got {mode!r}")
        self.pc = pclient
        self.su = su
        self.mode = mode
        self.k = 0
        self.rounds = 0  # sync rounds done: the ``round`` of the spans
        #: seconds inside ``round.exchange`` (first ``async_*`` call to
        #: the return of ``wait``): from the round spans while recording,
        #: from a plain timer at the same boundary with obs off
        self.sync_seconds = 0.0
        self._started = False
        # Training telemetry (mpit_tpu.obs): loss + shipped-update norm,
        # written on sync rounds only and only when obs is enabled, under
        # the round's ``telemetry`` phase (optim/sync.py: the norm is
        # reduced on the device, off the round's critical path).
        _reg = get_registry()
        self._spans = get_recorder()
        self._m_loss = _reg.gauge("mpit_train_loss", opt=f"rule-{mode}")
        self._m_unorm = _reg.gauge("mpit_train_update_norm",
                                   opt=f"rule-{mode}")
        self._has_aux = has_aux
        self.stats_last: dict = {}  # name -> the last recorded round's values
        # the vector's plain ranges (models/flat.py): in global mode the
        # servers' rule moves them by the raw step it is shipped, so the
        # servers are told where they lie (``start``); in local mode
        # this side's rule does
        self._plain = rules_mod.plain_of(value_and_grad_fn)
        if mode == "global":
            self._vgf = jax.jit(value_and_grad_fn)

        if mode == "local":
            # Client-side centered RMSProp producing an additive update.
            rule = rules_mod.make(
                "rmsprop", plain=self._plain, lr=lr, decay=decay,
                momentum=momentum, epsilon=epsilon
            )
            rule_apply = rules_mod.apply_at(rule)

            def _local(w, accum, rstate, *args):
                loss, g = value_and_grad_fn(w, *args)
                w_new, rstate = rule_apply(w, g, rstate)
                update = w_new - w  # the shipped quantity (reference :59-60)
                return loss, update, accum + update, rstate

            self._local = jax.jit(_local)
            self._rule = rule

    def start(self, w: jnp.ndarray) -> jnp.ndarray:
        self.w_host = host_mirror(w)
        self.grad_host = np.zeros_like(self.w_host)
        # the accumulator is a whole vector on the device: only where
        # something accumulates (at su 1 in global mode nothing does)
        self.accum = (jnp.zeros_like(w)
                      if self.su > 1 or self.mode == "local" else None)
        if self.mode == "local":
            self.rstate = self._rule.init(w)
        elif self._plain:
            # a client that cannot say so cannot serve this vector
            self.pc.announce_plain(self._plain)
        self.pc.start(self.w_host, self.grad_host)
        attach(self)  # the round streams where the client says how it is cut
        self._started = True
        return w

    def params(self, w: jnp.ndarray) -> jnp.ndarray:
        """The vector to evaluate or save behind the ``w`` that
        :meth:`step` returned: ``w`` itself (:class:`mpit_tpu.optim.MSGD`
        is the optimizer whose may differ)."""
        return w

    def step(self, w: jnp.ndarray, *fn_args: Any) -> Tuple[jnp.ndarray, jnp.ndarray]:
        assert self._started, "call start(w) first"
        if self.mode == "global":
            loss, g = self._vgf(w, *fn_args)
            loss, stats = loss if self._has_aux else (loss, None)
            if self.su == 1:
                # g is ours alone: its buffer goes once it is on the host
                w = push_pull(self, g, loss, consume=True, stats=stats)
            else:
                self.accum = self.accum + g
                if self.k % self.su == 0:
                    w = push_pull(self, self.accum, loss, stats=stats)
                    self.accum = jnp.zeros_like(self.accum)
                # else: params do not move between syncs (reference :41)
        else:  # local-mode RMSProp
            loss, update, accum, self.rstate = self._local(
                w, self.accum, self.rstate, *fn_args
            )
            if self.su == 1:
                w = push_pull(self, update, loss)
            elif self.k % self.su == 0:
                w = push_pull(self, accum, loss)
                self.accum = jnp.zeros_like(accum)
            else:
                self.accum = accum
                w = w + update  # move locally (reference :63)
        self.k += 1
        return w, loss

    def stop(self) -> None:
        if self._started:
            self.pc.stop()
            self._stream.close()  # the round's copying thread


class SingleWorker:
    """Full local optimizer + whole-param push (server as mirror)."""

    def __init__(
        self,
        value_and_grad_fn: Callable[..., Tuple[jnp.ndarray, jnp.ndarray]],
        pclient: ParamClientAPI,
        *,
        rule: str = "adam",
        **hyperparams: Any,
    ):
        self.pc = pclient
        self._started = False
        self.state = None
        self._msgd_cfg = None
        if rule == "msgd":
            self._msgd_cfg = cfg = MSGDConfig(**hyperparams)

            def _step(w, state, *args):
                return msgd_step(value_and_grad_fn, w, state, cfg, *args)

            self._step_fn = jax.jit(_step)
            self._init_fn = msgd_init
        else:
            # Single-worker bias correction uses the plain exponent t
            # (reference optim-adam-single.lua:28-30), hence step_div=None.
            bound = rules_mod.make(
                rule, plain=rules_mod.plain_of(value_and_grad_fn),
                **hyperparams)
            bound_apply = rules_mod.apply_at(bound)

            def _step(w, state, *args):
                loss, g = value_and_grad_fn(w, *args)
                w_new, state = bound_apply(w, g, state)
                return w_new, state, loss

            self._step_fn = jax.jit(_step)
            self._init_fn = bound.init

    def start(self, w: jnp.ndarray) -> jnp.ndarray:
        self.state = self._init_fn(w)
        self.w_host = host_mirror(w)
        self.grad_host = np.zeros_like(self.w_host)
        self.pc.start(self.w_host, self.grad_host)
        self._started = True
        return w

    def params(self, w: jnp.ndarray) -> jnp.ndarray:
        """The committed vector behind the ``w`` that :meth:`step`
        returned: ``w`` itself but under ``rule="msgd"``, whose step may
        return the point its next gradient is taken at
        (:func:`mpit_tpu.optim.msgd.msgd_params`)."""
        if self._msgd_cfg is None or self.state is None:
            return w
        return msgd_params(w, self.state, self._msgd_cfg)

    def step(self, w: jnp.ndarray, *fn_args: Any) -> Tuple[jnp.ndarray, jnp.ndarray]:
        assert self._started, "call start(w) first"
        w, self.state, loss = self._step_fn(w, self.state, *fn_args)
        # Push the whole parameter vector (reference optim-adam-single.lua:35-36).
        np.copyto(self.w_host, np.asarray(self.params(w)))
        self.pc.async_send_param()
        self.pc.wait()
        return w, loss

    def stop(self) -> None:
        if self._started:
            self.pc.stop()
