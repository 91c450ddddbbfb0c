"""EASGD / EAMSGD — elastic-averaging distributed SGD
(reference asyncsgd/optim-eamsgd.lua; mom == 0 gives EASGD, reference :3).

Per sync round (every su-th step, first step included):

1. fetch the center variable w* from the servers (reference :54-57);
2. elastic delta ``sug = mva * (w - w*)`` computed against the *pre-update*
   local w (reference :58-60);
3. push sug as a "gradient" — servers plain-add, i.e. ``w* += mva*(w-w*)``
   (reference :61); the push is *not* waited on: a single ``ping`` overlaps
   it with the local compute (reference :62-64) and it completes during the
   next round's ``wait`` at the latest;
4. the local Nesterov update runs (same math as msgd minus the momentum
   ramp, reference :24-45);
5. ``w -= sug`` pulls the worker toward the center (reference :66).

Between rounds only the local update runs.  TPU-native mechanics: w, vt and
the elastic algebra live in device HBM; the elastic delta and local update
are jitted XLA programs; only w* (in) and sug (out) cross the host boundary,
once per round.

Wire codecs (``MPIT_PS_CODEC``): the elastic push rides the client's GRAD
channel, so with ``int8`` the shipped ``sug`` is block-quantized and the
client's error-feedback residual re-ships each round's quantization error
next round — the center ``w*`` integrates the exact elastic force over
time even though individual pushes are lossy.  The local retract
(``w -= sug``) deliberately uses the *exact* sug: the worker-side
elastic symmetry stays unperturbed, and the center-side difference is
covered by the residual.  Convergence matches the uncompressed run on
the MNIST flagship (tests/test_trainer.py int8 variant).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from mpit_tpu.obs import get_recorder, get_registry
from mpit_tpu.optim.client_api import ParamClientAPI
from mpit_tpu.optim.msgd import MSGDConfig, msgd_commit, msgd_init, msgd_lookahead
from mpit_tpu.optim.rules import plain_of
from mpit_tpu.optim.sync import shipped_norm


class EAMSGD:
    def __init__(
        self,
        value_and_grad_fn: Callable[..., Tuple[jnp.ndarray, jnp.ndarray]],
        pclient: ParamClientAPI,
        *,
        lr: float,
        lrd: float = 0.0,
        lrp: float = 0.0,
        mom: float = 0.0,
        l2wd: float = 0.0,
        mva: float = 0.0,  # moving rate alpha (mlaunch uses beta/p = 0.9/6)
        su: int = 1,  # communication period tau
    ):
        if not (su > 0 and mva > 0):
            raise ValueError("eamsgd requires su>0 and mva>0 (reference :86)")
        self.pc = pclient
        self.su = su
        self.mva = mva
        self.rounds = 0  # sync rounds done: the ``round`` of the spans
        #: seconds inside ``round.exchange`` (the pull and its wait, the
        #: push and its ping): from the round spans while recording, from
        #: a plain timer at the same boundaries with obs off
        self.sync_seconds = 0.0
        self._started = False
        # Training telemetry (mpit_tpu.obs): the elastic distance
        # ||w - w*|| is EASGD's own convergence signal — the exploration
        # radius the mva force is pulling back.  Reduced on the device
        # from sug on sync rounds only, and only when obs is enabled;
        # read back under the round's ``telemetry`` phase.
        _reg = get_registry()
        self._spans = get_recorder()
        self._m_dist = _reg.gauge("mpit_train_elastic_distance", opt="eamsgd")
        self._m_unorm = _reg.gauge("mpit_train_update_norm", opt="eamsgd")
        # Local rule = msgd without the momentum ramp (reference :24-45).
        cfg = MSGDConfig(lr=lr, lrd=lrd, lrp=lrp, mom=mom, momdecay=0.0, l2wd=l2wd)
        self.cfg = cfg
        self._skip_local = lr == 0.0  # reference :25 guards localupdate on lr~=0

        # the vector's plain ranges move by their own step in the local
        # update (optim/msgd.py plain_commit) and elastically, like any
        # element, in the exchange
        self._plain = plain = plain_of(value_and_grad_fn)

        def _localupdate(w, state, *args):
            w_la, state = msgd_lookahead(w, state, cfg)
            loss, grad = value_and_grad_fn(w_la, *args)
            w_new, state = msgd_commit(w_la, grad, state, cfg, plain)
            return w_new, state, loss

        self._localupdate = jax.jit(_localupdate)
        self._elastic = jax.jit(lambda w, center: self.mva * (w - center))
        self._retract = jax.jit(lambda w, sug: w - sug)
        # Comm-only mode (lr == 0, reference :25): force and retract are
        # adjacent — no local update between — so both ride one fused HBM
        # sweep (ops.fused_update.fused_elastic) when enabled.
        from mpit_tpu.ops.fused_update import fused_elastic, fused_enabled

        self._use_fused_elastic = self._skip_local and fused_enabled(None)
        self._elastic_retract = jax.jit(
            lambda w, center: fused_elastic(w, center, self.mva)
        )

    @property
    def k(self) -> int:
        return int(self.state["k"]) if self._started else 0

    def start(self, w: jnp.ndarray) -> jnp.ndarray:
        self.state = msgd_init(w)
        self._steps = 0  # mirrors state["k"] host-side for the su modulus
        # Dedicated comm copies: recv target for w*, send source for sug
        # (reference :49-53 allocates suw/sug and retargets the client).
        self.center_host = np.zeros_like(np.asarray(w))
        self.sug_host = np.zeros_like(self.center_host)
        if self._plain:  # the client refuses a codec that would round them
            self.pc.announce_plain(self._plain)
        self.pc.start(np.array(w), self.sug_host)
        self.pc.reset(self.center_host, self.sug_host)
        self._started = True
        return w

    def params(self, w: jnp.ndarray) -> jnp.ndarray:
        """The vector to evaluate or save behind the ``w`` that
        :meth:`step` returned: ``w`` itself (:class:`mpit_tpu.optim.MSGD`
        is the optimizer whose may differ)."""
        return w

    def step(self, w: jnp.ndarray, *fn_args: Any) -> Tuple[jnp.ndarray, jnp.ndarray]:
        assert self._started, "call start(w) first"
        sync_round = self._steps % self.su == 0
        w_retracted = None
        if sync_round:
            # The round's span tree (docs/OBSERVABILITY.md): the same
            # phases as optim/sync.py's push-and-pull round, in this
            # rule's order: pull, elastic force, push.  The fences and
            # the telemetry exist only while recording.
            rec = self._spans
            span = rec.round(self.rounds, "exchange",
                             rank=getattr(self.pc, "rank", None))
            # obs off: a plain timer at the exchange phases' boundaries
            plain = not rec.enabled
            t0 = time.monotonic() if plain else 0.0
            self.pc.async_recv_param()  # center_host <- w*
            self.pc.wait()  # completes this recv and any prior send
            if plain:
                self.sync_seconds += time.monotonic() - t0
            span.mark("h2d")
            center = jnp.asarray(self.center_host)
            if rec.enabled:
                jax.block_until_ready(center)
                span.mark("wait_backward")  # here: the elastic force
            if self._use_fused_elastic:
                # One sweep computes sug and the retracted w together.
                w_retracted, sug = self._elastic_retract(w, center)
            else:
                sug = self._elastic(w, center)
            unorm = None
            if rec.enabled:
                jax.block_until_ready(sug)
                unorm = shipped_norm(sug)  # read under telemetry
            span.mark("d2h")
            host = np.asarray(sug)
            span.mark("stage")
            np.copyto(self.sug_host, host)
            span.mark("exchange")
            t0 = time.monotonic() if plain else 0.0
            self.pc.async_send_grad()  # server: w* += sug
            self.pc.ping()  # overlap I/O with local compute (reference :63)
            if plain:
                self.sync_seconds += time.monotonic() - t0
            if rec.enabled:
                span.mark("telemetry")
                # sug = mva * (w - w*): one norm serves both gauges.
                unorm = float(unorm)
                self._m_unorm.set(unorm)
                self._m_dist.set(unorm / self.mva)
            span.end()
            self.sync_seconds += span.phase_seconds("exchange")  # 0.0 if off
            self.rounds += 1

        if self._skip_local:
            loss = jnp.zeros(())
        else:
            w, self.state, loss = self._localupdate(w, self.state, *fn_args)
            self._steps += 1

        if sync_round:
            # w -= mva*(w - w*) (reference :66) — precomputed by the fused
            # sweep in comm-only mode, where no local update intervened.
            w = w_retracted if w_retracted is not None else self._retract(w, sug)
        return w, loss

    def stop(self) -> None:
        if self._started:
            self.pc.wait()  # drain the in-flight elastic push
            self.pc.stop()
