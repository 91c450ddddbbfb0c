"""DOWNPOUR distributed SGD (reference asyncsgd/optim-downpour.lua).

Semantics preserved exactly:

- Every step computes ``dfdx = -(clr) * (grad + l2wd*w)`` with
  ``clr = lr/(1 + k*lrd)`` (reference :22-28,48 — linear decay, no power).
- ``su == 1`` (Hogwild-style): ship ``dfdx`` to the servers (which
  plain-add it) and fetch fresh params every step (reference :46-54).
- ``su > 1``: accumulate ``dfdx``; on every su-th step (k % su == 0,
  checked *before* increment, so the first step syncs) ship the accumulated
  delta and fetch params; between syncs apply ``dfdx`` locally
  (reference :26-45).
- The vector's plain ranges (``models/flat.py`` ``plain_ranges``) are
  not scaled: ``dfdx`` there is minus the gradient as it is, the step
  the model worked out itself, whatever ``lr`` and ``l2wd`` are.  The
  servers add it like the rest, so the master copy moves by the sum of
  the pushed steps.

TPU-native changes from the reference mechanics (not semantics): the
parameter vector, gradient, and the DOWNPOUR accumulator live in device HBM
and the whole local step (feval + scale + accumulate + local move) is one
jitted XLA program; host<->device transfers happen only on sync steps, and
the host-side buffers the client ships are written with one device->host
copy (the reference instead mutates shared host tensors every step).

Wire codecs (``MPIT_PS_CODEC``): this driver needs no codec awareness —
it writes fp32 deltas into the client's registered ``grad`` mirror and
the ParamClient encodes at ship time.  With the lossy ``int8`` codec the
client's per-shard error-feedback residual folds each sync's
quantization error into the *next* shipped delta, so the server-side sum
of applied updates tracks the true accumulated ``dfdx`` within one
quantization step — the EF-SGD argument that keeps DOWNPOUR's
convergence intact (docs/PROTOCOL.md §error feedback).  The fetched
params are quantized too; su>1 local moves run on the exact local ``w``.
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from mpit_tpu.obs import get_recorder, get_registry
from mpit_tpu.optim.client_api import ParamClientAPI
from mpit_tpu.optim.rules import plain_of
from mpit_tpu.optim.sync import attach, push_pull


class Downpour:
    """Host driver around a jitted local step and a parameter client."""

    def __init__(
        self,
        value_and_grad_fn: Callable[..., Tuple[jnp.ndarray, jnp.ndarray]],
        pclient: ParamClientAPI,
        *,
        lr: float,
        lrd: float = 0.0,
        l2wd: float = 0.0,
        su: int = 1,
    ):
        if su < 1:
            raise ValueError("su must be >= 1 (reference asserts pc and su>=1)")
        self.pc = pclient
        self.su = su
        self.k = 0
        self.rounds = 0  # sync rounds done: the ``round`` of the spans
        #: seconds inside ``round.exchange`` (the reference's blocking-sync
        #: seconds, at the ParamClientAPI boundary): from the round spans
        #: while recording, from a plain timer there with obs off
        self.sync_seconds = 0.0
        self._started = False
        # Training telemetry (mpit_tpu.obs): loss + shipped-update norm
        # gauges, written only on sync rounds and only when obs is
        # enabled, under the round's ``telemetry`` phase (optim/sync.py).
        _reg = get_registry()
        self._spans = get_recorder()
        self._m_loss = _reg.gauge("mpit_train_loss", opt="downpour")
        self._m_unorm = _reg.gauge("mpit_train_update_norm", opt="downpour")

        self._plain = plain = plain_of(value_and_grad_fn)

        def _local(w, accum, k, *args):
            loss, raw = value_and_grad_fn(w, *args)
            g = raw
            if l2wd != 0:
                g = g + l2wd * w
            clr = lr / (1.0 + k.astype(jnp.float32) * lrd) if lrd != 0 else lr
            dfdx = -clr * g
            for start, stop in plain:  # their own step, at no rate
                dfdx = dfdx.at[start:stop].set(-raw[start:stop])
            return loss, dfdx, accum + dfdx, w + dfdx

        self._local = jax.jit(_local)

    def start(self, w: jnp.ndarray) -> jnp.ndarray:
        """Register buffers with the client; first client seeds servers."""
        self.w_host = np.array(w)  # dtype-preserving host mirror
        self.grad_host = np.zeros_like(self.w_host)
        self.accum = jnp.zeros_like(w)
        if self._plain:  # the client refuses a codec that would round them
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
        k = jnp.asarray(self.k, jnp.int32)
        loss, dfdx, accum, w_local = self._local(w, self.accum, k, *fn_args)

        if self.su == 1:
            w = push_pull(self, dfdx, loss)
        elif self.k % self.su == 0:
            w = push_pull(self, accum, loss)
            self.accum = jnp.zeros_like(accum)
        else:
            self.accum = accum
            w = w_local  # move locally between syncs (reference :44)

        self.k += 1
        return w, loss

    def stop(self) -> None:
        if self._started:
            self.pc.stop()
            self._stream.close()  # the round's copying thread
