"""One sync round of a push-and-pull optimizer, and its span tree.

:class:`RuleShell` and :class:`Downpour` sync the same way: the payload
(a gradient or a delta) goes device → host mirror → servers, fresh
parameters come servers → host mirror → device.  :func:`push_pull` is
that round, recorded as one ``round`` span whose phases tile it
(docs/OBSERVABILITY.md, *The round's span tree*):

``wait_backward`` → ``d2h`` → ``stage`` → ``exchange`` → ``h2d`` →
``telemetry``

With obs off every span site is a call on ``NULL_SPAN``: no fence is
taken and no telemetry is computed, and the only clock reads are the
two of a plain timer around the exchange, which keeps
``sync_seconds`` the same quantity at the same boundary whether obs is
on (the ``exchange`` phase of the span) or off.  While recording, two
fences split what would otherwise hide inside a host copy
(``np.asarray(payload)`` waits for the backward *and* copies; the
transfer behind ``jnp.asarray(w_host)`` completes after the call
returns), and the update norm is reduced on the device, off the round's
critical path, and read back as one scalar under ``telemetry``; the
model's own statistics (``stats``: a sparse-expert block's routing
imbalance, an auxiliary output of the step) are read there too, and
never with obs off.

EASGD's round has the same parts in another order (pull, then push) and
marks them itself (:mod:`mpit_tpu.optim.easgd`).
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from mpit_tpu.obs import get_registry


@jax.jit
def shipped_norm(x: jnp.ndarray) -> jnp.ndarray:
    """The L2 norm of the shipped update, on the device."""
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


def push_pull(opt: Any, payload: jnp.ndarray,
              loss: Optional[jnp.ndarray] = None, *, consume: bool = False,
              stats: Optional[Dict[str, jnp.ndarray]] = None,
              ) -> jnp.ndarray:
    """Ship ``payload`` as the gradient and fetch fresh parameters.
    ``opt`` is the shell: its ``pc``, ``grad_host``, ``w_host``,
    ``rounds``, ``sync_seconds``, its recorder ``_spans`` and its gauges
    ``_m_unorm`` and ``_m_loss``.  ``consume``: the payload is the
    caller's to give up (a gradient nobody else holds); its device
    buffer is freed once it is staged on the host, so the round's h2d
    does not find a dead whole vector still resident.  ``stats``: the
    model's own statistics of this round's step, device arrays with one
    entry a layer, fetched only while recording: each is noted on the
    round span by its name, set on the gauge ``mpit_<name>`` by layer
    and kept as ``opt.stats_last`` for the rank result."""
    rec = opt._spans
    span = rec.round(opt.rounds, "wait_backward",
                     rank=getattr(opt.pc, "rank", None))
    unorm = None
    if rec.enabled:
        jax.block_until_ready(payload)
        unorm = shipped_norm(payload)  # dispatched; read under telemetry
    span.mark("d2h")
    host = np.asarray(payload)
    span.mark("stage")
    np.copyto(opt.grad_host, host)
    if consume:
        del host  # on the CPU backend a view of the buffer freed next
        payload.delete()
    span.mark("exchange")
    plain = not rec.enabled  # obs off: a plain timer at this boundary
    t0 = time.monotonic() if plain else 0.0
    opt.pc.async_send_grad()
    opt.pc.async_recv_param()
    opt.pc.wait()
    if plain:
        opt.sync_seconds += time.monotonic() - t0
    span.mark("h2d")
    w = jnp.asarray(opt.w_host)
    if rec.enabled:
        jax.block_until_ready(w)
        span.mark("telemetry")
        opt._m_unorm.set(float(unorm))
        if loss is not None:
            opt._m_loss.set(float(loss))
        if stats:
            opt.stats_last = {
                name: [float(x) for x in np.ravel(np.asarray(value))]
                for name, value in stats.items()}
            span.note(**opt.stats_last)
            gauge = get_registry().gauge
            for name, per_layer in opt.stats_last.items():
                for layer, value in enumerate(per_layer):
                    gauge(f"mpit_{name}", layer=layer).set(value)
    span.end()
    opt.sync_seconds += span.phase_seconds("exchange")  # 0.0 if off
    opt.rounds += 1
    return w
