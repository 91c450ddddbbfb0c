"""Mesh-native (synchronous) EASGD/EAMSGD — elastic averaging as sharded
XLA programs over a (dp, shard) device mesh.

The reference realizes elastic averaging with *asynchronous* host-mediated
push/pull against sharded parameter servers (reference
asyncsgd/optim-eamsgd.lua, asyncsgd/pserver.lua).  That path exists here
too (:mod:`mpit_tpu.optim.easgd` + :mod:`mpit_tpu.ps`).  This module is
the ICI-resident expression of the same algorithm:

- every worker's parameters live as one row of a ``(n_dp, plong)`` array,
  rows sharded over ``dp`` and columns over ``shard`` — each device holds
  exactly one worker-shard tile in HBM;
- the center variable w* is a ``(plong,)`` array sharded over ``shard``
  (the mesh form of the reference's per-server shard slices,
  pclient.lua:111-129);
- the local Nesterov update (identical math to
  :mod:`mpit_tpu.optim.msgd`) is vmapped over the ``dp`` axis;
- the elastic exchange — every su-th step — is
  ``w* += mva * sum_i(w_i - w*)``, ``w_i -= mva * (w_i - w*)``
  (the simultaneous application of every worker's push, reference
  optim-eamsgd.lua:58-66 / pserver.lua:83), which XLA lowers to one
  reduce + broadcast over the ``dp`` ICI ring.

With ``mva = beta/p`` (the mlaunch config, reference mlaunch.lua:42) the
center moves by ``beta * (mean_i(w_i) - w*)`` per sync — the synchronous
EASGD of the paper.  All state stays in HBM across steps; nothing touches
the host.

Note on the historic intermittent ``Fatal Python error: Aborted`` under
the virtual-CPU test platform: an XLA:CPU collective-rendezvous
thread-starvation limitation, not a defect in this program — root cause
and workaround in docs/xla_cpu_rendezvous_abort.md.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mpit_tpu.ops.fused_update import fused_enabled
from mpit_tpu.optim.msgd import (
    MSGDConfig,
    _effective_lr,
    msgd_commit,
    msgd_lookahead,
)
from mpit_tpu.parallel.fused import mesh_fused_commit
from mpit_tpu.parallel.mesh import put_global, put_local


class MeshEASGD:
    """Synchronous elastic-averaging trainer over a (dp, shard) mesh.

    ``value_and_grad_fn(w, xb, yb) -> (loss, grad)`` operates on one
    worker's flat parameter vector.  Batches are fed stacked per worker:
    ``(n_dp, batch, ...)``.
    """

    def __init__(
        self,
        mesh: Mesh,
        value_and_grad_fn: Callable[..., Tuple[jnp.ndarray, jnp.ndarray]],
        cfg: MSGDConfig,
        *,
        mva: float,
        su: int = 1,
    ):
        if not (su > 0 and mva > 0):
            raise ValueError("easgd requires su>0 and mva>0 (reference :86)")
        self.mesh = mesh
        self.cfg = cfg
        self.mva = float(mva)
        self.su = int(su)
        self.n_dp = mesh.shape["dp"]
        self._steps = 0
        # Fused pallas commit: a pallas call can't be auto-partitioned by
        # the sharded jit, but shard_map runs the 1-D sweep on each
        # device's own (worker-row, shard) tile (parallel/fused.py).  The
        # kernel always folds the velocity update, so it needs mom > 0.
        use_fused = cfg.mom > 0 and fused_enabled(cfg.use_fused)
        self._use_fused = use_fused
        cfg_inner = cfg._replace(use_fused=False)  # vmapped halves stay XLA

        ws = NamedSharding(mesh, P("dp", "shard"))   # per-worker param rows
        ks = NamedSharding(mesh, P("dp"))            # per-worker counters
        cs = NamedSharding(mesh, P("shard"))         # center shards
        bs = NamedSharding(mesh, P("dp"))            # per-worker batches
        self._shardings = {"w": ws, "k": ks, "center": cs, "batch": bs}

        if use_fused:
            fused_local = mesh_fused_commit(
                mesh, P("dp", "shard"), P("dp"), l2wd=cfg.l2wd
            )
            fused_sync = mesh_fused_commit(
                mesh, P("dp", "shard"), P("dp"), l2wd=cfg.l2wd, retract=True
            )

        def _grads(w, vt, k, *args):
            def _one(w_i, vt_i, k_i, *a):
                st = {"k": k_i, "vt": vt_i}
                w_la, st = msgd_lookahead(w_i, st, cfg_inner)
                loss, grad = value_and_grad_fn(w_la, *a)
                return w_la, st["vt"], grad, loss

            return jax.vmap(_one)(w, vt, k, *args)

        def _commit(w_la, vt, g, k, sug=None):
            if use_fused:
                clr = jax.vmap(lambda ki: _effective_lr(cfg, ki))(k)
                if sug is not None:
                    return fused_sync(w_la, vt, g, clr, sug)
                return fused_local(w_la, vt, g, clr)

            def _c(w_i, g_i, vt_i, k_i, *s):
                w2, st = msgd_commit(
                    w_i, g_i, {"k": k_i, "vt": vt_i}, cfg_inner
                )
                if s:  # elastic retract after the local update (ref :66)
                    w2 = w2 - s[0]
                return w2, st["vt"]

            if sug is not None:
                return jax.vmap(_c)(w_la, g, vt, k, sug)
            return jax.vmap(_c)(w_la, g, vt, k)

        def _local(w, vt, k, *args):
            w_la, vt2, g, loss = _grads(w, vt, k, *args)
            w_n, vt_n = _commit(w_la, vt2, g, k)
            return w_n, vt_n, k + 1, loss

        def _step_sync(w, vt, k, center, *args):
            # Sync round: pull+push around the local update, same ordering
            # as the reference (elastic delta uses pre-update w,
            # optim-eamsgd.lua:54-61; retract after localupdate, :66 —
            # the retract rides the fused commit sweep when enabled).
            sug = self.mva * (w - center[None, :])  # every worker's push
            new_center = center + jnp.sum(sug, axis=0)
            w_la, vt2, g, loss = _grads(w, vt, k, *args)
            w_n, vt_n = _commit(w_la, vt2, g, k, sug)
            return w_n, vt_n, k + 1, new_center, loss

        self._local_jit = jax.jit(
            _local,
            in_shardings=(ws, ws, ks) + (bs, bs),
            out_shardings=(ws, ws, ks, ks),
            donate_argnums=(0, 1, 2),
        )
        self._sync_jit = jax.jit(
            _step_sync,
            in_shardings=(ws, ws, ks, cs) + (bs, bs),
            out_shardings=(ws, ws, ks, cs, ks),
            donate_argnums=(0, 1, 2, 3),
        )

        # Whole-epoch program: lax.scan over a staged (nsteps, ...) epoch
        # with the elastic exchange as a lax.cond on the device-resident
        # step counter.  ONE dispatch trains a whole epoch — the
        # per-call dispatch round-trip otherwise bounds small-model
        # throughput, not the TPU.
        def _epoch(w, vt, k, center, xs, ys):
            def body(carry, xy):
                w, vt, k, center = carry
                xb, yb = xy

                def _sync(ops):
                    w, vt, k, center = ops
                    w2, vt2, k2, c2, loss = _step_sync(w, vt, k, center,
                                                       xb, yb)
                    return (w2, vt2, k2, c2), loss

                def _loc(ops):
                    w, vt, k, center = ops
                    w2, vt2, k2, loss = _local(w, vt, k, xb, yb)
                    return (w2, vt2, k2, center), loss

                # Sync schedule from the device-resident counter (k rows
                # advance in lockstep; row 0 stands for all).  Fresh runs
                # match step()'s host-side ``_steps % su`` schedule
                # exactly; resumed runs continue the *global* schedule,
                # which step() (counting from process start) does not.
                return jax.lax.cond(
                    (k[0] % self.su) == 0, _sync, _loc, (w, vt, k, center)
                )

            (w, vt, k, center), losses = jax.lax.scan(
                body, (w, vt, k, center), (xs, ys)
            )
            return w, vt, k, center, losses

        ls = NamedSharding(mesh, P())  # per-step losses, replicated
        ebs = NamedSharding(mesh, P(None, *bs.spec))  # staged epoch batches
        self._epoch_jit = jax.jit(
            _epoch,
            in_shardings=(ws, ws, ks, cs, ebs, ebs),
            out_shardings=(ws, ws, ks, cs, ls),
            donate_argnums=(0, 1, 2, 3),
        )

    # -- state ---------------------------------------------------------------

    def init(self, w0: jnp.ndarray) -> Dict[str, Any]:
        """Replicate a single flat param vector into per-worker rows + the
        center, placed with their mesh shardings (all workers and the
        center start identical — the reference's init-once protocol,
        pserver.lua:92-102)."""
        w = jnp.broadcast_to(w0[None, :], (self.n_dp, w0.shape[0]))
        state = {
            "w": put_global(w, self._shardings["w"]),
            "vt": put_global(jnp.zeros_like(w), self._shardings["w"]),
            "k": put_global(
                jnp.zeros((self.n_dp,), jnp.int32), self._shardings["k"]
            ),
            # Copy w0: device_put may alias the caller's buffer for the
            # shard landing on the same device, and _sync_jit donates the
            # center — without the copy the first sync round deletes the
            # caller's w0.
            "center": put_global(
                jnp.array(w0, copy=True), self._shardings["center"]
            ),
        }
        self._steps = 0
        return state

    @property
    def batch_sharding(self):
        return self._shardings["batch"]

    def shard_batch(self, *arrays: jnp.ndarray):
        """Place (n_dp, batch, ...) stacked arrays with the dp sharding.
        Multi-process: pass only this process's worker rows
        (:func:`mpit_tpu.parallel.mesh.process_local_rows`)."""
        return tuple(put_local(a, self._shardings["batch"]) for a in arrays)

    # -- stepping ------------------------------------------------------------

    def step(self, state: Dict[str, Any], *batch: jnp.ndarray):
        """One training step for every worker; elastic exchange on every
        su-th call (first call included, as in the reference's
        ``k % su == 0`` test, optim-eamsgd.lua:47)."""
        if self._steps % self.su == 0:
            w, vt, k, center, loss = self._sync_jit(
                state["w"], state["vt"], state["k"], state["center"], *batch
            )
        else:
            w, vt, k, loss = self._local_jit(
                state["w"], state["vt"], state["k"], *batch
            )
            center = state["center"]
        self._steps += 1
        return {"w": w, "vt": vt, "k": k, "center": center}, loss

    def center_params(self, state: Dict[str, Any]) -> jnp.ndarray:
        return state["center"]

    def set_steps(self, n: int) -> None:
        """Resynchronize the host-side sync-schedule counter after steps
        were advanced outside :meth:`step`/:meth:`run_epoch` — e.g. the
        device_loop trainer runs the epoch scan inside a
        ``lax.while_loop``, advancing the device-resident schedule
        without touching this counter.  Trainer-owned so the invariant
        lives where the counter does."""
        self._steps = int(n)

    def run_epoch(self, state: Dict[str, Any], x_ep: jnp.ndarray,
                  y_ep: jnp.ndarray):
        """Train a whole staged epoch — ``(nsteps, n_dp, batch, ...)``
        arrays already placed with the epoch sharding — in ONE jitted
        scan.  Returns the new state and the (nsteps,) per-step losses.
        Equivalent trajectory to ``nsteps`` :meth:`step` calls for runs
        whose state counter started at 0 (regression-tested); the sync
        schedule reads the device-resident counter, so a resumed run
        continues the global schedule."""
        w, vt, k, center, losses = self._epoch_jit(
            state["w"], state["vt"], state["k"], state["center"], x_ep, y_ep
        )
        self._steps += int(x_ep.shape[0])
        return {"w": w, "vt": vt, "k": k, "center": center}, losses

    def precompile_epoch(self, state: Dict[str, Any], x_ep: jnp.ndarray,
                         y_ep: jnp.ndarray) -> None:
        """Compile-and-warm the whole-epoch scan program for this epoch
        shape without consuming the caller's buffers or advancing
        ``_steps``.

        Deliberately EXECUTES the program (on copied state) rather than
        AOT ``lower().compile()``: AOT compilation does not populate the
        jit's dispatch cache, so the first timed epoch would still pay
        tracing + cache deserialization — exactly the cost this warmup
        exists to move before t0.  One warm scan pass is milliseconds of
        device compute; the copies are transient."""
        cp = {k: jnp.copy(v) for k, v in state.items()}
        out = self._epoch_jit(cp["w"], cp["vt"], cp["k"], cp["center"],
                              x_ep, y_ep)
        from mpit_tpu.utils.timing import fetch_scalar

        fetch_scalar(out[-1])

    def precompile(self, state: Dict[str, Any], *batch: jnp.ndarray) -> None:
        """Compile-and-warm BOTH step programs (local and sync) against
        the real state/batch shardings, without advancing the sync
        schedule or consuming the caller's buffers.

        The jits donate their state arguments, so fresh copies are run
        through them and the outputs discarded; ``self._steps`` is
        untouched — a subsequent :meth:`step` sequence hits the elastic
        exchange on exactly the same schedule as an unwarmed run."""
        cp = {k: jnp.copy(v) for k, v in state.items()}
        self._sync_jit(cp["w"], cp["vt"], cp["k"], cp["center"], *batch)
        cp = {k: jnp.copy(v) for k, v in state.items()}
        out_l = self._local_jit(cp["w"], cp["vt"], cp["k"], *batch)
        from mpit_tpu.utils.timing import fetch_scalar

        # Devices execute their queue in order: fetching from the LAST
        # enqueued program fences both executions.
        fetch_scalar(out_l[-1])
