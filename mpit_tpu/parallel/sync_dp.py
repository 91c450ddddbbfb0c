"""Synchronous data-parallel trainer — the allreduce path, trained-in.

The reference exposes and smoke-tests Allreduce/Iallreduce
(reference mpifuncs.c:83,:1357; test/testreduceall.lua:31-33) but never
wires them into training.  SURVEY.md §2 calls for a sync-DP trainer as the
"testreduceall analog": here it is, the idiomatic way — the global batch
is sharded over the ``dp`` mesh axis, parameters are sharded 1-D over
``shard`` (so optimizer state also lives distributed, the mesh form of
the reference's server-resident shards), and XLA inserts the gradient
all-reduce and the parameter all-gathers automatically from the sharding
annotations.
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
    msgd_init,
    msgd_lookahead,
)
from mpit_tpu.parallel.fused import mesh_fused_commit
from mpit_tpu.parallel.mesh import put_global, put_local


class SyncDataParallel:
    """Jitted Nesterov-SGD step over a (dp, shard) mesh.

    ``value_and_grad_fn(w, xb, yb) -> (loss, grad)`` sees the *global*
    batch; sharding the batch over ``dp`` makes XLA compute per-device
    partial grads and psum them — the trained-in Allreduce.
    """

    def __init__(
        self,
        mesh: Mesh,
        value_and_grad_fn: Callable[..., Tuple[jnp.ndarray, jnp.ndarray]],
        cfg: MSGDConfig,
    ):
        self.mesh = mesh
        self.cfg = cfg
        # Fused pallas commit via shard_map over the 1-D shard slices
        # (parallel/fused.py); the kernel folds the velocity update, so
        # it needs mom > 0.
        use_fused = cfg.mom > 0 and fused_enabled(cfg.use_fused)
        self._use_fused = use_fused
        cfg_inner = cfg._replace(use_fused=False)
        ps = NamedSharding(mesh, P("shard"))  # 1-D param/state sharding
        bs = NamedSharding(mesh, P("dp"))     # batch rows over workers
        self._param_sharding = ps
        self._batch_sharding = bs
        if use_fused:
            fused = mesh_fused_commit(mesh, P("shard"), P(), l2wd=cfg.l2wd)

        def _step(w, vt, k, xb, yb):
            st = {"k": k, "vt": vt}
            w_la, st = msgd_lookahead(w, st, cfg_inner)
            loss, grad = value_and_grad_fn(w_la, xb, yb)
            if use_fused:
                w_n, vt_n = fused(w_la, st["vt"], grad, _effective_lr(cfg, k))
                return w_n, vt_n, k + 1, loss
            w_n, st = msgd_commit(w_la, grad, st, cfg_inner)
            return w_n, st["vt"], st["k"], loss

        self._step_jit = jax.jit(
            _step,
            in_shardings=(ps, ps, NamedSharding(mesh, P()), bs, bs),
            out_shardings=(ps, ps, NamedSharding(mesh, P()), NamedSharding(mesh, P())),
            donate_argnums=(0, 1),
        )

        # Whole-epoch scan: one dispatch per staged epoch (see
        # MeshEASGD._epoch for why).
        def _epoch(w, vt, k, xs, ys):
            def body(carry, xy):
                w, vt, k = carry
                w2, vt2, k2, loss = _step(w, vt, k, *xy)
                return (w2, vt2, k2), loss

            (w, vt, k), losses = jax.lax.scan(body, (w, vt, k), (xs, ys))
            return w, vt, k, losses

        rs = NamedSharding(mesh, P())
        ebs = NamedSharding(mesh, P(None, *bs.spec))
        self._epoch_jit = jax.jit(
            _epoch,
            in_shardings=(ps, ps, rs, ebs, ebs),
            out_shardings=(ps, ps, rs, rs),
            donate_argnums=(0, 1),
        )

    def init(self, w0: jnp.ndarray) -> Dict[str, Any]:
        # Copy w0: device_put may alias the caller's buffer on the device
        # whose shard stays put, and step() donates "w" — without the copy
        # the first step deletes the caller's w0 out from under them.
        return {
            "w": put_global(jnp.array(w0, copy=True), self._param_sharding),
            "vt": put_global(jnp.zeros_like(w0), self._param_sharding),
            "k": jnp.zeros((), jnp.int32),
        }

    @property
    def batch_sharding(self):
        return self._batch_sharding

    def shard_batch(self, *arrays: jnp.ndarray):
        """Multi-process: pass only this process's batch rows
        (:func:`mpit_tpu.parallel.mesh.process_local_rows`)."""
        return tuple(put_local(a, self._batch_sharding) for a in arrays)

    def step(self, state: Dict[str, Any], xb: jnp.ndarray, yb: jnp.ndarray):
        w, vt, k, loss = self._step_jit(state["w"], state["vt"], state["k"], xb, yb)
        return {"w": w, "vt": vt, "k": k}, loss

    def set_steps(self, n: int) -> None:
        """Sync-DP keeps no host-side schedule (the step count ``k``
        lives in device state) — accepted for trainer-interface parity
        with :class:`~mpit_tpu.parallel.easgd.MeshEASGD.set_steps`."""

    def precompile(self, state: Dict[str, Any], *batch: jnp.ndarray) -> None:
        """Compile-and-warm the step program against the real shardings
        without consuming the caller's buffers (the jit donates w/vt, so
        fresh copies are run through it and discarded)."""
        cp = {k: jnp.copy(v) for k, v in state.items()}
        out = self._step_jit(cp["w"], cp["vt"], cp["k"], *batch)
        from mpit_tpu.utils.timing import fetch_scalar

        fetch_scalar(out[-1])

    def run_epoch(self, state: Dict[str, Any], x_ep: jnp.ndarray,
                  y_ep: jnp.ndarray):
        """Train a whole staged epoch in one jitted scan; returns the new
        state and the (nsteps,) per-step losses."""
        w, vt, k, losses = self._epoch_jit(
            state["w"], state["vt"], state["k"], x_ep, y_ep
        )
        return {"w": w, "vt": vt, "k": k}, losses

    def precompile_epoch(self, state: Dict[str, Any], x_ep: jnp.ndarray,
                         y_ep: jnp.ndarray) -> None:
        """Compile-and-warm the whole-epoch scan for this epoch shape
        without consuming the caller's buffers."""
        cp = {k: jnp.copy(v) for k, v in state.items()}
        out = self._epoch_jit(cp["w"], cp["vt"], cp["k"], x_ep, y_ep)
        from mpit_tpu.utils.timing import fetch_scalar

        fetch_scalar(out[-1])
