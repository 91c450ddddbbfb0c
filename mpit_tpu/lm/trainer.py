"""The flagship LM training loop — async PS clients, tokens/sec meter.

Shape mirrors :class:`mpit_tpu.train.trainer.MnistTrainer` (model +
flat params, optimizer dispatch, phase timers) with the MNIST epoch
grid replaced by a step loop over the packed token stream, and the
north-star metric replaced by **tokens/second**:

- every step consumes one ``(batch, seq_len + 1)`` packed grid —
  ``batch * seq_len`` real prediction targets, no padding — so
  ``tokens/sec = batch * seq_len * steps / train_seconds``;
- ``train_seconds`` is the feval phase (local step + blocking PS sync),
  excluding start-up (INIT + seeding), evaluation and teardown — the
  methodology docs/WORKLOADS.md specifies;
- the ``mpit_lm_tokens_total`` counter (plus ``mpit_lm_loss``,
  ``mpit_lm_eval_loss`` and ``mpit_lm_tokens_per_s`` gauges) exposes
  the same quantities to the obs registry for traces and /status.

Evaluation never touches the servers: it runs the jitted loss on a
disjoint stream seed with the worker's current params.  Checkpoint-free
*mid-run* eval against the servers' params is the reader path
(``ReaderClient`` + the same :func:`mpit_tpu.lm.model.build` loss; see
tools/lm_smoke.py).
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from mpit_tpu.lm import archs
from mpit_tpu.lm.data import PackedStream
from mpit_tpu.lm.model import build, build_kw
from mpit_tpu.obs import PhaseTimers, get_registry, profiler_trace
from mpit_tpu.optim import EAMSGD, MSGD, Downpour, RuleShell
from mpit_tpu.optim.msgd import MSGDConfig, committed
from mpit_tpu.utils.config import Config
from mpit_tpu.utils.logging import get_logger

LM_DEFAULTS = Config(
    # model: the block and every block's sizes under their own names
    # (lm/archs.py has each one's meaning, and which block takes which)
    arch="gpt2",
    **archs.DEFAULTS,
    # -1 auto (flash on TPU, jnp reference elsewhere) | 0 reference |
    # 1 the Mosaic-compiled kernel or an error (lm/model.py _resolve_attn)
    use_flash=-1,
    # optimizer (the MnistTrainer knob names, so launch configs carry over)
    opt="downpour",  # sgd|msgd|downpour|eamsgd|easgd|rmsprop|adam|adamax|
    #                  adagrad|adadelta (rule names are server-stateful)
    lr=0.5,
    lrd=0.0,
    lrp=0.0,
    mom=0.0,
    mommax=1.0,
    momdecay=0.0,
    l2wd=0.0,
    mva=0.5,  # eamsgd moving rate
    su=1,     # communication period
    # loop
    steps=200,
    batch=8,
    seed=1,
    eval_every=50,    # 0 disables mid-run eval
    eval_batches=2,
    eval_seed_skew=100_003,  # eval stream seed = seed + skew (disjoint)
    dtype="float32",
    profile_dir="",
)


class LmTrainer:
    KNOWN_OPTS = (
        "sgd", "msgd", "downpour", "eamsgd", "easgd",
        "rmsprop", "adam", "adamax", "adagrad", "adadelta",
    )

    def __init__(self, cfg: Optional[Config] = None, pclient: Any = None,
                 rank: int = 0):
        self.cfg = LM_DEFAULTS.merged(cfg.to_dict() if cfg else None)
        cfg = self.cfg
        self.pc = pclient
        self.rank = rank
        self.log = get_logger("lm", rank)
        self.tm = PhaseTimers()

        use_flash = None if cfg.use_flash < 0 else bool(cfg.use_flash)
        self.model = build(use_flash=use_flash, **build_kw(cfg))
        dtype = jnp.dtype(cfg.dtype)
        # an alias of the model's seeded vector where the dtype is its
        # own; the local step donates what it is handed from its second
        # call on, never this (optim/msgd.py MSGD.step)
        self.w = self.model.flat.w0.astype(dtype)
        self._vgf = self.model.value_and_grad
        self._loss = jax.jit(self.model.loss)

        # Streams: the training stream is per-rank (workers must not
        # mirror each other's batches); eval is a disjoint fixed stream.
        self.stream = PackedStream(cfg.seed + rank, cfg.batch, cfg.seq_len)
        self.eval_stream = PackedStream(cfg.seed + cfg.eval_seed_skew,
                                        cfg.batch, cfg.seq_len)

        _reg = get_registry()
        self._obs = _reg.enabled
        self._m_tokens = _reg.counter("mpit_lm_tokens_total", rank=rank)
        self._m_steps = _reg.counter("mpit_lm_steps_total", rank=rank)
        self._m_loss = _reg.gauge("mpit_lm_loss", rank=rank)
        self._m_eval = _reg.gauge("mpit_lm_eval_loss", rank=rank)
        self._m_tps = _reg.gauge("mpit_lm_tokens_per_s", rank=rank)
        _reg.gauge("mpit_lm_kept_residual_bytes", rank=rank).set(
            cfg.batch * self.model.kept_residual_bytes)

    @functools.cached_property
    def optimizer(self):
        """Built at first use: eval-only roles never need one."""
        cfg = self.cfg
        name = cfg.opt
        if name not in self.KNOWN_OPTS:
            raise ValueError(f"unknown optimizer {name!r}; have {self.KNOWN_OPTS}")
        # a block with telemetry of its own returns it beside the loss,
        # to the local step and to the server-stateful rules' shell
        has_aux = self.model.value_grad_stats is not None
        step = self.model.value_grad_stats if has_aux else self._vgf
        if name in ("sgd", "msgd"):
            mcfg = MSGDConfig(lr=cfg.lr, lrd=cfg.lrd, lrp=cfg.lrp,
                              mom=cfg.mom, mommax=cfg.mommax,
                              momdecay=cfg.momdecay, l2wd=cfg.l2wd)
            return MSGD(mcfg, step, has_aux=has_aux)
        if self.pc is None:
            raise ValueError(
                f"optimizer {name!r} needs a parameter client "
                "(single-process LM runs use sgd/msgd)")
        if name == "downpour":
            return Downpour(self._vgf, self.pc, lr=cfg.lr, lrd=cfg.lrd,
                            l2wd=cfg.l2wd, su=cfg.su)
        if name in ("eamsgd", "easgd"):
            mom = 0.0 if name == "easgd" else cfg.mom
            return EAMSGD(self._vgf, self.pc, lr=cfg.lr, lrd=cfg.lrd,
                          lrp=cfg.lrp, mom=mom, l2wd=cfg.l2wd,
                          mva=cfg.mva, su=cfg.su)
        # Server-stateful rules: the launcher configures the matching
        # server rule; the client ships raw gradients.
        return RuleShell(step, self.pc, su=cfg.su, mode="global",
                         has_aux=has_aux)

    # -- evaluation -----------------------------------------------------------

    # the vector to evaluate or save (``self.w`` is the optimizer's to
    # hand back)
    params = property(committed)

    def eval_loss(self, w: Optional[jnp.ndarray] = None) -> float:
        """Mean NLL over ``eval_batches`` fixed batches of the disjoint
        eval stream — a pure read of ``w`` (or the live params)."""
        w = self.params if w is None else w
        losses = [
            float(self._loss(w, jnp.asarray(self.eval_stream.batch_at(i))))
            for i in range(max(self.cfg.eval_batches, 1))
        ]
        return float(np.mean(losses))

    # -- the step loop --------------------------------------------------------

    def run(self) -> Dict[str, Any]:
        cfg = self.cfg
        tokens_per_step = cfg.batch * cfg.seq_len  # real targets per grid
        opt = self.optimizer
        if hasattr(opt, "start"):
            with self.tm.phase("start"):
                self.w = opt.start(self.w)
        # the seeded vector now lives in self.w and the optimizer's
        # mirrors; the model's own reference would keep a whole vector
        # resident on the device for the rest of the run
        self.model.flat.w0 = None
        mosaic_calls = None
        if cfg.use_flash > 0:
            # Evidence that the step's attention is the compiled kernel:
            # a Pallas TPU kernel lowers to a ``tpu_custom_call``; an
            # interpreted or reference attention has none.
            mosaic_calls = jax.jit(self._vgf).lower(
                self.w, jnp.asarray(self.stream.batch_at(0))
            ).as_text().count("tpu_custom_call")
        history = []
        tokens_total = 0
        train_s = 0.0  # feval incl. blocking sync — the tokens/sec base
        first_step_s = None  # the step that holds the compile
        window_losses = []
        with profiler_trace(cfg.get("profile_dir", "")):
            for step in range(cfg.steps):
                tokens = jnp.asarray(self.stream.batch_at(step))
                t0 = time.monotonic()
                with self.tm.phase("feval"):
                    self.w, loss = opt.step(self.w, tokens)
                train_s += time.monotonic() - t0
                if first_step_s is None:
                    first_step_s = train_s
                tokens_total += tokens_per_step
                window_losses.append(loss)
                self._m_tokens.inc(tokens_per_step)
                self._m_steps.inc()
                last = (step == cfg.steps - 1)
                if cfg.eval_every and (step % cfg.eval_every
                                       == cfg.eval_every - 1 or last):
                    avg_loss = float(jnp.mean(jnp.stack(window_losses)))
                    window_losses = []
                    with self.tm.phase("eval"):
                        ev = self.eval_loss()
                    tps = tokens_total / max(train_s, 1e-9)
                    if self._obs:
                        self._m_loss.set(avg_loss)
                        self._m_eval.set(ev)
                        self._m_tps.set(tps)
                    history.append({"step": step, "avg_loss": avg_loss,
                                    "eval_loss": ev, "tokens_per_s": tps,
                                    "at": self.tm.elapsed()})
                    self.log.info(
                        "step %d avg_loss %.5f eval_loss %.5f tok/s %.0f",
                        step, avg_loss, ev, tps)
        # the optimizer's seconds at the ParamClientAPI boundary (its
        # round.exchange phases; a plain timer there with obs off)
        sync_time = getattr(opt, "sync_seconds", 0.0)
        self.tm.add("sync", sync_time)
        # feval net of blocking sync, like MnistTrainer — but tokens/sec
        # keeps the sync in its denominator (a stalled worker earns no
        # throughput credit).
        self.tm.total["feval"] = max(self.tm.total["feval"] - sync_time, 0.0)
        if hasattr(opt, "stop"):
            with self.tm.phase("stop"):
                opt.stop()
        tokens_per_s = tokens_total / max(train_s, 1e-9)
        if self._obs:
            self._m_tps.set(tokens_per_s)
        return {
            "history": history,
            "final_loss": history[-1]["avg_loss"] if history else None,
            "final_eval_loss": history[-1]["eval_loss"] if history else None,
            "tokens_total": tokens_total,
            "tokens_per_s": tokens_per_s,
            "train_seconds": train_s,
            "first_step_seconds": first_step_s,
            "mosaic_calls": mosaic_calls,
            # the block's own statistics of the last recorded round (a
            # local run's step), one entry a layer (lm/model.py
            # MOE_STATS); empty with obs off or a block that has none
            **getattr(opt, "stats_last", {}),
            "elapsed": self.tm.elapsed(),
            "timers": dict(self.tm.total),
            "steps": cfg.steps,
        }
