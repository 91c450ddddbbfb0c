"""MNIST trainer — the goot.lua analog, TPU-first.

Mirrors the reference trainer's shape (reference asyncsgd/goot.lua):
model + flat params (:29-36), data load/flatten (:43-57), optimizer
dispatch (:66-89), the feval closure (:101-126), the epoch x minibatch
loop with sequential unshuffled batches (:129-146), and per-phase timers
(:20-22, :152-157).  Differences, by design:

- the whole feval (forward+backward over the flat vector) is one jitted
  XLA program; the epoch loop feeds device-resident data slices;
- test-set error is evaluated every epoch — the reference only reports
  train avg_err (goot.lua:123,144-145) but the north-star metric is
  wall-clock to 1% *test* error (BASELINE.md), so the rebuild adds it;
- optimizer dispatch covers the full 12-name surface of the reference
  family (goot.lua:66-89 plus the BiCNN shells, bicnn.lua:127-252).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from mpit_tpu.data.mnist import load_mnist
from mpit_tpu.models import MnistCNN, MnistLinear, MnistMLP, flatten_module
from mpit_tpu.optim import EAMSGD, MSGD, Downpour, RuleShell, SingleWorker
from mpit_tpu.optim.msgd import MSGDConfig, committed
from mpit_tpu.utils.config import Config
from mpit_tpu.utils.logging import get_logger
from mpit_tpu.obs import PhaseTimers, profiler_trace

TRAINER_DEFAULTS = Config(
    model="linear",  # linear | mlp | cnn
    opt="msgd",  # msgd|sgd|downpour|eamsgd|easgd|rmsprop|adam|adamax|adagrad|
    #              adadelta|rmsprop-local|<rule>-single
    lr=1e-2,
    lrd=0.0,
    lrp=0.0,
    mom=0.99,
    mommax=1.0,
    momdecay=0.0,
    l2wd=0.0,
    mva=0.0,  # easgd moving rate; mlaunch uses beta/p = 0.9/nclients
    su=1,  # communication period
    epochs=10,
    batch=128,
    seed=1,
    side=32,
    shuffle=False,  # reference uses sequential batches (goot.lua:133)
    target_test_err=0.01,  # north-star threshold; loop records first hit
    dtype="float32",
    profile_dir="",  # jax.profiler trace of the epoch loop when set
)

MODELS = {"linear": MnistLinear, "mlp": MnistMLP, "cnn": MnistCNN}


class MnistTrainer:
    def __init__(
        self,
        cfg: Optional[Config] = None,
        pclient: Any = None,
        data: Any = None,
        rank: int = 0,
    ):
        self.cfg = TRAINER_DEFAULTS.merged(cfg.to_dict() if cfg else None)
        self.pc = pclient
        self.rank = rank
        self.log = get_logger("train", rank)
        self.tm = PhaseTimers()

        if data is None:
            data, source = load_mnist(side=self.cfg.side)
            self.log.info("data source: %s", source)
        x_train, y_train, x_test, y_test = data
        dtype = jnp.dtype(self.cfg.dtype)
        self.x_train = jnp.asarray(x_train, dtype)
        self.y_train = jnp.asarray(y_train)
        self.x_test = jnp.asarray(x_test, dtype)
        self.y_test = jnp.asarray(y_test)

        if self.cfg.model == "cnn":
            module = MnistCNN(num_classes=10, side=self.cfg.side)
        else:
            module = MODELS[self.cfg.model](num_classes=10)
        rng = jax.random.PRNGKey(self.cfg.seed + rank)
        self.flat = flatten_module(module, rng, self.x_train[:2])
        self.w = self.flat.w0.astype(dtype)

        def loss_fn(w, xb, yb):
            logp = self.flat.apply_flat(w, xb)
            nll = -jnp.mean(jnp.take_along_axis(logp, yb[:, None], axis=1))
            return nll

        self._vgf = jax.value_and_grad(loss_fn)

        def err_fn(w, xb, yb):
            logp = self.flat.apply_flat(w, xb)
            return jnp.mean((jnp.argmax(logp, axis=1) != yb).astype(jnp.float32))

        self._err = jax.jit(err_fn)

    # -- optimizer dispatch (reference goot.lua:66-89, bicnn.lua:127-252) ----

    KNOWN_OPTS = (
        "sgd", "msgd", "downpour", "eamsgd", "easgd",
        "rmsprop", "adam", "adamax", "adagrad", "adadelta", "rmsprop-local",
        "msgd-single", "rmsprop-single", "adam-single", "adamax-single",
        "adagrad-single", "adadelta-single",
    )

    @functools.cached_property
    def optimizer(self):
        """Built at first use: eval-only roles (the tester, reference
        bicnn.lua:580-596) never need one."""
        cfg = self.cfg
        name = cfg.opt
        if name not in self.KNOWN_OPTS:
            raise ValueError(f"unknown optimizer {name!r}; have {self.KNOWN_OPTS}")
        if name in ("sgd", "msgd"):
            mcfg = MSGDConfig(
                lr=cfg.lr, lrd=cfg.lrd, lrp=cfg.lrp, mom=cfg.mom,
                mommax=cfg.mommax, momdecay=cfg.momdecay, l2wd=cfg.l2wd,
            )
            return MSGD(mcfg, self._vgf)
        if self.pc is None:
            raise ValueError(
                f"optimizer {name!r} needs a parameter client "
                "(single-process runs use msgd — reference claunch.lua:6-12)"
            )
        if name == "downpour":
            return Downpour(self._vgf, self.pc, lr=cfg.lr, lrd=cfg.lrd,
                            l2wd=cfg.l2wd, su=cfg.su)
        if name in ("eamsgd", "easgd"):
            mom = 0.0 if name == "easgd" else cfg.mom
            return EAMSGD(self._vgf, self.pc, lr=cfg.lr, lrd=cfg.lrd,
                          lrp=cfg.lrp, mom=mom, l2wd=cfg.l2wd,
                          mva=cfg.mva, su=cfg.su)
        if name == "rmsprop-local":
            return RuleShell(self._vgf, self.pc, su=cfg.su, mode="local",
                             lr=cfg.lr)
        if name.endswith("-single"):
            rule = name[: -len("-single")]
            hp = {"lr": cfg.lr} if rule != "msgd" else {"lr": cfg.lr, "mom": cfg.mom}
            return SingleWorker(self._vgf, self.pc, rule=rule, **hp)
        if name in ("rmsprop", "adam", "adamax", "adagrad", "adadelta"):
            # Server-stateful: the launcher configures the matching server
            # rule (reference plaunch wires pserver the same way).
            return RuleShell(self._vgf, self.pc, su=cfg.su, mode="global")
        raise ValueError(f"unknown optimizer {name!r}")

    # -- evaluation ----------------------------------------------------------

    # the vector to evaluate or save (``self.w`` is the optimizer's to
    # hand back)
    params = property(committed)

    def test_error(self, w: Optional[jnp.ndarray] = None) -> float:
        w = self.params if w is None else w
        return float(self._err(w, self.x_test, self.y_test))

    # -- the epoch loop (reference goot.lua:129-146) -------------------------

    def run(self) -> Dict[str, Any]:
        cfg = self.cfg
        n = self.x_train.shape[0]
        steps_per_epoch = max(n // cfg.batch, 1)
        opt = self.optimizer
        if hasattr(opt, "start"):  # comm-aware optimizers; MSGD has none
            with self.tm.phase("start"):
                self.w = opt.start(self.w)
        history = []
        time_to_target = None
        rng = np.random.default_rng(cfg.seed + self.rank)
        with profiler_trace(cfg.get("profile_dir", "")):
            self._run_epochs(cfg, n, steps_per_epoch, opt, history, rng)
        # first epoch that reached the target, by cumulative wall clock
        for h in history:
            if h["test_err"] <= cfg.target_test_err:
                time_to_target = h["at"]
                break
        # the optimizer's seconds at the ParamClientAPI boundary (its
        # round.exchange phases; a plain timer there with obs off)
        sync_time = getattr(opt, "sync_seconds", 0.0)
        self.tm.add("sync", sync_time)
        # The blocking-sync seconds accrued inside opt.step were measured
        # under the 'feval' phase too; report feval net of sync so the
        # comm/compute split is honest.
        self.tm.total["feval"] = max(self.tm.total["feval"] - sync_time, 0.0)
        if hasattr(opt, "stop"):
            with self.tm.phase("stop"):
                opt.stop()
        return {
            "history": history,
            "final_test_err": history[-1]["test_err"] if history else None,
            "time_to_target": time_to_target,
            "elapsed": self.tm.elapsed(),
            "timers": dict(self.tm.total),
        }

    def _run_epochs(self, cfg, n, steps_per_epoch, opt, history, rng):
        for epoch in range(cfg.epochs):
            if cfg.shuffle:
                order = rng.permutation(n)
            losses = []
            for step in range(steps_per_epoch):
                lo = step * cfg.batch
                idx = order[lo : lo + cfg.batch] if cfg.shuffle else slice(lo, lo + cfg.batch)
                xb, yb = self.x_train[idx], self.y_train[idx]
                with self.tm.phase("feval"):
                    self.w, loss = opt.step(self.w, xb, yb)
                losses.append(loss)
            avg_loss = float(jnp.mean(jnp.stack(losses)))
            with self.tm.phase("eval"):
                test_err = self.test_error()
            history.append({"epoch": epoch, "avg_loss": avg_loss,
                            "test_err": test_err, "at": self.tm.elapsed()})
            self.log.info("epoch %d avg_loss %.5f test_err %.4f", epoch, avg_loss, test_err)
