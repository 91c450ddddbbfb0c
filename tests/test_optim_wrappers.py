"""Tests for the comm-aware optimizers against an in-process fake client.

The fake implements the ParamClientAPI protocol backed by a single "server"
center vector with plain-add semantics and deferred (queued) transfer
execution — enough to verify the wrappers' *algebra* against sequential
simulators, independent of the real transport (which gets its own tests).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu.optim.client_api import ParamClientAPI
from mpit_tpu.optim.downpour import Downpour
from mpit_tpu.optim.easgd import EAMSGD
from mpit_tpu.optim.shells import RuleShell, SingleWorker


class FakeClient:
    """Single-shard plain-add server with queued async ops."""

    def __init__(self):
        self.center = None
        self.ops = []
        self.stopped = False

    def start(self, param, grad):
        self.param_buf = param
        self.grad_buf = grad
        self.center = param.copy()  # first client seeds the server

    def reset(self, param, grad):
        self.param_buf = param
        self.grad_buf = grad

    def async_send_grad(self):
        self.ops.append("send_grad")

    def async_recv_param(self):
        self.ops.append("recv_param")

    def async_send_param(self):
        self.ops.append("send_param")

    def _run(self, op):
        if op == "send_grad":
            self.center += self.grad_buf
        elif op == "recv_param":
            np.copyto(self.param_buf, self.center)
        elif op == "send_param":
            np.copyto(self.center, self.param_buf)

    def ping(self):
        if self.ops:
            self._run(self.ops.pop(0))

    def wait(self):
        while self.ops:
            self._run(self.ops.pop(0))

    def stop(self):
        self.stopped = True


def quadratic_vgf(w, target):
    loss = 0.5 * jnp.sum((w - target) ** 2)
    return loss, w - target


@pytest.fixture
def w0(rng):
    return rng.normal(size=6).astype(np.float32)


@pytest.fixture
def target():
    return jnp.zeros(6, jnp.float32)


class TestDownpour:
    def test_su1_matches_serial_sgd(self, w0, target):
        """One worker, su=1: center and worker follow plain SGD exactly."""
        lr = 0.1
        pc = FakeClient()
        opt = Downpour(quadratic_vgf, pc, lr=lr, su=1)
        w = opt.start(jnp.asarray(w0))
        for _ in range(4):
            w, _ = opt.step(w, target)
        ref = w0.astype(np.float64)
        for _ in range(4):
            ref = ref - lr * ref  # grad of quadratic at target 0 is w
        np.testing.assert_allclose(np.asarray(w), ref, rtol=1e-4)
        np.testing.assert_allclose(pc.center, ref, rtol=1e-4)

    def test_su3_accumulates_and_moves_locally(self, w0, target):
        lr, su, steps = 0.05, 3, 7
        pc = FakeClient()
        opt = Downpour(quadratic_vgf, pc, lr=lr, su=su)
        w = opt.start(jnp.asarray(w0))
        for _ in range(steps):
            w, _ = opt.step(w, target)

        # Sequential simulator of reference optim-downpour.lua:26-45.
        center = w0.astype(np.float64).copy()
        ref = w0.astype(np.float64).copy()
        accum = np.zeros(6)
        for k in range(steps):
            dfdx = -lr * ref
            accum = accum + dfdx
            if k % su == 0:
                center = center + accum
                ref = center.copy()
                accum[:] = 0
            else:
                ref = ref + dfdx
        np.testing.assert_allclose(np.asarray(w), ref, rtol=1e-4)
        np.testing.assert_allclose(pc.center, center, rtol=1e-4)

    def test_lr_decay(self, w0, target):
        lr, lrd = 0.1, 0.5
        pc = FakeClient()
        opt = Downpour(quadratic_vgf, pc, lr=lr, lrd=lrd, su=1)
        w = opt.start(jnp.asarray(w0))
        for _ in range(3):
            w, _ = opt.step(w, target)
        ref = w0.astype(np.float64)
        for k in range(3):
            ref = ref - lr / (1 + k * lrd) * ref
        np.testing.assert_allclose(np.asarray(w), ref, rtol=1e-4)

    def test_su_validation(self):
        with pytest.raises(ValueError):
            Downpour(quadratic_vgf, FakeClient(), lr=0.1, su=0)


class TestEAMSGD:
    def test_elastic_algebra_one_round(self, w0, target):
        """One sync round: sug = mva*(w - w*); center += sug; w_local
        updated by Nesterov-less SGD then retracted by sug."""
        lr, mva = 0.1, 0.25
        pc = FakeClient()
        opt = EAMSGD(quadratic_vgf, pc, lr=lr, mva=mva, su=1)
        w = opt.start(jnp.asarray(w0))
        center0 = pc.center.copy()  # == w0
        w, _ = opt.step(w, target)

        sug = mva * (w0 - center0)  # zero on the very first round
        expected_center = center0 + sug
        expected_w = (w0 - lr * w0) - sug
        np.testing.assert_allclose(np.asarray(w), expected_w, rtol=1e-4)
        opt.pc.wait()
        np.testing.assert_allclose(pc.center, expected_center, rtol=1e-4)

    def test_su2_matches_simulator(self, w0, target):
        lr, mva, mom, su, steps = 0.05, 0.2, 0.9, 2, 6
        pc = FakeClient()
        opt = EAMSGD(quadratic_vgf, pc, lr=lr, mva=mva, mom=mom, su=su)
        w = opt.start(jnp.asarray(w0))
        for _ in range(steps):
            w, _ = opt.step(w, target)
        opt.pc.wait()

        # Sequential simulator of reference optim-eamsgd.lua:47-69.
        center = w0.astype(np.float64).copy()
        ref = w0.astype(np.float64).copy()
        vt = np.zeros(6)
        k = 0
        for _ in range(steps):
            sync = k % su == 0
            if sync:
                sug = mva * (ref - center)
                center = center + sug
            # localupdate (Nesterov, no ramp)
            vt = mom * vt
            ref = ref + vt
            g = ref  # quadratic grad at lookahead
            ref = ref - lr * g
            vt = vt - lr * g
            k += 1
            if sync:
                ref = ref - sug
        np.testing.assert_allclose(np.asarray(w), ref, rtol=1e-4)
        np.testing.assert_allclose(pc.center, center, rtol=1e-4)

    def test_requires_mva_and_su(self):
        with pytest.raises(ValueError):
            EAMSGD(quadratic_vgf, FakeClient(), lr=0.1, mva=0.0, su=1)

    def test_comm_only_fused_elastic_matches(self, w0, target, monkeypatch):
        """lr=0 (comm-only, reference :25): the fused one-sweep
        force+retract matches the two-op path."""
        finals = {}
        for env in ("0", "1"):
            monkeypatch.setenv("MPIT_FUSED", env)
            pc = FakeClient()
            opt = EAMSGD(quadratic_vgf, pc, lr=0.0, mva=0.3, su=1)
            assert opt._use_fused_elastic is (env == "1")
            w = opt.start(jnp.asarray(w0))
            for _ in range(3):
                w, _ = opt.step(w, target)
            opt.pc.wait()
            finals[env] = (np.asarray(w), pc.center.copy())
        np.testing.assert_allclose(finals["1"][0], finals["0"][0], atol=1e-6)
        np.testing.assert_allclose(finals["1"][1], finals["0"][1], atol=1e-6)


class TestRuleShell:
    def test_global_su1_ships_raw_grads(self, w0, target):
        pc = FakeClient()
        shell = RuleShell(quadratic_vgf, pc, su=1, mode="global")
        w = shell.start(jnp.asarray(w0))
        w, _ = shell.step(w, target)
        # Plain-add fake server: center += raw grad (= w0 here).
        np.testing.assert_allclose(pc.center, w0 + w0, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(w), pc.center, rtol=1e-4)

    def test_global_su3_accumulates(self, w0, target):
        su, steps = 3, 5
        pc = FakeClient()
        shell = RuleShell(quadratic_vgf, pc, su=su, mode="global")
        w = shell.start(jnp.asarray(w0))
        for _ in range(steps):
            w, _ = shell.step(w, target)

        center = w0.astype(np.float64).copy()
        ref = w0.astype(np.float64).copy()
        accum = np.zeros(6)
        for k in range(steps):
            g = ref
            accum = accum + g
            if k % su == 0:
                center = center + accum
                ref = center.copy()
                accum[:] = 0
            # else params do not move
        np.testing.assert_allclose(np.asarray(w), ref, rtol=1e-4)

    def test_local_rmsprop_su1(self, w0, target):
        lr, decay, momentum, eps = 0.01, 0.9, 0.5, 1e-4
        pc = FakeClient()
        shell = RuleShell(
            quadratic_vgf, pc, su=1, mode="local",
            lr=lr, decay=decay, momentum=momentum, epsilon=eps,
        )
        w = shell.start(jnp.asarray(w0))
        w, _ = shell.step(w, target)
        # update = centered-rmsprop step on g=w0; center += update; w = center.
        g = w0.astype(np.float64)
        ga = (1 - decay) * g
        gsa = (1 - decay) * g * g
        rms = np.sqrt(gsa - ga * ga + eps)
        update = -lr * g / rms
        np.testing.assert_allclose(pc.center, w0 + update, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(w), pc.center, rtol=1e-4)

    def test_mode_validation(self):
        with pytest.raises(ValueError):
            RuleShell(quadratic_vgf, FakeClient(), mode="bogus")


class TestSingleWorker:
    def test_adam_pushes_params_to_mirror(self, w0, target):
        pc = FakeClient()
        opt = SingleWorker(
            quadratic_vgf, pc, rule="adam", lr=1e-2, beta1=0.9, beta2=0.999,
            epsilon=1e-8,
        )
        w = opt.start(jnp.asarray(w0))
        for _ in range(3):
            w, _ = opt.step(w, target)
        # Server mirror tracks local params exactly.
        np.testing.assert_allclose(pc.center, np.asarray(w), rtol=1e-5)

    @pytest.mark.parametrize("fused", [False, True], ids=["phases", "kernel"])
    def test_msgd_single(self, w0, target, fused):
        """What is pushed is the committed vector, the one the two
        phases commit, whatever the step hands back: on the kernel's
        path that is ``msgd_step``'s displaced point."""
        from mpit_tpu.optim.msgd import (
            MSGDConfig, msgd_commit, msgd_init, msgd_lookahead)

        pc = FakeClient()
        opt = SingleWorker(quadratic_vgf, pc, rule="msgd", lr=0.1, mom=0.9,
                           use_fused=fused)
        seeded = jnp.asarray(w0)
        assert opt.params(seeded) is seeded  # no state before start()
        w = opt.start(seeded)
        cfg = MSGDConfig(lr=0.1, mom=0.9)
        ref, state = jnp.asarray(w0), msgd_init(jnp.asarray(w0))
        for _ in range(3):
            w, _ = opt.step(w, target)
            ref_la, state = msgd_lookahead(ref, state, cfg)
            ref, state = msgd_commit(ref_la, quadratic_vgf(ref_la, target)[1],
                                     state, cfg)
            np.testing.assert_array_equal(pc.center, np.asarray(opt.params(w)))
            np.testing.assert_allclose(pc.center, np.asarray(ref), rtol=1e-5,
                                       atol=1e-6)
        # three steps of momentum in: the kernel's point is not that vector
        assert np.allclose(np.asarray(w), pc.center, rtol=1e-3) is not fused
