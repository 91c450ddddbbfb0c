"""Golden-value tests for the shard-update rules and msgd.

Each rule is checked against an independent numpy re-derivation of the
reference update equations (reference BiCNN/pserver.lua:123-197,
asyncsgd/optim-msgd.lua) — not against the JAX code itself.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu.optim import rules
from mpit_tpu.optim.msgd import (
    MSGDConfig, msgd_commit, msgd_init, msgd_lookahead, msgd_params, msgd_step)

RTOL = 1e-5


def rollout(rule, p0, grads):
    state = rule.init(jnp.asarray(p0))
    p = jnp.asarray(p0)
    apply = jax.jit(rule.apply)
    for g in grads:
        p, state = apply(p, jnp.asarray(g), state)
    return np.asarray(p), state


@pytest.fixture
def grads(rng):
    return [rng.normal(size=5).astype(np.float32) for _ in range(4)]


@pytest.fixture
def p0(rng):
    return rng.normal(size=5).astype(np.float32)


class TestPlainAdd:
    def test_accumulates(self, p0, grads):
        p, _ = rollout(rules.make("add"), p0, grads)
        np.testing.assert_allclose(p, p0 + sum(grads), rtol=RTOL)


class TestRMSProp:
    def test_matches_numpy(self, p0, grads):
        lr, decay, momentum, eps = 0.01, 0.9, 0.5, 1e-4
        p, _ = rollout(
            rules.make("rmsprop", lr=lr, decay=decay, momentum=momentum, epsilon=eps),
            p0,
            grads,
        )
        # Independent simulator: centered RMSProp with momentum.
        ga = np.zeros(5, np.float64)
        gsa = np.zeros(5, np.float64)
        upd = np.zeros(5, np.float64)
        ref = p0.astype(np.float64)
        for g in grads:
            ga = decay * ga + (1 - decay) * g
            gsa = decay * gsa + (1 - decay) * g * g
            rms = np.sqrt(gsa - ga * ga + eps)
            upd = momentum * upd - lr * g / rms
            ref = ref + upd
        np.testing.assert_allclose(p, ref, rtol=1e-4)


class TestAdam:
    def test_single_mode_matches_numpy(self, p0, grads):
        lr, b1, b2, eps = 1e-3, 0.9, 0.999, 1e-8
        p, state = rollout(
            rules.make("adam", lr=lr, beta1=b1, beta2=b2, epsilon=eps), p0, grads
        )
        m = np.zeros(5, np.float64)
        v = np.zeros(5, np.float64)
        ref = p0.astype(np.float64)
        for t, g in enumerate(grads, start=1):
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            lr_t = lr * np.sqrt(1 - b2**t) / (1 - b1**t)
            ref = ref - lr_t * m / (np.sqrt(v) + eps)
        np.testing.assert_allclose(p, ref, rtol=1e-4)
        assert int(state["t"]) == len(grads)

    def test_server_mode_step_div(self, p0, grads):
        """Server mode: bias-correction exponent floor(t/step_div)+1
        (reference BiCNN/pserver.lua:151-153)."""
        lr, b1, b2, eps, sd = 1e-3, 0.9, 0.999, 1e-8, 2
        p, _ = rollout(
            rules.make("adam", lr=lr, beta1=b1, beta2=b2, epsilon=eps, step_div=sd),
            p0,
            grads,
        )
        m = np.zeros(5, np.float64)
        v = np.zeros(5, np.float64)
        ref = p0.astype(np.float64)
        for t, g in enumerate(grads, start=1):
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            e = t // sd + 1
            lr_t = lr * np.sqrt(1 - b2**e) / (1 - b1**e)
            ref = ref - lr_t * m / (np.sqrt(v) + eps)
        np.testing.assert_allclose(p, ref, rtol=1e-4)


class TestAdamax:
    def test_matches_numpy(self, p0, grads):
        lr, b1, b2, eps = 2e-3, 0.9, 0.999, 1e-8
        p, _ = rollout(
            rules.make("adamax", lr=lr, beta1=b1, beta2=b2, epsilon=eps), p0, grads
        )
        m = np.zeros(5, np.float64)
        u = np.zeros(5, np.float64)
        ref = p0.astype(np.float64)
        for t, g in enumerate(grads, start=1):
            m = b1 * m + (1 - b1) * g
            u = np.maximum(b2 * u, np.abs(g) + eps)  # eps inside the max
            ref = ref - (lr / (1 - b1**t)) * m / u
        np.testing.assert_allclose(p, ref, rtol=1e-4)


class TestAdagrad:
    def test_matches_numpy(self, p0, grads):
        lr, lrd, eps = 1e-2, 0.1, 1e-10
        p, _ = rollout(rules.make("adagrad", lr=lr, lrd=lrd, epsilon=eps), p0, grads)
        var = np.zeros(5, np.float64)
        ref = p0.astype(np.float64)
        for k, g in enumerate(grads):
            clr = lr / (1 + k * lrd)
            var = var + g * g
            ref = ref - clr * g / (np.sqrt(var) + eps)
        np.testing.assert_allclose(p, ref, rtol=1e-4)


class TestAdadelta:
    def test_matches_numpy(self, p0, grads):
        lr, rho, eps = 1.0, 0.9, 1e-6
        p, _ = rollout(rules.make("adadelta", lr=lr, rho=rho, epsilon=eps), p0, grads)
        var = np.zeros(5, np.float64)
        acc = np.zeros(5, np.float64)
        ref = p0.astype(np.float64)
        for g in grads:
            var = rho * var + (1 - rho) * g * g
            delta = np.sqrt(acc + eps) / np.sqrt(var + eps) * g
            ref = ref - lr * delta
            acc = rho * acc + (1 - rho) * delta * delta
        np.testing.assert_allclose(p, ref, rtol=1e-4)


class TestRegistry:
    def test_names(self):
        assert set(rules.names()) == {
            "add",
            "rmsprop",
            "adam",
            "adamax",
            "adagrad",
            "adadelta",
        }

    def test_unknown_raises(self):
        with pytest.raises(ValueError):
            rules.make("nope")

    def test_state_slots_pins_real_init_shapes(self):
        # STATE_SLOTS is the footprint model's load-bearing constant
        # (bytes per server = (1 + slots) * 4 * elems): pin it against
        # what each rule's init ACTUALLY allocates per element.
        p = jnp.zeros(7, jnp.float32)
        for name in rules.names():
            state = rules.make(name).init(p)
            vector_arrays = sum(
                1 for v in state.values() if np.ndim(v) == 1)
            assert vector_arrays == rules.state_slots(name), name
            # anything that is not per-element must be a free scalar
            assert all(np.ndim(v) in (0, 1) for v in state.values()), name

    def test_state_slots_unknown_raises(self):
        with pytest.raises(ValueError):
            rules.state_slots("nope")

    @pytest.mark.parametrize("name", sorted(rules.names()))
    def test_server_side_state_has_pairwise_distinct_buffers(self, name):
        # The host server's apply donates the shard and every slot: one
        # zeros array under two slots (adam's m and v as init hands them
        # out) would be donated twice, which XLA refuses.
        from mpit_tpu.comm.local import LocalRouter
        from mpit_tpu.ps import ParamServer

        server = ParamServer(0, [1], LocalRouter(2).endpoint(0), rule=name)
        server._negotiate(1, np.asarray([0, 64, 0], np.int64).tobytes())
        held = [server.param, *server.rule_state.values()]
        assert sorted(server.rule_state) == sorted(
            rules.make(name).init(server.param))
        where = [a.unsafe_buffer_pointer() for a in held]
        assert len(set(where)) == len(where), name


def quadratic_vgf(w, target):
    """loss = 0.5*||w-target||², grad = w-target."""
    loss = 0.5 * jnp.sum((w - target) ** 2)
    return loss, w - target


class TestMSGD:
    def test_no_momentum_is_plain_sgd(self, p0):
        cfg = MSGDConfig(lr=0.1)
        target = jnp.zeros(5)
        w = jnp.asarray(p0)
        state = msgd_init(w)
        w, state, _ = msgd_step(quadratic_vgf, w, state, cfg, target)
        np.testing.assert_allclose(np.asarray(w), p0 - 0.1 * p0, rtol=RTOL)

    def test_full_semantics_vs_numpy(self, p0):
        """Lookahead ordering + momentum ramp + lr decay + l2wd, 5 steps."""
        cfg = MSGDConfig(
            lr=0.1, lrd=0.01, lrp=2.0, mom=0.9, mommax=0.95, momdecay=10.0, l2wd=1e-3
        )
        target = np.zeros(5, np.float32)
        w = jnp.asarray(p0)
        state = msgd_init(w)
        step = jax.jit(
            lambda w, s, t: msgd_step(quadratic_vgf, w, s, cfg, t)
        )
        for _ in range(5):
            w, state, _ = step(w, state, jnp.asarray(target))

        # Independent reference-order simulator (optim-msgd.lua:20-40).
        ref = p0.astype(np.float64)
        vt = np.zeros(5, np.float64)
        for k in range(5):
            mom = min(cfg.mommax, 1 - 0.5 / (1 + k / cfg.momdecay))
            vt = mom * vt
            ref = ref + vt
            g = (ref - target) + cfg.l2wd * ref
            clr = cfg.lr / (1 + k * cfg.lrd) ** cfg.lrp
            ref = ref - clr * g
            vt = vt - clr * g
        np.testing.assert_allclose(np.asarray(msgd_params(w, state, cfg)), ref,
                                   rtol=1e-4)

    @pytest.mark.parametrize("momdecay", [0.0, 10.0],
                             ids=["constant_momentum", "momentum_ramp"])
    @pytest.mark.parametrize("form", ["folded", "kernel", "flat", "pytree"])
    def test_twenty_steps_are_the_two_phases_in_turn(self, momdecay, form,
                                                     monkeypatch):
        """``msgd_step`` against ``msgd_lookahead`` / ``msgd_commit``
        called in turn.  On the kernel's path it carries the displaced
        point and the scaled velocity where the phases carry the
        committed vector: the gradients are taken at the same points by
        the same arithmetic, so the losses are equal to the bit, and
        ``msgd_params`` is the committed vector to one rounding
        (``(w + vt) - vt``); the ramp keeps its schedule.  Off it (the
        unfused build, a pytree) it is the phases, pair and all.  Not
        jitted: operation by operation the arithmetic is IEEE's, where
        the CPU's compiler contracts a fused program's multiply-adds as
        it likes, the interpreted kernel's among them: ``folded`` runs
        the kernel's path on the kernel's contract
        (``fused_nesterov_commit_reference``) and is held to the bit,
        ``kernel`` runs the kernel and is held to a rounding a step."""
        from mpit_tpu.optim import msgd
        from mpit_tpu.ops.fused_update import fused_nesterov_commit_reference

        exact = form != "kernel"
        kernel, tree = form in ("folded", "kernel"), form == "pytree"
        if form == "folded":
            monkeypatch.setattr(msgd, "fused_nesterov_commit",
                                fused_nesterov_commit_reference)
        cfg = MSGDConfig(lr=0.05, lrd=0.01, lrp=1.0, mom=0.9, mommax=0.95,
                         momdecay=momdecay, l2wd=1e-3, use_fused=kernel)
        rs = np.random.RandomState(7)
        scale = jnp.asarray(rs.uniform(0.5, 2.0, 300), jnp.float32)
        target = jnp.asarray(rs.randn(300), jnp.float32)
        w0 = jnp.asarray(rs.randn(300), jnp.float32)
        pack = (lambda v: {"a": v[:100], "b": {"c": v[100:]}}) if tree else (
            lambda v: v)
        unpack = (lambda t: np.concatenate([t["a"], t["b"]["c"]])) if tree else (
            np.asarray)

        def vgf(w, target):
            def loss(w):
                flat = jnp.concatenate([w["a"], w["b"]["c"]]) if tree else w
                return 0.5 * jnp.sum(scale * (flat - target) ** 2)
            return jax.value_and_grad(loss)(w)

        phases = cfg._replace(use_fused=False)
        w, state = pack(w0), msgd_init(pack(w0))
        ref, ref_state = pack(w0), msgd_init(pack(w0))
        first = float(vgf(w, target)[0])
        for k in range(20):
            ref_la, ref_state = msgd_lookahead(ref, ref_state, phases)
            if exact:
                np.testing.assert_array_equal(
                    unpack(w), unpack(ref_la if kernel else ref))
            ref_loss, g = vgf(ref_la, target)
            ref, ref_state = msgd_commit(ref_la, g, ref_state, phases)
            w, state, loss = msgd_step(vgf, w, state, cfg, target)
            if exact:
                assert float(loss) == float(ref_loss), k
            else:
                np.testing.assert_allclose(float(loss), float(ref_loss),
                                           rtol=1e-5)
            got, want = unpack(msgd_params(w, state, cfg)), unpack(ref)
            if kernel:
                ulp = np.spacing(np.maximum(np.abs(want), np.abs(unpack(w))))
                slack = 1 if exact else 4 * (k + 1)
                assert np.all(np.abs(got - want) <= slack * ulp), k
                if k:  # momentum in: the point handed back is not that vector
                    assert not np.array_equal(unpack(w), want)
            else:
                np.testing.assert_array_equal(got, want)
        assert int(state["k"]) == int(ref_state["k"]) == 20
        assert float(loss) < 0.05 * first

    def test_momentum_ramp_capped(self):
        from mpit_tpu.optim.msgd import _effective_momentum

        cfg = MSGDConfig(mom=0.5, mommax=0.7, momdecay=1.0)
        m = _effective_momentum(cfg, jnp.asarray(10**6, jnp.int32))
        assert float(m) == pytest.approx(0.7)
