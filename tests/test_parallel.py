"""Mesh/collective layer tests on the 8-virtual-device CPU platform.

Invariant-based (SURVEY.md §7 "deterministic tests of nondeterministic
algorithms"): shard bookkeeping exactness, elastic algebra vs. a NumPy
sequential simulator, sync-DP equivalence to single-device training.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu.models import MnistMLP, flatten_module
from mpit_tpu.optim.msgd import MSGDConfig
from mpit_tpu.parallel import (
    MeshEASGD,
    SyncDataParallel,
    allreduce_mean,
    make_mesh,
    ps_pull,
    ps_push,
    ps_pushpull,
    ring_shift,
)


@pytest.fixture(scope="module")
def mesh():
    from mpit_tpu.utils.platform import default_devices

    assert len(default_devices()) == 8, "conftest must provide 8 mesh devices"
    return make_mesh(dp=4, shard=2)


def test_make_mesh_factoring():
    m = make_mesh()
    assert m.shape["dp"] * m.shape["shard"] == 8  # capped by MPIT_MESH_DEVICES
    with pytest.raises(ValueError):
        make_mesh(dp=3)


def test_ps_pull_concatenates_shards(mesh):
    x = jnp.arange(16.0)
    pulled = ps_pull(mesh)(x)
    np.testing.assert_allclose(np.asarray(pulled), np.arange(16.0))


def test_ps_push_delivers_exact_slices(mesh):
    # A replicated grad must arrive at each shard owner exactly once —
    # no shard-count-dependent scaling.
    g = jnp.arange(16.0)
    out = ps_push(mesh)(g)
    np.testing.assert_allclose(np.asarray(out), np.arange(16.0))


def test_ps_push_reduces_worker_stack(mesh):
    # Per-worker grads summed over dp, then sliced per shard owner.
    n_dp = mesh.shape["dp"]
    g = jnp.broadcast_to(jnp.arange(16.0), (n_dp, 16))
    out = ps_push(mesh, reduce_axis="dp")(g)
    np.testing.assert_allclose(np.asarray(out), n_dp * np.arange(16.0))


def test_ps_pushpull_round_plain_add(mesh):
    # One full PS round with the plain-add server rule (pserver.lua:83):
    # params move by exactly the pushed gradient.
    p = jnp.zeros((16,))
    g = jnp.arange(16.0)
    full, p_shard = ps_pushpull(mesh, lambda ps, gs: ps + gs)(p, g)
    np.testing.assert_allclose(np.asarray(full), np.arange(16.0))


def test_ring_shift_rotates_blocks(mesh):
    x = jnp.arange(8.0)  # 2 shard blocks of 4
    y = ring_shift(mesh, "shard")(x)
    np.testing.assert_allclose(np.asarray(y), np.r_[np.arange(4.0) + 4, np.arange(4.0)])


def test_allreduce_mean(mesh):
    x = jnp.arange(4.0).repeat(2)  # (8,) -> rows 0..3 over dp
    y = allreduce_mean(mesh)(jnp.arange(8.0))
    got = np.asarray(y).reshape(4, 2)
    np.testing.assert_allclose(got, np.tile(np.mean(np.arange(8.0).reshape(4, 2), 0), (4, 1)))


def _quadratic_vgf(target):
    def vgf(w, xb, yb):  # ignores batch content; deterministic quadratic
        loss = 0.5 * jnp.sum((w - target) ** 2)
        return loss, w - target
    return vgf


class TestMeshEASGD:
    def test_elastic_algebra_matches_simulator(self, mesh):
        """One sync step == the NumPy sequential simulation of p simultaneous
        elastic pushes (reference optim-eamsgd.lua:58-66 semantics)."""
        P_ = 16
        n_dp = mesh.shape["dp"]
        target = jnp.linspace(-1, 1, P_)
        cfg = MSGDConfig(lr=0.1, mom=0.0)
        tr = MeshEASGD(mesh, _quadratic_vgf(target), cfg, mva=0.9 / n_dp, su=1)
        w0 = jnp.ones((P_,))
        state = tr.init(w0)
        xb = jnp.zeros((n_dp, 2, 1))
        yb = jnp.zeros((n_dp, 2), jnp.int32)
        state, loss = tr.step(state, *tr.shard_batch(xb, yb))

        # simulator
        w = np.ones((n_dp, P_), np.float64)
        center = np.ones(P_, np.float64)
        mva = 0.9 / n_dp
        sug = mva * (w - center)
        center_new = center + sug.sum(0)
        w_local = w - 0.1 * (w - np.asarray(target, np.float64))  # msgd, mom=0
        w_new = w_local - sug

        np.testing.assert_allclose(np.asarray(state["center"]), center_new, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(state["w"]), w_new, rtol=1e-5)

    def test_su_gates_exchange(self, mesh):
        P_ = 16
        n_dp = mesh.shape["dp"]
        cfg = MSGDConfig(lr=0.1)
        tr = MeshEASGD(mesh, _quadratic_vgf(jnp.zeros(P_)), cfg, mva=0.1, su=3)
        state = tr.init(jnp.ones((P_,)))
        xb = jnp.zeros((n_dp, 2, 1)); yb = jnp.zeros((n_dp, 2), jnp.int32)
        batches = tr.shard_batch(xb, yb)
        c0 = np.asarray(state["center"]).copy()
        state, _ = tr.step(state, *batches)   # step 0: sync, but w==center -> no-op
        state, _ = tr.step(state, *batches)   # steps 1,2: local only
        state, _ = tr.step(state, *batches)
        np.testing.assert_array_equal(np.asarray(state["center"]), c0)
        state, _ = tr.step(state, *batches)   # step 3: sync, w has diverged
        c1 = np.asarray(state["center"]).copy()
        assert not np.allclose(c0, c1)
        state, _ = tr.step(state, *batches)   # step 4: local only
        np.testing.assert_array_equal(np.asarray(state["center"]), c1)

    def test_fused_commit_matches_xla(self, mesh):
        """use_fused=True (shard_map'd pallas sweep, retract riding the
        commit on sync rounds) reproduces the plain-XLA trajectory."""
        P_ = 300  # not a tile multiple: exercises the flat-vector padding
        n_dp = mesh.shape["dp"]
        target = jnp.linspace(-1, 1, P_)
        xb = jnp.zeros((n_dp, 2, 1)); yb = jnp.zeros((n_dp, 2), jnp.int32)
        states = {}
        for fused in (False, True):
            cfg = MSGDConfig(lr=0.1, mom=0.6, l2wd=1e-3, lrd=0.01, lrp=1.0,
                             use_fused=fused)
            tr = MeshEASGD(mesh, _quadratic_vgf(target), cfg,
                           mva=0.5 / n_dp, su=2)
            assert tr._use_fused is fused
            state = tr.init(jnp.ones((P_,)))
            batches = tr.shard_batch(xb, yb)
            for _ in range(5):
                state, _ = tr.step(state, *batches)
            states[fused] = state
        for key in ("w", "vt", "center"):
            np.testing.assert_allclose(
                np.asarray(states[True][key]), np.asarray(states[False][key]),
                atol=1e-6, err_msg=key,
            )

    def test_workers_converge_to_target(self, mesh):
        P_ = 16
        n_dp = mesh.shape["dp"]
        target = jnp.linspace(0.5, 1.5, P_)
        cfg = MSGDConfig(lr=0.2, mom=0.5)
        tr = MeshEASGD(mesh, _quadratic_vgf(target), cfg, mva=0.9 / n_dp, su=2)
        state = tr.init(jnp.zeros((P_,)))
        xb = jnp.zeros((n_dp, 2, 1)); yb = jnp.zeros((n_dp, 2), jnp.int32)
        batches = tr.shard_batch(xb, yb)
        for _ in range(60):
            state, loss = tr.step(state, *batches)
        np.testing.assert_allclose(
            np.asarray(tr.center_params(state)), np.asarray(target), atol=0.05
        )


class TestSyncDataParallel:
    def test_matches_single_device_msgd(self, mesh):
        """Sharded step == unsharded step: the shardings change placement,
        not math."""
        rng = jax.random.PRNGKey(0)
        module = MnistMLP(hidden=16)
        x = jax.random.normal(rng, (8, 64))
        y = jnp.arange(8) % 10
        flat = flatten_module(module, rng, x[:2])

        def vgf(w, xb, yb):
            def loss_fn(w):
                logp = flat.apply_flat(w, xb)
                return -jnp.mean(jnp.take_along_axis(logp, yb[:, None], 1))
            return jax.value_and_grad(loss_fn)(w)

        cfg = MSGDConfig(lr=0.1, mom=0.9)
        tr = SyncDataParallel(mesh, vgf, cfg)
        state = tr.init(flat.w0)
        xb, yb = tr.shard_batch(x, y)
        for _ in range(3):
            state, loss = tr.step(state, xb, yb)

        # reference: plain jit on one device
        from mpit_tpu.optim.msgd import MSGD
        ref = MSGD(cfg, vgf)
        w = flat.w0
        for _ in range(3):
            w, ref_loss = ref.step(w, x, y)
        np.testing.assert_allclose(np.asarray(state["w"]),
                                   np.asarray(ref.params(w)), atol=1e-5)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)

    def test_fused_commit_matches_xla(self, mesh):
        """The shard_map'd fused commit over 1-D shard slices reproduces
        the plain-XLA sync-DP trajectory."""
        rng = jax.random.PRNGKey(1)
        module = MnistMLP(hidden=16)
        x = jax.random.normal(rng, (8, 64))
        y = jnp.arange(8) % 10
        flat = flatten_module(module, rng, x[:2])

        def vgf(w, xb, yb):
            def loss_fn(w):
                logp = flat.apply_flat(w, xb)
                return -jnp.mean(jnp.take_along_axis(logp, yb[:, None], 1))
            return jax.value_and_grad(loss_fn)(w)

        finals = {}
        for fused in (False, True):
            cfg = MSGDConfig(lr=0.1, mom=0.9, l2wd=1e-4, use_fused=fused)
            tr = SyncDataParallel(mesh, vgf, cfg)
            assert tr._use_fused is fused
            state = tr.init(flat.w0)
            xb, yb = tr.shard_batch(x, y)
            for _ in range(3):
                state, _ = tr.step(state, xb, yb)
            finals[fused] = state
        for key in ("w", "vt"):
            np.testing.assert_allclose(
                np.asarray(finals[True][key]), np.asarray(finals[False][key]),
                atol=1e-6, err_msg=key,
            )
