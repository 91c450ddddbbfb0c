"""What a sync round keeps on the device, and the model's own
statistics (PR 26): at ``su`` 1 ``RuleShell`` allocates no accumulator
and gives the gradient's buffer up once it is staged on the host; a
model's ``stats``, an auxiliary output of the step, are fetched, noted
on the round span and set on gauges only while obs records, and never
read with obs off."""

import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu import obs
from mpit_tpu.optim.shells import RuleShell

SIZE = 32
TARGET = jnp.linspace(-1.0, 1.0, SIZE)


def quad(w, target):
    d = w - target
    return 0.5 * jnp.sum(d * d), d


class FakeClient:
    """The ``ParamClientAPI`` a shell needs, in process: the server is a
    plain subtraction of the staged gradient."""

    rank = 1

    def start(self, w_host, grad_host):
        self.w, self.g = w_host, grad_host
        self.pushed = []

    def async_send_grad(self):
        self.pushed.append(self.g.copy())
        self.w -= 0.5 * self.g

    def async_recv_param(self):
        pass

    def wait(self):
        pass

    def stop(self):
        pass


@pytest.fixture
def obs_on():
    obs.configure(enabled=True, reset=True)
    try:
        yield obs.get_recorder()
    finally:
        obs.configure(enabled=None, reset=True)


@pytest.mark.parametrize("su,mode,has", [(1, "global", False),
                                         (2, "global", True),
                                         (1, "local", True)])
def test_an_accumulator_exists_only_where_something_accumulates(su, mode, has):
    opt = RuleShell(quad, FakeClient(), su=su, mode=mode)
    opt.start(jnp.zeros(SIZE))
    assert (opt.accum is not None) is has


def test_at_su_1_the_gradient_is_shipped_whole_and_its_buffer_given_up():
    held = []  # the step's own outputs (jit would hide them)
    pc = FakeClient()
    opt = RuleShell(quad, pc, su=1)
    opt._vgf = lambda w, t: held.append(quad(w, t)) or held[-1]
    w = opt.start(jnp.zeros(SIZE))
    w, loss = opt.step(w, TARGET)
    _loss, g = held[0]
    assert g.is_deleted()              # freed once it was on the host
    np.testing.assert_allclose(pc.pushed[0], -np.asarray(TARGET))
    np.testing.assert_allclose(w, 0.5 * np.asarray(TARGET))
    assert float(loss) == pytest.approx(0.5 * float(jnp.sum(TARGET ** 2)))


def test_at_su_2_the_accumulator_outlives_its_round():
    opt = RuleShell(quad, FakeClient(), su=2)
    w = opt.start(jnp.zeros(SIZE))
    for _ in range(3):
        w, _loss = opt.step(w, TARGET)
    assert not opt.accum.is_deleted()


def test_the_device_pieces_of_a_consumed_payload_are_gone_after_the_round(
        monkeypatch):
    """A streamed round cuts the payload into pieces on the device:
    each is given up once it is on the host, and the payload once all
    are."""
    import threading

    from mpit_tpu.comm.local import LocalRouter
    from mpit_tpu.optim import sync
    from mpit_tpu.ps import ParamClient, ParamServer

    monkeypatch.setattr(sync, "PIECE_BYTES", 5 * 4)
    cuts, parts, held = [], [], []
    real_cut, real_paste = sync._cut, sync._paste
    monkeypatch.setattr(
        sync, "_cut",
        lambda x, start, *, size: cuts.append(
            real_cut(x, start, size=size)) or cuts[-1])
    monkeypatch.setattr(
        sync, "_paste",
        lambda whole, piece, start: parts.append(piece) or real_paste(
            whole, piece, start))
    router = LocalRouter(3)
    servers = [ParamServer(r, [2], router.endpoint(r)) for r in (0, 1)]
    threads = [threading.Thread(target=s.start, daemon=True)
               for s in servers]
    for t in threads:
        t.start()
    try:
        pc = ParamClient(2, [0, 1], router.endpoint(2), seed_servers=True)
        opt = RuleShell(quad, pc, su=1)
        opt._vgf = lambda w, t: held.append(quad(w, t)) or held[-1]
        w = opt.start(jnp.zeros(SIZE))
        w, _loss = opt.step(w, TARGET)
        assert opt.rounds == 1 and len(opt._stream.cut) == 2
        assert len(cuts) == len(parts) == len(opt._stream.pieces) == 8
        assert all(piece.is_deleted() for piece in cuts)
        assert held[0][1].is_deleted()  # the gradient itself
        # plain add of the raw gradient, whole on the device again
        np.testing.assert_allclose(w, -np.asarray(TARGET))
        del parts[:]
        opt.stop()
    finally:
        for s in servers:
            s.live.stop()
        for t in threads:
            t.join(10)


class Unreadable:
    """A statistic that fails the test if anything fetches it."""

    def __array__(self, *a, **k):
        raise AssertionError("fetched with obs off")


def test_stats_are_never_fetched_with_obs_off():
    obs.configure(enabled=False, reset=True)
    try:
        opt = RuleShell(quad, FakeClient(), su=1, has_aux=True)
        opt._vgf = lambda w, t: ((quad(w, t)[0], {"load": Unreadable()}),
                                 quad(w, t)[1])
        w = opt.start(jnp.zeros(SIZE))
        w, _loss = opt.step(w, TARGET)
        assert opt.stats_last == {}
    finally:
        obs.configure(enabled=None, reset=True)


def test_stats_reach_the_round_span_the_gauges_and_the_shell(obs_on):
    def quad_stats(w, target):
        loss, g = quad(w, target)
        return (loss, {"moe_load_max_over_mean": jnp.asarray([1.25, 1.5])}), g

    opt = RuleShell(quad_stats, FakeClient(), su=1, has_aux=True)
    w = opt.start(jnp.zeros(SIZE))
    w, loss = opt.step(w, TARGET)
    assert float(loss) == pytest.approx(0.5 * float(jnp.sum(TARGET ** 2)))
    (span,) = [s for s in obs_on.spans if s.name == "round"]
    assert span.args["moe_load_max_over_mean"] == [1.25, 1.5]
    assert opt.stats_last == {"moe_load_max_over_mean": [1.25, 1.5]}
    reg = obs.get_registry()
    assert reg.gauge("mpit_moe_load_max_over_mean", layer=1).value == 1.5
    assert reg.gauge("mpit_moe_load_max_over_mean", layer=0).value == 1.25
