"""The sync round that moves shard by shard (PR 27, ``optim/sync.py``):
rounds of two shards beside each other and rounds of one shard give the
same bits to the worker and to every server, a shard's GRAD waits for
its own staging and for nothing else, a landed shard goes back up while
the next is on the wire, the clients that give no cut move the vector as
one shard in the same pieces, and an error on either side of the gate
surfaces from ``wait`` and leaves no thread behind once the shell stops.
Where the wire can send pieces (shm) and the payload is the slice itself,
the push reads each piece where it landed and the gradient mirror is not
written (PR 45); the local wire cannot, and keeps the mirror.
In-process thread gangs over the local transport (over shm where a test
says so); every test runs under a time limit of its own.
"""

import contextlib
import gc
import os
import signal
import threading
import time
import weakref

import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu import obs
from mpit_tpu.aio import TaskError
from mpit_tpu.comm.local import LocalRouter
from mpit_tpu.comm.shm import ShmTransport
from mpit_tpu.ft import FTConfig
from mpit_tpu.optim import sync
from mpit_tpu.optim.client_api import ParamClientAPI
from mpit_tpu.optim.downpour import Downpour
from mpit_tpu.optim.shells import RuleShell
from mpit_tpu.ps import ParamClient, ParamServer

SIZE = 5000          # two shards of 2500: four whole pieces and a tail each
PIECE = 600 * 4      # bytes
LIMIT_S = 60
TARGET = jnp.linspace(-1.0, 1.0, SIZE)
FRAMED = FTConfig(op_deadline_s=5.0, max_retries=4)


def quad(w, target):
    """Loss and gradient of 0.5 |w - target|^2."""
    d = w - target
    return 0.5 * jnp.sum(d * d), d


@pytest.fixture(autouse=True)
def time_limit():
    """Each test's own limit: a hang fails it, not the suite's."""
    def expire(_signum, _frame):
        raise TimeoutError(f"over the test's limit of {LIMIT_S} s")

    try:
        old = signal.signal(signal.SIGALRM, expire)
    except ValueError:  # not the main thread: no alarm to set
        yield
        return
    signal.alarm(LIMIT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture(autouse=True)
def small_pieces(monkeypatch):
    monkeypatch.setattr(sync, "PIECE_BYTES", PIECE)


@pytest.fixture
def obs_on():
    obs.configure(enabled=True, reset=True)
    try:
        yield obs.get_recorder()
    finally:
        obs.configure(enabled=None, reset=True)


@contextlib.contextmanager
def gang(nservers, rule="add", codec=None, ft=None, shm=None, **client_kw):
    """Servers on threads, one client driven by the caller; ``shm``: a
    name, and the gang talks over the shm wire under it."""
    if shm is None:
        wires = []
        endpoint = LocalRouter(nservers + 1).endpoint
    else:
        wires = [ShmTransport(f"t_rs_{shm}_{os.getpid()}", r, nservers + 1,
                              ring_bytes=1 << 20)
                 for r in range(nservers + 1)]
        endpoint = wires.__getitem__
    sranks, crank = list(range(nservers)), nservers
    servers = [ParamServer(r, [crank], endpoint(r), rule=rule, ft=ft)
               for r in sranks]
    threads = [threading.Thread(target=s.start, daemon=True)
               for s in servers]
    for t in threads:
        t.start()
    client = ParamClient(crank, sranks, endpoint(crank),
                         seed_servers=True, codec=codec, ft=ft, **client_kw)
    try:
        yield servers, client
    finally:
        for s in servers:
            s.live.stop()
        for t in threads:
            t.join(10)
            assert not t.is_alive(), "server thread did not stop"
        for wire in wires:
            wire.close()


class Withheld:
    """A client's ``ParamClientAPI`` and nothing more: the extension is
    withheld, as a simulator or a foreign front would."""

    def __init__(self, inner):
        self._inner = inner
        self.rank = inner.rank
        for name in ("start", "reset", "async_send_grad", "async_recv_param",
                     "async_send_param", "ping", "wait", "stop"):
            setattr(self, name, getattr(inner, name))


SHELLS = {  # name -> (factory, micro-steps a round, the payload is consumed)
    "rule-su1": (lambda pc: RuleShell(quad, pc, su=1), 1, True),
    "rule-su2": (lambda pc: RuleShell(quad, pc, su=2), 2, False),
    "downpour-su1": (lambda pc: Downpour(quad, pc, lr=0.1, su=1), 1, False),
    "downpour-su2": (lambda pc: Downpour(quad, pc, lr=0.1, su=2), 2, False),
}


def train(shell, rounds, codec, ft, stream, rule="adam", shm=None):
    """``rounds`` sync rounds against two servers; returns the final
    parameters, both servers' shards and the shell."""
    make, su, _consume = SHELLS[shell]
    with gang(2, rule=rule, codec=codec, ft=ft, shm=shm) as (servers, pc):
        opt = make(pc if stream else Withheld(pc))
        w = opt.start(jnp.zeros(SIZE) + 0.25)
        for _ in range(rounds * su):
            w, _loss = opt.step(w, TARGET)
        out = np.array(w)
        opt.stop()
        shards = [np.array(s.param) for s in servers]
    return out, shards, opt


def stream_threads():
    return [t for t in threading.enumerate()
            if t.name == "mpit-round-stream"]


def idle_and_ends_at_stop(opt):
    """After a failed round the stream's thread holds nothing of it,
    and the shell's stop ends it."""
    assert opt._stream._worker is None
    opt.stop()
    assert not stream_threads()


# -- (a) the same bits, streamed or not ---------------------------------------


@pytest.mark.parametrize("ft", [None, FRAMED], ids=["unframed", "framed"])
@pytest.mark.parametrize("codec", ["none", "int8"])
@pytest.mark.parametrize("shell", sorted(SHELLS))
def test_streamed_rounds_equal_whole_vector_rounds_bitwise(shell, codec, ft):
    rounds = 4
    w_s, shards_s, opt_s = train(shell, rounds, codec, ft, stream=True)
    w_p, shards_p, opt_p = train(shell, rounds, codec, ft, stream=False)
    assert rounds_streamed(opt_s) == rounds == opt_s.rounds
    assert rounds_streamed(opt_p) == 0 and len(opt_p._stream.cut) == 1
    assert len(opt_s._stream.pieces) == 10  # five a shard, tails of 100
    assert len(opt_p._stream.pieces) == 9  # the whole vector, a tail of 200
    np.testing.assert_array_equal(w_s, w_p)
    for got, want in zip(shards_s, shards_p):
        np.testing.assert_array_equal(got, want)
    assert np.isfinite(w_s).all() and not np.array_equal(
        w_s, np.full(SIZE, 0.25, np.float32))  # it did train
    assert not stream_threads()


@pytest.mark.parametrize("shell", sorted(SHELLS))
def test_rounds_that_read_the_pieces_equal_rounds_by_the_mirror_bitwise(
        shell, obs_on):
    """Over shm the sends of both shards read the pieces (RuleShell and
    Downpour, a payload that is consumed and one that is not); the same
    rounds through a client that takes no gate go by the mirror."""
    rounds = 3
    w_d, shards_d, opt_d = train(shell, rounds, "none", None, stream=True,
                                 shm=f"d{shell}")
    base = len(obs_on.spans)
    w_m, shards_m, opt_m = train(shell, rounds, "none", None, stream=False,
                                 shm=f"m{shell}")
    assert opt_d._stream.follow == [True, True]
    assert opt_m._stream.follow == [False]
    np.testing.assert_array_equal(w_d, w_m)
    for got, want in zip(shards_d, shards_m):
        np.testing.assert_array_equal(got, want)
    assert np.isfinite(w_d).all() and not np.array_equal(
        w_d, np.full(SIZE, 0.25, np.float32))  # it did train
    direct = [[s.args["direct_bytes"] for s in spans if s.name == "round"]
              for spans in (obs_on.spans[:base], obs_on.spans[base:])]
    assert direct == [[SIZE * 4] * rounds, [0] * rounds]
    assert not opt_d.grad_host.any()  # the mirror was never written
    assert opt_m.grad_host.any()
    assert not stream_threads()


def test_over_the_local_wire_every_shard_keeps_the_mirror(obs_on):
    """A transport that cannot send pieces: the client says so shard by
    shard, and the stream copies every piece into the mirror."""
    _w, _shards, opt = train("rule-su1", 2, "none", None, stream=True)
    assert opt._stream.follow == [False, False] and opt._stream.gated
    assert [s.args["direct_bytes"] for s in obs_on.spans
            if s.name == "round"] == [0, 0]
    assert opt.grad_host.any()


def test_a_real_client_has_the_extension_and_a_withheld_one_has_not():
    router = LocalRouter(2)
    pc = ParamClient(1, [0], router.endpoint(1))
    assert callable(pc.stream_shards) and callable(pc.stream_pieces)
    assert isinstance(Withheld(pc), ParamClientAPI)
    assert not hasattr(Withheld(pc), "stream_shards")
    assert not hasattr(Withheld(pc), "stream_pieces")


class Forwarding:
    """A front that knows three calls and forwards the rest, as the
    benchmark's timing proxy does (``chipbench/child.py``)."""

    def __init__(self, inner):
        self._inner = inner
        self.calls = []

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def async_send_grad(self):
        self.calls.append("async_send_grad")
        self._inner.async_send_grad()

    def async_recv_param(self):
        self.calls.append("async_recv_param")
        self._inner.async_recv_param()

    def wait(self):
        self._inner.wait()
        self.calls.append("wait")


def test_the_round_streams_behind_a_forwarding_front_and_calls_it_thrice():
    rounds = 3
    with gang(2, rule="add") as (_servers, pc):
        front = Forwarding(pc)
        opt = RuleShell(quad, front, su=1)
        w = opt.start(jnp.zeros(SIZE))
        del front.calls[:]  # start() seeds through wait()
        for _ in range(rounds):
            w, _loss = opt.step(w, TARGET)
        opt.stop()
    assert rounds_streamed(opt) == rounds
    assert front.calls[:3 * rounds] == [
        "async_send_grad", "async_recv_param", "wait"] * rounds


# -- (b) the gate: a shard's push waits for its own staging only --------------


def test_server_0_is_acked_while_shard_1_is_held_and_its_slice_unread(obs_on):
    rec = obs_on
    rounds = 3
    opened = []  # (round, monotonic time the held gate opened)
    want, _shards, _opt = train("rule-su1", rounds, "none", None, stream=True)
    base = len(rec.spans)  # this run's spans come after the unheld one's
    with gang(2, rule="adam") as (servers, pc):
        opt = RuleShell(quad, pc, su=1)
        w = opt.start(jnp.zeros(SIZE) + 0.25)
        stream = opt._stream
        second = stream.cut[1]
        view = opt.grad_host[second.offset:second.end]
        state = {"saved": None}

        def acked_by_server_0(k):
            return any(s.name == "GRAD" and s.args["side"] == "client"
                       and s.args["peer"] == 0 and s.args.get("round") == k
                       for s in rec.spans[base:])  # finished spans only

        def held(shard):
            if shard.offset != second.offset:
                return stream.staged(shard)
            if stream.staged(shard) < view.nbytes:
                return 0
            if state["saved"] is None:  # staged for real: poison it
                state["saved"] = view.copy()
                view[:] = np.nan
            if not acked_by_server_0(opt.rounds):
                return 0
            view[:] = state["saved"]
            state["saved"] = None
            opened.append((opt.rounds, time.monotonic()))
            return view.nbytes

        assert pc.stream_shards(held, stream.landed) == stream.cut
        for _ in range(rounds):
            w, _loss = opt.step(w, TARGET)
        got = np.array(w)
        opt.stop()
    # nothing read the poisoned slice: the same bits as an unheld run
    np.testing.assert_array_equal(got, want)
    assert [k for k, _t in opened] == list(range(rounds))
    for k, t_open in opened:
        grads = {s.args["peer"]: s for s in rec.spans[base:]
                 if s.name == "GRAD" and s.args["side"] == "client"
                 and s.args["round"] == k}
        # server 0's GRAD was acked before shard 1 was let through, and
        # server 1's began after: its wait lies before its span
        assert grads[0].t1 <= t_open <= grads[1].t0
        assert grads[1].args["gated_ms"] > 0.0
        assert grads[0].args["gated_ms"] <= grads[1].args["gated_ms"]
        assert grads[1].args["gated_ms"] >= 1e3 * (
            grads[0].t1 - grads[0].t0) * 0.5
    assert not stream_threads()


# -- (c) the sink: a landed shard goes up while the next is on the wire -------


def test_shard_0_goes_up_before_server_1_has_answered(obs_on, monkeypatch):
    rec = obs_on
    pastes = []  # (start element, monotonic time the paste was issued)
    real_paste = sync._paste

    def logged(whole, piece, start):
        pastes.append((int(start), time.monotonic()))
        return real_paste(whole, piece, start)

    monkeypatch.setattr(sync, "_paste", logged)
    with gang(2, rule="add") as (servers, pc):
        opt = RuleShell(quad, pc, su=1)
        w0 = jnp.zeros(SIZE) + 0.25
        w = opt.start(w0)
        stream = opt._stream
        second = stream.cut[1]

        def held(shard):
            # channel 1 stays busy until shard 0 is on its way up: if
            # the uploads waited for ``wait`` this would never open
            if shard.offset == second.offset and not any(
                    lo < second.offset for lo, _t in pastes):
                return 0
            return stream.staged(shard)

        pc.stream_shards(held, stream.landed)
        w, _loss = opt.step(w, TARGET)
        got = np.array(w)
        param_1 = next(s for s in rec.spans if s.name == "PARAM"
                       and s.args["side"] == "client" and s.args["peer"] == 1)
        first_up = min(t for lo, t in pastes if lo < second.offset)
        assert first_up < param_1.t1
        assert sorted(lo for lo, _t in pastes) == [
            lo for _s, lo, _hi in stream.pieces]
        # plain add of the raw gradient: w0 + (w0 - target)
        np.testing.assert_allclose(
            got, 2 * np.asarray(w0) - np.asarray(TARGET), rtol=1e-6)
        np.testing.assert_array_equal(got, opt.w_host)
        # the mirror is the next round's landing place: w is not it
        opt.w_host[:] = 777.0
        np.testing.assert_array_equal(np.array(w), got)
        opt.stop()
    assert not stream_threads()


# -- (d) who moves the vector as one shard ------------------------------------


class Simulator:
    """An in-process plain-add server, one shard, no extension."""

    rank = 7

    def start(self, param, grad):
        self.param, self.grad = param, grad

    reset = start

    def async_send_grad(self):
        self.param += self.grad

    def async_recv_param(self):
        pass

    def ping(self):
        pass

    wait = stop = ping


def round_args(rec):
    """(pieces, shards that moved beside each other) of every round, by
    the stream thread's ``d2h`` copy spans."""
    out = []
    for r in (s for s in rec.spans if s.name == "round"):
        pieces = [s for s in rec.spans if s.name == "d2h"
                  and s.args["round"] == r.args["round"]]
        shards = len({s.args["shard"] for s in pieces})
        out.append((len(pieces), shards if shards > 1 else 0))
    return out


def rounds_streamed(opt):
    """Rounds in which two or more shards moved beside each other."""
    return opt.rounds if len(opt._stream.cut) > 1 else 0


def test_a_simulator_is_one_shard_in_pieces_that_the_shell_sinks(obs_on):
    opt = RuleShell(quad, Simulator(), su=1)
    w0 = jnp.zeros(SIZE) + 0.25
    w = opt.start(w0)
    for _ in range(2):
        w, _loss = opt.step(w, TARGET)
    got = np.array(w)
    assert [(s.offset, s.end) for s in opt._stream.cut] == [(0, SIZE)]
    assert rounds_streamed(opt) == 0
    assert round_args(obs_on) == [(9, 0)] * 2
    # plain add of the raw gradient, twice: w0 + d + 2 d, d = w0 - target
    np.testing.assert_allclose(
        got, 4 * np.asarray(w0) - 3 * np.asarray(TARGET), atol=1e-5)
    np.testing.assert_array_equal(got, opt.w_host)
    opt.w_host[:] = 777.0  # w is not the mirror
    np.testing.assert_array_equal(np.array(w), got)
    assert len(stream_threads()) == 1  # one for all rounds, until stop
    opt.stop()
    assert not stream_threads()


def test_a_shell_dropped_unstopped_takes_its_thread_and_mirrors_with_it():
    """What made the count depend on which file ran before this one:
    the thread used to hold the stream, and the stream the mirrors, for
    the life of the process."""
    opt = RuleShell(quad, Simulator(), su=1)
    w = opt.start(jnp.zeros(SIZE) + 0.25)
    w, _loss = opt.step(w, TARGET)
    (thread,) = stream_threads()
    mirrors = [weakref.ref(opt.grad_host), weakref.ref(opt.w_host)]
    del opt  # no stop
    gc.collect()
    thread.join(10)
    assert not thread.is_alive() and not stream_threads()
    assert [m() for m in mirrors] == [None, None]


def test_a_client_that_took_the_hooks_owns_the_stream_with_the_shell():
    with gang(2) as (_servers, pc):
        opt = RuleShell(quad, pc, su=1)
        w = opt.start(jnp.zeros(SIZE) + 0.25)
        w, _loss = opt.step(w, TARGET)
        (thread,) = stream_threads()
        stream = weakref.ref(opt._stream)
        del opt
        gc.collect()
        # the gate and the sink are the stream's: it serves on
        assert stream() is not None and thread.is_alive()
        pc.async_send_grad()  # between rounds the gate is open
        pc.wait()
        pc.stop()
    del pc, _servers
    gc.collect()
    thread.join(10)
    assert stream() is None and not stream_threads()


def test_one_server_goes_in_pieces_and_streams_nothing(obs_on):
    with gang(1, rule="add") as (_servers, pc):
        opt = RuleShell(quad, pc, su=1)
        w = opt.start(jnp.zeros(SIZE))
        for _ in range(2):
            w, _loss = opt.step(w, TARGET)
        np.testing.assert_array_equal(np.array(w), opt.w_host)
        opt.stop()
    # today's round, its pieces landing in reused memory: nothing beside
    # anything, and counted so
    assert round_args(obs_on) == [(9, 0)] * 2
    assert rounds_streamed(opt) == 0
    assert not stream_threads()


def test_two_servers_stream_and_the_piece_spans_say_so(obs_on):
    w, _shards, opt = train("rule-su1", 3, "none", None, stream=True,
                            rule="add")
    assert round_args(obs_on) == [(10, 2)] * 3
    assert rounds_streamed(opt) == 3


def test_a_shardctl_client_installs_nothing_and_is_one_shard(obs_on):
    with gang(2, rule="add", ft=FRAMED, shardctl=True) as (_servers, pc):
        opt = RuleShell(quad, pc, su=1)
        w = opt.start(jnp.zeros(SIZE))
        assert [(s.offset, s.end) for s in opt._stream.cut] == [(0, SIZE)]
        assert pc._staged is None and pc._landed is None
        for _ in range(2):
            w, _loss = opt.step(w, TARGET)
        np.testing.assert_array_equal(np.array(w), opt.w_host)
        opt.stop()
    assert round_args(obs_on) == [(9, 0)] * 2
    assert rounds_streamed(opt) == 0
    assert not stream_threads()


# -- (e) errors surface from wait() and leave no thread -----------------------


def test_a_failed_stager_surfaces_from_wait_and_leaves_no_thread(monkeypatch):
    real_cut = sync._cut
    in_wait = threading.Event()
    with gang(2, rule="add") as (servers, pc):
        opt = RuleShell(quad, pc, su=1)
        w = opt.start(jnp.zeros(SIZE))
        second = opt._stream.cut[1]

        def broken(x, start, *, size):
            # shard 1's tail, cut only once shard 0 is whole in the
            # mirror; it breaks while the exchange is under way
            if int(start) >= second.offset + 2400:
                in_wait.wait(LIMIT_S)
                raise OSError("the d2h broke")
            return real_cut(x, start, size=size)

        monkeypatch.setattr(sync, "_cut", broken)
        waits = []
        real_wait = pc.wait

        def wait():
            waits.append("in")
            in_wait.set()
            real_wait()
            waits.append("out")  # not reached: the error leaves from it

        pc.wait = wait
        with pytest.raises(TaskError) as err:
            opt.step(w, TARGET)
        assert waits == ["in"]
        assert "copying thread failed" in str(err.value.cause)
        assert isinstance(err.value.cause.__cause__, OSError)
        assert "send_grad" in err.value.task.name
        idle_and_ends_at_stop(opt)
        # the shard whose pieces never all arrived never left
        assert servers[1].grads_applied == 0


def test_a_failed_stager_stops_an_ungated_round_before_the_exchange(
        monkeypatch):
    real_cut = sync._cut

    def broken(x, start, *, size):
        if int(start) >= 3000:
            raise OSError("the d2h broke")
        return real_cut(x, start, size=size)

    sim = Simulator()
    opt = RuleShell(quad, sim, su=1)
    w = opt.start(jnp.zeros(SIZE) + 0.25)
    monkeypatch.setattr(sync, "_cut", broken)
    with pytest.raises(RuntimeError, match="copying thread failed") as err:
        opt.step(w, TARGET)
    assert isinstance(err.value.__cause__, OSError)
    # nobody gates a client without hooks: a half-staged vector never left
    np.testing.assert_array_equal(sim.param, np.full(SIZE, 0.25, np.float32))
    idle_and_ends_at_stop(opt)


def test_a_failed_gated_op_surfaces_from_wait_and_leaves_no_thread():
    with gang(2, rule="add") as (servers, pc):
        opt = RuleShell(quad, pc, su=1)
        w = opt.start(jnp.zeros(SIZE))
        stream = opt._stream
        second = stream.cut[1]

        def gate(shard):
            if shard.offset == second.offset:
                raise ConnectionError("channel 1 is gone")
            return stream.staged(shard)

        pc.stream_shards(gate, stream.landed)
        with pytest.raises(TaskError) as err:
            opt.step(w, TARGET)
        assert isinstance(err.value.cause, ConnectionError)
        idle_and_ends_at_stop(opt)
        assert servers[0].grads_applied == 1
