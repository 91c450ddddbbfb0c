"""A send whose bytes become ready while it is on the wire, and the
round that uses it (``comm/native/transport.cpp`` ``mt_isend_marked`` /
``mt_send_extend``, ``comm/shm.py`` ``isend(ready=)`` / ``extend``,
``aio_send(ready=)``, ``ps/client.py`` ``_send_grad``, ``optim/sync.py``).

The wire, native, two endpoints in one process: a marked send delivers
no byte beyond its mark, is done only at its length, gives the receiver
the bytes an unmarked send gives, keeps its place in front of a later
send, can be cancelled half-ready, and counts what it placed early.

The round, two servers on threads over shm: with the early gate every
server receives the messages it receives with the whole-shard gate, byte
for byte and in the same order; a staging that dies after its first
piece fails the round, completes no GRAD and leaves the next round
sound; and every client whose payload is not the slice itself keeps the
whole-shard gate.
"""

import contextlib
import os
import signal
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu import obs
from mpit_tpu.aio import TaskError
from mpit_tpu.comm.shm import ShmTransport
from mpit_tpu.comm.transport import Transport
from mpit_tpu.ft import FTConfig
from mpit_tpu.optim import sync
from mpit_tpu.optim.shells import RuleShell
from mpit_tpu.ps import ParamClient, ParamServer, tags

LIMIT_S = 120
RING = 1 << 20
HEADER = 48                  # sizeof(ChunkHeader)
CHUNK = RING // 4 - HEADER   # transport.cpp max_chunk at this ring
BIG = 3 * CHUNK + 1000       # three whole chunks and a tail
SENTINEL = 0xA5


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


@pytest.fixture
def obs_on():
    obs.configure(enabled=True, reset=True)
    try:
        yield obs.get_recorder()
    finally:
        obs.configure(enabled=None, reset=True)


def noise(seed, nbytes):
    return np.random.default_rng(seed).integers(0, 256, nbytes, dtype=np.uint8)


def spin(*steps, limit=10**6):
    spins = 0
    while not all([step() for step in steps]):
        spins += 1
        assert spins < limit


def settle(*wires, passes=50):
    """Let every endpoint make what progress it can."""
    for _ in range(passes):
        for wire in wires:
            wire.iprobe(wire.rank, 999)


@contextlib.contextmanager
def pair(name):
    ns = f"t_ep_{name}_{os.getpid()}"
    wires = [ShmTransport(ns, r, 2, ring_bytes=RING) for r in range(2)]
    try:
        yield wires
    finally:
        for wire in wires:
            wire.close()


# -- the wire -----------------------------------------------------------------

MARKS = {"zero": 0, "inside_a_chunk": 100_000, "on_a_chunks_edge": CHUNK,
         "in_the_third_chunk": 2 * CHUNK + 5}


@pytest.mark.parametrize("mark", sorted(MARKS))
def test_a_marked_send_delivers_up_to_its_mark_and_is_done_at_its_length(mark):
    ready = MARKS[mark]
    data = noise(1, BIG)
    with pair(f"mark{ready}") as (a, b):
        out = np.full(BIG, SENTINEL, np.uint8)
        hr = b.irecv(0, 4, out=out)
        hs = a.isend(data, 1, 4, ready=ready)
        settle(a, b)
        assert not a.test(hs) and not b.test(hr)
        np.testing.assert_array_equal(out[:ready], data[:ready])
        assert (out[ready:] == SENTINEL).all()  # no byte beyond the mark
        assert a.ring_counters()["tx_early_bytes"] == ready
        assert a.extend(hs, BIG) == BIG
        spin(lambda: a.test(hs), lambda: b.test(hr))
        np.testing.assert_array_equal(out, data)
        assert b.rx_path_bytes() == {"rx_direct_bytes": BIG,
                                     "rx_assembled_bytes": 0}
        # what left after the mark reached the length was not early
        assert a.ring_counters()["tx_early_bytes"] == ready
        # an unmarked send of the same bytes gives the receiver the same
        again = np.full(BIG, SENTINEL, np.uint8)
        hr = b.irecv(0, 4, out=again)
        hs = a.isend(data, 1, 4)
        spin(lambda: a.test(hs), lambda: b.test(hr))
        np.testing.assert_array_equal(again, out)
        assert a.ring_counters()["tx_early_bytes"] == ready


def test_a_mark_that_moves_piece_by_piece_gives_the_same_bytes():
    """The shell's use: the mark follows a writer, in steps that are no
    multiple of a chunk; the chunks are cut at the marks and the
    receiver's buffer never runs ahead of the writer."""
    data = noise(2, BIG)
    staging = np.zeros(BIG, np.uint8)
    step = 70_001
    with pair("steps") as (a, b):
        out = np.full(BIG, SENTINEL, np.uint8)
        hr = b.irecv(0, 4, out=out)
        hs = a.isend(staging, 1, 4, ready=0)
        for hi in range(step, BIG + step, step):
            hi = min(hi, BIG)
            staging[:hi] = data[:hi]
            a.extend(hs, hi)
            settle(a, b, passes=4)
            np.testing.assert_array_equal(out[:hi], data[:hi])
            assert (out[hi:] == SENTINEL).all()
        spin(lambda: a.test(hs), lambda: b.test(hr))
        np.testing.assert_array_equal(out, data)
        early = a.ring_counters()["tx_early_bytes"]
        assert BIG - step <= early < BIG  # all but the last step's bytes


def test_extend_moves_forward_only_and_no_further_than_the_length():
    data = noise(3, BIG)
    with pair("mono") as (a, b):
        hs = a.isend(data, 1, 4, ready=500)
        assert a.extend(hs, 100) == 500       # never back
        assert a.extend(hs, 700) == 700
        assert a.extend(hs, 10**12) == BIG    # clamped
        assert a.extend(hs, 0) == BIG
        out = np.zeros_like(data)
        hr = b.irecv(0, 4, out=out)
        spin(lambda: a.test(hs), lambda: b.test(hr))
        np.testing.assert_array_equal(out, data)
        assert a.extend(hs, BIG) == -1        # done: nothing to move
        # a plain send has its mark at its length from the start
        hs = a.isend(data, 1, 4)
        assert a.extend(hs, 5) == BIG
        a.cancel(hs)
        assert a.extend(hs, BIG) == -1


def test_a_later_send_to_the_same_rank_waits_behind_the_marked_one():
    first, second = noise(4, BIG), noise(5, 1000)
    with pair("fifo") as (a, b):
        h1 = a.isend(first, 1, 4, ready=CHUNK // 2)
        h2 = a.isend(second, 1, 6)  # another tag, the same destination
        settle(a, b)
        assert not a.test(h1) and not a.test(h2)
        assert not b.iprobe(0, 6)  # it has not overtaken
        a.extend(h1, BIG)
        spin(lambda: a.test(h1), lambda: a.test(h2),
             lambda: b.iprobe(0, 4) and b.iprobe(0, 6))
        np.testing.assert_array_equal(
            np.frombuffer(b.recv(0, 4), np.uint8), first)
        np.testing.assert_array_equal(
            np.frombuffer(b.recv(0, 6), np.uint8), second)


def test_cancel_of_a_half_ready_send_leaves_the_next_message_whole():
    torn, retry = noise(6, BIG), noise(7, BIG)
    with pair("torn") as (a, b):
        out = np.full(BIG, SENTINEL, np.uint8)
        hr = b.irecv(0, 4, out=out)
        hs = a.isend(torn, 1, 4, ready=CHUNK + 17)
        settle(a, b)
        np.testing.assert_array_equal(out[:CHUNK + 17], torn[:CHUNK + 17])
        assert not b.test(hr) and not a.test(hs)
        a.cancel(hs)
        assert a.extend(hs, BIG) == -1
        settle(a, b)
        assert not b.test(hr)  # the torn message is never taken for whole
        hs = a.isend(retry, 1, 4)
        spin(lambda: a.test(hs), lambda: b.test(hr))
        np.testing.assert_array_equal(out, retry)
        assert b.rx_path_bytes()["rx_direct_bytes"] == BIG


def test_the_tx_span_says_what_was_early_and_how_long_it_was_unready(obs_on):
    nbytes = 4 << 20  # over the 1 MB a message needs for a span
    data = noise(8, nbytes)
    with pair("span") as (a, b):
        out = np.zeros_like(data)
        hr = b.irecv(0, 4, out=out)
        hs = a.isend(data, 1, 4, ready=CHUNK)
        settle(a, b)
        time.sleep(0.05)  # at its mark, the ring empty, the thread here
        settle(a, b)
        a.extend(hs, nbytes)
        spin(lambda: a.test(hs), lambda: b.test(hr))
        # an unmarked one beside it
        hr = b.irecv(0, 4, out=out)
        hs = a.isend(data, 1, 4)
        spin(lambda: a.test(hs), lambda: b.test(hr))
    marked, plain = [s for s in obs_on.spans
                     if getattr(s, "cat", "wire") == "wire" and s.name == "tx"
                     and "early_bytes" in s.args]
    assert marked.args["early_bytes"] == CHUNK
    assert marked.args["unready_ms"] >= 50.0
    # a part of the time away, never of the time blocked
    assert marked.args["unready_ms"] <= marked.args["away_ms"]
    assert marked.args["blocked_ms"] < 50.0
    parts = sum(marked.args[k] for k in ("copy_ms", "blocked_ms", "away_ms"))
    assert parts == pytest.approx(marked.args["flight_ms"], rel=1e-9)
    assert plain.args["early_bytes"] == 0 and plain.args["unready_ms"] == 0.0
    # the receiver sees the same wait as starvation
    (rx, _rx2) = [s for s in obs_on.spans if s.name == "rx"]
    assert rx.args["starved_ms"] >= 50.0


# -- the round ----------------------------------------------------------------

SIZE = 5000          # two shards of 2500: four whole pieces and a tail each
PIECE = 600 * 4      # bytes
TARGET = jnp.linspace(-1.0, 1.0, SIZE)
FRAMED = FTConfig(op_deadline_s=10.0, max_retries=4)
CHUNKED = FTConfig(op_deadline_s=10.0, max_retries=4, chunk_bytes=4096)
RULES = {"chunked": "rmsprop"}  # per-chunk applies need a splittable rule


def quad(w, target):
    d = w - target
    return 0.5 * jnp.sum(d * d), d


@pytest.fixture(autouse=True)
def small_pieces(monkeypatch):
    monkeypatch.setattr(sync, "PIECE_BYTES", PIECE)


@pytest.fixture
def slow_staging(monkeypatch):
    """One piece in flight and 20 ms to cut each, so a shard of five is
    whole in the mirror 80 ms after its first piece and the client is
    there to see it."""
    real_cut = sync._cut

    def slow(x, start, *, size):
        time.sleep(0.02)
        return real_cut(x, start, size=size)

    monkeypatch.setattr(sync, "IN_FLIGHT", 1)
    monkeypatch.setattr(sync, "_cut", slow)


class Taped(ShmTransport):
    """An endpoint that keeps what it received, in the order it became
    whole: (source, tag, bytes)."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.tape = []

    def test(self, handle):
        was = handle.done
        done = super().test(handle)
        if done and not was and handle.kind == "recv":
            got = self.payload(handle)
            self.tape.append((handle.peer, handle.tag,
                              got.tobytes() if isinstance(got, np.ndarray)
                              else bytes(got)))
        return done


class Plain(Transport):
    """The contract of ``comm/transport.py`` and nothing more: a wire
    that cannot hold a send that is not yet whole."""

    def __init__(self, inner):
        self.inner, self.rank, self.nranks = inner, inner.rank, inner.nranks

    def isend(self, data, dst, tag):
        return self.inner.isend(data, dst, tag)

    def irecv(self, src, tag, out=None):
        return self.inner.irecv(src, tag, out=out)

    def iprobe(self, src, tag):
        return self.inner.iprobe(src, tag)

    def test(self, handle):
        return self.inner.test(handle)

    def cancel(self, handle):
        self.inner.cancel(handle)

    def payload(self, handle):
        return self.inner.payload(handle)


@contextlib.contextmanager
def shm_gang(name, rule="adam", codec=None, ft=None, plain=False):
    """Servers 0 and 1 on threads, the client (rank 2) driven by the
    caller, all over the shm wire; yields servers, client and the
    client's own shm endpoint."""
    ns = f"t_ep_{name}_{os.getpid()}"
    wires = [Taped(ns, r, 3, ring_bytes=RING) for r in range(3)]
    servers = [ParamServer(r, [2], wires[r], rule=rule, ft=ft)
               for r in (0, 1)]
    threads = [threading.Thread(target=s.start, daemon=True)
               for s in servers]
    for t in threads:
        t.start()
    client = ParamClient(2, [0, 1], Plain(wires[2]) if plain else wires[2],
                         seed_servers=True, codec=codec, ft=ft)
    try:
        yield servers, client, wires[2]
    finally:
        for s in servers:
            s.live.stop()
        for t in threads:
            t.join(10)
            assert not t.is_alive(), "server thread did not stop"
        for wire in wires:
            wire.close()


def train(name, rounds, **gang_kw):
    """``rounds`` rounds; the final parameters, what each server
    received, each server's shard and the client's early bytes."""
    with shm_gang(name, **gang_kw) as (servers, pc, wire):
        opt = RuleShell(quad, pc, su=1)
        w = opt.start(jnp.zeros(SIZE) + 0.25)
        for _ in range(rounds):
            w, _loss = opt.step(w, TARGET)
        out = np.array(w)
        opt.stop()
        assert opt.rounds_streamed == rounds
        # (a STOP is taken whenever its server gets to it: not compared)
        tapes = [[m for m in s.transport.tape if m[1] != tags.STOP]
                 for s in servers]
        shards = [np.array(s.param) for s in servers]
        early = wire.ring_counters()["tx_early_bytes"]
    return out, tapes, shards, early


def test_the_early_gate_gives_every_server_the_frames_of_the_whole_gate(
        slow_staging):
    rounds = 4
    w_e, tapes_e, shards_e, early_e = train("early", rounds)
    w_w, tapes_w, shards_w, early_w = train("whole", rounds, plain=True)
    # the mechanism ran in one run and not in the other
    assert early_w == 0
    assert early_e >= rounds * (SIZE * 4 - 2 * PIECE) * 0.5
    for got, want in zip(tapes_e, tapes_w):
        grads = [m for m in got if m[1] == tags.GRAD]
        assert len(grads) == rounds and len(grads[0][2]) == SIZE // 2 * 4
        assert got == want  # every message, byte for byte, in order
    np.testing.assert_array_equal(w_e, w_w)
    for got, want in zip(shards_e, shards_w):
        np.testing.assert_array_equal(got, want)
    assert not np.array_equal(w_e, np.full(SIZE, 0.25, np.float32))


def test_a_stager_that_dies_after_its_first_piece_fails_the_round_only(
        slow_staging, monkeypatch):
    want, _tapes, want_shards, _early = train("clean", 1, rule="add")
    slow_cut = sync._cut
    on_the_wire = threading.Event()
    with shm_gang("dies", rule="add") as (servers, pc, wire):
        opt = RuleShell(quad, pc, su=1)
        w = opt.start(jnp.zeros(SIZE) + 0.25)
        stream = opt._stream
        first = stream.cut[0]

        def gate(shard):
            if wire.ring_counters()["tx_early_bytes"] > 0:
                on_the_wire.set()
            return stream.staged(shard)

        def broken(x, start, *, size):
            # shard 0's last piece, cut once its first is in the mirror:
            # it breaks when bytes of the shard have left for server 0
            if int(start) >= first.offset + 2400:
                assert on_the_wire.wait(LIMIT_S / 2)
                raise OSError("the d2h broke")
            return slow_cut(x, start, size=size)

        pc.stream_shards(gate, stream.landed)
        monkeypatch.setattr(sync, "_cut", broken)
        with pytest.raises(TaskError) as err:
            opt.step(w, TARGET)
        assert "copying thread failed" in str(err.value.cause)
        assert isinstance(err.value.cause.__cause__, OSError)
        assert "send_grad" in err.value.task.name
        assert stream._worker is None
        assert pc.sched.errors == []  # one cause, raised once
        sent = wire.ring_counters()["tx_early_bytes"]
        assert 0 < sent < first.size * 4  # part of shard 0 and no more
        time.sleep(0.2)
        # no server took a gradient whose tail was never staged
        assert [s.grads_applied for s in servers] == [0, 0]
        np.testing.assert_array_equal(
            servers[0].param, np.full(first.size, 0.25, np.float32))
        # the next round is sound: the servers take its GRADs whole
        monkeypatch.setattr(sync, "_cut", slow_cut)
        w, _loss = opt.step(w, TARGET)
        np.testing.assert_array_equal(np.array(w), want)
        assert [s.grads_applied for s in servers] == [1, 1]
        for server, shard in zip(servers, want_shards):
            np.testing.assert_array_equal(server.param, shard)
        opt.stop()
    assert not [t for t in threading.enumerate()
                if t.name == "mpit-round-stream"]


WHOLE_GATE = {
    "codec": dict(codec="int8"),
    "framed": dict(ft=FRAMED),
    "chunked": dict(ft=CHUNKED),
    "no_capability": dict(plain=True),
}


@pytest.mark.parametrize("case", sorted(WHOLE_GATE))
def test_a_payload_that_is_not_the_slice_keeps_the_whole_shard_gate(
        case, slow_staging, obs_on):
    rounds = 2
    w, _tapes, _shards, early = train(f"wg_{case}", rounds,
                                      rule=RULES.get(case, "adam"),
                                      **WHOLE_GATE[case])
    assert early == 0  # no byte left before its shard was whole
    assert np.isfinite(w).all()
    grads = [s for s in obs_on.spans if s.name == "GRAD"
             and s.args.get("side") == "client"]
    assert len(grads) == 2 * rounds
    # each op waited out its shard's staging, four more pieces of 20 ms
    # at the least, before its span opened
    assert all(s.args["gated_ms"] >= 60.0 for s in grads)


def test_the_slice_itself_over_shm_follows_the_staging(slow_staging, obs_on):
    rounds = 2
    _w, _tapes, _shards, early = train("follow", rounds)
    assert early > 0
    grads = sorted((s for s in obs_on.spans if s.name == "GRAD"
                    and s.args.get("side") == "client"
                    and s.args.get("round") == 1),
                   key=lambda s: s.args["peer"])
    # shard 0's op opened at its first piece, shard 1's at its own
    # first piece, which is after all of shard 0's
    assert grads[0].args["gated_ms"] < 40.0 < 60.0 <= grads[
        1].args["gated_ms"]
    # and each lasted as long as its shard's staging
    assert all(1e3 * (s.t1 - s.t0) >= 60.0 for s in grads)
    tx = [s for s in obs_on.spans if s.name == "tx"
          and s.args.get("tag") == tags.GRAD]
    assert not tx  # shards of 10 kB: under the megabyte a span needs


def test_the_round_span_says_where_the_staging_threads_time_went(
        slow_staging, obs_on):
    rounds = 2
    train("parts", rounds)
    spans = [s for s in obs_on.spans if s.name == "round"]
    assert len(spans) == rounds
    for span in spans:
        wait, copy, issue = (span.args[key] for key in sync.STAGE_PARTS)
        # ten pieces, one in flight: nine are cut inside the loop at 20 ms
        assert issue >= 9 * 20.0 and wait >= 0.0 and copy > 0.0
        staging = 1e3 * (span.phase_seconds("d2h")
                         + span.phase_seconds("stage")
                         + span.phase_seconds("exchange"))
        assert wait + copy + issue <= staging


def test_obs_off_the_staging_thread_reads_no_clock(slow_staging, monkeypatch):
    reads = []
    real = time.monotonic

    def counted():
        if threading.current_thread().name == "mpit-round-stream":
            reads.append(1)
        return real()

    monkeypatch.setattr(time, "monotonic", counted)
    train("noclock", 2)
    assert reads == []
