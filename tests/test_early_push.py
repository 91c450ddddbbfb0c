"""A send that is not yet whole, made of pieces, and the round that uses
it (``comm/native/transport.cpp`` ``mt_isend_pieces`` / ``mt_send_append``
/ ``mt_send_written``, ``comm/shm.py`` ``isend_pieces`` / ``append`` /
``written``, ``aio_send(pieces=)``, ``ps/client.py`` ``_send_grad``,
``optim/sync.py``).

The wire, native, two endpoints in one process: a send delivers no byte
beyond its last appended one, is done only at its length, gives the
receiver the bytes a whole send gives (one message, whatever the pieces:
across a chunk's edge, smaller than a chunk, a buffer's own slices), keeps
its place in front of a later send, can be cancelled half-made, counts
what it placed early, and holds a piece until its bytes are in the ring
and not after.

The round, two servers on threads over shm: with the pieces handed to
the sends every server receives the messages it receives with the mirror
and the whole-shard gate, byte for byte and in the same order; a staging
that dies after its first piece fails the round, completes no GRAD and
leaves the next round sound; and every client whose payload is not the
slice itself keeps the mirror and the whole-shard gate.

The pull's twin (``ShmTransport.filled`` / ``follow``, ``aio_recv(landing=)``,
``ps/client.py`` ``_mark``, ``optim/sync.py`` ``_Copies.sink`` /
``_upload``), the same gang with a client's endpoint that lands a PARAM
in three steps and says so (``follow``): a shard's pieces go up as the
mark passes them and never above it, each once, to the bits of the
whole-shard path; a codec, the framed wire and a transport that cannot
say how far a receive is filled hear of the shard once, whole, through
the same code; a read aborted
mid-shard sends the shard up again whole; an upload that fails fails the
round once.
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


def marked(wire, data, dst, tag, ready):
    """A send of ``data`` of which the first ``ready`` bytes are there:
    the buffer that fills from the front, as a send of its slices."""
    handle = wire.isend_pieces(data.nbytes, dst, tag)
    handle.meta["of"] = data
    extend(wire, handle, ready)
    return handle


def extend(wire, handle, ready):
    """The bytes of the buffer up to ``ready`` are there now."""
    data, at = handle.meta["of"], handle.meta["appended"]
    if ready > at:
        wire.append(handle, data[at:ready])
    return handle.meta["appended"]


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
        hs = marked(a, data, 1, 4, ready)
        settle(a, b)
        assert not a.test(hs) and not b.test(hr)
        np.testing.assert_array_equal(out[:ready], data[:ready])
        assert (out[ready:] == SENTINEL).all()  # no byte beyond the mark
        assert a.ring_counters()["tx_early_bytes"] == ready
        assert extend(a, hs, BIG) == BIG
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
        hs = marked(a, staging, 1, 4, 0)
        for hi in range(step, BIG + step, step):
            hi = min(hi, BIG)
            staging[:hi] = data[:hi]
            extend(a, hs, hi)
            settle(a, b, passes=4)
            np.testing.assert_array_equal(out[:hi], data[:hi])
            assert (out[hi:] == SENTINEL).all()
        spin(lambda: a.test(hs), lambda: b.test(hr))
        np.testing.assert_array_equal(out, data)
        early = a.ring_counters()["tx_early_bytes"]
        assert BIG - step <= early < BIG  # all but the last step's bytes


def test_append_goes_no_further_than_the_length_and_only_while_pending():
    data = noise(3, BIG)
    with pair("mono") as (a, b):
        hs = marked(a, data, 1, 4, 500)
        assert extend(a, hs, 700) == 700
        with pytest.raises(ValueError, match="passes the send's length"):
            a.append(hs, noise(0, BIG))   # 700 + BIG: refused whole
        assert hs.meta["appended"] == 700 and a.written(hs) <= 700
        assert extend(a, hs, BIG) == BIG
        with pytest.raises(ValueError, match="passes the send's length"):
            a.append(hs, data[:1])
        out = np.zeros_like(data)
        hr = b.irecv(0, 4, out=out)
        spin(lambda: a.test(hs), lambda: b.test(hr))
        np.testing.assert_array_equal(out, data)
        assert a.written(hs) == BIG and hs.buf is None  # done: all let go
        with pytest.raises(RuntimeError, match="not pending"):
            a.append(hs, data[:1])
        hs = marked(a, data, 1, 4, 5)
        a.cancel(hs)
        with pytest.raises(RuntimeError, match="not pending"):
            a.append(hs, data[5:6])
        with pytest.raises(ValueError, match="C-contiguous"):
            a.append(marked(a, data, 1, 4, 0), data[::2])


def test_a_later_send_to_the_same_rank_waits_behind_the_marked_one():
    first, second = noise(4, BIG), noise(5, 1000)
    with pair("fifo") as (a, b):
        h1 = marked(a, first, 1, 4, CHUNK // 2)
        h2 = a.isend(second, 1, 6)  # another tag, the same destination
        settle(a, b)
        assert not a.test(h1) and not a.test(h2)
        assert not b.iprobe(0, 6)  # it has not overtaken
        extend(a, h1, BIG)
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
        hs = marked(a, torn, 1, 4, CHUNK + 17)
        settle(a, b)
        np.testing.assert_array_equal(out[:CHUNK + 17], torn[:CHUNK + 17])
        assert not b.test(hr) and not a.test(hs)
        a.cancel(hs)
        assert hs.buf is None  # the pieces are the caller's again
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
        hs = marked(a, data, 1, 4, CHUNK)
        settle(a, b)
        time.sleep(0.05)  # at its last byte, the ring empty, the thread here
        settle(a, b)
        extend(a, hs, nbytes)
        spin(lambda: a.test(hs), lambda: b.test(hr))
        # an unmarked one beside it
        hr = b.irecv(0, 4, out=out)
        hs = a.isend(data, 1, 4)
        spin(lambda: a.test(hs), lambda: b.test(hr))
    early, plain = [s for s in obs_on.spans
                    if getattr(s, "cat", "wire") == "wire" and s.name == "tx"
                    and "early_bytes" in s.args]
    assert early.args["early_bytes"] == CHUNK
    assert early.args["unready_ms"] >= 50.0
    # a part of the time away, never of the time blocked
    assert early.args["unready_ms"] <= early.args["away_ms"]
    assert early.args["blocked_ms"] < 50.0
    parts = sum(early.args[k] for k in ("copy_ms", "blocked_ms", "away_ms"))
    assert parts == pytest.approx(early.args["flight_ms"], rel=1e-9)
    assert plain.args["early_bytes"] == 0 and plain.args["unready_ms"] == 0.0
    # the receiver sees the same wait as starvation
    (rx, _rx2) = [s for s in obs_on.spans if s.name == "rx"]
    assert rx.args["starved_ms"] >= 50.0


# -- a send made of pieces that lie apart --------------------------------------

PIECES = {
    # a chunk's edge falls inside the second piece, and the third's too
    "across_a_chunks_edge": [CHUNK - 1000, CHUNK + 700, BIG - 2 * CHUNK + 300],
    # a chunk is cut across a dozen pieces, down to one of a single byte
    "smaller_than_a_chunk": [70_001] * (BIG // 70_001) + [1, BIG % 70_001 - 1],
    "one_piece": [BIG],
}


def apart(seed, sizes):
    """Pieces of ``sizes`` bytes, each an array of its own."""
    assert sum(sizes) == BIG
    return [noise(seed + i, n) for i, n in enumerate(sizes)]


@pytest.mark.parametrize("case", sorted(PIECES))
@pytest.mark.parametrize("pace", ["all_at_once", "one_a_pass"])
def test_appended_pieces_arrive_as_one_message_bit_for_bit(case, pace):
    pieces = apart(10, PIECES[case])
    want = np.concatenate(pieces)
    with pair(f"pcs_{case}_{pace}") as (a, b):
        out = np.full(BIG, SENTINEL, np.uint8)
        hr = b.irecv(0, 4, out=out)
        hs = a.isend_pieces(BIG, 1, 4)
        sent = 0
        for piece in pieces:
            a.append(hs, piece)
            sent += piece.nbytes
            if pace == "one_a_pass":
                settle(a, b, passes=3)
                np.testing.assert_array_equal(out[:sent], want[:sent])
                assert (out[sent:] == SENTINEL).all()
                assert a.written(hs) == sent or a.test(hs)
        spin(lambda: a.test(hs), lambda: b.test(hr))
        np.testing.assert_array_equal(out, want)
        # one message, landed where it was asked for
        assert b.rx_path_bytes() == {"rx_direct_bytes": BIG,
                                     "rx_assembled_bytes": 0}
        if pace == "all_at_once":
            # the chunks are cut across the pieces: three whole and a tail
            assert a.ring_counters()["tx_chunks"] == 4
        # a whole send of the same bytes gives the receiver the same
        again = np.full(BIG, SENTINEL, np.uint8)
        hr = b.irecv(0, 4, out=again)
        hs = a.isend(want, 1, 4)
        spin(lambda: a.test(hs), lambda: b.test(hr))
        np.testing.assert_array_equal(again, out)


def test_a_send_with_nothing_appended_holds_its_queue():
    pieces, second = apart(20, PIECES["across_a_chunks_edge"]), noise(5, 1000)
    with pair("empty") as (a, b):
        h1 = a.isend_pieces(BIG, 1, 4)
        h2 = a.isend(second, 1, 6)  # whole, another tag, the same peer
        settle(a, b)
        assert not a.test(h1) and not a.test(h2)
        assert a.written(h1) == 0
        assert not b.iprobe(0, 6) and not b.iprobe(0, 4)
        assert a.ring_counters()["tx_chunks"] == 0  # not a byte, not a header
        for piece in pieces[:-1]:
            a.append(h1, piece)
        settle(a, b)
        assert not a.test(h1) and not a.test(h2) and not b.iprobe(0, 6)
        a.append(h1, pieces[-1])
        spin(lambda: a.test(h1), lambda: a.test(h2),
             lambda: b.iprobe(0, 4) and b.iprobe(0, 6))
        np.testing.assert_array_equal(
            np.frombuffer(b.recv(0, 4), np.uint8), np.concatenate(pieces))
        np.testing.assert_array_equal(
            np.frombuffer(b.recv(0, 6), np.uint8), second)


def test_a_piece_is_held_until_its_bytes_are_in_the_ring_and_not_after():
    """The ring takes a megabyte and nobody drains it: the first piece is
    in it whole, the second in part, the third not at all."""
    third = 600_000
    pieces = [noise(30 + i, third) for i in range(3)]
    want = np.concatenate(pieces)
    with pair("held") as (a, b):
        hs = a.isend_pieces(3 * third, 1, 4)
        for piece in pieces:
            a.append(hs, piece)
        assert len(hs.buf) == 3 and all(
            p is q for (_end, p), q in zip(hs.buf, pieces))
        for _ in range(20):
            assert not a.test(hs)
        written = a.written(hs)
        assert third <= written < 2 * third
        held = [p for _end, p in hs.buf]
        assert len(held) == 2 and held[0] is pieces[1] and \
            held[1] is pieces[2]
        # the first piece is the caller's again: what it writes there now
        # changes nothing the peer receives
        pieces[0][:] = SENTINEL
        out = np.zeros(3 * third, np.uint8)
        hr = b.irecv(0, 4, out=out)
        spin(lambda: a.test(hs), lambda: b.test(hr))
        np.testing.assert_array_equal(out, want)
        assert hs.buf is None and a.written(hs) == 3 * third


def test_aio_send_tells_the_feed_what_is_written_and_sends_what_it_gives():
    from mpit_tpu.aio import Scheduler, aio_send

    pieces = apart(40, PIECES["smaller_than_a_chunk"])
    want = np.concatenate(pieces)
    todo, told = list(pieces), []

    def feed(written):
        told.append(written)
        return [todo.pop(0)] if todo else []

    with pair("aio") as (a, b):
        out = np.zeros(BIG, np.uint8)
        hr = b.irecv(0, 4, out=out)
        sched = Scheduler()
        sched.spawn(aio_send(a, BIG, 1, 4, pieces=feed), name="send")
        while sched.queue:
            sched.ping_pass(0.0)
            b.test(hr)
        assert sched.errors == []
        spin(lambda: b.test(hr))
    np.testing.assert_array_equal(out, want)
    assert told == sorted(told) and told[0] == 0 and told[-1] == BIG


def test_a_feed_that_raises_cancels_the_send_part_way():
    from mpit_tpu.aio import Scheduler, aio_send

    pieces = apart(50, PIECES["across_a_chunks_edge"])
    todo = list(pieces[:1])

    def feed(_written):
        if not todo:
            raise OSError("the d2h broke")
        return [todo.pop(0)]

    with pair("aio_err") as (a, b):
        out = np.full(BIG, SENTINEL, np.uint8)
        hr = b.irecv(0, 4, out=out)
        sched = Scheduler()
        sched.spawn(aio_send(a, BIG, 1, 4, pieces=feed), name="send")
        while sched.queue:
            sched.ping_pass(0.0)
        (err,) = sched.errors
        assert isinstance(err.cause, OSError)
        settle(a, b)
        assert not b.test(hr)  # never taken for whole
        retry = noise(51, BIG)
        hs = a.isend(retry, 1, 4)
        spin(lambda: a.test(hs), lambda: b.test(hr))
        np.testing.assert_array_equal(out, retry)


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


class Withheld:
    """A client's ``ParamClientAPI`` and nothing more: it takes no gate."""

    def __init__(self, inner):
        self.rank = inner.rank
        for name in ("start", "reset", "async_send_grad", "async_recv_param",
                     "async_send_param", "ping", "wait", "stop"):
            setattr(self, name, getattr(inner, name))


@contextlib.contextmanager
def shm_gang(name, rule="adam", codec=None, ft=None, plain=False,
             endpoint=Taped):
    """Servers 0 and 1 on threads, the client (rank 2, on an
    ``endpoint``) driven by the caller, all over the shm wire; yields
    servers, client and the client's own shm endpoint."""
    ns = f"t_ep_{name}_{os.getpid()}"
    wires = [(endpoint if r == 2 else Taped)(ns, r, 3, ring_bytes=RING)
             for r in range(3)]
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


def train(name, rounds, withheld=False, mirrors=None, **gang_kw):
    """``rounds`` rounds; the final parameters, what each server
    received, each server's shard and the client's early bytes.
    ``mirrors``: a list that takes the gradient mirror as each round
    left it."""
    with shm_gang(name, **gang_kw) as (servers, pc, wire):
        opt = RuleShell(quad, Withheld(pc) if withheld else pc, su=1)
        w = opt.start(jnp.zeros(SIZE) + 0.25)
        for _ in range(rounds):
            w, _loss = opt.step(w, TARGET)
            if mirrors is not None:
                mirrors.append(opt.grad_host.copy())
        out = np.array(w)
        opt.stop()
        assert len(opt._stream.cut) == (1 if withheld else 2)
        assert opt.rounds == rounds
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

        def feed(shard):
            gate(shard)
            return stream.feed(shard)

        assert stream.follow == [True, True]  # the sends read the pieces
        pc.stream_shards(gate, stream.landed)
        pc.stream_pieces(feed)
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


KEEPS_THE_MIRROR = dict(WHOLE_GATE, no_gate=dict(withheld=True))


@pytest.mark.parametrize("case", sorted(KEEPS_THE_MIRROR))
def test_every_other_path_keeps_the_mirror_and_reads_no_piece(case, obs_on):
    rounds, mirrors = 2, []
    w, _tapes, _shards, early = train(f"km_{case}", rounds, mirrors=mirrors,
                                      rule=RULES.get(case, "adam"),
                                      **KEEPS_THE_MIRROR[case])
    assert early == 0 and np.isfinite(w).all()
    spans = [s for s in obs_on.spans if s.name == "round"]
    assert [s.args["direct_bytes"] for s in spans] == [0] * rounds
    # every piece was copied into the mirror: a ``hand`` that took time,
    # and three passes over the host's memory, not one
    pieces = [s for s in obs_on.spans if s.name == "d2h"]
    assert pieces and all(s.args["streams"] == 3 for s in pieces)
    assert all(phase_ms(obs_on, k)["hand"] > 0.0 for k in range(rounds))
    # the mirror holds each round's whole gradient: w - target at the
    # parameters the round began with
    np.testing.assert_allclose(
        mirrors[0], np.full(SIZE, 0.25, np.float32) - np.asarray(TARGET),
        rtol=1e-6, atol=1e-7)
    assert not np.array_equal(mirrors[0], mirrors[1])
    assert np.count_nonzero(mirrors[1]) > 0.99 * SIZE  # both shards'


def test_a_followed_round_never_writes_the_mirror_and_gives_the_same_bits(
        obs_on):
    """Three rounds with every shard's send reading the pieces, and the
    same three with the mirror (a wire that cannot send pieces)."""
    rounds, followed, mirrored = 3, [], []
    w_f, tapes_f, shards_f, early_f = train("f3", rounds, mirrors=followed)
    base = len(obs_on.spans)
    w_m, tapes_m, shards_m, early_m = train("m3", rounds, mirrors=mirrored,
                                            plain=True)
    np.testing.assert_array_equal(w_f, w_m)
    for got, want in zip(shards_f, shards_m):
        np.testing.assert_array_equal(got, want)
    assert tapes_f == tapes_m  # every message, byte for byte, in order
    assert not np.array_equal(w_f, np.full(SIZE, 0.25, np.float32))
    direct = [[s.args["direct_bytes"] for s in spans if s.name == "round"]
              for spans in (obs_on.spans[:base], obs_on.spans[base:])]
    assert direct == [[SIZE * 4] * rounds, [0] * rounds]
    assert all(not mirror.any() for mirror in followed)  # never written
    assert all(mirror.any() for mirror in mirrored)
    assert early_m == 0


def test_the_stream_learns_shard_by_shard_whose_send_reads_pieces():
    """The predicate is the client's, a shard at a time: a codec on one
    server's channel keeps that shard in the mirror and no other."""
    with shm_gang("mixed", rule="add") as (servers, pc, wire):
        opt = RuleShell(quad, pc, su=1)
        w = opt.start(jnp.zeros(SIZE) + 0.25)
        stream = opt._stream
        assert stream.follow == [True, True]
        assert pc.stream_pieces(stream.feed) == [True, True]
        # between rounds there is no feed: the slice is the payload
        assert [stream.feed(shard) for shard in stream.cut] == [None, None]
        stream.follow[1] = False
        real = pc._follows
        pc._follows = lambda srank: srank == 0 and real(srank)
        w, _loss = opt.step(w, TARGET)
        grad = np.full(SIZE, 0.25, np.float32) - np.asarray(TARGET)
        first, second = stream.cut
        assert not opt.grad_host[first.offset:first.end].any()
        np.testing.assert_allclose(opt.grad_host[second.offset:second.end],
                                   grad[second.offset:second.end], rtol=1e-6)
        np.testing.assert_allclose(np.array(w), 0.25 + grad, rtol=1e-6)
        opt.stop()


def test_the_stager_stands_at_the_bound_until_the_client_has_placed_more(
        monkeypatch, obs_on):
    """A bound of one piece: no piece is cut while more than that is
    handed over and not in a ring, and the round still ends, equal."""
    want, _tapes, want_shards, _early = train("unbound", 2)
    monkeypatch.setattr(sync, "HELD_BYTES", PIECE)
    monkeypatch.setattr(sync, "IN_FLIGHT", 1)
    most = []
    real = sync._Copies._no_room

    def watched(self):
        most.append(self.direct_bytes - sum(self.wrote))
        return real(self)

    monkeypatch.setattr(sync._Copies, "_no_room", watched)
    got, _tapes, shards, _early = train("bound", 2)
    np.testing.assert_array_equal(got, want)
    for a, b in zip(shards, want_shards):
        np.testing.assert_array_equal(a, b)
    # one piece in flight: at most the bound and the piece just landed
    assert most and max(most) <= 2 * PIECE
    assert any(held > PIECE for held in most)  # it did stand there


@pytest.mark.parametrize("lands", ["at_once", "in_steps"])
def test_many_small_pieces_under_a_tight_bound_and_a_short_switch_interval(
        lands, monkeypatch):
    """The stager and the client's thread share the pieces, the count of
    bytes written and the wake-up: with 157 pieces a round, a bound of
    two and the interpreter switching threads every 10 us, a lost piece,
    a piece out of order or a lost wake-up would show as other bits or
    as the test's time limit.  On the way up they share the marks
    (``in_steps``: a PARAM lands a third at a poll, :class:`Stepped`, with
    the upload racing the landing): a piece gone up short would show as
    other bits too."""
    import sys

    rounds = 12
    endpoint = {"at_once": Taped, "in_steps": Stepped}[lands]
    want, _tapes, want_shards, _early = train(f"calm_{lands}", rounds,
                                              plain=True)
    monkeypatch.setattr(sync, "PIECE_BYTES", 128)
    monkeypatch.setattr(sync, "HELD_BYTES", 256)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        got, _tapes, shards, early = train(f"stress_{lands}", rounds,
                                           endpoint=endpoint)
    finally:
        sys.setswitchinterval(interval)
    np.testing.assert_array_equal(got, want)
    for a, b in zip(shards, want_shards):
        np.testing.assert_array_equal(a, b)
    assert early > 0


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


def phase_ms(rec, k):
    """Milliseconds by phase over round ``k``'s ``d2h`` piece spans."""
    out = dict.fromkeys(sync.STAGE_PHASES, 0.0)
    for span in rec.spans:
        if span.name == "d2h" and span.args["round"] == k:
            ends = [t for _p, t in span.marks[1:]] + [span.t1]
            for (phase, t), end in zip(span.marks, ends):
                out[phase] += (end - t) * 1e3
    return out


def test_the_piece_spans_say_where_the_staging_threads_time_went(
        slow_staging, obs_on):
    rounds = 2
    train("parts", rounds)
    spans = [s for s in obs_on.spans if s.name == "round"]
    assert len(spans) == rounds
    for span in spans:
        assert not any(key.startswith("stage_") for key in span.args)
        wait, hand, held, issue = (phase_ms(obs_on, span.args["round"])[key]
                                   for key in sync.STAGE_PHASES)
        # ten pieces, one in flight: all ten are cut at 20 ms each, one
        # before the first piece and nine inside the loop
        assert issue >= 10 * 20.0 and wait >= 0.0 and hand >= 0.0
        assert held >= 0.0
        staging = 1e3 * (span.phase_seconds("d2h")
                         + span.phase_seconds("stage")
                         + span.phase_seconds("exchange"))
        assert wait + hand + held + issue <= staging
        # every shard's send read the pieces: nothing went by the mirror
        assert span.args["direct_bytes"] == SIZE * 4


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


# -- the pull follows its landing ---------------------------------------------

STEPS = 3


class Stepped(Taped):
    """The client's endpoint, whose PARAM receives land in the caller's
    buffer a third at a poll once the message is whole in a buffer of
    this endpoint's own, and say how far they are (``follow``).  ``may``,
    where a test sets it: ``may(server, step) -> bool``, asked before a
    step is taken."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.may = None
        self.asked = 0  # calls of ``follow``

    def irecv(self, src, tag, out=None):
        if tag != tags.PARAM or out is None:
            return super().irecv(src, tag, out=out)
        handle = super().irecv(src, tag, out=np.empty_like(out))
        handle.meta.update(shown=out, step=0)
        return handle

    def test(self, handle):
        whole = super().test(handle)
        shown = handle.meta.get("shown")
        if shown is None or not whole:
            return whole
        step = handle.meta["step"]
        if step < STEPS and (self.may is None or self.may(handle.peer, step)):
            step = handle.meta["step"] = step + 1
            upto = shown.nbytes * step // STEPS
            shown.view(np.uint8)[:upto] = handle.out.view(np.uint8)[:upto]
            if "told" in handle.meta:
                handle.meta["told"](upto)
        return step == STEPS

    def follow(self, handle, told):
        self.asked += 1
        assert "shown" in handle.meta  # only a PARAM into its slice
        handle.meta["told"] = told


def watch_uploads(monkeypatch, log):
    """Every dispatch of a piece into the device's vector, as ``(round,
    shard, lo, hi, mark, whole)``: ``lo`` and ``hi`` in elements from the
    shard's front, ``mark`` the bytes of the shard the sink had handed
    over as whole by then, ``whole`` the piece's host bytes at that
    instant."""
    real = sync._paste

    def logged(whole, piece, start):
        worker = next(t for t in threading.enumerate()
                      if t.name == "mpit-round-stream")
        copies = worker.copies
        cut = copies.stream.cut
        shard = next(i for i, c in enumerate(cut)
                     if c.offset <= int(start) < c.end)
        lo = int(start) - cut[shard].offset
        log.append((copies.k, shard, lo, lo + piece.shape[0],
                    copies.marks[shard], np.asarray(piece).tobytes()))
        return real(whole, piece, start)

    monkeypatch.setattr(sync, "_paste", logged)
    real_run = sync._Copies.run

    def run(self):
        threading.current_thread().copies = self
        try:
            real_run(self)
        finally:
            threading.current_thread().copies = None

    monkeypatch.setattr(sync._Copies, "run", run)


def lockstep(wire, log, rounds_done):
    """Hold a shard's next step until every piece under its mark has gone
    up: the stream's thread uploads while the shard is landing, or the
    test meets its time limit."""
    ends = [600, 1200, 1800, 2400, 2500]  # a shard's pieces, in elements

    def may(server, step):
        mark = (SIZE // 2 * 4) * step // STEPS
        due = sum(1 for hi in ends if hi * 4 <= mark)
        k = rounds_done()
        return sum(1 for r, shard, *_ in log
                   if r == k and shard == server) >= due

    wire.may = may


LANDS = {
    "identity": dict(),
    "codec": dict(codec="int8"),
    "framed": dict(ft=FRAMED),
    "no_capability": dict(plain=True),
}
THIRDS = [SIZE // 2 * 4 * step // STEPS for step in (1, 2, 3)]


@pytest.mark.parametrize("case", sorted(LANDS))
def test_a_shards_pieces_go_up_under_the_mark_each_once_to_the_same_bits(
        case, monkeypatch):
    rounds, log = 3, []
    want, _tapes, want_shards, _early = train(f"pw_{case}", rounds,
                                              **dict(LANDS[case], plain=True))
    watch_uploads(monkeypatch, log)
    with shm_gang(f"pl_{case}", endpoint=Stepped, **LANDS[case]) as (
            servers, pc, wire):
        opt = RuleShell(quad, pc, su=1)
        w = opt.start(jnp.zeros(SIZE) + 0.25)
        if case == "identity":
            lockstep(wire, log, lambda: opt.rounds)
        finals = []
        for _ in range(rounds):
            w, _loss = opt.step(w, TARGET)
            finals.append(opt.w_host.tobytes())
        got = np.array(w)
        shards = [np.array(s.param) for s in servers]
        opt.stop()
    # the same parameters on the device and the servers, to the bit
    np.testing.assert_array_equal(got, want)
    for a, b in zip(shards, want_shards):
        np.testing.assert_array_equal(a, b)
    assert not np.array_equal(got, np.full(SIZE, 0.25, np.float32))
    half = SIZE // 2
    for k in range(rounds):
        for shard in (0, 1):
            mine = [(lo, hi, mark, data) for r, s, lo, hi, mark, data in log
                    if (r, s) == (k, shard)]
            # every piece exactly once, in order
            assert [(lo, hi) for lo, hi, _m, _d in mine] == [
                (0, 600), (600, 1200), (1200, 1800), (1800, 2400),
                (2400, 2500)]
            # none above the mark, and each whole on the host when it went
            whole = finals[k][shard * half * 4:(shard + 1) * half * 4]
            for lo, hi, mark, data in mine:
                assert hi * 4 <= mark
                assert data == whole[lo * 4:hi * 4]
            marks = [mark for _lo, _hi, mark, _d in mine]
            if case == "identity":
                # a piece a third, and the rest at the whole
                assert marks == [THIRDS[0], THIRDS[1]] + [THIRDS[2]] * 3
            else:
                assert marks == [THIRDS[2]] * 5
    # the transport was asked to say how far only where the slice is its
    # buffer
    assert (wire.asked > 0) == (case == "identity")


def test_a_read_aborted_mid_shard_sends_the_shard_up_again_whole(
        monkeypatch):
    log = []
    watch_uploads(monkeypatch, log)
    with shm_gang("abort", rule="add", endpoint=Stepped) as (
            servers, pc, wire):
        opt = RuleShell(quad, pc, su=1)
        w = opt.start(jnp.zeros(SIZE) + 0.25)
        lockstep(wire, log, lambda: opt.rounds)
        w, _loss = opt.step(w, TARGET)  # a sound round
        held = wire.may

        def may(server, step):
            # shard 1's first piece is up (a third of it has landed):
            # the gang shuts down under the read
            if server == 1 and step == 1 and held(server, step):
                pc.live.io = False
                return False
            return held(server, step)

        wire.may = may
        w, _loss = opt.step(w, TARGET)
        got = np.array(w)
        mine = [(lo, hi, mark) for r, s, lo, hi, mark, _d in log
                if (r, s) == (1, 1)]
        # its first piece went up under the first third, and then, the
        # read aborted, every piece again once the round was over
        whole = SIZE // 2 * 4
        assert mine == [(0, 600, THIRDS[0]), (0, 600, whole),
                        (600, 1200, whole), (1200, 1800, whole),
                        (1800, 2400, whole), (2400, 2500, whole)]
        # the device holds what the mirror holds, whatever the read left
        np.testing.assert_array_equal(got, opt.w_host)
        second = opt._stream.cut[1]
        landed = THIRDS[0] // 4
        assert not np.array_equal(
            got[second.offset:second.offset + landed],
            np.array(opt.w_host)[second.end - landed:second.end])
        assert opt._stream._worker is None
        opt._stream.close()
    assert not [t for t in threading.enumerate()
                if t.name == "mpit-round-stream"]


def test_an_upload_that_fails_fails_the_round_once_and_the_next_is_sound(
        monkeypatch):
    real = sync._paste
    with shm_gang("up_fail", rule="add", endpoint=Stepped) as (
            servers, pc, wire):
        opt = RuleShell(quad, pc, su=1)
        w0 = opt.start(jnp.zeros(SIZE) + 0.25)
        first = opt._stream.cut[0]

        def broken(whole, piece, start):
            if int(start) >= first.offset + 600:  # shard 0's second piece
                raise OSError("the h2d broke")
            return real(whole, piece, start)

        monkeypatch.setattr(sync, "_paste", broken)
        with pytest.raises(RuntimeError, match="copying thread failed") as err:
            opt.step(w0, TARGET)
        assert isinstance(err.value.__cause__, OSError)
        assert pc.sched.errors == []  # raised once, and not by the client
        assert opt._stream._worker is None
        # the exchange itself was sound: both servers took their GRADs
        assert [s.grads_applied for s in servers] == [1, 1]
        monkeypatch.setattr(sync, "_paste", real)
        w, _loss = opt.step(w0, TARGET)
        # the same gradient, added by the servers a second time
        grad = np.full(SIZE, 0.25, np.float32) - np.asarray(TARGET)
        np.testing.assert_array_equal(
            np.array(w), (np.float32(0.25) + grad) + grad)
        opt.stop()
    assert not [t for t in threading.enumerate()
                if t.name == "mpit-round-stream"]


def test_obs_off_the_upload_reads_no_clock_while_it_follows_the_landing(
        monkeypatch):
    log = []
    watch_uploads(monkeypatch, log)
    real = time.monotonic

    def guarded():
        if threading.current_thread().name == "mpit-round-stream":
            raise AssertionError("the stream's thread read the clock")
        return real()

    with shm_gang("up_noclock", endpoint=Stepped) as (servers, pc, wire):
        opt = RuleShell(quad, pc, su=1)
        w = opt.start(jnp.zeros(SIZE) + 0.25)
        lockstep(wire, log, lambda: opt.rounds)
        monkeypatch.setattr(time, "monotonic", guarded)
        for _ in range(2):
            w, _loss = opt.step(w, TARGET)
        monkeypatch.setattr(time, "monotonic", real)
        opt.stop()
    # the early path ran: pieces went up under a third of their shard
    assert sum(1 for *_x, mark, _d in log if mark == THIRDS[0]) == 4
