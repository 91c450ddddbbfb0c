"""One sync round of a push-and-pull optimizer, and its span tree.

:class:`RuleShell` and :class:`Downpour` sync the same way: the payload
(a gradient or a delta) goes device → host mirror → servers, fresh
parameters come servers → host mirror → device.  :func:`push_pull` is
that round, recorded as one ``round`` span whose phases tile it
(docs/OBSERVABILITY.md, *The round's span tree*; while recording the
span also carries what the client's thread did on the wire during
``exchange``: ``wire_tx_copy_ms``, ``wire_rx_copy_ms``, ``wire_poll_ms``
and ``sched_sleep_ms``, and what is left of the phase is the
interpreter's, with its back-off sleeps by what the pending ops waited
for, ``sleep_<reason>_ms``; and ``direct_bytes``, the payload bytes the
push read from the pieces and not from the mirror.  What the stream's
thread did is a ``copy`` span a piece, :data:`STAGE_PHASES`):

``wait_backward`` → ``d2h`` → ``stage`` → ``exchange`` → ``h2d`` →
``telemetry``

**The round moves in pieces, and shard by shard** where the client says
how the vector is cut (``stream_shards``, an optional extension of
``ParamClientAPI`` that a :class:`~mpit_tpu.ps.client.ParamClient` not
under shardctl has).
Three resources take part in a round and none needs the others' turn:
the chip's DMA engine (d2h, h2d), the client's thread (its copies into
and out of the transport) and the servers.  So the payload leaves the
device in pieces of :data:`PIECE_BYTES`, in the client's shard order,
a few in flight at a time, and the stream's thread takes each as it
lands.  Where the client says a shard's GRAD payload is the slice itself
and its transport can send from anywhere (``stream_pieces``: identity
codec, unframed, unchunked, the shm wire), the piece is *handed to the
send where the DMA left it*: the client's thread copies it from there
into the server's ring and ``grad_host`` is not written at all (the
``round`` span's ``direct_bytes``; a mirror nobody else reads cost the
thread that paces the push most of its work, PERF.md section 6, PR 45);
the handle keeps the piece alive until its bytes are in the ring, and the
stream cuts no further piece while :data:`HELD_BYTES` are handed over and
not yet there.  Every other shard's piece is copied into its slice of
``grad_host`` as before.  Shard ``s``'s GRAD op begins once
the first piece of shard ``s`` is on the host and sends no byte beyond
those that are (the client asks the *gate*, :meth:`ShardStream.staged`,
how many bytes of the shard are staged, before it touches the slice, and
a followed shard's *feed*, :meth:`ShardStream.feed`, at every poll of
its send: a send made of pieces, ``comm/transport.py``), so a push ends
about a piece
after its shard's staging does and not a whole push later; where the
payload is not the slice itself (a codec, the framed or the chunked
wire) or the transport cannot hold such a send, the client waits for
the whole shard as it did; and as server ``s``'s
PARAM lands the client tells the *sink*, :meth:`ShardStream.landed`,
how many bytes of that slice of ``w_host`` are whole from its front, and
the same thread sends every piece below that mark back up, into one
donated device buffer, while the rest of the shard and the other
servers' PARAMs are still landing.  Where the receive lands in the slice
itself and the transport says how far (identity codec, unframed,
unchunked, the shm wire: ``ParamClient._lands``) the mark moves with
the landing, so a shard's upload ends about a piece after its pull does
and not a whole upload later (PERF.md section 6, PR 49); everywhere
else (a codec decodes into the slice, the framed or the chunked wire, a
transport that cannot say) the sink hears of the shard once, whole, and
its pieces go up then, through the same code.  No piece goes up before
its last byte is below the mark, and a mark that falls (the message
that had begun to land was abandoned) or a PARAM op that is aborted
sends the shard up again from its front once it is whole or the round
ends.  Every server
sees the frames it saw, in the order it saw them.  No whole-vector host
array is made on the way up, and the pieces land in memory the
allocator hands out again (a host array of the whole vector is fresh
pages every round, which cost more than the copy: most of what the
pieces gain, they gain without any overlap).  So there is one round,
not two.  A client that gives no cut (the tests' simulators, shardctl,
the device plane's front) is one shard, the whole vector, with no hook
on it: its payload goes down in the same pieces, the three calls run
once the vector is whole in the mirror (nobody gates them), and the
shell sinks the shard itself after ``wait``.  With one shard nothing
moves beside anything; the pieces are what is left of the gain.

With obs off every span site is a call on ``NULL_SPAN``: no fence is
taken and no telemetry is computed, and the only clock reads are the
two of a plain timer around the exchange, which keeps
``sync_seconds`` the same quantity at the same boundary whether obs is
on (the ``exchange`` phase of the span) or off.  While recording, two
fences split what would otherwise hide inside a host copy (the first
d2h waits for the backward *and* copies; the transfers of the h2d
complete after the calls return), and the update norm is reduced on the
device, off the round's critical path, and read back as one scalar
under ``telemetry``; the model's own statistics (``stats``: a
sparse-expert block's routing imbalance, an auxiliary output of the
step) are read there too, and never with obs off.

The phases name what the worker *waited for with nothing else going
on*: ``d2h`` until the first piece is on the host,
``stage`` nothing where the client gates its own ops (and from there
until the vector is whole in the mirror where it does not),
``exchange`` from the first ``async_*`` call to the return of ``wait``
(the staging of every shard but its first piece, and the earlier
shards' h2d, run inside it),
``h2d`` from there until the parameters are whole on the device.

EASGD's round has the same parts in another order (pull, then push) and
marks them itself (:mod:`mpit_tpu.optim.easgd`).
"""

from __future__ import annotations

import queue
import threading
import time
import weakref
from collections import deque
from functools import partial
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from mpit_tpu.obs import get_registry

#: A piece of the vector on its way down or up.  Small enough that the
#: host arrays the d2h lands in come back from the allocator's free
#: lists: from 32 MB on glibc maps fresh pages for every one.  The d2h
#: of a 598 MB vector into the mirror takes 0.21-0.22 s whole (and a
#: 0.032 s stage), 0.30-0.45 s in pieces of 32-64 MB and 0.094 s in
#: pieces of 8 MB (PERF.md section 6, PR 27).  Large enough that a
#: 2.5 GB vector is a few hundred of them.
PIECE_BYTES = 8 << 20
#: Pieces cut from the payload and on their way to the host at once:
#: what the device holds beside the payload, and enough that the DMA
#: engine never waits for the host (2 is slower, 8 no faster).
IN_FLIGHT = 4
#: Bytes handed to the client's sends as pieces and not yet in a server's
#: ring at which the stream cuts no further piece: what the pieces may
#: hold of the host's memory beside the :data:`IN_FLIGHT` that are
#: landing, so that a slow client does not find a whole 2.5 GB vector
#: staged ahead of it.  Eight rings' worth (``comm/shm.py``, 64 MB), and
#: not one: the client places up to a ring's worth a pass and says how far
#: it is only between passes, so at one ring's worth the DMA and the
#: client's copy took turns and the push gained nothing (OLMoE on a v5e:
#: 7.0-7.3k tokens/s at 16 and 64 MB as at the parent, 7.9k at 256 MB,
#: 8.0-8.2k at 512 MB and with no bound: PERF.md section 6, PR 45).
HELD_BYTES = 512 << 20


@jax.jit
def shipped_norm(x: jnp.ndarray) -> jnp.ndarray:
    """The L2 norm of the shipped update, on the device."""
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


@partial(jax.jit, static_argnames="size")
def _cut(x: jnp.ndarray, start: int, *, size: int) -> jnp.ndarray:
    return jax.lax.dynamic_slice(x, (start,), (size,))


@partial(jax.jit, donate_argnums=0)
def _paste(whole: jnp.ndarray, piece: jnp.ndarray, start: int) -> jnp.ndarray:
    return jax.lax.dynamic_update_slice(whole, piece, (start,))


def _exchange(opt: Any, span: Any) -> None:
    """The round at the ``ParamClientAPI`` boundary: the three calls,
    once each, and with obs off the plain timer around them."""
    span.mark("exchange")
    plain = not opt._spans.enabled  # obs off: a plain timer at this boundary
    t0 = time.monotonic() if plain else 0.0
    meter = opt._wire_meter
    meter.start()
    opt.pc.async_send_grad()
    opt.pc.async_recv_param()
    opt.pc.wait()
    if plain:
        opt.sync_seconds += time.monotonic() - t0
    # what the client's thread did on the wire during the phase
    meter.note(span, stretch=False)
    span.mark("h2d")


#: The phases of a piece's ``d2h`` copy span (category ``copy``, one a
#: piece while recording, on the stream's thread; they tile it, and the
#: spans tile the thread's time from before the first cut until the
#: payload is whole on the host): waiting for the piece's DMA
#: (``np.asarray`` returning), handing it to its send or copying it into
#: the mirror (a piece handed over is not copied: about 0 where every
#: shard is followed), standing at :data:`HELD_BYTES` until the client
#: had placed more, and the thread's own work between them: freeing the
#: piece, cutting the next (before the first piece, the first
#: :data:`IN_FLIGHT` cuts).  The span's args: ``pass`` ``d2h``,
#: ``round``, ``shard``, ``lo`` (the piece's first element), ``bytes``,
#: ``streams`` (passes over the host's memory: 1, the DMA's landing; 3
#: where the piece was copied into the mirror as well), ``in_flight``
#: (cuts outstanding when this one was popped, itself included) and
#: ``issued_ms`` (how long before the span's ``wait`` its cut was
#: dispatched).  On the way up a piece's ``h2d`` span is the dispatch of
#: its ``device_put`` and paste (``pass`` ``h2d``; it begins when the
#: thread has heard that the piece is whole on the host, so the spans of
#: a shard that goes up as it lands lie apart), and one ``h2d_shard``
#: span a shard closes them: from its first piece to its last dispatch
#: (``ready`` 0: a transfer completes after the call returns and nothing
#: waits for it) or, the round's last, to the instant the shell found
#: the parameters whole on the device (``ready`` 1: the fence it takes
#: while recording anyway, the end of the ``h2d`` phase).
STAGE_PHASES = ("wait", "hand", "held", "issue")


def _no_clock() -> float:
    return 0.0


class _Whole(NamedTuple):
    """The cut of a client that gives none: one shard, all of it."""

    offset: int
    end: int


class _Copies:
    """The copies of one round, run off the client's thread (on the
    stream's): every piece of the payload to the host, a shard's pieces
    in order, and there either into the hands of the shard's send
    (``handed[shard]``, a followed shard: :meth:`feed` gives them to the
    client's thread) or to its place in ``grad_host``; ``staged[shard]``
    says how many of the shard's bytes are whole on the host either way
    and moves only after a piece's hand-over or copy has returned.  Then
    each shard of ``w_host`` back to the device as it is sunk.  The
    waits for the DMA engine and the host copies release the interpreter
    lock, so the client's thread keeps pumping beside them."""

    def __init__(self, stream: "ShardStream", payload: jnp.ndarray,
                 consume: bool, rec: Any, k: int, rank: object):
        self.stream = stream
        self.payload = payload
        self.consume = consume
        # Where the staging went: a copy span a piece (no span, no clock
        # read and nothing made for one unless recording).  It paces the
        # push.
        self.rec = rec if rec.enabled else None
        self.now = time.monotonic if rec.enabled else _no_clock
        self.k, self.rank = k, rank  # the round, the worker
        #: the last sunk shard's ``h2d_shard`` span, not yet recorded:
        #: ``(rank, begin, end of dispatch, args)``
        self.closing: Optional[tuple] = None
        self.first_piece = threading.Event()
        self.staged = [0] * len(stream.cut)  # bytes whole on the host
        # The followed shards' pieces: landed and not yet taken by the
        # client, and the bytes the client says are in the ring (the
        # client thread's to write).
        self.handed: List[deque] = [deque() for _ in stream.cut]
        self.wrote = [0] * len(stream.cut)
        self.direct_bytes = 0  # handed over, all shards
        self.room = threading.Event()  # ``wrote`` moved, or the round quit
        self.whole = threading.Event()  # all of them, or the thread ended
        # The way up.  ``marks[shard]``: the bytes of the shard, from its
        # front, that the sink last handed to the stream's thread as
        # whole in ``w_host`` (the client thread's to write); ``landed``
        # carries each hand-over as one integer, mark and shard
        # (:meth:`sink`), and a None at the round's end.
        self.marks = [0] * len(stream.cut)
        self.landed: "queue.SimpleQueue[Optional[int]]" = queue.SimpleQueue()
        self.w: Optional[jnp.ndarray] = None
        self.error: Optional[BaseException] = None
        self.failure: Optional[RuntimeError] = None  # ``error``, as raised
        self.quit = False  # the round failed: stop copying
        self.done = threading.Event()

    def run(self) -> None:
        try:
            self._stage()
            self._upload()
        except BaseException as exc:  # noqa: BLE001 — ``check`` raises it
            self.error = exc
        finally:
            self.payload = None
            self.first_piece.set()
            self.whole.set()  # after ``error``: a waiter sees both
            self.done.set()

    def check(self) -> None:
        """Raise what stopped the copies, if anything did: the same
        exception to everyone who asks (the client raises it once)."""
        if self.error is not None:
            if self.failure is None:
                self.failure = RuntimeError(
                    "the round's copying thread failed")
                self.failure.__cause__ = self.error
            raise self.failure

    def stop(self) -> None:
        """No copy after a failed exchange, and no wait for room."""
        self.quit = True
        self.room.set()

    def sink(self, shard: int, upto: int) -> None:
        """On the client's thread: ``upto`` bytes of ``shard`` are whole
        in ``w_host``, from the shard's front.  The stream's thread hears
        of it when the mark has passed another piece's end; a mark no
        higher than the last one handed over (it fell, or the slice was
        read again) voids what was said of the shard before, and its
        pieces go up again from the first.  Nothing is made for the
        hand-over but the integer."""
        mark = self.marks[shard]
        if 0 < mark >= upto:
            self.landed.put(shard)  # the mark 0: the shard starts over
            mark = self.marks[shard] = 0
        step = self.stream.piece_bytes
        ahead = min((mark // step + 1) * step, self.stream.nbytes[shard])
        if mark < ahead <= upto:
            self.marks[shard] = upto
            self.landed.put(upto * len(self.marks) + shard)

    def feed(self, shard: int, written: int) -> List[np.ndarray]:
        """On the client's thread, at every poll of followed shard
        ``shard``'s send: ``written`` of its bytes are in the ring; the
        pieces that landed since the last call, in order.  With none to
        give and the shard short, raises what stopped the copies, if
        anything did: the rest will never come."""
        if written > self.wrote[shard]:
            self.wrote[shard] = written
            self.room.set()
        landed = self.handed[shard]
        pieces = []
        while landed:
            pieces.append(landed.popleft())
        if not pieces and self.staged[shard] < self.stream.nbytes[shard]:
            self.check()
        return pieces

    def _no_room(self) -> bool:
        return (self.direct_bytes - sum(self.wrote) > HELD_BYTES
                and not self.quit)

    def _wait_for_room(self) -> None:
        """Stand while more than :data:`HELD_BYTES` are handed over and
        not yet in a ring (and the round has not quit)."""
        while self._no_room():
            self.room.clear()
            if self._no_room():  # still, now that a ``set`` cannot be lost
                self.room.wait()

    def _stage(self) -> None:
        stream, payload = self.stream, self.payload
        todo = iter(zip(stream.pieces, stream.starts))
        flight: deque = deque()
        # On the CPU backend the host array is a view of the device
        # piece's buffer: a piece that is handed over goes when the send
        # lets go of the view, not here.
        aliased = jax.default_backend() == "cpu"

        now, rec = self.now, self.rec

        def issue() -> None:
            piece, start = next(todo, (None, None))
            if piece is not None:
                _shard, lo, hi = piece
                part = _cut(payload, start, size=hi - lo)
                part.copy_to_host_async()
                flight.append((piece, part, now()))

        t_end = now()  # the first span begins before the first cuts
        for _ in range(IN_FLIGHT):
            issue()
        while flight and not self.quit:
            t_begin, in_flight = t_end, len(flight)
            (shard, lo, hi), part, t_issued = flight.popleft()
            t_pop = now()
            host = np.asarray(part)
            self.first_piece.set()
            t_host = now()
            nbytes = host.nbytes
            if stream.follow[shard]:
                self.direct_bytes += nbytes
                self.handed[shard].append(host)
            else:
                np.copyto(stream.grad_host[lo:hi], host)
            self.staged[shard] = (
                hi - stream.cut[shard].offset) * stream.grad_host.itemsize
            t_staged = now()
            if not (aliased and stream.follow[shard]):
                del host  # on the CPU backend a view of the buffer freed next
                part.delete()
            t_freed = now()
            self._wait_for_room()
            t_room = now()
            issue()
            t_end = now()
            if rec is not None:
                rec.copy(
                    "d2h", self.rank, "stream", t_begin, t_end,
                    (("issue", t_begin), ("wait", t_pop), ("hand", t_host),
                     ("issue", t_staged), ("held", t_freed),
                     ("issue", t_room)),
                    round=self.k, shard=shard, lo=lo,
                    bytes=nbytes, streams=1 if stream.follow[shard] else 3,
                    in_flight=in_flight,
                    issued_ms=(t_pop - t_issued) * 1e3, **{"pass": "d2h"})
        if not flight:  # every cut has run
            self.whole.set()
            if self.consume:
                payload.delete()

    def _upload(self) -> None:
        """Every piece of ``w_host`` to the device, each as soon as the
        sink's mark of its shard (:meth:`sink`) has reached its end and
        never before: a shard's pieces in order, the shards in the order
        their marks move.  A mark that fell sends its shard's pieces up
        again from the first."""
        stream = self.stream
        # On the CPU backend a put may alias host memory, and the
        # mirror is overwritten by the next round's PARAM.
        private = jax.default_backend() == "cpu"
        now, rec = self.now, self.rec
        nshards = len(stream.cut)
        itemsize = stream.w_host.itemsize
        todo = [0] * nshards  # by shard: the next piece of ``parts`` to go
        heard = [0] * nshards  # ... the mark last heard
        begun = [0.0] * nshards  # ... when its first piece's dispatch began
        while True:
            item = self.landed.get()
            if item is None or self.quit:
                return
            upto, shard = divmod(item, nshards)
            if upto < heard[shard]:
                todo[shard] = 0
            heard[shard] = upto
            parts = stream.parts[shard]
            top = stream.cut[shard].offset + upto // itemsize
            t0 = now()
            while todo[shard] < len(parts) and parts[todo[shard]][1] <= top:
                lo, hi = parts[todo[shard]]
                if self.closing is not None:
                    # not the round's last dispatch: its own alone
                    _close_upload(rec, self.closing, None)
                    self.closing = None
                if self.w is None:
                    self.w = jnp.zeros(stream.w_host.shape,
                                       stream.w_host.dtype)
                    t0 = now()
                if todo[shard] == 0:
                    begun[shard] = t0
                view = stream.w_host[lo:hi]
                part = jax.device_put(view.copy() if private else view)
                self.w = _paste(self.w, part, lo)
                todo[shard] += 1
                if rec is not None:
                    t1 = now()
                    rec.copy("h2d", self.rank, "stream", t0, t1,
                             round=self.k, shard=shard, lo=lo,
                             bytes=view.nbytes, streams=1, **{"pass": "h2d"})
                    t0 = t1
                    if todo[shard] == len(parts):
                        self.closing = (self.rank, begun[shard], t1, {
                            "round": self.k, "shard": shard,
                            "bytes": stream.nbytes[shard],
                            "pieces": len(parts)})


def _close_upload(rec: Any, closing: tuple,
                  ready_at: Optional[float]) -> None:
    """Record a shard's ``h2d_shard`` span (``closing``:
    :attr:`_Copies.closing`): to ``ready_at``, the instant its pieces
    were seen whole on the device, or (None) to the return of its last
    dispatch."""
    rank, t0, t1, args = closing
    # a track a shard: two shards that go up as they land lie across
    # each other
    rec.copy("h2d_shard", rank, "stream", t0,
             t1 if ready_at is None else max(t1, ready_at),
             track=f":shard{args['shard']}",
             ready=int(ready_at is not None), **args)


def _serve(rounds: "queue.SimpleQueue[Optional[_Copies]]") -> None:
    """The stream's thread: each round's copies in turn, until a None.
    It holds the queue and, for the length of a round, that round's
    copies; never the stream, which can therefore be collected."""
    while (copies := rounds.get()) is not None:
        copies.run()
        del copies  # or the last round's would hold the stream here


class ShardStream:
    """A shell's side of the round: the cut it moves by, the gate, the
    sink and the feed it offers the client once (:func:`attach`), and the one
    thread that makes every round's copies, from the first round until
    :meth:`close` (a thread a round would leave each round's host
    pieces behind in an allocator arena of its own).  Between rounds the
    gate is open (every shard whole) and the sink does nothing, so ops
    issued outside :func:`push_pull` run as before.  The thread ends
    with the stream:
    at :meth:`close`, or when the last owner (the shell, and a client
    that took the gate and the sink) lets go of it unclosed."""

    def __init__(self, grad_host: np.ndarray, w_host: np.ndarray):
        self.grad_host, self.w_host = grad_host, w_host
        self.cut: List[Any] = []
        self.follow: List[bool] = []  # by shard: its send reads the pieces
        self.nbytes: List[int] = []  # by shard
        self.parts: List[List[Tuple[int, int]]] = []  # (lo, hi), by shard
        self.piece_bytes = PIECE_BYTES  # of every piece but a shard's last
        self.pieces: List[Tuple[int, int, int]] = []  # (shard, lo, hi), all
        #: every piece's ``lo`` on the device: a cut dispatched with a
        #: Python integer sends it up first, and the dispatch is serial
        #: work of the thread that paces the push (0.35 against 0.20 ms a
        #: piece on a v5e: PERF.md section 6, PR 40)
        self.starts: List[jnp.ndarray] = []
        self.gated = False  # the client took the gate and asks it itself
        self._index: Dict[int, int] = {}  # a shard's offset -> its number
        self._worker: Optional[_Copies] = None  # this round's, in a round
        #: the last round's last ``h2d_shard`` span, until it is recorded
        self.closing: Optional[tuple] = None
        self._rounds: "queue.SimpleQueue[Optional[_Copies]]" = (
            queue.SimpleQueue())
        self._thread: Optional[threading.Thread] = None
        self._serve = partial(_serve, self._rounds)  # the thread's target
        weakref.finalize(self, self._rounds.put, None)

    def bind(self, cut: List[Any],
             follow: Optional[List[bool]] = None) -> None:
        """Take the cut; a piece never crosses a shard.  ``follow``:
        which shards' sends read the pieces where they land (none, where
        the client does not say)."""
        itemsize = self.grad_host.dtype.itemsize
        step = max(PIECE_BYTES // itemsize, 1)
        self.cut = list(cut)
        self.piece_bytes = step * itemsize
        self.follow = list(follow) if follow else [False] * len(cut)
        self.nbytes = [(shard.end - shard.offset) * itemsize for shard in cut]
        self._index = {shard.offset: i for i, shard in enumerate(cut)}
        self.parts = [
            [(lo, min(lo + step, shard.end))
             for lo in range(shard.offset, shard.end, step)]
            for shard in cut]
        self.pieces = [(i, lo, hi) for i, parts in enumerate(self.parts)
                       for lo, hi in parts]
        self.starts = [jnp.asarray(lo, jnp.int32)
                       for _shard, lo, _hi in self.pieces]

    # -- the hooks (on the client's thread; neither blocks) ------------------

    def staged(self, shard: Any) -> int:
        """The bytes of ``shard`` that are whole on the host, from its
        front: in its slice of ``grad_host``, or in the pieces of
        :meth:`feed` where its send reads those; all of them, in the
        slice, between rounds.  Short of all, raises what stopped the
        copies, if anything did: the rest will never come, and nothing
        half staged is taken for whole."""
        index = self._index[shard.offset]
        worker = self._worker
        if worker is None:
            return self.nbytes[index]
        staged = worker.staged[index]
        if staged < self.nbytes[index]:
            worker.check()
        return staged

    def feed(self, shard: Any) -> Optional[Any]:
        """This round's feed of ``shard`` (``feed(written) -> pieces``,
        :meth:`_Copies.feed`), where its send reads the pieces; None
        where the shard is staged into ``grad_host``, and between
        rounds, when the slice is whole and is the payload."""
        index = self._index[shard.offset]
        worker = self._worker
        if worker is None or not self.follow[index]:
            return None
        return partial(worker.feed, index)

    def landed(self, shard: Any, nbytes: int) -> None:
        """``nbytes`` of ``shard``'s slice of ``w_host`` are whole, from
        its front: all of them once its PARAM op is done, fewer while
        the op's receive is landing in the slice and says how far
        (:meth:`_Copies.sink`).  Between rounds, nothing."""
        worker = self._worker
        if worker is not None:
            worker.sink(self._index[shard.offset], nbytes)

    # -- the thread, and one round -------------------------------------------

    def close(self) -> None:
        """End the thread (a shell's ``stop``); a later round starts
        another."""
        if self._thread is not None:
            self._rounds.put(None)
            self._thread.join()
            self._thread = None

    def round(self, opt: Any, span: Any, payload: jnp.ndarray,
              consume: bool) -> jnp.ndarray:
        span.mark("d2h")
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._serve, name="mpit-round-stream", daemon=True)
            self._thread.start()
        worker = self._worker = _Copies(
            self, payload, consume, opt._spans, opt.rounds,
            getattr(opt.pc, "rank", None))
        self._rounds.put(worker)
        try:
            worker.first_piece.wait()
            span.mark("stage")
            if not self.gated:
                worker.whole.wait()  # nothing half staged leaves ungated
            worker.check()
            _exchange(opt, span)
            # What the client did not sink whole goes up now, from its
            # shard's front: it took no hooks, or a read was aborted (at
            # shutdown), and what that read had said of its shard is void.
            for shard, nbytes in enumerate(self.nbytes):
                if worker.marks[shard] < nbytes:
                    worker.sink(shard, 0)
                    worker.sink(shard, nbytes)
        except BaseException:
            worker.stop()  # no copy after a failed exchange
            raise
        finally:
            worker.landed.put(None)
            worker.done.wait()
            self._worker = None
        worker.check()
        span.note(direct_bytes=worker.direct_bytes)  # (the null span's, off)
        self.closing = worker.closing
        return worker.w

    def uploaded(self, opt: Any, ready_at: float) -> None:
        """While recording: the shell saw the round's parameters whole
        on the device at ``ready_at``, which ends the last sunk shard's
        ``h2d_shard`` span."""
        if self.closing is not None:
            _close_upload(opt._spans, self.closing, ready_at)
            self.closing = None


def attach(opt: Any) -> None:
    """Called by a shell's ``start`` once its client has started: bind
    the round's stream, ``opt._stream``, to the client's cut and give
    the client the gate and the sink, if it takes them
    (``stream_shards``), and then the feed (``stream_pieces``: the client
    says which shards' sends read the pieces); if not, to one shard with
    no hook on it."""
    stream = opt._stream = ShardStream(opt.grad_host, opt.w_host)
    # Tested by name: ``isinstance`` on a protocol looks past
    # ``__getattr__``, so it would not see the extension behind a front
    # that forwards to its client (the benchmark's timing proxy).
    install = getattr(opt.pc, "stream_shards", None)
    cut = install(stream.staged, stream.landed) if install else None
    stream.gated = bool(cut)
    follow = getattr(opt.pc, "stream_pieces", None) if cut else None
    stream.bind(cut or [_Whole(0, opt.grad_host.size)],
                follow(stream.feed) if follow else None)
    # What the client's one thread does on the wire during ``exchange``
    # (obs/spans.py ``WireMeter``; the null one while obs is off).
    opt._wire_meter = opt._spans.wire_meter(
        getattr(opt.pc, "transport", None), getattr(opt.pc, "sched", None))


def note_stats(opt: Any, span: Any, stats: Dict[str, jnp.ndarray]) -> None:
    """The model's own statistics of one step (device arrays with one
    entry a layer), fetched and recorded: each noted on ``span`` by its
    name, set on the gauge ``mpit_<name>`` by layer and kept as
    ``opt.stats_last`` for the rank result.  For callers that are
    recording only: the fetch waits for the step."""
    opt.stats_last = {
        name: [float(x) for x in np.ravel(np.asarray(value))]
        for name, value in stats.items()}
    span.note(**opt.stats_last)
    gauge = get_registry().gauge
    for name, per_layer in opt.stats_last.items():
        for layer, value in enumerate(per_layer):
            gauge(f"mpit_{name}", layer=layer).set(value)


def push_pull(opt: Any, payload: jnp.ndarray,
              loss: Optional[jnp.ndarray] = None, *, consume: bool = False,
              stats: Optional[Dict[str, jnp.ndarray]] = None,
              ) -> jnp.ndarray:
    """Ship ``payload`` as the gradient and fetch fresh parameters.
    ``opt`` is the shell: its ``pc``, ``grad_host``, ``w_host``,
    ``rounds``, ``sync_seconds``, its recorder ``_spans``, its gauges
    ``_m_unorm`` and ``_m_loss`` and the stream :func:`attach` bound,
    ``_stream``.  ``consume``: the payload is the
    caller's to give up (a gradient nobody else holds); its device
    buffer is freed once it is staged on the host, so the round's h2d
    does not find a dead whole vector still resident.  ``stats``: the
    model's own statistics of this round's step, device arrays with one
    entry a layer, fetched only while recording: each is noted on the
    round span by its name, set on the gauge ``mpit_<name>`` by layer
    and kept as ``opt.stats_last`` for the rank result."""
    rec = opt._spans
    span = rec.round(opt.rounds, "wait_backward",
                     rank=getattr(opt.pc, "rank", None))
    unorm = None
    if rec.enabled:
        jax.block_until_ready(payload)
        unorm = shipped_norm(payload)  # dispatched; read under telemetry
    stream = opt._stream
    w = stream.round(opt, span, payload, consume)
    if rec.enabled:
        jax.block_until_ready(w)
        span.mark("telemetry")
        stream.uploaded(opt, span.marks[-1][1])
        opt._m_unorm.set(float(unorm))
        if loss is not None:
            opt._m_loss.set(float(loss))
        if stats:
            note_stats(opt, span, stats)
    span.end()
    opt.sync_seconds += span.phase_seconds("exchange")  # 0.0 if off
    opt.rounds += 1
    return w
