"""ShmTransport — the native C++ shared-memory transport, Python side.

Implements the :class:`mpit_tpu.comm.transport.Transport` contract over
libmt_transport.so (mpit_tpu/comm/native/transport.cpp) via the generated
ctypes bindings.  This is the host transport for same-host multi-process
role topologies — the deployment shape the reference exercises with
``mpirun -np N`` on one machine (reference README.md:28-31,57-61), with
the asynchronous one-sided PS semantics XLA collectives can't express
(SURVEY.md section 7 "hard parts").

The wire: every rank owns one shm segment, its inbox, and in it one
ring per sending rank (``ring_bytes`` each; tmpfs backs only the pages a
pair has touched).  A ring has one producer and one consumer and no lock:
the sender alone writes its ``head``, the owner alone its ``tail``, the
sender copies a chunk in and then publishes ``head``, the owner copies it
out and then publishes ``tail``, chunk by chunk, so the two copy at the
same time, and several senders into one inbox never wait for each other.
A sender killed inside a chunk has published nothing; what it left
half-sent is dropped when its next incarnation's first message opens.
``ring_counters`` says how often a sender found its ring full and how
often the two sides were copying at once.

Who copies: one thread a process makes all the progress (reads and
writes the ring indices, matches messages, holds the handles), from
inside ``test``, ``iprobe`` and ``isend``.  Where the host has cores to
spare (:func:`copy_helpers`: worked out from the cores this process may
run on and the ranks that share them, no variable and no flag) the
endpoint starts that many helper threads in the native library, and a
ring copy of ``SPLIT_MIN_BYTES`` or more is cut into parts on cache
lines that the calling thread and the helpers copy at once, fork and
join inside the one ``memcpy``'s place.  A helper is handed a
destination, a source and a length and says when it is done: it reads
no ring index, matches no message, holds no handle and touches no
Python.  ``head`` and ``tail`` are published after the join, where they
always were, so a chunk is visible to its peer only whole and every
recovery rule holds as it stood: a sender killed inside a chunk (inside
a part) has published nothing, ``filled`` moves in order and by whole
chunks, ``written`` says what it said, a cancelled receive's message
stays whole.  Acks, headers, heartbeats and every message under the
threshold are one ``memcpy`` on the caller's thread at the latency they
had, and with no helper every copy is.  ``ring_counters`` says how many
payload bytes went in and out in parts (``tx_split_bytes``,
``rx_split_bytes``, counted with obs off too), and a ``wire`` span its
message's (``split_bytes`` beside ``bytes``).

Where a message's time went: while the span recorder records (and only
then: the recorder's ``enabled`` at construction is the one switch, there
is no variable and no flag of its own) the native side keeps a record a
message on each end and stamps every chunk with the instant it was
published.  The ``test`` that first sees a transfer of at least 1 MB done
turns its record into one ``wire`` span, ``tx`` or ``rx``, begun and
ended at the native stamps (this file reads no clock), whose args tile
the flight: ``copy_ms`` (inside the ring copies), ``blocked_ms`` (``tx``:
a full ring refused a chunk, until the ring took the next one: the owner's
drain is the slower side) or ``starved_ms`` (``rx``: the message was
partial and the ring empty: the sender is the slower side), and
``away_ms`` (``tx``: the ring had room and the sender's thread was
elsewhere; ``rx``: a chunk lay published and the owner was not copying it
out: asleep in the back-off, in another ring, in Python, off the core).
``copies`` says when the copying was: ``[begin_ns, end_ns, bytes]`` of every
run of chunks this end's thread copied back to back, on the monotonic
clock, in order and apart, at most 64 a message (``copies_merged``: how
often two were made one over the gap between them).  A ``tx``'s are a pass
``ring_in`` over the host's memory, an ``rx``'s ``ring_out``
(``obs/copies.py``).
``wire_totals`` are the endpoint's own sums, acks and headers included.

A send that is not yet whole (the optional capability of
:class:`~mpit_tpu.comm.transport.Transport`, and its one form here): a
send is a list of pieces placed in order, and ``isend`` posts the list of
one.  ``isend_pieces(nbytes, dst, tag)`` posts a send of ``nbytes`` with
no piece yet, ``append(handle, piece)`` adds the next run of its bytes
where it lies (nothing is copied together first), and ``test`` is true
only once the whole length has been placed.  ``written(handle)`` says how
many of the message's bytes are in the ring; the handle keeps every piece
alive until its last byte is, and lets go of it then.  The peer receives
the message it always received (same header, same bytes in the same
order, one message); a later ``isend`` to the same rank waits behind the
unfinished one.  A ``tx`` span then also says ``early_bytes`` (placed
while the pieces were short of the length) and ``unready_ms``: the ring
had room, the thread was here and no appended byte was left.  That wait
is the caller's own staging, so it is a part of ``away_ms``, noted beside
it, and never ``blocked_ms``; the receiver sees it as ``starved_ms``.
``tx_early_bytes`` in ``ring_counters`` is the sum of ``early_bytes`` over
all sends, counted with obs off too.

Zero-copy discipline: sends pass the numpy buffer's raw pointer to C and
the Handle holds the array reference until completion.  A receive posted
with a buffer *before* its message's first chunk is drained lands in that
buffer straight from the ring, chunk by chunk, from whichever call makes
progress (any ``test``, ``iprobe`` or ``isend`` of this endpoint): the
buffer belongs to the transport from ``irecv`` until ``test`` is true or
``cancel`` returns, and the endpoint keeps it alive that long.  Every
other receive is assembled in a buffer of the library's own and copied
out by the ``test`` that takes it: one posted after a probe (``out``
absent, the chunked client, the serving tier), one posted while its
message was already arriving, one behind a message queued on its channel.
A buffer of the wrong size raises the size mismatch and the message stays.
``cancel`` of a receive whose message has begun to land moves what has
landed into an assembly buffer, the rest follows it there, and the next
receive gets the message whole; the cancelled buffer is not written again.
``filled(handle)`` says how far a posted receive's buffer is filled from
its front (the optional capability of
:class:`~mpit_tpu.comm.transport.Transport`, the mirror of ``written``):
the chunks land in order, so every byte below that is the message's for
good and a reader may take it before the message is whole.
``follow(handle, told)`` has the endpoint say so itself, ``told(filled)``
whenever the mark has moved, after *every* call that made progress and
not only the receive's own ``test``: any call drains every ring, and an
op that sends a request or takes an ack makes several in a row, a ring's
worth of another receive's landing each, before its generator yields
(the PARAM of one server stood 37 ms without a poll of its own beside
the other server's ack and request: PERF.md section 6, PR 49).
``rx_path_bytes`` says how many bytes went which way.  Completed native
handles are freed test-once style (like MPI requests); the Python Handle
caches completion so repeated ``test`` stays idempotent.
"""

from __future__ import annotations

import atexit
import functools
import os
from collections import deque
from typing import Any

import numpy as np

from mpit_tpu.comm.transport import Handle, Transport
from mpit_tpu.obs import metrics as _obs
from mpit_tpu.obs import spans as _spans

#: words of a native timing record (transport.cpp ``mt_op_timing``), and
#: of the most copy intervals a record keeps (``mt_op_intervals``)
_TIMING_WORDS = 13
_RUN_WORDS = 3 * 64
#: what :meth:`ShmTransport.waiting` names, by bit of ``mt_waiting``
_WAITING = ((1, "unready"), (2, "blocked"), (4, "unanswered"), (8, "partial"))
#: :meth:`ShmTransport.ring_counters`' names, by ``mt_ring_counts``' index
_RING_COUNTS = ((0, "tx_chunks"), (1, "tx_ring_full"), (2, "rx_chunks"),
                (3, "rx_overlap_chunks"), (4, "tx_early_bytes"),
                (6, "tx_split_bytes"), (7, "rx_split_bytes"))
#: a ring copy of at least this many bytes is cut into parts
#: (:func:`copy_helpers` has the timings), and a helper waits spinning
#: this long after a part before it sleeps
SPLIT_MIN_BYTES = 1 << 20
_HELPER_SPIN_NS = 1_000_000


def copy_helpers(cores: int, ranks: int) -> int:
    """How many helper threads an endpoint starts for its ring copies, on
    a host where this process may run on ``cores`` cores
    (``os.sched_getaffinity``) that ``ranks`` ranks share (the
    endpoint's ``nranks``): of a rank's share of the cores one is its
    own thread's and one its other threads' (a worker's stream thread,
    a server's sweep), and half of what is left, two at most, copy
    beside it; 0 where nothing is left, and the copies are one
    ``memcpy`` on the caller's thread as they always were.

    The timings that chose it (PERF.md section 5, "After PR 66": my chip
    runs, PR 66, the chip's host, 13 cores of a KVM guest).  The bare
    wire, ``benchmarks/ptest.py`` over shm with one client and two
    servers, MB/s both ways with 0 / 1 / 2 / 3 helpers an endpoint:
    727-729 / 1,329-1,347 / 1,679-1,739 / 1,991-2,038 at 600 MB and
    773-790 / 1,458-1,468 / 1,881-1,897 / 2,196-2,228 at 1,650 MB: one
    helper gives 1.85 times a lone copy, the second 0.55 more, the
    third 0.4 more.  Under a round of ``c111m-ps1w-su1`` (13 cores, 3
    ranks, the servers' sweeps and the stream's thread beside the
    copies; one run a count) 38,004 / 42,245 / 40,374 / 42,018
    tokens/s: the first helper is the gain and the others are inside
    the cell's spread, so a rank with four cores gets one.  A helper
    that slept between parts (no spinning) gave a pair of endpoints
    11.0-11.7 GB/s of payload where one spinning for a millisecond
    gave 13.3-14.6 and a lone copy 9.6-10.2, hence ``_HELPER_SPIN_NS``;
    ``SPLIT_MIN_BYTES``: see PERF.md, the same section."""
    return max(0, min(2, (cores // ranks - 2) // 2))


@functools.lru_cache(maxsize=1)
def _load_lib():
    from mpit_tpu.comm.native import build
    from mpit_tpu.comm.native._bindings import NativeTransportLib

    return NativeTransportLib(build.ensure_built())


class ShmTransport(Transport):
    def __init__(
        self,
        namespace: str,
        rank: int,
        nranks: int,
        ring_bytes: int = 64 << 20,
    ):
        self.lib = _load_lib()
        self.rank = rank
        self.nranks = nranks
        self.namespace = namespace
        self._ctx = self.lib.mt_init(namespace, rank, nranks, ring_bytes)
        if not self._ctx:
            raise RuntimeError(
                f"mt_init failed for namespace={namespace!r} rank={rank}"
            )
        self._closed = False
        self.lib.mt_copy_helpers(
            self._ctx, copy_helpers(len(os.sched_getaffinity(0)), nranks),
            SPLIT_MIN_BYTES, _HELPER_SPIN_NS)
        # The wire's timing follows the span recorder: off, the native
        # side reads no clock and ``test`` asks for no record.
        self._rec = _spans.get_recorder()
        self._record = np.zeros(_TIMING_WORDS, np.uint64)
        self._runs = None  # a record's copy intervals, while recording
        if self._rec.enabled:
            self.lib.mt_set_timing(self._ctx, 1)
            self._runs = np.zeros(_RUN_WORDS, np.uint64)
        # Per-peer traffic counters (mpit_tpu.obs): rank-indexed lists,
        # null singletons when obs is disabled (no-op on the hot path).
        _reg = _obs.get_registry()
        self._m_tx_msgs = [_reg.counter("mpit_shm_tx_messages_total",
                                        rank=rank, peer=r)
                           for r in range(nranks)]
        self._m_tx_bytes = [_reg.counter("mpit_shm_tx_bytes_total",
                                         rank=rank, peer=r)
                            for r in range(nranks)]
        self._m_rx_msgs = [_reg.counter("mpit_shm_rx_messages_total",
                                        rank=rank, peer=r)
                           for r in range(nranks)]
        self._m_rx_bytes = [_reg.counter("mpit_shm_rx_bytes_total",
                                         rank=rank, peer=r)
                            for r in range(nranks)]
        # Which way the received bytes went and how the rings were used:
        # the native side counts, and with obs on the counters follow it
        # whenever a transfer completes.
        self._m_native = {
            key: _reg.counter(f"mpit_shm_{key}_total", rank=rank)
            for key in self.wire_counts()
        } if _reg.enabled else {}
        self._native_counted = dict.fromkeys(self._m_native, 0)
        # Posted receives, by native handle: their buffers are written from
        # the drain, so they live until the receive is done or cancelled
        # even if the caller lets go of the Handle.
        self._posted: dict = {}
        # Followed receives (:meth:`follow`), by native handle:
        # ``[handle, told, the mark last told]``.
        self._followed: dict = {}
        atexit.register(self.close)

    # -- Transport ----------------------------------------------------------

    def isend(self, data: Any, dst: int, tag: int) -> Handle:
        buf = self._sendable(data)
        nbytes = buf.nbytes if isinstance(buf, np.ndarray) else len(buf)
        native = self.lib.mt_isend(self._ctx, dst, tag, buf, nbytes)
        if self._followed:
            self._tell()
        return self._posted_send(native, dst, tag, nbytes, buf)

    def isend_pieces(self, nbytes: int, dst: int, tag: int) -> Handle:
        """Post a send of ``nbytes`` of which no piece is there yet (see
        :meth:`append`).  It holds its place in front of every later send
        to ``dst`` until its whole length has been appended and placed."""
        native = self.lib.mt_isend_pieces(self._ctx, dst, tag, nbytes)
        handle = self._posted_send(native, dst, tag, nbytes, deque())
        handle.meta["nbytes"] = nbytes
        handle.meta["appended"] = 0
        return handle

    def _posted_send(self, native: int, dst: int, tag: int, nbytes: int,
                     buf: Any) -> Handle:
        if native < 0:
            raise ValueError(f"isend to invalid rank {dst}")
        self._m_tx_msgs[dst].inc()
        self._m_tx_bytes[dst].inc(nbytes)
        return Handle(kind="send", peer=dst, tag=tag, buf=buf, native_id=native)

    def append(self, handle: Handle, piece: np.ndarray) -> None:
        """The next bytes of the pending send ``handle`` (of
        :meth:`isend_pieces`) are ``piece``'s: they are read where they
        lie, so ``piece`` stays unmodified until :meth:`written` has
        passed its end, and the handle keeps it alive that long.  The
        next ``test`` places them.  More bytes than the send has left is
        a ``ValueError`` and appends nothing."""
        if not piece.flags["C_CONTIGUOUS"]:
            raise ValueError("a piece must be C-contiguous (zero-copy rule)")
        end = int(self.lib.mt_send_append(self._ctx, handle.native_id, piece,
                                          piece.nbytes))
        if end == -2:
            raise ValueError(
                f"append of {piece.nbytes}B passes the send's length "
                f"({handle.meta['appended']}B of {handle.meta['nbytes']}B "
                "appended)")
        if end < 0:
            raise RuntimeError(f"append to a send that is not pending: {handle}")
        handle.meta["appended"] = end
        handle.buf.append((end, piece))

    def written(self, handle: Handle) -> int:
        """Bytes of the send ``handle`` that are in the peer's ring.  The
        pieces that end at or before that byte have been read for the
        last time, and the handle lets go of them here."""
        if handle.done:
            return handle.meta["nbytes"]
        done = int(self.lib.mt_send_written(self._ctx, handle.native_id))
        held = handle.buf
        while held and held[0][0] <= done:
            held.popleft()
        return done

    def irecv(self, src: int, tag: int, out: Any | None = None) -> Handle:
        if out is None:
            size = self.lib.mt_probe_size(self._ctx, src, tag)
            if self._followed:
                self._tell()
            if size < 0:
                raise RuntimeError(
                    "irecv without a buffer requires a probed message "
                    "(call iprobe first — the reference does the same, "
                    "init.lua:67-102)"
                )
            out_arr = np.empty(int(size), dtype=np.uint8)
            handle = self._post_recv(src, tag, out_arr)
            handle.meta["as_bytes"] = True
            return handle
        return self._post_recv(src, tag, out)

    def _post_recv(self, src: int, tag: int, out: Any) -> Handle:
        if isinstance(out, np.ndarray):
            if not out.flags["WRITEABLE"]:
                raise ValueError("recv buffer must be writable")
            nbytes = out.nbytes
        else:
            view = memoryview(out)
            if view.readonly:
                raise ValueError("recv buffer must be writable")
            nbytes = view.nbytes
        native = self.lib.mt_irecv(self._ctx, src, tag, out, nbytes)
        if native < 0:
            raise ValueError(f"irecv from invalid rank {src}")
        self._posted[native] = out
        return Handle(kind="recv", peer=src, tag=tag, out=out, native_id=native)

    def filled(self, handle: Handle) -> int:
        """Bytes of the posted receive ``handle`` that lie in its buffer,
        from the front, and are its message's for good: what the drain has
        copied out of the ring so far where the message lands in the buffer
        itself, the whole size once ``test`` is true, 0 while no message
        has begun to land or the message goes through an assembly buffer.
        Negative once a message that had begun to land was abandoned by its
        sender (the next one fills the buffer from its front again, so the
        earlier answers no longer hold; it stays negative when the receive
        is done: the reader takes the buffer whole), and for a receive that
        was cancelled.  One native read, no progress made."""
        if not handle.done:
            handle.meta["filled"] = int(
                self.lib.mt_recv_filled(self._ctx, handle.native_id))
        return handle.meta["filled"]

    def follow(self, handle: Handle, told: Any) -> None:
        """From now until the posted receive ``handle`` is done or
        cancelled, call ``told(filled)`` (:meth:`filled`) whenever the
        mark has moved, on the thread and at the end of whichever call of
        this endpoint made the progress (``test`` of any handle,
        ``iprobe``, ``isend``, ``irecv`` of a probed message), and once
        more, with the size (or the negative of a receive that was torn),
        from the ``test`` that finds it done.  ``told`` must not block
        and must not call the endpoint."""
        self._followed[handle.native_id] = [handle, told, 0]

    def _tell(self) -> None:
        """Tell the followed receives' marks that moved."""
        for entry in self._followed.values():
            mark = self.filled(entry[0])
            if mark != entry[2]:
                entry[2] = mark
                entry[1](mark)

    def iprobe(self, src: int, tag: int) -> bool:
        there = bool(self.lib.mt_iprobe(self._ctx, src, tag))
        if self._followed:
            self._tell()
        return there

    def test(self, handle: Handle) -> bool:
        if handle.done or handle.cancelled:
            return handle.done
        code = self.lib.mt_test(self._ctx, handle.native_id)
        if code == 0:
            if self._followed:
                self._tell()
            return False
        self._posted.pop(handle.native_id, None)  # every other code is final
        if code != 1:
            self._followed.pop(handle.native_id, None)
        if code == 1:
            handle.done = True
            if handle.kind == "recv" and handle.meta.get("as_bytes"):
                handle.payload = handle.out.tobytes()
                handle.out = None
            elif handle.kind == "recv":  # its last word, for ``filled``
                handle.meta["filled"] = int(
                    self.lib.mt_recv_filled(self._ctx, handle.native_id))
            if handle.kind == "recv":
                out = handle.out if handle.out is not None else handle.payload
                self._m_rx_msgs[handle.peer].inc()
                self._m_rx_bytes[handle.peer].inc(
                    int(getattr(out, "nbytes", None) or len(out or b"")))
            if handle.kind == "send":
                handle.buf = None  # release ownership back to the caller
            if self._followed:
                self._tell()  # this one's last word, the others' marks
                self._followed.pop(handle.native_id, None)
            if self._rec.enabled:
                self._wire_span(handle)
            if self._m_native:
                now = self.wire_counts()
                for key, counter in self._m_native.items():
                    counter.inc(now[key] - self._native_counted[key])
                self._native_counted = now
            self.lib.mt_release(self._ctx, handle.native_id)
            return True
        if code == -2:
            size = self.lib.mt_recv_size(self._ctx, handle.native_id)
            # Terminal: release the native op and poison the handle so the
            # error raises exactly once and nothing leaks.
            self.lib.mt_cancel(self._ctx, handle.native_id)
            handle.cancelled = True
            raise ValueError(
                f"recv size mismatch: message {size}B does not fit buffer "
                f"(src={handle.peer}, tag={handle.tag})"
            )
        handle.cancelled = True
        raise RuntimeError(f"native test error {code} on {handle}")

    def cancel(self, handle: Handle) -> None:
        if not handle.done:
            self.lib.mt_cancel(self._ctx, handle.native_id)
            self._posted.pop(handle.native_id, None)
            self._followed.pop(handle.native_id, None)
        handle.cancelled = True
        handle.buf = None

    def rx_path_bytes(self) -> dict:
        """Bytes of the messages received whole so far, by the way they
        took: into the buffer of a receive posted before they arrived, or
        through an assembly buffer and one more copy."""
        return {
            "rx_direct_bytes": int(self.lib.mt_rx_bytes(self._ctx, 0)),
            "rx_assembled_bytes": int(self.lib.mt_rx_bytes(self._ctx, 1)),
        }

    def ring_counters(self) -> dict:
        """How the rings were used so far: chunks this endpoint placed in
        its peers' rings and placements a full ring refused (it waited for
        the owner's drain); chunks it copied out of its own rings and those
        of them during whose copy the sender moved the ring's head (both
        sides were copying at once); payload bytes it placed while their
        send's pieces were short of its length; payload bytes it placed,
        and copied out, in parts that its thread and its helpers copied
        at once (:func:`copy_helpers`)."""
        return {key: int(self.lib.mt_ring_counts(self._ctx, which))
                for which, key in _RING_COUNTS}

    def wire_counts(self) -> dict:
        return {**self.rx_path_bytes(), **self.ring_counters()}

    def wire_totals(self) -> dict:
        """Seconds this endpoint's thread has spent, so far and while
        the recorder records (zeros otherwise): copying into its peers'
        rings, copying out of its own (the hand-over of an assembled
        message included), and inside the native ``progress`` altogether:
        less the two copies, the cost of polling.  And its helper
        threads' (:func:`copy_helpers`; zeros with none), by their own
        readings of the clock: inside the parts they copied, and
        spinning with no part to take: together their time on a core."""
        return {key: self.lib.mt_wire_ns(self._ctx, which) * 1e-9
                for which, key in enumerate(
                    ("tx_copy", "rx_copy", "progress",
                     "crew_copy", "crew_spin"))}

    def waiting(self) -> tuple:
        """What this endpoint's unfinished transfers stood before when it
        last made progress, from the native side's own state (no clock):
        ``unready`` (a send with every appended piece placed and short of
        its length: its caller has staged no more), ``blocked`` (a send
        with bytes left and a ring with no room for them: the owner's
        drain), ``unanswered`` (a receive posted with a buffer that no
        message has begun to land in: the peer has not begun to send),
        ``partial`` (a receive whose message is landing: its next chunks
        are not published).  For a caller that names its idle time."""
        bits = self.lib.mt_waiting(self._ctx)
        return tuple(name for bit, name in _WAITING if bits & bit)

    def _wire_span(self, handle: Handle) -> None:
        """The native record of the transfer ``handle`` just finished,
        as one ``wire`` span (see the module docstring)."""
        if not self.lib.mt_op_timing(self._ctx, handle.native_id,
                                     self._record):
            return
        (kind, msg_id, t_first, t_done, copy, wait, away, t_pub, nbytes,
         early, unready, merged, split) = self._record.tolist()
        if nbytes < _spans.WIRE_SPAN_MIN_BYTES:
            return
        runs = self.lib.mt_op_intervals(self._ctx, handle.native_id,
                                        self._runs)
        args = {"bytes": nbytes, "split_bytes": split, "msg_id": msg_id,
                "copy_ms": copy / 1e6, "away_ms": away / 1e6,
                "copies": self._runs[:3 * runs].reshape(-1, 3).tolist(),
                "copies_merged": merged}
        if kind == 1:
            args.update(blocked_ms=wait / 1e6,
                        early_bytes=early, unready_ms=unready / 1e6,
                        flight_ms=(t_done - t_first) / 1e6)
        else:
            args.update(starved_ms=wait / 1e6,
                        flight_ms=(t_done - t_pub) / 1e6)
        self._rec.wire("tx" if kind == 1 else "rx", self.rank, handle.peer,
                       handle.tag, t_first * 1e-9, t_done * 1e-9, **args)

    def close(self) -> None:
        if not self._closed and self._ctx:
            self.lib.mt_finalize(self._ctx)
            self._closed = True

    # -- helpers ------------------------------------------------------------

    @staticmethod
    def _sendable(data: Any):
        """Keepalive-friendly buffer form: ndarray stays as-is (raw pointer
        + held reference), everything else becomes bytes.  Non-contiguous
        arrays are rejected rather than silently copied — same fail-loud
        zero-copy rule as :func:`mpit_tpu.comm.transport.as_bytes_view`."""
        if data is None:
            return b""
        if isinstance(data, np.ndarray):
            if not data.flags["C_CONTIGUOUS"]:
                raise ValueError(
                    "send buffer must be C-contiguous (zero-copy rule: a "
                    "hidden copy would break buffer-liveness semantics)"
                )
            return data
        if isinstance(data, (bytes, bytearray)):
            return bytes(data)
        if isinstance(data, memoryview):
            return data.tobytes()
        return np.ascontiguousarray(np.asarray(data))

    @staticmethod
    def wtime() -> float:
        return _load_lib().mt_time()
