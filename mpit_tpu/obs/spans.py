"""Op spans and task lifecycles — who spent how long in which phase.

A counter says *how many* retries happened; a span says *which op*
retried, against *which peer*, and where its time went.  Two record
kinds:

- **Op spans** (:class:`OpSpan`): one per PS op.  Created when the op
  starts processing, phase-marked at each transition (client:
  ``encode`` → ``send`` → ``ack``, with ``backoff``/``send``/``ack``
  repeating per retry attempt; server: ``apply`` → ``ack``), annotated
  with the op's wire identity (peer, ``[epoch, seq]``) and closed with
  an outcome (``ok`` / ``applied`` / ``dup`` / ``stale`` / ``aborted``
  / ``exhausted``).  Closing also feeds the ``mpit_ps_op_seconds``
  histogram, so the metrics and the trace always agree.
- **Task lifecycles**: the cooperative scheduler records each task's
  spawn→completion window and terminal state — service loops, pumps,
  and reapers show up as rows in the exported trace.
- **Round spans** (:meth:`SpanRecorder.round`): the parent of one sync
  round of a worker, phase-marked by the optimizer shell
  (``wait_backward`` → ``d2h`` → ``stage`` → ``exchange`` → ``h2d`` →
  ``telemetry``) so its children tile it.  While it is open, every
  client op span this thread begins carries ``round=k``; the span also
  enters one ``jax.profiler.TraceAnnotation("mpit.round", round=k,
  mono_ns=...)``, whose ``mono_ns`` is the span's own begin stamp: the
  pair (profiler timestamp, monotonic timestamp) that puts the spans
  and a device trace on one clock (docs/OBSERVABILITY.md, *One
  clock*).

- **Wire spans** (:meth:`SpanRecorder.wire`): one per message of at
  least :data:`WIRE_SPAN_MIN_BYTES` and end of the shm wire (``tx``,
  ``rx``; category ``wire``), begun and ended at the stamps the native
  transport took itself (``comm/shm.py``, ``comm/native/transport.cpp``
  ``TxTiming``/``RxTiming``): the recorder takes these times as given
  and reads no clock for them.  Their args tile the message's flight
  into copying, blocked on a full ring (``tx``) or starved by an empty
  one (``rx``), and away.  A :class:`WireMeter` notes, on a span that
  covers a stretch of one endpoint's thread (the ``round`` span's
  ``exchange``, a server's GRAD and PARAM op spans), what that thread
  spent copying, polling and asleep in the scheduler's back-off.

- **Copy spans** (:meth:`SpanRecorder.copy`): one per piece of the
  vector that the round's stream thread moves between the device and the
  host (category ``copy``, names ``d2h`` and ``h2d``, and ``h2d_shard``
  that closes a shard's upload), finished when recorded, at stamps the
  caller took.  With the wire spans' ``copies`` and ``apply_exec``'s
  ``bytes_moved`` they are the round's passes over the host's memory
  (obs/copies.py).

Every op span carries ``n``, its ordinal on its channel (``tid``).  The
channels are strictly sequential on both sides, so the client half and
the server half of one op share (client rank, server rank, op, ``n``)
even on the unframed wire, where no ``[epoch, seq]`` identity exists;
obs/causal.py joins on it.

The recorder owns every clock read.  Role files (``ps/``, ``ft/``,
``comm/``) never call ``time.monotonic()`` to measure — the MT-O4xx
lint family enforces it — so a disabled recorder (the default) means
zero clock reads on the hot path: :data:`NULL_SPAN` and
:data:`NULL_RECORDER` are shared do-nothing objects.

Cross-process alignment: spans are recorded on the monotonic clock, and
the recorder captures a wall-clock offset at construction; the trace
exporter adds it so per-rank files merge onto one timeline (host NTP
skew applies, which is fine at the phase granularity traced here).
"""

from __future__ import annotations

import queue
import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Tuple

from mpit_tpu.obs import clock as _clock
from mpit_tpu.obs import flight as _flight
from mpit_tpu.obs import metrics as _metrics
from mpit_tpu.obs import profile as _profile


class NullSpan:
    """Shared no-op span — the disabled path's op object."""

    __slots__ = ()

    def mark(self, phase: str) -> None:
        pass

    def note(self, **kw) -> None:
        pass

    def end(self, outcome: str = "ok", **kw) -> None:
        pass

    def phase_seconds(self, phase: str) -> float:
        return 0.0


NULL_SPAN = NullSpan()


class OpSpan:
    cat = "ps_op"
    __slots__ = ("_rec", "name", "tid", "t0", "t1", "marks", "args",
                 "outcome", "cpu0", "cpu1", "cpu_marks", "cpu_us",
                 "seen_ready", "exec_cpu")

    def __init__(self, rec: "SpanRecorder", name: str, tid: str,
                 args: Dict[str, object]):
        self._rec = rec
        self.name = name
        self.tid = tid
        self.t0 = time.monotonic()
        self.t1: Optional[float] = None
        self.marks: List[Tuple[str, float]] = []
        self.args = args
        self.outcome = ""
        #: when a thread other than the one that ends this span saw its
        #: result ready (:meth:`SpanRecorder.seen_ready`), if one did
        self.seen_ready: Optional[float] = None
        #: on a span handed to the waiter (:meth:`end_when_ready`): the
        #: process's CPU seconds at the waiter's ``exec`` mark, at its
        #: end stamp and at ``seen_ready``'s (None: not stamped)
        self.exec_cpu: Optional[List[Optional[float]]] = None
        # CPU attribution (obs/profile.py): when profiling is enabled
        # the span stamps the stepping thread's CPU clock alongside
        # every wall stamp, so the exporter can split each phase into
        # on-cpu vs off-cpu.  Off (cpu0 None): zero extra clock reads.
        self.cpu0: Optional[float] = (
            rec._prof.cpu_now() if rec._prof.enabled else None)
        self.cpu1: float = 0.0
        self.cpu_marks: List[float] = []
        self.cpu_us: Optional[float] = None

    def mark(self, phase: str) -> None:
        """Phase ``phase`` begins now (it runs until the next mark or
        the end of the span)."""
        self.marks.append((phase, time.monotonic()))
        if self.cpu0 is not None:
            self.cpu_marks.append(self._rec._prof.cpu_now())

    def phase_seconds(self, phase: str) -> float:
        """Seconds this (ended) span spent in the phases named
        ``phase``: each runs from its mark to the next mark or the
        span's end."""
        ends = [t for _p, t in self.marks[1:]] + [self.t1]
        return sum(end - t for (p, t), end in zip(self.marks, ends)
                   if p == phase)

    def note(self, **kw) -> None:
        """Attach args discovered mid-op (e.g. seq assigned after the
        encode, retry counts)."""
        self.args.update(kw)

    def end(self, outcome: str = "ok", **kw) -> None:
        if self.t1 is not None:
            return  # idempotent: error paths may end defensively
        self.t1 = time.monotonic()
        if self.cpu0 is not None:
            self.cpu1 = self._rec._prof.cpu_now()
            self.cpu_us = max((self.cpu1 - self.cpu0) * 1e6, 0.0)
        self.outcome = outcome
        if kw:
            self.args.update(kw)
        self._rec._finish(self)


#: Messages smaller than this get no wire span (acks and headers would be
#: thousands of events a run); their time stays in the endpoint's totals.
WIRE_SPAN_MIN_BYTES = 1 << 20


class WireSpan:
    """One message on one end of the shm wire: a finished span whose
    begin and end are the transport's own stamps (seconds on the
    monotonic clock), with nothing of an :class:`OpSpan` but what the
    exporter reads."""

    __slots__ = ("name", "tid", "t0", "t1", "args")
    cat = "wire"
    marks: tuple = ()
    cpu_marks: tuple = ()
    cpu0 = None
    cpu_us = None
    outcome = "ok"

    def __init__(self, name: str, tid: str, t0: float, t1: float,
                 args: Dict[str, object]):
        self.name = name
        self.tid = tid
        self.t0 = t0
        self.t1 = t1
        self.args = args


class CopySpan(WireSpan):
    """One pass of a piece of the vector over the host's memory, as the
    thread that made it timed it: a finished span with phases."""

    __slots__ = ("marks",)
    cat = "copy"

    def __init__(self, name: str, tid: str, t0: float, t1: float,
                 marks: tuple, args: Dict[str, object]):
        super().__init__(name, tid, t0, t1, args)
        self.marks = marks


class NullMeter:
    """The disabled :class:`WireMeter`: notes nothing, reads nothing."""

    __slots__ = ()

    def start(self) -> None:
        pass

    def note(self, span, stretch: bool = True) -> None:
        pass


NULL_METER = NullMeter()


class WireMeter:
    """What one endpoint's thread did on the wire between two looks:
    the deltas of the transport's totals (``wire_totals``: seconds inside
    the copies into peers' rings, inside the copies out of its own, and
    inside the native ``progress``) and of the scheduler's measured
    back-off sleep (``Scheduler.sleep_s``).  :meth:`note` writes them on
    a span as ``wire_tx_copy_ms``, ``wire_rx_copy_ms``, ``wire_poll_ms``
    (progress less the copies) and ``sched_sleep_ms``, with
    ``wire_span_ms``, the stretch they are of: since :meth:`start` or
    the note before (left out where the span has a phase of that very
    stretch, as the ``round`` span's ``exchange``: ``stretch=False``).
    A transport that keeps no totals (tcp, local)
    leaves the wire's three out.  Where the scheduler's owner names its
    sleeps (``Scheduler.sleep_by``: the PS client does, by what its
    pending ops waited for), each name's share goes beside them as
    ``sleep_<name>_ms``; they sum to ``sched_sleep_ms``.  Beside them
    ``cpu_ms``, the CPU of all threads of the process over the same
    stretch (``obs/profile.py`` ``process_cpu``: the stretch's cores are
    ``cpu_ms`` over its length), and, from a transport that keeps them
    (shm), ``crew_copy_ms`` and ``crew_spin_ms``: what the endpoint's
    copy helpers spent inside their parts and spinning with none."""

    __slots__ = ("_totals", "_sched", "_last", "_t")

    def __init__(self, transport: Any, sched: Any):
        self._totals = getattr(transport, "wire_totals", None)
        self._sched = sched
        self.start()

    def _read(self) -> Dict[str, float]:
        now = dict(self._totals()) if self._totals is not None else {}
        now["sched_sleep"] = getattr(self._sched, "sleep_s", 0.0)
        now["cpu"] = _profile.process_cpu()
        for name, slept in getattr(self._sched, "sleep_by", {}).items():
            now[f"sleep_{name}"] = slept
        return now

    def start(self) -> None:
        now = self._read()
        # a helper spins on past the last copy of a stretch: the crew's
        # two run from note to note, whatever a start leaves out
        now.update({k: v for k, v in getattr(self, "_last", {}).items()
                    if k.startswith("crew_")})
        self._last = now
        self._t = time.monotonic()

    def note(self, span, stretch: bool = True) -> None:
        now, t = self._read(), time.monotonic()
        d = {k: (v - self._last.get(k, 0.0)) * 1e3 for k, v in now.items()}
        out = {f"{k}_ms": v for k, v in d.items() if k.startswith("sleep_")}
        out["sched_sleep_ms"] = d["sched_sleep"]
        out["cpu_ms"] = d["cpu"]
        if stretch:
            out["wire_span_ms"] = (t - self._t) * 1e3
        if "progress" in d:
            out.update(
                wire_tx_copy_ms=d["tx_copy"], wire_rx_copy_ms=d["rx_copy"],
                wire_poll_ms=d["progress"] - d["tx_copy"] - d["rx_copy"])
        if "crew_copy" in d:
            out.update(crew_copy_ms=d["crew_copy"],
                       crew_spin_ms=d["crew_spin"])
        span.note(**out)
        self._last, self._t = now, t


class RoundSpan(OpSpan):
    """The parent span of one sync round (:meth:`SpanRecorder.round`)."""

    __slots__ = ("_annotation",)

    def __init__(self, rec: "SpanRecorder", tid: str,
                 args: Dict[str, object], phase: str):
        super().__init__(rec, "round", tid, args)
        # The first phase begins with the span, so the phases tile it.
        self.marks.append((phase, self.t0))
        if self.cpu0 is not None:
            self.cpu_marks.append(self.cpu0)
        rec._ctx.round = args["round"]
        self._annotation = None
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:  # a jax-free reader of this package
            return
        # The anchor: the annotation's profiler timestamp against this
        # span's own monotonic begin stamp.  A no-op outside a profiler
        # session.
        self._annotation = TraceAnnotation(
            "mpit.round", round=args["round"],
            mono_ns=int(round(self.t0 * 1e9)))
        self._annotation.__enter__()

    def end(self, outcome: str = "ok", **kw) -> None:
        if self.t1 is not None:
            return
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        self._rec._ctx.round = None
        super().end(outcome, **kw)


def _end_no_later_than_seen(span: OpSpan) -> None:
    """A span the waiter ends, ended at the earlier of the waiter's
    stamp and the one of a role thread that waited on the same result
    (never before its last mark); ``end_from`` says whose it is.  Both
    threads call this after their own write, so whichever comes second
    finds both stamps.  ``cpu_ms`` is the process's CPU from the
    ``exec`` mark to the stamp the span ends at: every thread's, so the
    phase's cores are ``cpu_ms`` over its length.  Where the role
    thread's stamp ends the span, ``waiter_late_ms`` says how much later
    the waiter's own came: what ``exec`` overstates by wherever no role
    thread waited on the result."""
    seen = span.seen_ready
    cpu0, cpu_end, cpu_seen = span.exec_cpu or (None, None, None)
    if seen is not None and span.t1 is not None and seen < span.t1:
        span.args["waiter_late_ms"] = (span.t1 - seen) * 1e3
        span.t1 = max(seen, span.marks[-1][1] if span.marks else span.t0)
        span.args["end_from"] = "wait_apply"
        cpu_end = cpu_seen
    if cpu0 is not None and cpu_end is not None and span.t1 is not None:
        span.args["cpu_ms"] = max(cpu_end - cpu0, 0.0) * 1e3


class _ReadyWaiter(threading.Thread):
    """Ends spans when the device result they wait for is ready, so that
    the role thread that dispatched the work never blocks on it.  Its end
    stamp waits for the interpreter lock, which a busy role thread holds
    for up to a tenth of a second: where that thread waited on the same
    result itself and says so (:meth:`SpanRecorder.seen_ready`), the span
    ends at the earlier stamp.  Exists
    only while recording (:meth:`SpanRecorder.end_when_ready` starts it
    on first use) and ends with the recorder that started it
    (:meth:`SpanRecorder.close`, or the recorder's collection: a None
    behind what was handed over).  Results are waited for in the order
    handed over, which is the order the backend runs them in: the
    ``exec`` mark of an item is stamped when the item before it became
    ready."""

    def __init__(self) -> None:
        super().__init__(name="obs-ready-waiter", daemon=True)
        self.items: "queue.SimpleQueue" = queue.SimpleQueue()
        self.done = 0  # spans ended; this thread is the only writer

    def run(self) -> None:
        import jax

        while (item := self.items.get()) is not None:
            span, result = item
            span.mark("exec")
            span.exec_cpu[0] = _profile.process_cpu()
            try:
                jax.block_until_ready(result)
                outcome = "ready"
            except Exception:  # a donated or deleted result: no stamp
                outcome = "lost"
            span.exec_cpu[1] = _profile.process_cpu()
            span.end(outcome, end_from="waiter")
            _end_no_later_than_seen(span)
            self.done += 1
            del item, span, result  # a span holds its recorder


class SpanRecorder:
    """Process-local span sink (one per process; role threads share it —
    appends are GIL-atomic and records are immutable once finished)."""

    enabled = True

    def __init__(self, registry=None):
        self.registry = registry if registry is not None \
            else _metrics.get_registry()
        self.spans: List[OpSpan] = []
        #: (name, t0, t1, state, cpu_us) — cpu_us is 0.0 unless the
        #: profiler was live (obs/profile.py) and the scheduler fed
        #: the task's accumulated thread-time through task_end.
        self.tasks: List[Tuple[str, float, float, str, float]] = []
        #: the CPU clock source for op spans — the null profiler when
        #: profiling is off, so spans stamp no thread-time by default.
        self._prof = _profile.get_profiler()
        #: monotonic -> wall offset for cross-rank trace merging — the
        #: process-wide time base (obs/clock.py), shared with the flight
        #: recorder and the FLAG_TIMING wire stamps so every timestamp
        #: this process emits subtracts cleanly against the others.
        self.epoch_offset = _clock.epoch_offset()
        self.flight = _flight.get_flight()
        self._hist_lock = threading.Lock()
        self._hists: Dict[Tuple[str, str], object] = {}
        #: spans begun but not yet ended — the live in-flight op table
        #: served by the /status introspection endpoint (obs/statusd.py)
        #: and attached to flight-recorder dumps.
        self._open: Dict[int, OpSpan] = {}
        #: spans begun per channel: the next op's ordinal ``n``
        self._ordinals: Dict[str, int] = {}
        #: the sync round this thread is in (``round``), if any
        self._ctx = threading.local()
        self._waiter: Optional[_ReadyWaiter] = None
        self._end_waiter: Optional[weakref.finalize] = None
        self._handed = 0  # spans given to the waiter
        #: where the last wire span of a track ended: the next begins no
        #: earlier, so a track's begin/end events nest
        self._wire_end: Dict[str, float] = {}

    def _begin(self, span: OpSpan) -> OpSpan:
        n = self._ordinals.get(span.tid, 0)
        self._ordinals[span.tid] = n + 1
        span.args["n"] = n
        self._open[id(span)] = span
        return span

    def round(self, k: int, phase: str, rank: object = None) -> OpSpan:
        """Begin the parent span of sync round ``k`` of worker ``rank``
        in its first phase ``phase`` (see the module docstring).  The
        caller marks the later phases and ends the span on the thread
        that began it."""
        prefix = f"r{rank}:" if rank is not None else ""
        return self._begin(RoundSpan(
            self, f"{prefix}round",
            {"round": int(k), "rank": rank, "side": "worker"}, phase))

    def end_when_ready(self, span: OpSpan, result: Any) -> None:
        """Hand ``span`` to the waiter thread, which marks ``exec`` when
        the result handed over before this one is ready and ends the
        span when ``result`` is.  ``result`` must be something no later
        computation donates (the server hands over its apply's token,
        not the shard): a deleted array cannot be waited on, and its
        span ends ``lost`` at once.  The caller must not touch the span
        again."""
        span.cpu0 = None  # no one thread's CPU: the waiter notes ``cpu_ms``
        span.exec_cpu = [None, None, None]
        with self._hist_lock:
            if self._waiter is None:
                self._waiter = _ReadyWaiter()
                self._end_waiter = weakref.finalize(
                    self, self._waiter.items.put, None)
                self._waiter.start()
            self._handed += 1
        self._waiter.items.put((span, result))

    def seen_ready(self, span: OpSpan) -> None:
        """The calling thread has just seen ready the result that
        ``span``, handed to :meth:`end_when_ready`, waits for: the span
        ends no later than now (see :class:`_ReadyWaiter`)."""
        span.seen_ready = time.monotonic()
        if span.exec_cpu is not None:
            span.exec_cpu[2] = _profile.process_cpu()
        _end_no_later_than_seen(span)

    def close(self) -> None:
        """End the waiter thread once what was handed over has drained
        (a recorder that is replaced: :func:`reset`)."""
        if self._end_waiter is not None:
            self._end_waiter()

    def wire(self, name: str, rank: int, peer: int, tag: int,
             t0: float, t1: float, **args) -> None:
        """Record the finished wire span ``name`` (``tx`` or ``rx``) of
        one message between ``rank`` and ``peer`` on ``tag``, from ``t0``
        to ``t1``: the transport's own stamps, seconds on the clock of
        :meth:`clock`.  A message taken late may end after the next one
        on its track began to land; the later span then begins where the
        earlier ended (``flight_ms`` in its args stays what was
        measured)."""
        tid = f"r{rank}:wire:{peer}:{tag}:{name}"
        t0 = max(t0, self._wire_end.get(tid, t0))
        self._wire_end[tid] = t1 = max(t0, t1)
        args.update(rank=rank, peer=peer, tag=tag)
        current = getattr(self._ctx, "round", None)
        if current is not None:
            args["round"] = current
        self.spans.append(WireSpan(name, tid, t0, t1, args))

    def copy(self, name: str, rank: object, thread: str, t0: float,
             t1: float, marks: tuple = (), track: str = "", **args) -> None:
        """Record the finished copy span ``name`` of ``rank``'s thread
        ``thread``, from ``t0`` to ``t1`` with the phases ``marks``
        (``(phase, begin)`` in order, the first at ``t0``): the caller's
        own stamps, seconds on the clock of :meth:`clock`.  One thread's
        spans follow each other on one track; ``track`` names another
        for spans that lie across them."""
        prefix = f"r{rank}:" if rank is not None else ""
        args.update(rank=rank, thread=thread)
        self.spans.append(CopySpan(
            name, f"{prefix}copy:{thread}{track}", t0, t1, marks, args))

    def wire_meter(self, transport: Any, sched: Any) -> WireMeter:
        return WireMeter(transport, sched)

    def sleep(self, seconds: float) -> float:
        """Sleep, and say how long it took (the scheduler's back-off:
        the null recorder sleeps as long and says 0.0)."""
        t0 = time.monotonic()
        time.sleep(seconds)
        return time.monotonic() - t0

    def clock(self) -> float:
        """The clock the spans are stamped with, for a wait that has to
        stay outside every span (the client's shard gate measures
        ``gated_ms`` before its GRAD span opens).  The null recorder
        answers 0.0 and reads no clock."""
        return time.monotonic()

    def drain(self, timeout: float = 10.0) -> bool:
        """Wait until every span handed to :meth:`end_when_ready` has
        ended (the exporter calls this before it reads ``spans``)."""
        deadline = time.monotonic() + timeout
        while self._waiter is not None and self._waiter.done < self._handed:
            if time.monotonic() > deadline:
                return False
            time.sleep(0.005)
        return True

    def op(self, name: str, peer: object = "?", side: str = "client",
           **args) -> OpSpan:
        """Begin an op span.  ``tid`` groups ops into trace rows — one
        per (role rank, side, peer, tag) channel, which the protocol
        already keeps strictly sequential (client pump FIFO, per-channel
        server loops), so begin/end events nest cleanly.  The role's own
        rank (``rank=`` arg) is part of the channel id: in a
        single-process multi-role gang (thread tests, np=1) two servers
        otherwise share e.g. ``server:2:GRAD`` and their interleaved
        B/E events scramble the channel."""
        args["peer"] = peer
        args["side"] = side
        rank = args.get("rank")
        prefix = f"r{rank}:" if rank is not None else ""
        current = getattr(self._ctx, "round", None)
        if current is not None and side == "client":
            args["round"] = current
        return self._begin(
            OpSpan(self, name, f"{prefix}{side}:{peer}:{name}", args))

    def open_ops(self) -> List[Dict[str, object]]:
        """Snapshot of the in-flight ops: identity args, current phase,
        the full wall-anchored phase-mark chain (the open half of the
        op's causal chain — a flight dump can say which phase an op died
        in and line it up against a sibling rank's timeline), and
        seconds in flight so far (one clock read per request — this runs
        on the introspection path, never the hot path)."""
        now = time.monotonic()
        off = self.epoch_offset
        out = []
        for span in list(self._open.values()):
            out.append({
                "op": span.name,
                "elapsed_s": now - span.t0,
                "phase": span.marks[-1][0] if span.marks else "",
                "t0": span.t0 + off,
                "marks": [[phase, t + off] for phase, t in list(span.marks)],
                **{k: v for k, v in span.args.items()},
            })
        return out

    def _finish(self, span: OpSpan) -> None:
        self._open.pop(id(span), None)
        self.spans.append(span)
        self.flight.record(
            "op", name=span.name, outcome=span.outcome,
            dur_s=span.t1 - span.t0, t0=span.t0,
            **{k: v for k, v in span.args.items()})
        key = (span.name, str(span.args.get("side", "")))
        hist = self._hists.get(key)
        if hist is None:
            with self._hist_lock:
                hist = self._hists.get(key)
                if hist is None:
                    hist = self.registry.histogram(
                        "mpit_ps_op_seconds", op=key[0], side=key[1])
                    self._hists[key] = hist
        hist.observe(span.t1 - span.t0)

    # -- task lifecycles (driven by aio.Scheduler) ---------------------------

    def task_begin(self, name: str) -> float:
        return time.monotonic()

    def task_end(self, token: Optional[float], name: str, state: str,
                 cpu_us: float = 0.0) -> None:
        if token is None:
            return  # task spawned while recording was disabled
        now = time.monotonic()
        self.tasks.append((name, token, now, state, cpu_us))
        self.flight.record("task", name=name, state=state,
                           dur_s=now - token, t0=token)


class NullRecorder:
    """The disabled recorder: hands out :data:`NULL_SPAN`, records
    nothing, reads no clock."""

    enabled = False
    spans: tuple = ()
    tasks: tuple = ()
    epoch_offset = 0.0

    def op(self, name: str, peer: object = "?", side: str = "client",
           **args) -> NullSpan:
        return NULL_SPAN

    def round(self, k: int, phase: str, rank: object = None) -> NullSpan:
        return NULL_SPAN

    def end_when_ready(self, span, result) -> None:
        pass

    def seen_ready(self, span) -> None:
        pass

    def wire(self, name: str, rank: int, peer: int, tag: int,
             t0: float, t1: float, **args) -> None:
        pass

    def copy(self, name: str, rank: object, thread: str, t0: float,
             t1: float, marks: tuple = (), track: str = "", **args) -> None:
        pass

    def wire_meter(self, transport: Any, sched: Any) -> NullMeter:
        return NULL_METER

    def sleep(self, seconds: float) -> float:
        time.sleep(seconds)
        return 0.0

    def clock(self) -> float:
        return 0.0

    def drain(self, timeout: float = 10.0) -> bool:
        return True

    def open_ops(self) -> list:
        return []

    def task_begin(self, name: str) -> None:
        return None

    def task_end(self, token, name: str, state: str,
                 cpu_us: float = 0.0) -> None:
        pass


NULL_RECORDER = NullRecorder()

_GLOBAL: Optional[SpanRecorder] = None
_LOCK = threading.Lock()


def get_recorder():
    """The process-global recorder when obs is enabled, else the null
    recorder.  Same capture-at-construction contract as the registry."""
    if not _metrics.obs_enabled():
        return NULL_RECORDER
    global _GLOBAL
    if _GLOBAL is None:
        with _LOCK:
            if _GLOBAL is None:
                _GLOBAL = SpanRecorder()
    return _GLOBAL


def reset() -> None:
    """Drop the global recorder (tests; called by obs.configure) and
    end its waiter thread."""
    global _GLOBAL
    old, _GLOBAL = _GLOBAL, None
    if old is not None:
        old.close()
