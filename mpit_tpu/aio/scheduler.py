"""Cooperative task scheduler (analog of reference init.lua:21-25,128-185).

The reference schedules Lua coroutines that yield one of five signals; the
scheduler pops one coroutine from a FIFO, resumes it one step, and re-pushes
it unless it finished (init.lua:147-174).  ``co_wait`` spins until the queue
drains (init.lua:178-185).  That cooperative single-step model is what lets
a parameter-server client overlap communication polls with device compute
(``pc:ping()``, reference optim-eamsgd.lua:63) without threads.

Here tasks are Python generators.  A generator yields ``EXEC`` (still
working — typically between transfer polls) and returns normally when done;
its return value is captured.  Exceptions become ``ERR`` state and are
re-raised from :meth:`Scheduler.wait` / :meth:`Scheduler.wait_for`.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Generator, List, Optional

from mpit_tpu.aio.queue import Queue
from mpit_tpu.obs import flight as _obs_flight
from mpit_tpu.obs import metrics as _obs_metrics
from mpit_tpu.obs import profile as _obs_profile
from mpit_tpu.obs import spans as _obs_spans

# Idle backoff (microseconds) for the wait loops: after a full pass over
# the queue completes NO task, the waiter sleeps this long before polling
# again.  On a host whose roles share cores (colocated server/client
# threads, 1-core CI boxes) a busy-spinning waiter steals exactly the
# cycles its peer needs to make the data arrive — the 1-core shm PS
# bench sweep measured (MB/s aggregate at 64 MB payload): 0us -> 298,
# 100us -> 368, 200-300us -> ~400, with diminishing returns and growing
# small-message latency beyond.  A pass that moves chunks but completes
# nothing still sleeps; at 4 MB chunks the duty cycle stays far above
# wire speed.  0 disables.
IDLE_USEC = float(os.environ.get("MPIT_AIO_IDLE_USEC", "200"))

# Stuck-gang watchdog (obs/flight.py): when a non-empty queue has
# accumulated this many seconds of idle backoff without completing a
# single task, the scheduler dumps its live task table plus the flight
# recorder's recent events — a hang produces a postmortem instead of
# nothing.  Counted in *idle-backoff* seconds as the span recorder
# measured them (with obs off nothing is measured and nothing counted):
# a pass that completes a task resets the budget, so a healthy-but-busy
# gang never trips it.  Active only when obs is enabled; 0 disables.
STALL_S = float(os.environ.get("MPIT_OBS_STALL_S", "60"))

# Task signals (reference init.lua:21-25).  INIT/OK are retained for state
# reporting; the scheduler itself only reacts to EXEC (keep going) vs DONE.
INIT = "INIT"
EXEC = "EXEC"
OK = "OK"
ERR = "ERR"
DONE = "DONE"


class TaskError(RuntimeError):
    """An exception raised inside a scheduled task, with the task attached."""

    def __init__(self, task: "Task", cause: BaseException):
        super().__init__(f"task {task.name!r} failed: {cause!r}")
        self.task = task
        self.cause = cause


class DeadlineExceeded(RuntimeError):
    """An aio transfer missed its deadline (mpit_tpu.ft op-deadline path).

    Carries enough context for the retry layer to identify the op: the
    peer rank, the wire tag, and which side (send/recv) timed out."""

    def __init__(self, kind: str, peer: int, tag: int, late_by: float):
        super().__init__(
            f"aio_{kind} (peer={peer}, tag={tag}) missed its deadline "
            f"by {late_by:.3f}s"
        )
        self.kind = kind
        self.peer = peer
        self.tag = tag
        self.late_by = late_by


def deadline_at(seconds: Optional[float]) -> Optional[float]:
    """Absolute monotonic deadline ``seconds`` from now (None passes
    through: no deadline).  The tiny helper every FT call site uses so
    deadlines are always absolute by the time they reach the poll loops —
    relative timeouts restarted per retry attempt would never fire under
    a steady trickle of progress."""
    return None if seconds is None else time.monotonic() + seconds


class Task:
    """A cooperatively-scheduled unit of work wrapping a generator.

    The generator is *not* primed at construction; the scheduler steps it.
    ``result`` holds the generator's return value once state is DONE.
    """

    __slots__ = ("gen", "name", "state", "result", "error", "on_done",
                 "t_obs", "cpu_s")

    def __init__(
        self,
        gen: Generator[Any, None, Any],
        name: str = "task",
        on_done: Optional[Callable[["Task"], None]] = None,
    ) -> None:
        self.gen = gen
        self.name = name
        self.state = INIT
        self.result: Any = None
        self.error: Optional[BaseException] = None
        self.on_done = on_done
        self.t_obs: Any = None  # span-recorder token (None when disabled)
        self.cpu_s = 0.0  # on-CPU seconds (profiler-stamped; 0 when off)

    def step(self) -> str:
        """Advance the generator one yield.  Returns the new state."""
        if self.state in (DONE, ERR):
            return self.state
        try:
            next(self.gen)
            self.state = EXEC
        except StopIteration as stop:
            self.result = stop.value
            self.state = DONE
            if self.on_done is not None:
                self.on_done(self)
        except BaseException as exc:  # noqa: BLE001 — recorded, re-raised by wait()
            self.error = exc
            self.state = ERR
        return self.state

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return f"Task({self.name!r}, state={self.state})"


class Scheduler:
    """FIFO round-robin scheduler of generator tasks.

    One scheduler per role-process (server or client), exactly as the
    reference runs one coroutine queue per rank.  Methods map to the
    reference API: ``spawn`` = co_execute (init.lua:133-144), ``ping`` =
    co_ping (init.lua:147-174), ``wait`` = co_wait (init.lua:178-185).
    """

    def __init__(self, idle_usec: Optional[float] = None,
                 stall_s: Optional[float] = None) -> None:
        self.queue: Queue[Task] = Queue()
        self.errors: list[TaskError] = []
        self.idle_usec = IDLE_USEC if idle_usec is None else float(idle_usec)
        self._completions = 0
        # Observability (mpit_tpu.obs): instruments are captured once —
        # disabled they are the shared null objects, so the per-step and
        # idle accounting below costs one no-op method call.
        self._rec = _obs_spans.get_recorder()
        self._flight = _obs_flight.get_flight()
        self._prof = _obs_profile.get_profiler()
        self.stall_s = STALL_S if stall_s is None else float(stall_s)
        self._idle_accum = 0.0
        self._stall_dumped = False
        #: seconds inside the back-off sleeps, as the recorder measured
        #: them: 0.0 and no clock read while it does not record
        self.sleep_s = 0.0
        #: the owner's name for a back-off sleep, asked after each one
        #: while the recorder measures them (``why() -> str``: the PS
        #: client says what its pending ops waited for), and ``sleep_s``
        #: by that name; None and empty unless an owner installs them
        self.why: Optional[Callable[[], str]] = None
        self.sleep_by: dict[str, float] = {}
        _reg = _obs_metrics.get_registry()
        self._m_steps = _reg.counter("mpit_aio_steps_total")
        self._m_idle = _reg.counter("mpit_aio_idle_seconds_total")
        self._m_tasks = _reg.counter("mpit_aio_tasks_total")
        self._m_stalls = _reg.counter("mpit_aio_stall_dumps_total")

    # -- co_execute ---------------------------------------------------------
    def spawn(
        self,
        gen: Generator[Any, None, Any],
        name: str = "task",
        on_done: Optional[Callable[[Task], None]] = None,
    ) -> Task:
        """Create a task, prime it with one step, queue it if still running."""
        task = Task(gen, name=name, on_done=on_done)
        self._m_tasks.inc()
        task.t_obs = self._rec.task_begin(name)
        self._step_and_requeue(task)
        return task

    # -- co_ping ------------------------------------------------------------
    def ping(self) -> Optional[Task]:
        """Pop one task, advance it one step, re-queue unless finished.

        Returns the task stepped (or None when the queue is empty).  This is
        the comm/compute-overlap primitive: call between device ops to make
        transfer progress without blocking.
        """
        task = self.queue.pop()
        if task is None:
            return None
        self._step_and_requeue(task)
        return task

    def ping_pass(self, usec: float = 0.0) -> bool:
        """One full pass over the current queue (one ping per queued
        task), then the idle backoff when the pass completed no task.
        Returns True when anything completed.  The single building block
        of every wait loop — the backoff rule lives here only."""
        done0 = self._completions
        for _ in range(len(self.queue)):
            self.ping()
            if usec > 0:
                time.sleep(usec * 1e-6)
        if self._prof.enabled:
            # Counter-track sample (throttled inside the profiler):
            # run-queue depth + cumulative task CPU + pool utilization.
            self._prof.sample(len(self.queue))
        progressed = self._completions != done0
        if progressed:
            self._idle_accum = 0.0
            self._stall_dumped = False
        elif self.idle_usec > 0 and self.queue:
            # Full pass, nothing finished: yield the core (see IDLE_USEC)
            # instead of burning it on iprobe spins.  What the sleep took
            # is the recorder's to say: 0.0 with obs off.
            slept = self._rec.sleep(self.idle_usec * 1e-6)
            self.sleep_s += slept
            if slept and self.why is not None:
                name = self.why()
                self.sleep_by[name] = self.sleep_by.get(name, 0.0) + slept
            self._m_idle.inc(slept)
            self._idle_accum += slept
            if (self._flight.enabled and self.stall_s > 0
                    and not self._stall_dumped
                    and self._idle_accum >= self.stall_s):
                # Stuck gang: nothing completed across stall_s of idle
                # backoff.  Dump once per stall episode.
                self._stall_dumped = True
                self._m_stalls.inc()
                self._flight.record(
                    "scheduler_stall", idle_s=self._idle_accum,
                    pending=[t.name for t in self.queue])
                self._flight.dump(
                    "scheduler_stall",
                    tasks=[(t.name, t.state) for t in self.queue],
                    idle_s=self._idle_accum)
        return progressed

    # -- co_wait ------------------------------------------------------------
    def wait(self, usec: float = 0.0, deadline: Optional[float] = None) -> None:
        """Drain the queue, optionally sleeping ``usec`` microseconds after
        each single-task ping — exactly the reference's co_wait cadence,
        which defaults usec to 0 for I/O throughput (init.lua:178-185,
        README:65).

        Raises the first :class:`TaskError` encountered after draining; with
        ``deadline`` (seconds), raises TimeoutError if tasks remain.
        """
        t_end = None if deadline is None else time.monotonic() + deadline
        while self.queue:
            self.ping_pass(usec)
            if t_end is not None and time.monotonic() > t_end and self.queue:
                raise TimeoutError(
                    f"scheduler.wait: {len(self.queue)} task(s) still pending "
                    f"after {deadline}s: {[t.name for t in self.queue]}"
                )
        if self.errors:
            raise self.errors.pop(0)

    def wait_for(self, task: Task, usec: float = 0.0) -> Any:
        """Drive the queue until ``task`` completes; return its result."""
        while task.state not in (DONE, ERR):
            if not self.queue:
                raise RuntimeError(f"task {task.name!r} pending but queue empty")
            self.ping_pass(usec)
        if task.state == ERR:
            # Drop the queued duplicate so a later wait() doesn't re-raise
            # an error the caller already handled here.
            self.errors = [e for e in self.errors if e.task is not task]
            raise TaskError(task, task.error)  # type: ignore[arg-type]
        return task.result

    def _step_and_requeue(self, task: Task) -> None:
        prof = self._prof
        if prof.enabled:
            # Per-task CPU attribution (obs/profile.py): the delta of
            # the stepping thread's CPU clock across this step belongs
            # to this task — the task-switch boundary IS the yield.
            c0 = prof.cpu_now()
            state = task.step()
            d = prof.cpu_now() - c0
            if d > 0:
                task.cpu_s += d
            prof.step(task.name, d)
        else:
            state = task.step()
        self._m_steps.inc()
        if state == EXEC:
            self.queue.push(task)
        elif state == ERR:
            self._completions += 1
            self._rec.task_end(task.t_obs, task.name, ERR,
                               cpu_us=task.cpu_s * 1e6)
            self.errors.append(TaskError(task, task.error))  # type: ignore[arg-type]
        elif state == DONE:
            self._completions += 1
            self._rec.task_end(task.t_obs, task.name, DONE,
                               cpu_us=task.cpu_s * 1e6)

    def __len__(self) -> int:
        return len(self.queue)


# ---------------------------------------------------------------------------
# Async transfer generators (analog of reference init.lua:40-102).
#
# A transport (mpit_tpu.comm) exposes nonblocking primitives:
#   isend(data, dst, tag) -> handle          irecv(src, tag) -> handle
#   test(handle) -> bool                     iprobe(src, tag) -> bool
#   cancel(handle) -> None                   payload(handle) -> bytes/array
# The generators below poll those handles, yielding EXEC between polls, and
# honour a shared LiveFlag for the graceful-shutdown cancel path
# (reference init.lua:50-58,88-96; README:71).
# ---------------------------------------------------------------------------


class LiveFlag:
    """Shared on/off switch for a role-process's I/O (reference ``state.io``)."""

    __slots__ = ("io", "on")

    def __init__(self) -> None:
        self.io = True  # transfers may progress
        self.on = True  # service loops may continue

    def stop(self) -> None:
        self.io = False
        self.on = False


def aio_send(
    transport: Any,
    data: Any,
    dst: int,
    tag: int,
    live: Optional[LiveFlag] = None,
    cb: Optional[Callable[[Any], None]] = None,
    deadline: Optional[float] = None,
    abort: Optional[Callable[[], bool]] = None,
    pieces: Optional[Callable[[int], List[Any]]] = None,
) -> Generator[str, None, None]:
    """Nonblocking send: post, then poll-test until complete.

    Mirrors reference init.lua:40-65 — including the shutdown path: when the
    live flag drops, the in-flight send is cancelled so buffer ownership
    returns to the caller before exit.

    ``deadline`` (absolute monotonic seconds, see :func:`deadline_at`)
    raises :class:`DeadlineExceeded` if the transfer has not completed by
    then — the op-deadline primitive of the ``mpit_tpu.ft`` retry layer.
    ``abort`` is polled between steps; returning True cancels the send
    and returns None (the lease-eviction path: a server must stop waiting
    on a peer its lease registry has declared dead).

    ``pieces``: the message is not whole yet and arrives in pieces;
    ``data`` is then its length in bytes.  ``pieces(written)`` is asked at
    the post and at every poll: told how many of the message's bytes the
    transport has placed by now (every piece that ends there or before is
    the caller's again), it returns the arrays that became whole since it
    was last asked, in order, and the send reads each where it lies; for a
    transport that can hold such a send (``append``,
    ``comm/transport.py``).  If ``pieces`` raises (whoever makes the rest
    has failed) the send is cancelled part-way and the error is the
    task's: the peer never takes the message for whole.
    """
    if pieces is None:
        handle = transport.isend(data, dst, tag)
    else:
        handle = transport.isend_pieces(data, dst, tag)
    while True:
        if pieces is not None:
            try:
                for piece in pieces(transport.written(handle)):
                    transport.append(handle, piece)
            except BaseException:
                transport.cancel(handle)
                raise
        if transport.test(handle):
            break
        if live is not None and not live.io:
            transport.cancel(handle)
            return
        if abort is not None and abort():
            transport.cancel(handle)
            return
        if deadline is not None and time.monotonic() > deadline:
            transport.cancel(handle)
            raise DeadlineExceeded("send", dst, tag, time.monotonic() - deadline)
        yield EXEC
    if pieces is not None:
        pieces(transport.written(handle))  # all of them: none is held now
    if cb is not None:
        cb(handle)


def aio_recv(
    transport: Any,
    src: int,
    tag: int,
    live: Optional[LiveFlag] = None,
    cb: Optional[Callable[[Any], None]] = None,
    out: Optional[Any] = None,
    deadline: Optional[float] = None,
    abort: Optional[Callable[[], bool]] = None,
    request: Optional[Generator[str, None, Any]] = None,
    landing: Optional[Callable[[int], None]] = None,
) -> Generator[str, None, Any]:
    """Nonblocking receive.  Returns the payload, or None if it gave up.

    With ``out``, a preallocated buffer of the message's size, the receive
    is posted at once and polled to completion: a transport that can lands
    the message in ``out`` as it arrives (``comm/shm.py``), with no pass
    over it afterwards.  Without ``out`` (acks, headers, anything whose
    size is not known) it is the reference's idiom, init.lua:67-102:
    Iprobe poll -> Irecv -> Test poll.

    ``request`` is the send that asks the peer for this message (an
    ``aio_send``, unstarted).  It runs here, once the receive is posted:
    an answer that is on its way before its receive is known is
    assembled elsewhere and copied, and a quick peer beats a slow
    requester often enough to matter (the round's PARAM did in one pull
    of ten when the request went first: PERF.md section 6, PR 29).

    ``landing``, with ``out`` and a transport that can say how far a
    posted receive's buffer is filled (``follow``, ``comm/transport.py``):
    told whenever that has moved, and once more when the message is whole,
    how many bytes of ``out`` are the message's for good, from its front
    (negative: the message that had begun to land was abandoned and
    ``out`` fills anew), so that a reader may follow the landing.  The
    transport tells it from whichever call made the progress, this
    receive's own polls or another task's.  It must not block.

    ``deadline`` (absolute monotonic seconds) raises
    :class:`DeadlineExceeded` if the message is not whole in time;
    ``abort`` returning True, or the live flag dropping, gives up and
    returns None (lease eviction / generation change / shutdown).  All
    three are checked until the message is whole.  Giving up cancels the
    posted receive — also when the generator is closed or dropped — and
    every transport keeps a message whole for the next receiver when a
    receive it had begun to fill is cancelled, so no service generation
    strands or tears a message its successor still needs.
    """

    def gave_up() -> bool:
        if live is not None and not live.io:
            return True
        if abort is not None and abort():
            return True
        if deadline is not None and time.monotonic() > deadline:
            raise DeadlineExceeded("recv", src, tag, time.monotonic() - deadline)
        return False

    if out is None:
        yield from request or ()
        while not transport.iprobe(src, tag):
            if gave_up():
                return None
            yield EXEC
    handle = transport.irecv(src, tag, out=out)
    if landing is not None:
        transport.follow(handle, landing)
    whole = False
    try:
        if out is not None:
            yield from request or ()
        while not transport.test(handle):
            if gave_up():
                return None
            yield EXEC
        whole = True
    finally:
        if not whole:
            transport.cancel(handle)
    payload = transport.payload(handle)
    if cb is not None:
        cb(payload)
    return payload


def aio_sleep(
    seconds: float, live: Optional[LiveFlag] = None
) -> Generator[str, None, bool]:
    """Cooperative sleep: yield EXEC until ``seconds`` have elapsed (the
    scheduler-timer primitive behind retry backoff and lease reaping).
    Returns False if the live flag dropped before the timer fired, True
    otherwise.  Never blocks the scheduler — other tasks run between
    polls, and the ping_pass idle backoff keeps an otherwise-idle queue
    from busy-spinning the core while a timer counts down."""
    wake = time.monotonic() + seconds
    while time.monotonic() < wake:
        if live is not None and not live.on:
            return False
        yield EXEC
    return True
