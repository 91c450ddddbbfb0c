"""Tests for the L1 async engine: Queue, Scheduler, aio_send/aio_recv.

Coverage model follows the reference's semantics (queue.lua FIFO behavior,
init.lua scheduler round-robin, cancel-on-shutdown) but as real assertions
rather than eyeballed prints (SURVEY.md section 4).
"""

import os
import time

import numpy as np
import pytest

from mpit_tpu.aio import (
    DONE,
    DeadlineExceeded,
    EXEC,
    LiveFlag,
    Queue,
    Scheduler,
    TaskError,
    aio_recv,
    aio_send,
)


class TestQueue:
    def test_fifo_order(self):
        q = Queue()
        for i in range(5):
            q.push(i)
        assert [q.pop() for _ in range(5)] == [0, 1, 2, 3, 4]

    def test_pop_empty_returns_none(self):
        assert Queue().pop() is None

    def test_len_and_bool(self):
        q = Queue()
        assert not q and len(q) == 0
        q.push("x")
        assert q and len(q) == 1

    def test_interleaved(self):
        q = Queue()
        q.push(1)
        q.push(2)
        assert q.pop() == 1
        q.push(3)
        assert q.pop() == 2
        assert q.pop() == 3


class TestScheduler:
    def test_spawn_runs_to_completion(self):
        sched = Scheduler()
        log = []

        def work():
            for i in range(3):
                log.append(i)
                yield EXEC

        task = sched.spawn(work(), name="w")
        sched.wait()
        assert task.state == DONE
        assert log == [0, 1, 2]

    def test_round_robin_interleaves(self):
        sched = Scheduler()
        log = []

        def work(tag, n):
            for i in range(n):
                log.append((tag, i))
                yield EXEC

        sched.spawn(work("a", 2))
        sched.spawn(work("b", 2))
        sched.wait()
        # Spawn primes one step each, then round-robin alternates.
        assert log == [("a", 0), ("b", 0), ("a", 1), ("b", 1)]

    def test_return_value_captured(self):
        sched = Scheduler()

        def work():
            yield EXEC
            return 42

        task = sched.spawn(work())
        assert sched.wait_for(task) == 42

    def test_immediate_completion(self):
        sched = Scheduler()

        def work():
            return "done"
            yield  # pragma: no cover

        task = sched.spawn(work())
        assert task.state == DONE
        assert task.result == "done"
        assert len(sched) == 0

    def test_error_propagates_from_wait(self):
        sched = Scheduler()

        def boom():
            yield EXEC
            raise ValueError("boom")

        sched.spawn(boom(), name="boom")
        with pytest.raises(TaskError) as excinfo:
            sched.wait()
        assert isinstance(excinfo.value.cause, ValueError)

    def test_ping_single_steps(self):
        sched = Scheduler()
        log = []

        def work():
            log.append("a")
            yield EXEC
            log.append("b")

        sched.spawn(work())  # primes: runs to first yield
        assert log == ["a"]
        sched.ping()
        assert log == ["a", "b"]
        assert len(sched) == 0

    def test_wait_deadline(self):
        sched = Scheduler()

        def forever():
            while True:
                yield EXEC

        sched.spawn(forever())
        with pytest.raises(TimeoutError):
            sched.wait(deadline=0.05)

    def test_on_done_callback(self):
        sched = Scheduler()
        seen = []

        def work():
            yield EXEC
            return 7

        sched.spawn(work(), on_done=lambda t: seen.append(t.result))
        sched.wait()
        assert seen == [7]


class FakeTransport:
    """Scripted transport: messages become visible/complete after N polls."""

    def __init__(self, send_delay=2, recv_delay=2):
        self.send_delay = send_delay
        self.recv_delay = recv_delay
        self.mailbox = {}
        self.cancelled = []
        self._handles = {}
        self._next = 0

    def isend(self, data, dst, tag):
        handle = self._next
        self._next += 1
        self._handles[handle] = {"polls": 0, "data": data, "dst": dst, "tag": tag}
        return handle

    def irecv(self, src, tag, out=None):
        handle = self._next
        self._next += 1
        self._handles[handle] = {"polls": 0, "data": self.mailbox[(src, tag)]}
        return handle

    def iprobe(self, src, tag):
        entry = self.mailbox.get((src, tag))
        if entry is None:
            return False
        probe = self._handles.setdefault(("probe", src, tag), {"polls": 0})
        probe["polls"] += 1
        return probe["polls"] > self.recv_delay

    def test(self, handle):
        info = self._handles[handle]
        info["polls"] += 1
        if info["polls"] > self.send_delay:
            if "dst" in info:
                self.mailbox[(info["dst"], info["tag"])] = info["data"]
            return True
        return False

    def cancel(self, handle):
        self.cancelled.append(handle)

    def payload(self, handle):
        return self._handles[handle]["data"]


class TestAioTransfers:
    def test_send_then_recv(self):
        transport = FakeTransport()
        sched = Scheduler()
        got = []
        sched.spawn(aio_send(transport, b"hello", dst=1, tag=3), name="send")
        recv = sched.spawn(
            aio_recv(transport, src=1, tag=3, cb=got.append), name="recv"
        )
        sched.wait()
        assert got == [b"hello"]
        assert recv.result == b"hello"

    def test_send_cancelled_on_stop(self):
        transport = FakeTransport(send_delay=10**9)
        sched = Scheduler()
        live = LiveFlag()
        sched.spawn(aio_send(transport, b"x", dst=0, tag=1, live=live))
        for _ in range(3):
            sched.ping()
        live.stop()
        sched.wait()
        assert transport.cancelled  # in-flight send released (reference README:71)

    def test_recv_cancelled_while_probing(self):
        transport = FakeTransport()  # nothing ever arrives
        sched = Scheduler()
        live = LiveFlag()
        task = sched.spawn(aio_recv(transport, src=0, tag=1, live=live))
        sched.ping()
        live.stop()
        sched.wait()
        assert task.state == DONE
        assert task.result is None


class TestAioRecvPostsEarly:
    """``aio_recv`` with ``out`` posts its receive before the message
    arrives, on a real shm endpoint pair; the message is five rings long,
    so "part-way" is a state the test can hold."""

    RING = 1 << 20
    BIG = 5 << 20

    @pytest.fixture
    def wire(self):
        from mpit_tpu.comm.shm import ShmTransport

        ns = f"t_aio_{os.getpid()}"
        a, b = (ShmTransport(ns, r, 2, ring_bytes=self.RING) for r in (0, 1))
        data = np.random.default_rng(0).integers(0, 256, self.BIG,
                                                 dtype=np.uint8)
        yield a, b, data
        a.close()
        b.close()

    def test_completes_for_a_message_that_arrives_after_the_post(self, wire):
        a, b, data = wire
        out = np.zeros_like(data)
        sched = Scheduler()
        recv = sched.spawn(aio_recv(b, 0, 4, out=out))
        for _ in range(3):
            sched.ping()
        assert recv.state != DONE  # posted, nothing has arrived
        sched.spawn(aio_send(a, data, 1, 4))
        sched.wait()
        assert recv.result is out
        np.testing.assert_array_equal(out, data)
        assert b.rx_path_bytes() == {"rx_direct_bytes": self.BIG,
                                     "rx_assembled_bytes": 0}

    @pytest.mark.parametrize("how", ["abort", "deadline", "live", "closed"])
    def test_giving_up_part_way_neither_loses_nor_tears(self, wire, how):
        a, b, data = wire
        out = np.zeros_like(data)
        sched = Scheduler()
        live = LiveFlag()
        gone = []
        recv = sched.spawn(aio_recv(
            b, 0, 4, out=out, live=live,
            abort=lambda: how == "abort" and bool(gone),
            deadline=time.monotonic() + 0.2 if how == "deadline" else None))
        hs = a.isend(data, 1, 4)
        sched.ping()  # drains the first ring into ``out``
        assert out.any() and recv.state != DONE and not a.test(hs)
        gone.append(True)
        if how == "live":
            live.io = False
        if how == "deadline":
            time.sleep(0.25)
        if how == "closed":
            recv.gen.close()  # a generator dropped without a word
        elif how == "deadline":
            with pytest.raises(TaskError) as failed:
                sched.wait()
            assert isinstance(failed.value.cause, DeadlineExceeded)
        else:
            sched.wait()
            assert recv.state == DONE and recv.result is None
        out[:] = 0
        # The next receive gets the message whole, bit for bit, and the
        # buffer that was given up is not written again.
        again = np.zeros_like(data)
        sched = Scheduler()
        nxt = sched.spawn(aio_recv(b, 0, 4, out=again))
        while not (a.test(hs) and nxt.state == DONE):
            sched.ping()
        np.testing.assert_array_equal(again, data)
        assert not out.any()

    @pytest.mark.parametrize("with_out", [True, False])
    def test_request_leaves_once_the_receive_is_posted(self, with_out):
        """With ``out`` the receive is posted before the request that the
        message answers is sent; without it there is nothing to post
        yet, and the request goes first."""
        from mpit_tpu.comm.local import LocalRouter, LocalTransport

        calls = []

        class Recording(LocalTransport):
            def isend(self, data, dst, tag):
                calls.append("isend")
                return super().isend(data, dst, tag)

            def irecv(self, src, tag, out=None):
                calls.append("irecv")
                return super().irecv(src, tag, out=out)

        router = LocalRouter(2)
        asker, peer = Recording(router, 0), router.endpoint(1)
        out = np.zeros(4, np.uint8) if with_out else None
        sched = Scheduler()
        recv = sched.spawn(aio_recv(
            asker, 1, 5, out=out, request=aio_send(asker, b"", 1, 4)))

        def answer():
            yield from aio_recv(peer, 0, 4)
            yield from aio_send(peer, np.arange(4, dtype=np.uint8), 0, 5)

        sched.spawn(answer())
        sched.wait()
        assert calls == (["irecv", "isend"] if with_out
                         else ["isend", "irecv"])
        assert bytes(recv.result) == bytes(range(4))


def idle_rounds(sched, passes):
    """``passes`` passes over a queue whose one task never finishes:
    each ends in the back-off sleep."""

    def forever():
        while True:
            yield EXEC

    sched.spawn(forever())
    for _ in range(passes):
        assert not sched.ping_pass()


class TestBackoffIsMeasured:
    """``ping_pass``'s back-off sleep is timed by the span recorder and
    only while it records (PR 34): ``Scheduler.sleep_s`` and
    ``mpit_aio_idle_seconds_total`` are what the sleeps took, not the
    ``idle_usec`` they were asked for."""

    def test_recording_counts_what_the_sleeps_took(self):
        from mpit_tpu import obs

        obs.configure(enabled=True, reset=True)
        try:
            sched = Scheduler(idle_usec=2000)
            t0 = time.monotonic()
            idle_rounds(sched, 10)
            wall = time.monotonic() - t0
            # a sleep is never shorter than asked and the passes
            # themselves are next to nothing
            assert 10 * 2000e-6 <= sched.sleep_s <= wall
            assert sched.sleep_s >= 0.9 * wall
            counter = obs.get_registry().counter(
                "mpit_aio_idle_seconds_total")
            assert counter.value == pytest.approx(sched.sleep_s)
            assert sched._idle_accum == pytest.approx(sched.sleep_s)
        finally:
            obs.configure(enabled=None, reset=True)

    def test_off_sleeps_as_long_and_reads_no_clock(self, monkeypatch):
        from mpit_tpu.aio import scheduler as scheduler_mod
        from mpit_tpu.obs import spans as obs_spans

        sched = Scheduler(idle_usec=2000)
        assert sched._rec is obs_spans.NULL_RECORDER
        reads = []
        real = time.monotonic
        slept = []
        monkeypatch.setattr(obs_spans.time, "monotonic",
                            lambda: reads.append(1) or real())
        monkeypatch.setattr(scheduler_mod.time, "sleep", slept.append)
        idle_rounds(sched, 10)
        assert slept == [2000e-6] * 10
        assert not reads and sched.sleep_s == 0.0
        assert sched._idle_accum == 0.0
