"""Tests for the native C++ shm transport: in-process endpoint pairs, the
chunking path, and real multi-process runs (the mpirun-analog shape).
"""

import os
import struct
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from mpit_tpu import obs
from mpit_tpu.comm import shm
from mpit_tpu.comm.shm import ShmTransport

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def pair(ns, ring_bytes=1 << 20):
    return (
        ShmTransport(ns, 0, 2, ring_bytes=ring_bytes),
        ShmTransport(ns, 1, 2, ring_bytes=ring_bytes),
    )


class TestShmTransport:
    def test_roundtrip_array(self):
        a, b = pair(f"t_rt_{os.getpid()}")
        try:
            data = np.arange(32, dtype=np.float32)
            a.send(data, 1, 3)
            out = np.zeros_like(data)
            b.recv(0, 3, out=out)
            np.testing.assert_array_equal(out, data)
        finally:
            a.close()
            b.close()

    def test_payload_without_buffer(self):
        a, b = pair(f"t_nb_{os.getpid()}")
        try:
            a.send(b"hello-wire", 1, 9)
            while not b.iprobe(0, 9):
                pass
            assert b.recv(0, 9) == b"hello-wire"
        finally:
            a.close()
            b.close()

    def test_chunked_larger_than_ring(self):
        """5 MB message through a 1 MB ring: chunks stream as the receiver
        drains — the path 640 MB reference payloads rely on (ptest.lua:3)."""
        a, b = pair(f"t_ch_{os.getpid()}")
        try:
            big = np.random.default_rng(0).standard_normal(5 * 1024 * 128)
            hs = a.isend(big, 1, 4)
            out = np.zeros_like(big)
            hr = b.irecv(0, 4, out=out)
            spins = 0
            # Poll BOTH sides each round: the sender can only finish as the
            # receiver drains the ring (message is 5x the ring size).
            while True:
                send_done = a.test(hs)
                recv_done = b.test(hr)
                if send_done and recv_done:
                    break
                spins += 1
                assert spins < 10**6
            np.testing.assert_array_equal(out, big)
        finally:
            a.close()
            b.close()

    def test_zero_byte_header_ack(self):
        a, b = pair(f"t_zb_{os.getpid()}")
        try:
            a.send(b"", 1, 5)
            assert b.iprobe(0, 5)
            assert b.recv(0, 5) == b""
        finally:
            a.close()
            b.close()

    def test_size_mismatch_raises(self):
        a, b = pair(f"t_sm_{os.getpid()}")
        try:
            a.send(np.ones(4, np.float32), 1, 6)
            while not b.iprobe(0, 6):
                pass
            handle = b.irecv(0, 6, out=np.zeros(3, np.float32))
            with pytest.raises(ValueError, match="size mismatch"):
                while not b.test(handle):
                    pass
        finally:
            a.close()
            b.close()

    def test_tag_isolation(self):
        a, b = pair(f"t_ti_{os.getpid()}")
        try:
            a.send(np.full(2, 1.0, np.float32), 1, 11)
            a.send(np.full(2, 2.0, np.float32), 1, 12)
            out12 = np.zeros(2, np.float32)
            b.recv(0, 12, out=out12)  # later tag first: no head-of-line block
            out11 = np.zeros(2, np.float32)
            b.recv(0, 11, out=out11)
            assert out12[0] == 2.0 and out11[0] == 1.0
        finally:
            a.close()
            b.close()

    def test_fifo_per_channel(self):
        a, b = pair(f"t_ff_{os.getpid()}")
        try:
            for i in range(5):
                a.send(np.full(1, float(i), np.float32), 1, 7)
            got = []
            for _ in range(5):
                out = np.zeros(1, np.float32)
                b.recv(0, 7, out=out)
                got.append(float(out[0]))
            assert got == [0.0, 1.0, 2.0, 3.0, 4.0]
        finally:
            a.close()
            b.close()

    def test_cancel_releases(self):
        a, b = pair(f"t_cx_{os.getpid()}")
        try:
            handle = b.irecv(0, 99, out=np.zeros(1, np.float32))
            b.cancel(handle)
            assert handle.cancelled and not b.test(handle)
        finally:
            a.close()
            b.close()

    def test_wtime_monotonic(self):
        t0 = ShmTransport.wtime()
        t1 = ShmTransport.wtime()
        assert t1 >= t0


class TestShmCancelAndProbe:
    """Focused coverage for ShmTransport.cancel/iprobe (comm/shm.py) —
    the shutdown path (reference init.lua:50-58) and the probe-then-recv
    rendezvous the aio schedulers rely on."""

    def test_iprobe_lifecycle(self):
        """False before arrival, true once assembled, false after the
        matching recv drains it."""
        a, b = pair(f"t_ip_{os.getpid()}")
        try:
            assert not b.iprobe(0, 31)
            a.send(np.ones(4, np.float32), 1, 31)
            while not b.iprobe(0, 31):
                pass
            assert b.iprobe(0, 31)  # idempotent: probing consumes nothing
            out = np.zeros(4, np.float32)
            b.recv(0, 31, out=out)
            assert not b.iprobe(0, 31)
        finally:
            a.close()
            b.close()

    def test_iprobe_is_src_and_tag_selective(self):
        a, b = pair(f"t_is_{os.getpid()}")
        try:
            a.send(b"x", 1, 41)
            while not b.iprobe(0, 41):
                pass
            assert not b.iprobe(0, 42)  # different tag
            assert not a.iprobe(1, 41)  # different endpoint/direction
        finally:
            a.close()
            b.close()

    def test_cancelled_recv_leaves_message_for_next_recv(self):
        """cancel releases the native op; the queued message must still
        serve a later correctly-posted receive."""
        a, b = pair(f"t_cl_{os.getpid()}")
        try:
            pending = b.irecv(0, 51, out=np.zeros(2, np.float32))
            b.cancel(pending)
            a.send(np.asarray([3.0, 4.0], np.float32), 1, 51)
            out = np.zeros(2, np.float32)
            b.recv(0, 51, out=out)
            np.testing.assert_array_equal(out, [3.0, 4.0])
            assert pending.cancelled and not b.test(pending)
        finally:
            a.close()
            b.close()

    def test_cancel_after_completion_keeps_done(self):
        """cancel on a tested-done handle is a no-op for correctness:
        test stays True (idempotent completion caching) and nothing
        double-releases natively."""
        a, b = pair(f"t_cd_{os.getpid()}")
        try:
            data = np.ones(2, np.float32)
            hs = a.isend(data, 1, 61)
            out = np.zeros(2, np.float32)
            hr = b.irecv(0, 61, out=out)
            while not (a.test(hs) and b.test(hr)):
                pass
            a.cancel(hs)
            b.cancel(hr)
            assert a.test(hs) and b.test(hr)
            np.testing.assert_array_equal(out, data)
        finally:
            a.close()
            b.close()

    def test_cancelled_send_ownership_released(self):
        """cancel drops the transport's buffer reference (the liveness
        contract's release half) and test reports not-done."""
        a, b = pair(f"t_co_{os.getpid()}")
        try:
            # Clog the 64 KiB ring so the second send stays in flight.
            big = np.ones(1 << 16, np.uint8)
            h1 = a.isend(big, 1, 71)
            h2 = a.isend(np.ones(8, np.float32), 1, 72)
            a.cancel(h2)
            assert h2.cancelled and h2.buf is None
            assert not a.test(h2)
            # The clogged first message still completes once drained.
            out = np.zeros(1 << 16, np.uint8)
            b.recv(0, 71, out=out)
            while not a.test(h1):
                pass
        finally:
            a.close()
            b.close()

    def test_non_contiguous_send_rejected(self):
        """Satellite regression (zero-copy rule): the shm transport must
        refuse a non-contiguous send buffer like as_bytes_view does, not
        silently detach from the caller's memory."""
        a, b = pair(f"t_nc_{os.getpid()}")
        try:
            with pytest.raises(ValueError, match="C-contiguous"):
                a.isend(np.arange(16, dtype=np.float32)[::2], 1, 81)
        finally:
            a.close()
            b.close()


def spin(*steps, limit=10**6):
    """Poll every side until all steps are true: a sender finishes only
    as the receiver drains (messages here are several rings long)."""
    spins = 0
    while not all([step() for step in steps]):
        spins += 1
        assert spins < limit


def noise(seed, nbytes):
    return np.random.default_rng(seed).integers(0, 256, nbytes, dtype=np.uint8)


class TestPostedRecvLandsInPlace:
    """A receive posted with a buffer before its message arrives is bound
    when the message's first chunk is drained, and the chunks go from the
    ring into that buffer; everything else is assembled and copied out by
    the test that takes it (transport.cpp ``posted_recv``).  Messages are
    five 1 MB rings long unless a case says otherwise."""

    RING = 1 << 20
    BIG = 5 << 20

    def pair(self, name, nranks=2):
        ns = f"t_pr_{name}_{os.getpid()}"
        return [ShmTransport(ns, r, nranks, ring_bytes=self.RING)
                for r in range(nranks)]

    def test_posted_before_arrival_lands_direct(self):
        a, b = self.pair("direct")
        try:
            data = noise(1, self.BIG)
            out = np.zeros_like(data)
            hr = b.irecv(0, 4, out=out)
            assert not b.test(hr)
            hs = a.isend(data, 1, 4)
            spin(lambda: a.test(hs), lambda: b.test(hr))
            np.testing.assert_array_equal(out, data)
            assert b.rx_path_bytes() == {"rx_direct_bytes": self.BIG,
                                         "rx_assembled_bytes": 0}
        finally:
            a.close()
            b.close()

    def test_small_and_empty_messages_land_direct_too(self):
        """No size threshold: what decides is whether a buffer waits."""
        a, b = self.pair("small")
        try:
            out = np.zeros(3, np.float32)
            hr = b.irecv(0, 4, out=out)
            he = b.irecv(0, 5, out=bytearray())
            a.send(np.asarray([1, 2, 3], np.float32), 1, 4)
            a.send(b"", 1, 5)
            spin(lambda: b.test(hr), lambda: b.test(he))
            np.testing.assert_array_equal(out, [1, 2, 3])
            assert b.rx_path_bytes() == {"rx_direct_bytes": 12,
                                         "rx_assembled_bytes": 0}
        finally:
            a.close()
            b.close()

    @pytest.mark.parametrize("when", ["whole", "half_way"])
    def test_posted_late_is_assembled(self, when):
        """Posted after the message was assembled, or half-way through
        its arrival: bit-equal, by the assembly buffer."""
        a, b = self.pair(f"late_{when}")
        try:
            data = noise(2, self.BIG)
            hs = a.isend(data, 1, 4)
            if when == "whole":
                spin(lambda: a.test(hs), lambda: b.iprobe(0, 4))
            else:
                assert not b.iprobe(0, 4)  # drains the first ring
                assert not a.test(hs)
            out = np.zeros_like(data)
            hr = b.irecv(0, 4, out=out)
            spin(lambda: a.test(hs), lambda: b.test(hr))
            np.testing.assert_array_equal(out, data)
            assert b.rx_path_bytes() == {"rx_direct_bytes": 0,
                                         "rx_assembled_bytes": self.BIG}
        finally:
            a.close()
            b.close()

    def test_two_posted_take_two_messages_in_order(self):
        a, b = self.pair("order")
        try:
            first, second = noise(3, self.BIG), noise(4, self.BIG)
            out1, out2 = np.zeros_like(first), np.zeros_like(second)
            h1 = b.irecv(0, 4, out=out1)
            h2 = b.irecv(0, 4, out=out2)
            s1 = a.isend(first, 1, 4)
            s2 = a.isend(second, 1, 4)
            # Polling the younger receive first must not hand it the
            # older message: the match is made in the order of posting.
            spin(lambda: a.test(s1), lambda: a.test(s2),
                 lambda: b.test(h2), lambda: b.test(h1))
            np.testing.assert_array_equal(out1, first)
            np.testing.assert_array_equal(out2, second)
            assert b.rx_path_bytes()["rx_direct_bytes"] == 2 * self.BIG
        finally:
            a.close()
            b.close()

    def test_message_queued_ahead_keeps_the_order(self):
        """A whole message waits on the channel when the receives are
        posted: the first receive takes it, and the message that arrives
        next is not bound past it."""
        a, b = self.pair("ahead")
        try:
            first, second = noise(5, 1 << 16), noise(6, 1 << 16)
            a.send(first, 1, 4)
            while not b.iprobe(0, 4):
                pass
            out1, out2 = np.zeros_like(first), np.zeros_like(second)
            h1 = b.irecv(0, 4, out=out1)
            h2 = b.irecv(0, 4, out=out2)
            a.send(second, 1, 4)
            spin(lambda: b.test(h1), lambda: b.test(h2))
            np.testing.assert_array_equal(out1, first)
            np.testing.assert_array_equal(out2, second)
        finally:
            a.close()
            b.close()

    def test_other_tag_and_other_source_do_not_disturb(self):
        a, b, c = self.pair("beside", nranks=3)
        try:
            data = noise(7, self.BIG)
            other_tag, other_src = noise(8, self.BIG), noise(9, self.BIG)
            out = np.zeros_like(data)
            hr = b.irecv(0, 4, out=out)
            sends = [(a, a.isend(other_tag, 1, 5)), (c, c.isend(other_src, 1, 4)),
                     (a, a.isend(data, 1, 4))]
            spin(lambda: b.test(hr),
                 *[lambda t=t, h=h: t.test(h) for t, h in sends],
                 lambda: b.iprobe(0, 5), lambda: b.iprobe(2, 4))
            np.testing.assert_array_equal(out, data)
            got_tag, got_src = np.zeros_like(data), np.zeros_like(data)
            b.recv(0, 5, out=got_tag)
            b.recv(2, 4, out=got_src)
            np.testing.assert_array_equal(got_tag, other_tag)
            np.testing.assert_array_equal(got_src, other_src)
            assert b.rx_path_bytes() == {"rx_direct_bytes": self.BIG,
                                         "rx_assembled_bytes": 2 * self.BIG}
        finally:
            for t in (a, b, c):
                t.close()

    @pytest.mark.parametrize("when", ["part_way", "whole_uncollected",
                                      "one_chunk_uncollected"])
    def test_cancel_leaves_the_message_whole(self, when):
        """Cancelled with one ring of the message in its buffer, or with
        all of it there (five rings, or one chunk) and no ``test`` having
        said so: the next receive gets the message, and the cancelled
        buffer is left alone."""
        a, b = self.pair(f"cancel_{when}")
        try:
            data = noise(10, 1000 if when.startswith("one_chunk") else self.BIG)
            out = np.zeros_like(data)
            hr = b.irecv(0, 4, out=out)
            hs = a.isend(data, 1, 4)
            if when == "part_way":
                assert not b.test(hr)  # one ring of it has landed
                landed = int(np.flatnonzero(out)[-1]) + 1
                assert 0 < landed < self.BIG
            else:  # b makes progress through calls about another channel
                spin(lambda: a.test(hs), lambda: not b.iprobe(0, 9)
                     and b.rx_path_bytes()["rx_direct_bytes"] == data.nbytes)
                landed = data.nbytes
            np.testing.assert_array_equal(out[:landed], data[:landed])
            b.cancel(hr)
            out[:] = 0
            again = np.zeros_like(data)
            hr2 = b.irecv(0, 4, out=again)
            spin(lambda: a.test(hs), lambda: b.test(hr2))
            np.testing.assert_array_equal(again, data)
            assert not out.any()  # nothing wrote to it after the cancel
            assert hr.cancelled and not b.test(hr)
            if when == "part_way":
                assert b.rx_path_bytes() == {"rx_direct_bytes": 0,
                                             "rx_assembled_bytes": self.BIG}
        finally:
            a.close()
            b.close()

    def test_send_cancelled_part_way_frees_the_bound_recv(self):
        """The sender gives a message up half-placed (a framed op's
        deadline) and sends it again: the receive bound to the torn one
        takes the retry, whole."""
        a, b = self.pair("torn")
        try:
            torn, retry = noise(11, self.BIG), noise(12, self.BIG)
            out = np.zeros_like(retry)
            hr = b.irecv(0, 4, out=out)
            hs = a.isend(torn, 1, 4)
            assert not b.test(hr) and not a.test(hs)
            a.cancel(hs)
            hs = a.isend(retry, 1, 4)
            spin(lambda: a.test(hs), lambda: b.test(hr))
            np.testing.assert_array_equal(out, retry)
            assert b.rx_path_bytes()["rx_direct_bytes"] == self.BIG
        finally:
            a.close()
            b.close()

    def test_wrong_size_posted_early_raises_and_message_survives(self):
        a, b = self.pair("size")
        try:
            data = noise(13, self.BIG)
            small = np.zeros(self.BIG - 4, np.uint8)
            hr = b.irecv(0, 4, out=small)
            hs = a.isend(data, 1, 4)
            with pytest.raises(ValueError, match="size mismatch"):
                spin(lambda: a.test(hs) and False, lambda: b.test(hr))
            assert not small.any()
            out = np.zeros_like(data)
            hr2 = b.irecv(0, 4, out=out)
            spin(lambda: a.test(hs), lambda: b.test(hr2))
            np.testing.assert_array_equal(out, data)
        finally:
            a.close()
            b.close()

    def test_dropped_handle_keeps_its_buffer_alive(self):
        """The drain writes to a posted buffer whoever still holds the
        Handle, so the endpoint holds it until done or cancelled."""
        import gc
        import weakref

        a, b = self.pair("alive")
        try:
            out = np.zeros(self.BIG, np.uint8)
            ref = weakref.ref(out)
            b.irecv(0, 4, out=out)
            del out
            gc.collect()
            assert ref() is not None
            hs = a.isend(noise(14, self.BIG), 1, 4)
            spin(lambda: a.test(hs),
                 lambda: not b.iprobe(0, 9)  # any call of b's makes progress
                 and b.rx_path_bytes()["rx_direct_bytes"] == self.BIG)
        finally:
            a.close()
            b.close()


class TestFilledFollowsTheLanding:
    """``filled(handle)``: how far a posted receive's buffer is filled
    from its front (transport.cpp ``mt_recv_filled``, the mirror of
    ``written``): it moves with the chunks, in order, where the message
    lands in the buffer itself, says nothing of a message that is
    assembled elsewhere until it is whole, and goes negative where what
    it said no longer holds.  Messages are five 1 MB rings long."""

    RING = 1 << 20
    BIG = 5 << 20

    def pair(self, name):
        ns = f"t_fl_{name}_{os.getpid()}"
        return [ShmTransport(ns, r, 2, ring_bytes=self.RING)
                for r in range(2)]

    def test_it_moves_in_order_with_the_senders_chunks(self):
        a, b = self.pair("moves")
        try:
            data = noise(21, self.BIG)
            out = np.zeros_like(data)
            hr = b.irecv(0, 4, out=out)
            assert b.filled(hr) == 0  # no message is bound to it yet
            hs = a.isend(data, 1, 4)
            marks = [0]
            for _ in range(10**6):
                a.test(hs)
                done = b.test(hr)
                mark = b.filled(hr)
                assert mark >= marks[-1]  # never back
                assert b.filled(hr) == mark  # a read, no progress
                # every byte below the mark is the message's, for good
                np.testing.assert_array_equal(out[marks[-1]:mark],
                                              data[marks[-1]:mark])
                marks.append(mark)
                if done:
                    break
            assert marks[-1] == self.BIG == b.filled(hr)
            # it said so on the way: the message is five rings long
            assert len({m for m in marks if 0 < m < self.BIG}) >= 3
            np.testing.assert_array_equal(out, data)
        finally:
            a.close()
            b.close()

    @pytest.mark.parametrize("nbytes", [1, 1000])
    def test_a_message_of_one_chunk_is_all_there_or_not_at_all(self, nbytes):
        a, b = self.pair(f"one_{nbytes}")
        try:
            data = noise(22, nbytes)
            out = np.zeros_like(data)
            hr = b.irecv(0, 4, out=out)
            assert b.filled(hr) == 0
            a.send(data, 1, 4)
            spin(lambda: b.test(hr))
            assert b.filled(hr) == nbytes
        finally:
            a.close()
            b.close()

    def test_an_assembled_message_reads_0_until_it_is_whole(self):
        """Posted half-way through the message's arrival: it goes by the
        assembly buffer, and nothing of it is in ``out`` before the
        ``test`` that hands it over."""
        a, b = self.pair("assembled")
        try:
            data = noise(23, self.BIG)
            hs = a.isend(data, 1, 4)
            assert not b.iprobe(0, 4)  # drains the first ring
            out = np.zeros_like(data)
            hr = b.irecv(0, 4, out=out)
            polls = 0
            while not b.test(hr):
                a.test(hs)
                assert b.filled(hr) == 0 and not out.any()
                polls += 1
                assert polls < 10**6
            assert polls > 0
            assert b.filled(hr) == self.BIG
            np.testing.assert_array_equal(out, data)
            assert b.rx_path_bytes()["rx_assembled_bytes"] == self.BIG
        finally:
            a.close()
            b.close()

    def test_it_survives_cancel(self):
        """A receive cancelled with one ring of its message landed: the
        message moves to an assembly buffer, so the cancelled handle
        says nothing (negative) and the next receive reads 0 until the
        message is whole in its buffer."""
        a, b = self.pair("cancel")
        try:
            data = noise(24, self.BIG)
            out = np.zeros_like(data)
            hr = b.irecv(0, 4, out=out)
            hs = a.isend(data, 1, 4)
            assert not b.test(hr)
            landed = b.filled(hr)
            assert 0 < landed < self.BIG
            np.testing.assert_array_equal(out[:landed], data[:landed])
            b.cancel(hr)
            assert b.filled(hr) < 0
            again = np.zeros_like(data)
            hr2 = b.irecv(0, 4, out=again)
            while not b.test(hr2):
                a.test(hs)
                assert b.filled(hr2) == 0 and not again.any()
            assert b.filled(hr2) == self.BIG
            np.testing.assert_array_equal(again, data)
        finally:
            a.close()
            b.close()

    @pytest.mark.parametrize("asked_on_the_way", [True, False])
    def test_a_torn_message_takes_back_what_was_said(self, asked_on_the_way):
        """The sender gives a message up half-placed and sends another:
        the receive bound to the torn one takes the next from the front
        of the same buffer, so the bytes below the earlier mark were not
        the message's after all.  ``filled`` says so by going negative,
        and stays so when the receive is done, however late it is asked:
        whoever followed the landing takes the buffer whole."""
        a, b = self.pair(f"torn_{asked_on_the_way}")
        try:
            torn, retry = noise(25, self.BIG), noise(26, self.BIG)
            out = np.zeros_like(retry)
            hr = b.irecv(0, 4, out=out)
            hs = a.isend(torn, 1, 4)
            assert not b.test(hr) and not a.test(hs)
            said = b.filled(hr)
            assert 0 < said < self.BIG
            a.cancel(hs)
            hs = a.isend(retry, 1, 4)
            marks = []
            while not b.test(hr):
                a.test(hs)
                if asked_on_the_way:
                    marks.append(b.filled(hr))
            # the torn message's mark (what of it lay in the ring still
            # lands) while its bytes lie there untouched, then, from the
            # drain that began to write over them, negative
            back = marks.index(-1) if marks else 0
            assert marks[:back] == sorted(marks[:back])
            assert all(said <= m < self.BIG for m in marks[:back])
            assert marks[back:] == [-1] * (len(marks) - back)
            assert not asked_on_the_way or len(marks) > back
            assert b.filled(hr) < 0
            np.testing.assert_array_equal(out, retry)
        finally:
            a.close()
            b.close()

    def test_a_receive_torn_before_a_byte_landed_says_what_it_always_said(
            self):
        """Nothing had been said of the buffer, so nothing is taken back."""
        a, b = self.pair("untorn")
        try:
            data = noise(27, self.BIG)
            out = np.zeros_like(data)
            hr = b.irecv(0, 4, out=out)
            first = a.isend_pieces(self.BIG, 1, 4)  # a header, no byte
            a.test(first)
            a.cancel(first)
            hs = a.isend(data, 1, 4)
            spin(lambda: a.test(hs), lambda: b.test(hr))
            assert b.filled(hr) == self.BIG
            np.testing.assert_array_equal(out, data)
        finally:
            a.close()
            b.close()


class TestFollowTellsTheMark:
    """``follow(handle, told)``: the endpoint says a posted receive's mark
    itself, whenever it has moved, from whichever call made the progress
    (a receive's own polls are not the only calls that drain its ring),
    and once more from the ``test`` that finds it done."""

    RING = 1 << 20
    BIG = 5 << 20

    def pair(self, name):
        ns = f"t_fo_{name}_{os.getpid()}"
        return [ShmTransport(ns, r, 2, ring_bytes=self.RING)
                for r in range(2)]

    @pytest.mark.parametrize("by", ["its_own_test", "another_handles_test",
                                    "a_probe", "a_send"])
    def test_told_from_whichever_call_made_the_progress(self, by):
        a, b = self.pair(f"by_{by}")
        try:
            data = noise(31, self.BIG)
            out = np.zeros_like(data)
            hr = b.irecv(0, 4, out=out)
            told = []
            b.follow(hr, told.append)
            other = b.irecv(0, 7, out=np.zeros(8, np.uint8))  # never comes
            hs = a.isend(data, 1, 4)
            step = {
                "its_own_test": lambda: b.test(hr),
                "another_handles_test": lambda: b.test(other),
                "a_probe": lambda: b.iprobe(0, 9),
                "a_send": lambda: b.test(b.isend(b"x", 0, 9)),
            }[by]
            for _ in range(10**6):
                a.test(hs)
                step()
                if told and told[-1] == self.BIG:
                    break
                if by != "its_own_test" and b.filled(hr) == self.BIG:
                    assert b.test(hr)  # the one that finds it done says so
                # every byte below what was told is the message's
                upto = told[-1] if told else 0
                np.testing.assert_array_equal(out[:upto], data[:upto])
            assert told == sorted(set(told))  # only when it moved, never back
            assert told[-1] == self.BIG and len(told) >= 4
            assert b.test(hr) and b._followed == {}
            np.testing.assert_array_equal(out, data)
            b.cancel(other)
        finally:
            a.close()
            b.close()

    def test_nothing_is_told_after_cancel(self):
        a, b = self.pair("cancel")
        try:
            data = noise(32, self.BIG)
            out = np.zeros_like(data)
            hr = b.irecv(0, 4, out=out)
            told = []
            b.follow(hr, told.append)
            hs = a.isend(data, 1, 4)
            assert not b.test(hr)
            assert told and 0 < told[-1] < self.BIG
            b.cancel(hr)
            said = list(told)
            again = np.zeros_like(data)
            hr2 = b.irecv(0, 4, out=again)
            spin(lambda: a.test(hs), lambda: b.test(hr2))
            assert told == said and b._followed == {}
            np.testing.assert_array_equal(again, data)
        finally:
            a.close()
            b.close()

    def test_a_torn_message_is_told_once_as_negative(self):
        a, b = self.pair("torn")
        try:
            torn, retry = noise(33, self.BIG), noise(34, self.BIG)
            out = np.zeros_like(retry)
            hr = b.irecv(0, 4, out=out)
            told = []
            b.follow(hr, told.append)
            hs = a.isend(torn, 1, 4)
            assert not b.test(hr) and not a.test(hs)
            a.cancel(hs)
            hs = a.isend(retry, 1, 4)
            spin(lambda: a.test(hs), lambda: b.test(hr))
            back = told.index(-1)
            assert back > 0 and told[back:] == [-1]  # and never the size
            assert told[:back] == sorted(told[:back])
            np.testing.assert_array_equal(out, retry)
        finally:
            a.close()
            b.close()


SEND_PEER = textwrap.dedent(
    """
    import sys, numpy as np
    sys.path.insert(0, {repo!r})
    from mpit_tpu.comm.shm import ShmTransport
    t = ShmTransport({ns!r}, {rank}, {nranks}, ring_bytes={ring})
    data = np.random.default_rng({seed}).integers(0, 256, {nbytes}, dtype=np.uint8)
    t.send(data, {dst}, {tag})
    t.close()
    """
)


def send_peer(**fields):
    """A process that sends one seeded message and leaves."""
    return subprocess.Popen(
        [sys.executable, "-c", SEND_PEER.format(repo=REPO, **fields)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


@pytest.mark.parametrize("nranks,ring", [(3, 1 << 20), (4, 300_007),
                                         (6, 1 << 16)])
class TestPairRings:
    """One ring a (sender, owner) pair in the owner's segment, no lock:
    the sender alone moves ``head``, the owner alone ``tail``, chunk by
    chunk (transport.cpp ``drain_ring`` / ``pump_sends``).  A chunk and its
    header take a quarter of the ring, so 300,007 leaves 3 bytes over and
    every lap's chunks lie elsewhere and straddle the ring's end."""

    def gang(self, name, nranks, ring):
        ns = f"t_ring_{name}_{nranks}_{os.getpid()}"
        return ns, [ShmTransport(ns, r, nranks, ring_bytes=ring)
                    for r in range(nranks)]

    def test_two_producers_interleave_whole_and_in_order(self, nranks, ring):
        """Three messages each from two senders, every one several rings
        long, all in flight at once: each arrives whole, and a pair's in
        the order they were sent."""
        _, wires = self.gang("two", nranks, ring)
        try:
            owner, senders = wires[0], (1, nranks - 1)
            sizes = [3 * ring + 17, 2 * ring + 1, 5 * ring - 3]
            sent = {src: [noise(10 * src + k, n) for k, n in enumerate(sizes)]
                    for src in senders}
            outs = {src: [np.zeros_like(m) for m in sent[src]]
                    for src in senders}
            recvs = [owner.irecv(src, 4, out=out)
                     for src in senders for out in outs[src]]
            sends = [(wires[src], wires[src].isend(m, 0, 4))
                     for k in range(len(sizes)) for src in senders
                     for m in [sent[src][k]]]
            spin(*[lambda t=t, h=h: t.test(h) for t, h in sends],
                 *[lambda h=h: owner.test(h) for h in recvs])
            for src in senders:
                for out, msg in zip(outs[src], sent[src]):
                    np.testing.assert_array_equal(out, msg)
            assert owner.rx_path_bytes() == {
                "rx_direct_bytes": 2 * sum(sizes), "rx_assembled_bytes": 0}
        finally:
            for t in wires:
                t.close()

    def test_message_of_many_rings_streams_through(self, nranks, ring):
        """Forty rings and a bit, then a second message that starts where
        the first left off: the indices wrap many times, chunks straddle
        the ring's end, and a sender never gets more than a ring ahead."""
        _, wires = self.gang("long", nranks, ring)
        try:
            a, b = wires[1], wires[0]
            for seed, nbytes in ((20, 40 * ring + 12_345), (21, 3 * ring + 1)):
                data = noise(seed, nbytes)
                out = np.zeros_like(data)
                hr = b.irecv(1, 4, out=out)
                hs = a.isend(data, 0, 4)
                assert not a.test(hs)  # a ring's worth is out, no more
                assert a.ring_counters()["tx_ring_full"] > 0
                spin(lambda: a.test(hs), lambda: b.test(hr))
                np.testing.assert_array_equal(out, data)
            assert (b.ring_counters()["rx_chunks"]
                    == a.ring_counters()["tx_chunks"] >= 4 * 43)
        finally:
            for t in wires:
                t.close()

    def test_send_to_self(self, nranks, ring):
        _, wires = self.gang("self", nranks, ring)
        try:
            t = wires[nranks - 1]
            small, big = noise(30, 100), noise(31, 3 * ring + 5)
            out_small, out_big = np.zeros_like(small), np.zeros_like(big)
            recvs = [t.irecv(t.rank, 4, out=out_small),
                     t.irecv(t.rank, 4, out=out_big)]
            sends = [t.isend(small, t.rank, 4), t.isend(big, t.rank, 4)]
            spin(*[lambda h=h: t.test(h) for h in sends + recvs])
            np.testing.assert_array_equal(out_small, small)
            np.testing.assert_array_equal(out_big, big)
        finally:
            for t in wires:
                t.close()

    def test_killed_producer_leaves_the_inbox_usable(self, nranks, ring):
        """A sender process dies with its message part-way in (what it had
        published is landed, what it was copying was never published): the
        other sender's message arrives beside it, and the dead one's next
        incarnation carries on from the ring's head and its first message
        takes the receive the torn one was bound to."""
        ns, wires = self.gang("kill", nranks, ring)
        wires.pop(1).close()  # rank 1 lives in processes of its own
        try:
            owner, other = wires[0], wires[-1]
            nbytes = 8 * ring
            peer = dict(ns=ns, rank=1, nranks=nranks, ring=ring, dst=0, tag=4,
                        nbytes=nbytes)
            out = np.zeros(nbytes, np.uint8)
            hr = owner.irecv(1, 4, out=out)
            doomed = send_peer(seed=40, **peer)
            spin(lambda: owner.test(hr) or out[2 * ring] != 0
                 or out[2 * ring + 1] != 0, limit=10**8)
            assert not owner.test(hr)
            doomed.kill()
            doomed.wait(60)
            beside = noise(41, 2 * ring)
            got = np.zeros_like(beside)
            hb = owner.irecv(other.rank, 4, out=got)
            hs = other.isend(beside, 0, 4)
            spin(lambda: other.test(hs), lambda: owner.test(hb))
            np.testing.assert_array_equal(got, beside)
            assert not owner.test(hr)  # the torn message never completes
            again = send_peer(seed=42, **peer)
            spin(lambda: owner.test(hr), limit=10**8)
            assert again.wait(60) == 0
            np.testing.assert_array_equal(out, noise(42, nbytes))
        finally:
            for t in wires:
                t.close()

    def test_counters_say_who_waited_and_who_overlapped(self, nranks, ring):
        _, wires = self.gang("count", nranks, ring)
        try:
            a, b = wires[1], wires[0]
            zero = dict.fromkeys(("tx_chunks", "tx_ring_full", "rx_chunks",
                                  "rx_overlap_chunks", "tx_early_bytes",
                                  "tx_split_bytes", "rx_split_bytes"), 0)
            assert a.ring_counters() == b.ring_counters() == zero
            # One-chunk messages to a receiver that has nothing else to do:
            # ten chunks each side, no ring ever full, and no chunk copied
            # out while the sender copied in (it is the same thread here).
            for k in range(10):
                a.send(noise(50 + k, 1000), 0, 4)
                b.recv(1, 4, out=np.zeros(1000, np.uint8))
            assert a.ring_counters() == {**zero, "tx_chunks": 10}
            assert b.ring_counters() == {**zero, "rx_chunks": 10}
            # A receiver that sleeps: the sender finds the ring full.
            data = noise(60, 2 * ring)
            hs = a.isend(data, 0, 4)
            for _ in range(3):
                assert not a.test(hs)
            assert a.ring_counters()["tx_ring_full"] >= 3
            assert b.ring_counters()["rx_chunks"] == 10
            out = np.zeros_like(data)
            hr = b.irecv(1, 4, out=out)
            spin(lambda: a.test(hs), lambda: b.test(hr))
            np.testing.assert_array_equal(out, data)
            assert b.ring_counters()["rx_overlap_chunks"] == 0
        finally:
            for t in wires:
                t.close()

    def test_a_pair_that_never_talks_costs_nothing(self, nranks, ring):
        """A segment is ``nranks`` rings, but tmpfs backs only the pages
        that were touched: rank 1 talks to ranks 0 and 2 and they answer,
        so two rings' worth at rank 1 and one each at the other two."""
        ns, wires = self.gang("sparse", nranks, ring)
        try:
            hub = wires[1]
            data = noise(70, 2 * ring)
            for peer in (wires[0], wires[2]):
                for src, dst in ((hub, peer), (peer, hub)):
                    out = np.zeros_like(data)
                    hr = dst.irecv(src.rank, 4, out=out)
                    hs = src.isend(data, dst.rank, 4)
                    spin(lambda: src.test(hs), lambda: dst.test(hr))
            slack = 64 << 10  # the indices' page and the rings' edges
            for rank, touched in ((0, 1), (1, 2), (2, 1)):
                used = os.stat(f"/dev/shm/mt_{ns}_r{rank}").st_blocks * 512
                assert touched * ring <= used + slack, (rank, used)
                assert used <= touched * ring + slack, (rank, used)
        finally:
            for t in wires:
                t.close()


OVERLAP_RING = 1 << 20
OVERLAP_BYTES = 96 * OVERLAP_RING


class TestCopiesOverlap:
    def test_sender_and_receiver_copy_at_the_same_time(self):
        """One sender process, one receiver, one message of 96 rings: a
        chunk counts as overlapped if the sender published another while
        the receiver was copying it out, which a ring-wide lock round both
        copies makes impossible (nought then, by construction).  Alone on
        the eight-core CPU host 378-379 of the 385 chunks overlap (six
        runs), beside ten spinning processes 286-380; a quarter is the
        bound, so that a sender kept off its core for most of the message
        still passes beside the suite's other workers, and a lock does
        not."""
        ns = f"t_ovl_{os.getpid()}"
        b = ShmTransport(ns, 0, 2, ring_bytes=OVERLAP_RING)
        try:
            out = np.zeros(OVERLAP_BYTES, np.uint8)
            hr = b.irecv(1, 4, out=out)
            peer = send_peer(ns=ns, rank=1, nranks=2, ring=OVERLAP_RING,
                             dst=0, tag=4, nbytes=OVERLAP_BYTES, seed=80)
            spin(lambda: b.test(hr), limit=10**9)
            assert peer.wait(60) == 0
            np.testing.assert_array_equal(out, noise(80, OVERLAP_BYTES))
            counts = b.ring_counters()
            assert counts["rx_chunks"] == 4 * 96 + 1
            assert counts["rx_overlap_chunks"] >= counts["rx_chunks"] // 4, counts
        finally:
            b.close()


RING = 1 << 20
TIMED_BYTES = 8 * RING
#: transport.cpp ``ChunkHeader``: src, tag, msg_id, chunk_idx, nchunks,
#: chunk_bytes, total_bytes, pub_ns
CHUNK_HEADER = struct.Struct("<iiQIIQQQ")


def headers_in_ring(ns, owner, src, nranks=2, ring=RING):
    """The headers of the chunks that lie published and undrained in the
    ring ``src`` writes at ``owner``, read from the segment's file."""
    with open(f"/dev/shm/mt_{ns}_r{owner}", "rb") as fh:
        seg = fh.read()
    index = 64 + src * 128  # kIndexOffset, one RingIndex a sender
    head, = struct.unpack_from("<Q", seg, index)
    tail, = struct.unpack_from("<Q", seg, index + 64)
    data = ((64 + nranks * 128 + 4095) & ~4095) + src * ring
    out = []
    while tail < head:
        raw = bytes(seg[data + (tail + i) % ring]
                    for i in range(CHUNK_HEADER.size))
        out.append(CHUNK_HEADER.unpack(raw))
        tail += CHUNK_HEADER.size + out[-1][5]
    return out


@pytest.fixture
def obs_on():
    obs.configure(enabled=True, reset=True)
    try:
        yield obs.get_recorder()
    finally:
        obs.configure(enabled=None, reset=True)


def wire_spans(rec):
    return {sp.name: sp for sp in rec.spans if sp.cat == "wire"}


def tiles(span, waited):
    """A wire span's three parts over its flight."""
    a = span.args
    return (a["copy_ms"] + a[waited] + a["away_ms"]) / a["flight_ms"]


class TestWireTiming:
    """Where a message's time went (transport.cpp ``TxTiming`` /
    ``RxTiming``, comm/shm.py ``_wire_span``): kept only while the span
    recorder records, a record a message and end, tiled into copying,
    blocked on a full ring or starved by an empty one, and away."""

    def test_off_the_wire_reads_no_clock(self):
        """Obs off (the default): every chunk's ``pub_ns`` is 0, a
        finished op has no record, the endpoint's totals stay 0 and no
        span is made."""
        ns = f"t_toff_{os.getpid()}"
        a, b = pair(ns, RING)
        try:
            data = noise(90, TIMED_BYTES)
            out = np.zeros_like(data)
            recv = b.lib.mt_irecv(b._ctx, 0, 4, out, out.nbytes)
            send = a.lib.mt_isend(a._ctx, 1, 4, data, data.nbytes)
            heads = headers_in_ring(ns, owner=1, src=0)
            assert [h[3] for h in heads] == [0, 1, 2, 3]  # the ring, full
            assert all(h[6] == TIMED_BYTES and h[7] == 0 for h in heads)
            spin(lambda: a.lib.mt_test(a._ctx, send) == 1,
                 lambda: b.lib.mt_test(b._ctx, recv) == 1)
            np.testing.assert_array_equal(out, data)
            record = np.full(3 * 64, 7, np.uint64)
            assert a.lib.mt_op_timing(a._ctx, send, record) == 0
            assert b.lib.mt_op_timing(b._ctx, recv, record) == 0
            # no copy interval either, and no buffer was made for one
            assert a.lib.mt_op_intervals(a._ctx, send, record) == 0
            assert b.lib.mt_op_intervals(b._ctx, recv, record) == 0
            assert (record == 7).all()
            assert a._runs is None and b._runs is None
            assert a.lib.mt_ring_counts(a._ctx, 5) == 0
            assert b.lib.mt_ring_counts(b._ctx, 5) == 0
            zero = dict.fromkeys(("tx_copy", "rx_copy", "progress",
                                  "crew_copy", "crew_spin"), 0.0)
            assert a.wire_totals() == b.wire_totals() == zero
            assert not a._rec.enabled and a._rec.spans == ()
        finally:
            a.close()
            b.close()

    def test_on_both_ends_tile_the_flight(self, obs_on):
        """An 8 MB message through a 1 MB ring: one ``tx`` and one ``rx``
        span with the wire's own identity, every header stamped, and the
        three parts of each end sum to its flight."""
        ns = f"t_ton_{os.getpid()}"
        a, b = pair(ns, RING)
        try:
            data = noise(91, TIMED_BYTES)
            out = np.zeros_like(data)
            hr = b.irecv(0, 4, out=out)
            t0 = time.monotonic()
            hs = a.isend(data, 1, 4)
            heads = headers_in_ring(ns, owner=1, src=0)
            assert heads and all(h[7] > t0 * 1e9 for h in heads)
            assert [h[7] for h in heads] == sorted(h[7] for h in heads)
            spin(lambda: a.test(hs), lambda: b.test(hr))
            t1 = time.monotonic()
            np.testing.assert_array_equal(out, data)
            spans = wire_spans(obs_on)
            tx, rx = spans["tx"], spans["rx"]
            assert len(obs_on.spans) == 2
            assert tx.tid == "r0:wire:1:4:tx" and rx.tid == "r1:wire:0:4:rx"
            for span, me, peer in ((tx, 0, 1), (rx, 1, 0)):
                args = span.args
                assert (args["rank"], args["peer"], args["tag"]) == (
                    me, peer, 4)
                assert args["bytes"] == TIMED_BYTES
                assert not {"chunks", "refused", "overlap_chunks",
                            "direct"} & set(args)
                assert t0 <= span.t0 <= span.t1 <= t1
            assert tx.args["msg_id"] == rx.args["msg_id"] == 1
            # the message's 33 chunks, straight into the posted buffer:
            # the endpoints' own counts say it, not the span
            assert a.ring_counters()["tx_chunks"] == 33
            assert b.ring_counters()["rx_chunks"] == 33
            assert b.rx_path_bytes() == {"rx_direct_bytes": TIMED_BYTES,
                                         "rx_assembled_bytes": 0}
            assert tiles(tx, "blocked_ms") == pytest.approx(1.0, rel=0.01)
            assert tiles(rx, "starved_ms") == pytest.approx(1.0, rel=0.01)
            assert tx.args["flight_ms"] == pytest.approx(
                (tx.t1 - tx.t0) * 1e3, rel=1e-6)
            # first chunk published to message whole: the rx span opens
            # when its copy-out begins, inside that flight
            assert rx.args["flight_ms"] >= (rx.t1 - rx.t0) * 1e3
            # the endpoint's totals are the same copies
            assert a.wire_totals()["tx_copy"] == pytest.approx(
                tx.args["copy_ms"] / 1e3, rel=1e-6)
            assert b.wire_totals()["rx_copy"] == pytest.approx(
                rx.args["copy_ms"] / 1e3, rel=1e-6)
            for wire in (a, b):
                totals = wire.wire_totals()
                assert totals["progress"] >= (totals["tx_copy"]
                                              + totals["rx_copy"])
        finally:
            a.close()
            b.close()

    @pytest.mark.parametrize("ring, nbytes, merged", [
        (RING, TIMED_BYTES, False), (64 << 10, 4 << 20, True)])
    def test_copy_intervals_lie_in_the_flight_apart_and_bounded(
            self, obs_on, ring, nbytes, merged):
        """``copies``: when each end's thread was inside the ring copies,
        a run of chunks copied back to back in one pass an interval, in
        order and apart, inside the span, their bytes the message's and
        their lengths its ``copy_ms`` and what lay between the chunks of
        a run.  A 4 MB message through a 64 kB ring is 257 chunks and at
        most four of them a pass: past 64 intervals the two with the
        smallest gap between them are merged, and counted."""
        a, b = pair(f"t_truns_{ring}_{os.getpid()}", ring)
        try:
            data = noise(96, nbytes)
            out = np.zeros_like(data)
            hr = b.irecv(0, 4, out=out)
            hs = a.isend(data, 1, 4)
            spin(lambda: a.test(hs), lambda: b.test(hr))
            np.testing.assert_array_equal(out, data)
            spans = wire_spans(obs_on)
            for span in (spans["tx"], spans["rx"]):
                runs = span.args["copies"]
                assert 1 <= len(runs) <= 64
                assert (len(runs) == 64) == merged
                assert (span.args["copies_merged"] > 0) == merged
                assert span.t0 * 1e9 - 1 <= runs[0][0]
                assert runs[-1][1] <= span.t1 * 1e9 + 1
                assert all(b0 < e0 for b0, e0, _n in runs)
                assert all(x[1] <= y[0] for x, y in zip(runs, runs[1:]))
                assert sum(n for _b, _e, n in runs) == nbytes
                # the copies, and what lay between the chunks of a run (and
                # the gaps of merged intervals): at most the span's length
                copying = sum(e0 - b0 for b0, e0, _n in runs) / 1e6
                assert span.args["copy_ms"] <= copying + 1e-6
                assert copying <= (span.t1 - span.t0) * 1e3 + 1e-3
            # one buffer a message and end, made at its first timed chunk
            assert a.lib.mt_ring_counts(a._ctx, 5) == 1
            assert b.lib.mt_ring_counts(b._ctx, 5) == 1
        finally:
            a.close()
            b.close()

    def test_an_endpoint_says_what_its_transfers_stand_before(self):
        """``waiting``: from the native side's own state, with obs on or
        off: a receive no message has begun to land in, one that is
        landing, a send the ring has no room for, and a send of pieces
        with every appended byte placed."""
        a, b = pair(f"t_twait_{os.getpid()}", RING)
        try:
            assert a.waiting() == b.waiting() == ()
            data = noise(97, TIMED_BYTES)
            out = np.zeros_like(data)
            hr = b.irecv(0, 4, out=out)
            assert not b.test(hr) and b.waiting() == ("unanswered",)
            hs = a.isend(data, 1, 4)
            assert not a.test(hs) and a.waiting() == ("blocked",)
            assert not b.test(hr) and b.waiting() == ("partial",)
            spin(lambda: a.test(hs), lambda: b.test(hr))
            assert a.waiting() == b.waiting() == ()
            hp = a.isend_pieces(data.nbytes, 1, 5)
            assert not a.test(hp) and a.waiting() == ("unready",)
            a.append(hp, data)
            out2 = np.zeros_like(data)
            hr2 = b.irecv(0, 5, out=out2)
            spin(lambda: a.test(hp), lambda: b.test(hr2))
            assert a.waiting() == b.waiting() == ()
        finally:
            a.close()
            b.close()

    def test_a_receiver_that_pauses_blocks_the_sender(self, obs_on):
        """The ring fills, the owner sleeps 60 ms, then drains: the sender
        reads that as ``blocked_ms`` (and counts the refused polls), the
        owner as its own ``away_ms``; nobody starved."""
        a, b = pair(f"t_tblk_{os.getpid()}", RING)
        try:
            data = noise(92, TIMED_BYTES)
            out = np.zeros_like(data)
            hr = b.irecv(0, 4, out=out)
            hs = a.isend(data, 1, 4)
            for _ in range(3):
                assert not a.test(hs)
            time.sleep(0.06)
            spin(lambda: a.test(hs), lambda: b.test(hr))
            spans = wire_spans(obs_on)
            tx, rx = spans["tx"].args, spans["rx"].args
            assert tx["blocked_ms"] >= 55
            assert rx["away_ms"] >= 55
            assert tx["away_ms"] < 55 and rx["starved_ms"] < 55
            assert a.ring_counters()["tx_ring_full"] >= 3
            assert tiles(spans["tx"], "blocked_ms") == pytest.approx(
                1.0, rel=0.01)
            assert tiles(spans["rx"], "starved_ms") == pytest.approx(
                1.0, rel=0.01)
        finally:
            a.close()
            b.close()

    def test_a_sender_that_pauses_starves_the_receiver(self, obs_on):
        """The owner drains what the ring held, the sender sleeps 60 ms
        with the message partial and the ring empty: ``starved_ms`` on
        the receiver, ``away_ms`` on the sender; the ring was never
        found full after that."""
        a, b = pair(f"t_tstv_{os.getpid()}", RING)
        try:
            data = noise(93, TIMED_BYTES)
            out = np.zeros_like(data)
            hr = b.irecv(0, 4, out=out)
            hs = a.isend(data, 1, 4)
            for _ in range(3):
                assert not b.test(hr)  # the ring is empty after the first
            time.sleep(0.06)
            spin(lambda: a.test(hs), lambda: b.test(hr))
            spans = wire_spans(obs_on)
            tx, rx = spans["tx"].args, spans["rx"].args
            assert rx["starved_ms"] >= 55 and tx["away_ms"] >= 55
            assert tx["blocked_ms"] < 55 and rx["away_ms"] < 55
            assert tiles(spans["tx"], "blocked_ms") == pytest.approx(
                1.0, rel=0.01)
            assert tiles(spans["rx"], "starved_ms") == pytest.approx(
                1.0, rel=0.01)
        finally:
            a.close()
            b.close()

    @pytest.mark.parametrize("nbytes,spans", [((1 << 20) - 1, 0),
                                              (1 << 20, 2)])
    def test_only_messages_of_a_megabyte_get_a_span(self, obs_on, nbytes,
                                                    spans):
        """Acks and headers would be thousands of events a run: their
        time stays in the endpoint's totals."""
        a, b = pair(f"t_tmin_{nbytes}_{os.getpid()}", 16 * RING)
        try:
            data = noise(94, nbytes)
            out = np.zeros_like(data)
            hr = b.irecv(0, 4, out=out)
            hs = a.isend(data, 1, 4)
            spin(lambda: a.test(hs), lambda: b.test(hr))
            assert len(obs_on.spans) == spans
            assert a.wire_totals()["tx_copy"] > 0
            assert b.wire_totals()["rx_copy"] > 0
        finally:
            a.close()
            b.close()

    def test_an_assembled_message_counts_its_hand_over(self, obs_on):
        """No receive posted when the message arrives: it is assembled,
        waits for its taker (``away_ms``), and the ``memcpy`` that hands
        it over is copying too; ``rx_path_bytes`` says which way it went."""
        a, b = pair(f"t_tasm_{os.getpid()}", RING)
        try:
            data = noise(95, TIMED_BYTES)
            hs = a.isend(data, 1, 4)
            spin(lambda: a.test(hs) or b.iprobe(0, 4),
                 lambda: b.iprobe(0, 4))
            time.sleep(0.06)
            out = np.zeros_like(data)
            hr = b.irecv(0, 4, out=out)
            spin(lambda: a.test(hs), lambda: b.test(hr))
            np.testing.assert_array_equal(out, data)
            rx = wire_spans(obs_on)["rx"]
            assert rx.args["away_ms"] >= 55
            assert b.rx_path_bytes() == {"rx_direct_bytes": 0,
                                         "rx_assembled_bytes": TIMED_BYTES}
            assert tiles(rx, "starved_ms") == pytest.approx(1.0, rel=0.01)
            assert b.wire_totals()["rx_copy"] == pytest.approx(
                rx.args["copy_ms"] / 1e3, rel=1e-6)
        finally:
            a.close()
            b.close()


ECHO_PEER = textwrap.dedent(
    """
    import sys, numpy as np
    sys.path.insert(0, {repo!r})
    from mpit_tpu.comm.shm import ShmTransport
    t = ShmTransport({ns!r}, 1, 2)
    out = np.zeros({n}, np.float32)
    t.recv(0, 21, out=out)
    t.send(out * 2.0, 0, 22)
    # hold until the send drains for sure (send() already blocks on test)
    t.close()
    """
)


class TestMultiProcess:
    def test_cross_process_echo(self):
        ns = f"t_mp_{os.getpid()}"
        n = 4096
        main = ShmTransport(ns, 0, 2)
        try:
            peer = subprocess.Popen(
                [sys.executable, "-c", ECHO_PEER.format(repo=REPO, ns=ns, n=n)],
                env={**os.environ, "JAX_PLATFORMS": "cpu"},
            )
            data = np.linspace(0, 1, n, dtype=np.float32)
            main.send(data, 1, 21)
            out = np.zeros(n, np.float32)
            main.recv(1, 22, out=out)
            np.testing.assert_allclose(out, data * 2.0, rtol=1e-6)
            assert peer.wait(60) == 0
        finally:
            main.close()


class TestBuild:
    """comm/native/build.py decides from content, not mtimes."""

    def test_rebuilds_on_hash_mismatch_though_library_is_newer(
            self, monkeypatch):
        from mpit_tpu.comm.native import build

        lib = build.ensure_built()
        assert build.STAMP.read_text().strip() == build.source_hash()
        calls = []
        real_run = subprocess.run
        monkeypatch.setattr(
            build.subprocess, "run",
            lambda cmd, **kw: calls.append(cmd) or real_run(cmd, **kw))
        build.ensure_built()
        assert not calls  # current: no compiler run
        # A library from other flags/another CPU: newer than the source,
        # recorded hash differs -> rebuilt, via a temporary name.
        build.STAMP.write_text("0" * 64 + "\n")
        os.utime(lib)  # mtime now >= the source's
        assert lib.stat().st_mtime >= build.SRC.stat().st_mtime
        build.ensure_built()
        (cmd,) = calls
        out = cmd[cmd.index("-o") + 1]
        assert out != str(lib) and os.path.dirname(out) == str(lib.parent)
        assert not os.path.exists(out)  # renamed into place
        assert build.STAMP.read_text().strip() == build.source_hash()


#: (cores the process may run on, ranks of the gang, helpers an endpoint)
RULE_CASES = [(1, 3, 0), (8, 3, 0), (13, 3, 1), (30, 6, 1), (20, 3, 2),
              (64, 3, 2)]

SPLIT_PEER = textwrap.dedent(
    """
    import sys, numpy as np
    sys.path.insert(0, {repo!r})
    from mpit_tpu.comm import shm
    shm.copy_helpers = lambda cores, ranks: {helpers}
    t = shm.ShmTransport({ns!r}, {rank}, 2, ring_bytes={ring})
    data = np.random.default_rng({seed}).integers(0, 256, {nbytes}, dtype=np.uint8)
    t.send(data, {dst}, 4)
    t.close()
    """
)


class TestSplitCopies:
    """A ring copy of ``shm.SPLIT_MIN_BYTES`` or more is cut into parts
    that the calling thread and the endpoint's helper threads copy at
    once (transport.cpp ``Crew``, ``copy_bytes``), fork and join inside
    ``circ_write`` / ``circ_read``: the bytes, the order of the chunks,
    ``filled`` and what a killed sender leaves behind are what they were,
    and the counters say how much was copied so.  The helpers' count is
    ``shm.copy_helpers(cores, ranks)``; the tests name it themselves.  A
    ring of 8 MB cuts chunks of 2 MB less a header, twice the threshold."""

    RING = 8 << 20
    CHUNK = RING // 4 - CHUNK_HEADER.size
    MIN = shm.SPLIT_MIN_BYTES

    @pytest.fixture(params=[0, 1, 3], ids=lambda n: f"helpers{n}")
    def helpers(self, request, monkeypatch):
        monkeypatch.setattr(shm, "copy_helpers",
                            lambda cores, ranks: request.param)
        return request.param

    @pytest.fixture
    def one_helper(self, monkeypatch):
        monkeypatch.setattr(shm, "copy_helpers", lambda cores, ranks: 1)

    def pair(self, name, ring=RING):
        ns = f"t_sp_{name}_{os.getpid()}"
        return [ShmTransport(ns, r, 2, ring_bytes=ring) for r in range(2)]

    @pytest.mark.parametrize("nbytes", [MIN - 1, MIN, MIN + 1, CHUNK + 1,
                                        3 * RING + 17],
                             ids=["under", "at", "over", "two_chunks",
                                  "three_rings"])
    def test_the_bytes_are_the_senders(self, helpers, nbytes):
        """Sizes on both sides of the threshold, and one that laps the
        ring three times, its chunks straddling the ring's end: a bound
        receive and, posted behind it, an assembled one."""
        a, b = self.pair(f"bytes_{helpers}_{nbytes}")
        try:
            first, second = noise(1, nbytes), noise(2, nbytes)
            out = np.zeros_like(first)
            hr = b.irecv(0, 4, out=out)
            sends = [a.isend(first, 1, 4), a.isend(second, 1, 4)]
            spin(lambda: a.test(sends[0]), lambda: b.test(hr))
            np.testing.assert_array_equal(out, first)
            while not b.iprobe(0, 4):  # the second is assembled meanwhile
                a.test(sends[1])
            late = np.zeros_like(second)
            b.recv(0, 4, out=late)
            np.testing.assert_array_equal(late, second)
            assert a.test(sends[1])
            assert b.rx_path_bytes() == {"rx_direct_bytes": nbytes,
                                         "rx_assembled_bytes": nbytes}
            tx = a.ring_counters()["tx_split_bytes"]
            rx = b.ring_counters()["rx_split_bytes"]
            if helpers == 0 or nbytes < self.MIN:
                assert tx == rx == 0
            else:  # all of it but a chunk's short end or a short chunk
                assert 2 * nbytes - 8 * self.MIN <= tx <= 2 * nbytes
                assert 2 * nbytes - 8 * self.MIN <= rx <= 2 * nbytes
                assert tx > 0 and rx > 0
            assert (a.ring_counters()["rx_split_bytes"]
                    == b.ring_counters()["tx_split_bytes"] == 0)
        finally:
            a.close()
            b.close()

    def test_a_send_of_pieces_whose_piece_ends_inside_a_chunk(self, helpers):
        """Pieces of 1.5 MB and 3 bytes, 2.7 MB, 300 kB and the rest,
        appended as the sender goes: a chunk is cut across them, so a
        chunk's copies are some over and some under the threshold."""
        a, b = self.pair(f"pieces_{helpers}")
        try:
            data = noise(3, 9 << 20)
            cuts = [0, (3 << 19) + 3, 4_400_000, 4_700_000, data.nbytes]
            out = np.zeros_like(data)
            hr = b.irecv(0, 4, out=out)
            hs = a.isend_pieces(data.nbytes, 1, 4)
            for lo, hi in zip(cuts, cuts[1:]):
                a.append(hs, data[lo:hi])
                for _ in range(3):
                    a.test(hs)
                    b.test(hr)
                assert b.filled(hr) <= hi
                np.testing.assert_array_equal(out[:b.filled(hr)],
                                              data[:b.filled(hr)])
            spin(lambda: a.test(hs), lambda: b.test(hr))
            np.testing.assert_array_equal(out, data)
            split = a.ring_counters()["tx_split_bytes"]
            assert split == 0 if helpers == 0 else 0 < split < data.nbytes
        finally:
            a.close()
            b.close()

    def test_filled_moves_in_order_and_by_whole_chunks(self, helpers):
        a, b = self.pair(f"filled_{helpers}")
        try:
            data = noise(4, 4 * self.CHUNK + 12_345)
            out = np.zeros_like(data)
            hr = b.irecv(0, 4, out=out)
            told = []
            b.follow(hr, told.append)
            hs = a.isend(data, 1, 4)
            spin(lambda: a.test(hs), lambda: b.test(hr))
            assert told == sorted(told) and told[-1] == data.nbytes
            assert set(told) <= {k * self.CHUNK for k in range(1, 5)} | {
                data.nbytes}
            np.testing.assert_array_equal(out, data)
        finally:
            a.close()
            b.close()

    def test_the_counters_are_the_large_messages_payload(self, one_helper):
        """Through a ring that nothing wraps in: every chunk of the two
        large messages is 4 MB or the rest, 1 MB at the least, and the
        small ones, the one under the threshold among them, are one
        ``memcpy`` on the caller's thread."""
        a, b = self.pair("counters", ring=64 << 20)
        try:
            sizes = [1, 1000, self.MIN - 1, 9 << 20, (4 << 20) + self.MIN]
            for seed, nbytes in enumerate(sizes):
                data, out = noise(seed, nbytes), np.zeros(nbytes, np.uint8)
                hr = b.irecv(0, 4, out=out)
                hs = a.isend(data, 1, 4)
                spin(lambda: a.test(hs), lambda: b.test(hr))
                np.testing.assert_array_equal(out, data)
            large = sum(n for n in sizes if n >= self.MIN)
            assert a.ring_counters()["tx_split_bytes"] == large
            assert b.ring_counters()["rx_split_bytes"] == large
            assert a.wire_counts()["tx_split_bytes"] == large
        finally:
            a.close()
            b.close()

    def test_a_wire_span_says_how_much_of_it_was_split(self, one_helper,
                                                       obs_on):
        a, b = self.pair("span", ring=64 << 20)
        try:
            for seed, nbytes in ((1, (8 << 20) + 1000), (2, self.MIN + 5)):
                data, out = noise(seed, nbytes), np.zeros(nbytes, np.uint8)
                hr = b.irecv(0, 4, out=out)
                hs = a.isend(data, 1, 4)
                spin(lambda: a.test(hs), lambda: b.test(hr))
            spans = [sp for sp in obs_on.spans if sp.cat == "wire"]
            assert sorted((sp.name, sp.args["bytes"], sp.args["split_bytes"])
                          for sp in spans) == sorted(
                (end, nbytes, split) for end in ("tx", "rx")
                for nbytes, split in (((8 << 20) + 1000, 8 << 20),
                                      (self.MIN + 5, self.MIN + 5)))
        finally:
            a.close()
            b.close()

    @pytest.mark.parametrize("helpers", [1, 3])
    def test_killed_inside_a_split_copy_has_published_nothing(
            self, helpers, monkeypatch):
        """The killed-producer case with both ends copying in parts: the
        sender dies while it streams 2 MB chunks with room in the ring
        (so inside a copy, as far as a signal can be aimed), and what the
        owner then finds published is whole chunks of the sender's bytes,
        no part of a chunk whose other parts never came; the dead one's
        next incarnation takes the receive the torn message was bound
        to."""
        monkeypatch.setattr(shm, "copy_helpers", lambda cores, ranks: helpers)
        ns = f"t_sp_kill_{helpers}_{os.getpid()}"
        owner = ShmTransport(ns, 0, 2, ring_bytes=self.RING)
        try:
            nbytes = 12 * self.RING
            peer = dict(repo=REPO, ns=ns, rank=1, ring=self.RING, dst=0,
                        nbytes=nbytes, helpers=helpers)

            def run(seed):
                return subprocess.Popen(
                    [sys.executable, "-c",
                     SPLIT_PEER.format(seed=seed, **peer)],
                    env={**os.environ, "JAX_PLATFORMS": "cpu"})

            out = np.zeros(nbytes, np.uint8)
            hr = owner.irecv(1, 4, out=out)
            doomed = run(40)
            spin(lambda: owner.test(hr) or owner.filled(hr) >= 3 * self.RING,
                 limit=10**8)
            doomed.kill()
            doomed.wait(60)
            for _ in range(8):  # drain whatever it had published
                assert not owner.test(hr)
            landed = owner.filled(hr)
            assert 3 * self.RING <= landed < nbytes
            assert landed % self.CHUNK == 0  # whole chunks, nothing else
            np.testing.assert_array_equal(out[:landed],
                                          noise(40, nbytes)[:landed])
            assert headers_in_ring(ns, 0, 1, ring=self.RING) == []
            again = run(42)
            spin(lambda: owner.test(hr), limit=10**8)
            assert again.wait(60) == 0
            np.testing.assert_array_equal(out, noise(42, nbytes))
            assert owner.filled(hr) < 0  # it was torn on the way
        finally:
            owner.close()

    def test_more_helpers_than_cores_copy_every_byte_once(self, monkeypatch):
        """Both directions at once with twice as many helpers an endpoint
        as the host has cores, so that helpers are late, asleep or off
        their core in the middle of a part: a part taken twice, lost or
        published before it was whole would show in the bytes."""
        crowd = 2 * len(os.sched_getaffinity(0))
        monkeypatch.setattr(shm, "copy_helpers", lambda cores, ranks: crowd)
        a, b = self.pair("crowd")
        try:
            there, back = noise(5, 6 * self.RING + 3), noise(6, 5 * self.RING)
            got_there, got_back = np.zeros_like(there), np.zeros_like(back)
            recvs = [(b, b.irecv(0, 4, out=got_there)),
                     (a, a.irecv(1, 4, out=got_back))]
            sends = [(a, a.isend(there, 1, 4)), (b, b.isend(back, 0, 4))]
            spin(*[lambda t=t, h=h: t.test(h) for t, h in sends + recvs],
                 limit=10**5)
            np.testing.assert_array_equal(got_there, there)
            np.testing.assert_array_equal(got_back, back)
            assert a.ring_counters()["tx_split_bytes"] > 5 * self.RING
        finally:
            a.close()
            b.close()

    @pytest.mark.parametrize("timed", [False, True], ids=["off", "timed"])
    def test_a_helper_keeps_its_own_time_only_while_the_wire_is_timed(
            self, one_helper, timed):
        """``mt_wire_ns`` 3 and 4 (``wire_totals`` ``crew_copy`` and
        ``crew_spin``): what the helper spent inside its parts and
        spinning with none, by its own readings of the clock; with the
        timing off (obs off) it reads no clock for them and both stay 0,
        and neither falls."""
        import time

        a, b = self.pair(f"crewns_{int(timed)}")
        try:
            for wire in (a, b):
                wire.lib.mt_set_timing(wire._ctx, int(timed))
            data = noise(9, 6 * self.RING)
            out = np.zeros_like(data)
            hr, hs = b.irecv(0, 4, out=out), a.isend(data, 1, 4)
            spin(lambda: a.test(hs), lambda: b.test(hr))
            np.testing.assert_array_equal(out, data)
            assert a.ring_counters()["tx_split_bytes"] > 0
            first = a.wire_totals()
            deadline = time.monotonic() + 10.0
            while timed and time.monotonic() < deadline and (
                    a.wire_totals()["crew_spin"] == first["crew_spin"]):
                time.sleep(0.005)  # it spins on past the last part
            after = a.wire_totals()
            if timed:
                assert first["crew_copy"] > 0.0
                assert after["crew_spin"] > 0.0
                assert after["crew_copy"] >= first["crew_copy"]
            else:
                assert first["crew_copy"] == first["crew_spin"] == 0.0
                assert after == first
        finally:
            a.close()
            b.close()

    @pytest.mark.parametrize("cores,ranks,count", RULE_CASES)
    def test_the_count_is_worked_out_from_the_cores_and_the_ranks(
            self, cores, ranks, count):
        assert shm.copy_helpers(cores, ranks) == count
