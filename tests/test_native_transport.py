"""Tests for the native C++ shm transport: in-process endpoint pairs, the
chunking path, and real multi-process runs (the mpirun-analog shape).
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

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
