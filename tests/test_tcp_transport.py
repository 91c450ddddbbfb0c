"""TcpTransport: the cross-host wire, exercised on localhost — contract
parity with the shm transport (roundtrip, FIFO, tags, size mismatch,
zero-byte header/ack), a real cross-process run, and the full PS stack
over TCP."""

import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from mpit_tpu.comm.tcp import TcpTransport, allocate_local_addresses

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_mesh_transports(n):
    addrs, socks = allocate_local_addresses(n)
    out = [None] * n

    def build(r):
        out[r] = TcpTransport(r, n, addrs, listener=socks[r])

    # Construction blocks on the full-mesh rendezvous: run concurrently.
    threads = [threading.Thread(target=build, args=(r,)) for r in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert all(o is not None for o in out), "mesh construction hung"
    return out


@pytest.fixture
def pair():
    a, b = make_mesh_transports(2)
    yield a, b
    a.close()
    b.close()


class TestTcpTransport:
    def test_roundtrip_array(self, pair):
        a, b = pair
        data = np.arange(64, dtype=np.float32)
        a.send(data, 1, 3)
        out = np.zeros_like(data)
        b.recv(0, 3, out=out)
        np.testing.assert_array_equal(out, data)

    def test_payload_without_buffer(self, pair):
        a, b = pair
        a.send(b"over-the-wire", 1, 9)
        while not b.iprobe(0, 9):
            pass
        assert b.recv(0, 9) == b"over-the-wire"

    def test_zero_byte_header_ack(self, pair):
        a, b = pair
        a.send(b"", 1, 5)
        while not b.iprobe(0, 5):
            pass
        assert b.recv(0, 5) == b""

    def test_fifo_per_channel(self, pair):
        a, b = pair
        for i in range(5):
            a.send(np.full(4, i, np.int32), 1, 7)
        for i in range(5):
            out = np.zeros(4, np.int32)
            b.recv(0, 7, out=out)
            assert out[0] == i

    def test_tag_isolation(self, pair):
        a, b = pair
        a.send(np.full(2, 1.0, np.float32), 1, 11)
        a.send(np.full(2, 2.0, np.float32), 1, 22)
        out22 = np.zeros(2, np.float32)
        b.recv(0, 22, out=out22)  # later tag first
        assert out22[0] == 2.0
        out11 = np.zeros(2, np.float32)
        b.recv(0, 11, out=out11)
        assert out11[0] == 1.0

    def test_size_mismatch_raises_and_message_survives(self, pair):
        a, b = pair
        a.send(np.zeros(8, np.float32), 1, 4)
        while not b.iprobe(0, 4):
            pass
        small = np.zeros(2, np.float32)
        h = b.irecv(0, 4, out=small)
        with pytest.raises(ValueError, match="size mismatch"):
            b.test(h)
        # The message is still deliverable to a right-sized buffer.
        ok = np.ones(8, np.float32)
        b.recv(0, 4, out=ok)
        assert (ok == 0).all()

    def test_cancel_releases(self, pair):
        a, b = pair
        h = b.irecv(0, 99)
        b.cancel(h)
        assert h.cancelled and not b.test(h)

    def test_large_message(self, pair):
        a, b = pair
        data = np.random.default_rng(0).normal(size=1 << 20).astype(np.float32)
        h = a.isend(data, 1, 2)
        out = np.zeros_like(data)
        b.recv(0, 2, out=out)
        while not a.test(h):
            pass
        np.testing.assert_array_equal(out, data)

    def test_outbox_is_zero_copy_and_nonblocking(self, pair):
        # A deep backlog must not snapshot payloads (O(1) transport-owned
        # memory per queued message) and isend must stay nonblocking.
        # Stall b's reader (its frame loop needs b._lock) so TCP
        # backpressure provably retains entries in a's outbox.
        a, b = pair
        payload = np.arange(1 << 18, dtype=np.float32)  # 1 MiB each
        with b._lock:
            handles = [a.isend(payload, 1, 5) for _ in range(8)]
            with a._out_cv[1]:
                entries = list(a._outboxes[1])
        assert entries, "outbox must retain entries while the peer stalls"
        assert all(isinstance(e[2], memoryview) for e in entries)
        outs = [np.zeros_like(payload) for _ in range(8)]
        for out in outs:
            b.recv(0, 5, out=out)
        for h in handles:
            while not a.test(h):
                pass
        for out in outs:
            np.testing.assert_array_equal(out, payload)

    def test_isend_to_dead_peer_cancels_and_raises_once(self, pair):
        a, b = pair
        a._drain_outbox(1, error="rank 1 connection lost")
        h = a.isend(np.arange(4, dtype=np.float32), 1, 6)
        assert h.cancelled and not h.done
        with pytest.raises(RuntimeError, match="unreachable"):
            a.test(h)
        assert a.test(h) is False  # raise-once, then quiet not-done

    def test_peer_crash_fails_blocked_recvs(self):
        """A mid-run peer death must fail pending receives loudly (the
        raise-once convention), not leave them polling forever; messages
        delivered before the crash still serve matching receives."""
        a, b = make_mesh_transports(2)
        try:
            # One message lands before the crash...
            hs = b.isend(np.arange(3, dtype=np.float32), 0, 7)
            deadline = time.monotonic() + 10
            while not a.iprobe(1, 7):
                assert time.monotonic() < deadline, "delivery hung"
            # the receiver can see the message before the sender's I/O
            # thread has marked its handle done
            while not b.test(hs):
                assert time.monotonic() < deadline, "send never completed"
            # ...then rank 1 dies (simulated: close without orderly flag).
            for conn in b._peers.values():
                conn.shutdown(socket.SHUT_RDWR)
            h_served = a.irecv(1, 7, out=np.empty(3, np.float32))
            h_starved = a.irecv(1, 7, out=np.empty(3, np.float32))
            deadline = time.monotonic() + 10
            while not a.test(h_served):
                assert time.monotonic() < deadline, "backlog recv hung"
            # The starved recv fails loudly once the reader notices.
            deadline = time.monotonic() + 10
            while True:
                try:
                    assert not a.test(h_starved)
                except RuntimeError as e:
                    assert "connection lost" in str(e)
                    break
                assert time.monotonic() < deadline, "starved recv never failed"
            # New receives from the dead peer fail immediately.
            h_new = a.irecv(1, 9)
            with pytest.raises(RuntimeError, match="connection lost"):
                a.test(h_new)
            # Probe loops (the aio probe-then-recv pattern) fail loudly
            # too once the channel is drained.
            with pytest.raises(RuntimeError, match="connection lost"):
                a.iprobe(1, 11)
        finally:
            a.close()
            b.close()

    def test_graceful_close_keeps_old_silent_semantics(self):
        """An orderly close() announces itself (goodbye frame): the
        surviving side's probes/recvs must NOT raise connection-lost —
        that convention is reserved for crashes.  This is the normal PS
        teardown order (a client finishes and closes while the server
        still serves)."""
        a, b = make_mesh_transports(2)
        try:
            b.close()
            # The reader consumes the goodbye asynchronously (its thread
            # exits when it does — observable via the role-named thread);
            # probes stay quietly False throughout, and the wait below is
            # REQUIRED to observe consumption, so the post-goodbye asserts
            # can never pass vacuously.  Common case: milliseconds.
            deadline = time.monotonic() + 5
            consumed = False
            while time.monotonic() < deadline and not consumed:
                assert a.iprobe(1, 7) is False
                consumed = not any(
                    t.is_alive() and t.name.startswith("_reader")
                    for t in a._threads
                )
                time.sleep(0.02)
            assert consumed, "goodbye never consumed within 5s"
            assert a.iprobe(1, 7) is False
            h = a.irecv(1, 7, out=np.empty(1, np.float32))
            assert a.test(h) is False  # pending, not poisoned
            a.cancel(h)
        finally:
            a.close()

    def test_close_cancels_queued_sends(self):
        """No orphaned handles: after close every send handle is done or
        cancelled (a blocking sender must not spin forever), and isend on
        a closed transport raises."""
        a, b = make_mesh_transports(2)
        hs = [a.isend(np.zeros(4, np.float32), 1, 1) for _ in range(3)]
        a.close()
        b.close()
        assert all(h.done or h.cancelled for h in hs)
        with pytest.raises(RuntimeError, match="closed"):
            a.isend(b"x", 1, 1)

    def test_invalid_rank(self, pair):
        a, _ = pair
        with pytest.raises(ValueError):
            a.isend(b"x", 0, 1)  # self
        with pytest.raises(ValueError):
            a.irecv(5, 1)


class TestPSOverTcp:
    def test_downpour_end_to_end(self, rng):
        """Full PS stack over TCP sockets matches serial SGD — the
        cross-host deployment shape on localhost."""
        import jax.numpy as jnp

        from mpit_tpu.optim.downpour import Downpour
        from mpit_tpu.ps import ParamClient, ParamServer

        transports = make_mesh_transports(3)
        w0 = rng.normal(size=10).astype(np.float32)
        lr, steps = 0.1, 4
        servers = [
            ParamServer(r, [2], transports[r], rule="add") for r in (0, 1)
        ]
        sthreads = [threading.Thread(target=s.start, daemon=True) for s in servers]
        for t in sthreads:
            t.start()
        client = ParamClient(2, [0, 1], transports[2], seed_servers=True)

        def vgf(w, target):
            return 0.5 * jnp.sum((w - target) ** 2), w - target

        opt = Downpour(vgf, client, lr=lr, su=1)
        w = opt.start(jnp.asarray(w0))
        for _ in range(steps):
            w, _ = opt.step(w, jnp.zeros(10))
        opt.stop()
        for t in sthreads:
            t.join(20)
            assert not t.is_alive()
        for tr in transports:
            tr.close()

        ref = w0.astype(np.float64)
        for _ in range(steps):
            ref = ref - lr * ref
        np.testing.assert_allclose(np.asarray(w), ref, rtol=1e-4)


class TestPSOverFlakyTcp:
    def test_downpour_survives_mid_training_tear(self, rng):
        """The full PS stack over a FLAKY link: a client<->server socket
        is torn mid-training with reconnect enabled — the exactly-once
        transport layer makes the optimizer trajectory identical to the
        healthy run (no lost push, no duplicated grad apply)."""
        import jax.numpy as jnp

        from mpit_tpu.optim.downpour import Downpour
        from mpit_tpu.ps import ParamClient, ParamServer

        addrs, socks = allocate_local_addresses(3)
        out = [None] * 3

        def build(r):
            out[r] = TcpTransport(r, 3, addrs, listener=socks[r],
                                  reconnect=20.0)

        ts = [threading.Thread(target=build, args=(r,)) for r in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(30)
        transports = out
        w0 = rng.normal(size=10).astype(np.float32)
        lr, steps = 0.1, 6
        servers = [
            ParamServer(r, [2], transports[r], rule="add") for r in (0, 1)
        ]
        sthreads = [threading.Thread(target=s.start, daemon=True)
                    for s in servers]
        for t in sthreads:
            t.start()
        client = ParamClient(2, [0, 1], transports[2], seed_servers=True)

        def vgf(w, target):
            return 0.5 * jnp.sum((w - target) ** 2), w - target

        opt = Downpour(vgf, client, lr=lr, su=1)
        w = opt.start(jnp.asarray(w0))
        for step in range(steps):
            if step == 2:  # tear the client<->server-0 link mid-run
                try:
                    transports[2]._peers[0].shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
            w, _ = opt.step(w, jnp.zeros(10))
        opt.stop()
        for t in sthreads:
            t.join(30)
            assert not t.is_alive()
        for tr in transports:
            tr.close()

        ref = w0.astype(np.float64)
        for _ in range(steps):
            ref = ref - lr * ref
        np.testing.assert_allclose(np.asarray(w), ref, rtol=1e-4)


class TestCrossProcess:
    def test_echo_between_processes(self, tmp_path):
        """Two real OS processes over TCP — the cross-host shape."""
        addrs, socks = allocate_local_addresses(2)
        for s in socks:  # children bind their own listeners on these ports
            s.close()
        code = """
import sys
import numpy as np
from mpit_tpu.comm.tcp import TcpTransport

rank = int(sys.argv[1])
addrs = sys.argv[2].split(",")
t = TcpTransport(rank, 2, addrs, connect_timeout=30)
if rank == 0:
    data = np.arange(16, dtype=np.float32)
    t.send(data, 1, 1)
    out = np.zeros(16, np.float32)
    t.recv(1, 2, out=out)
    assert (out == data * 2).all()
    print("RANK0 OK")
else:
    out = np.zeros(16, np.float32)
    t.recv(0, 1, out=out)
    t.send(out * 2, 0, 2)
    print("RANK1 OK")
t.close()
"""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        procs = [
            subprocess.Popen(
                [sys.executable, "-c", code, str(r), ",".join(addrs)],
                cwd=REPO, env=env, stdout=subprocess.PIPE, text=True,
            )
            for r in range(2)
        ]
        outs = [p.communicate(timeout=60)[0] for p in procs]
        assert all(p.returncode == 0 for p in procs), outs
        assert "RANK0 OK" in outs[0] and "RANK1 OK" in outs[1]


@pytest.mark.slow
class TestGangOverTcp:
    def test_mnist_gang_tcp(self):
        """np=2 launcher gang wired over TCP instead of shm."""
        from mpit_tpu.train.launch import LAUNCH_DEFAULTS, launch_processes

        addrs, socks = allocate_local_addresses(2)
        for s in socks:
            s.close()  # children re-bind these ports
        cfg = LAUNCH_DEFAULTS.merged(
            np=2, opt="downpour", epochs=1, model="linear", side=8,
            batch=64, transport="tcp", tcp_addrs=",".join(addrs),
        )
        results = launch_processes(cfg, timeout=600)
        assert results[1]["role"] == "worker"
        assert np.isfinite(results[1]["final_test_err"])


class TestReconnect:
    """Bounded fault recovery (reconnect > 0): torn sockets are
    re-established, in-flight frames are resent whole, duplicates are
    dropped, and a restarted rank can rejoin the mesh."""

    def _mesh(self, n, reconnect):
        addrs, socks = allocate_local_addresses(n)
        out = [None] * n

        def build(r):
            out[r] = TcpTransport(r, n, addrs, listener=socks[r],
                                  reconnect=reconnect)

        threads = [threading.Thread(target=build, args=(r,)) for r in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert all(o is not None for o in out), "mesh construction hung"
        return addrs, socks, out

    def test_socket_break_resends_and_dedups(self):
        _addrs, _socks, (a, b) = self._mesh(2, reconnect=15.0)
        try:
            # Warm traffic, then tear the live socket pair mid-run.
            a.send(np.arange(32, dtype=np.float32), 1, 1)
            out = np.zeros(32, np.float32)
            b.recv(0, 1, out=out)

            a._peers[1].shutdown(socket.SHUT_RDWR)  # simulate a torn link

            # Both directions must survive: frames queued before, during,
            # and after the break arrive exactly once, in order.
            sends = [a.isend(np.full(64, i, np.float32), 1, 7)
                     for i in range(8)]
            got = []
            for i in range(8):
                buf = np.zeros(64, np.float32)
                b.recv(0, 7, out=buf)
                got.append(buf[0])
            assert got == list(map(float, range(8))), got
            for h in sends:
                while not a.test(h):
                    pass
            # reverse direction over the reconnected socket
            b.send(b"back at you", 0, 9)
            assert a.recv(1, 9) == b"back at you"
        finally:
            a.close()
            b.close()

    def test_restarted_rank_rejoins(self):
        addrs, _socks, (a, b) = self._mesh(2, reconnect=15.0)
        b2 = None
        try:
            a.send(b"pre-crash", 1, 3)
            assert b.recv(0, 3) == b"pre-crash"
            # Rank 1 dies hard (no goodbye) and a fresh process takes
            # over its address: new listener on the same port, redial.
            for conn in b._peers.values():
                conn.shutdown(socket.SHUT_RDWR)
            b._closed = True  # suppress b's own recovery; it is "dead"
            b._listener.close()
            b2 = TcpTransport(1, 2, addrs, reconnect=15.0)
            # a's sends reach the replacement (nonce reset accepts the
            # restarted sequence space), and the replacement can send.
            a.send(b"hello new rank", 1, 5)
            assert b2.recv(0, 5) == b"hello new rank"
            b2.send(b"reporting in", 0, 6)
            assert a.recv(1, 6) == b"reporting in"
        finally:
            a.close()
            if b2 is not None:
                b2.close()

    def test_stale_generation_ack_is_dropped(self):
        """An ack enqueued by a reader of a superseded connection must not
        reach the outbox: after a restarted peer installs (nonce reset
        purges queued acks), a stale ack carrying the dead instance's
        sequence horizon would release the replacement's unacked window."""
        _addrs, _socks, (a, b) = self._mesh(2, reconnect=15.0)
        try:
            a.send(b"warm", 1, 2)
            assert b.recv(0, 2) == b"warm"
            with b._lock:
                old_gen = b._gen[0]
                b._gen[0] += 1  # simulate a replacement install winning
            with b._out_cv[0]:
                b._pending_ack[0] = None
                b._outboxes[0].clear()
            b._enqueue_ack(0, 10**9, old_gen)  # the racing reader's enqueue
            with b._out_cv[0]:
                assert b._pending_ack.get(0) is None
                assert not b._outboxes[0]
            with b._lock:
                b._gen[0] = old_gen  # restore so close() is orderly
        finally:
            a.close()
            b.close()

    def test_window_expiry_falls_back_to_fail_loud(self):
        _addrs, _socks, (a, b) = self._mesh(2, reconnect=0.3)
        try:
            # Kill rank 1 outright; nothing ever redials its address.
            for conn in b._peers.values():
                conn.shutdown(socket.SHUT_RDWR)
            b._closed = True
            b._listener.close()
            h = a.isend(np.zeros(8, np.float32), 1, 2)
            deadline = time.monotonic() + 10
            with pytest.raises(RuntimeError, match="connection lost"):
                while time.monotonic() < deadline:
                    if a.test(h):
                        raise AssertionError("send completed to dead rank")
                    time.sleep(0.01)
                raise TimeoutError("fail-loud never triggered")
        finally:
            a.close()
            b.close()


def test_cross_process_kill_and_resume(tmp_path):
    """A rank process dies hard (no goodbye) mid-gang and a replacement
    process rebinds its address: the surviving rank's queued frames reach
    the replacement and traffic resumes — the TCP analog of the shm
    transport's stale-segment remap."""
    addrs, socks = allocate_local_addresses(2)
    for s in socks:  # children rebind their own listeners
        s.close()
    child_src = (
        "import sys, time\n"
        "import numpy as np\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from mpit_tpu.comm.tcp import TcpTransport\n"
        "addrs = sys.argv[1].split(',')\n"
        "phase = sys.argv[2]\n"
        "t = TcpTransport(1, 2, addrs, reconnect=20.0)\n"
        "out = np.zeros(128, np.float32)\n"
        "if phase == 'first':\n"
        "    t.recv(0, 5, out=out)\n"
        "    assert out[0] == 1.0\n"
        "    time.sleep(0.2)\n"
        "    sys.exit(37)  # hard death: no goodbye, no close\n"
        "else:\n"
        "    t.recv(0, 6, out=out)  # frame queued while rank was dead\n"
        "    assert out[0] == 2.0\n"
        "    t.send(b'replacement alive', 0, 7)\n"
        "    t.close()\n"
    )
    p1 = subprocess.Popen(
        [sys.executable, "-c", child_src, ",".join(addrs), "first"])
    parent = TcpTransport(0, 2, addrs, reconnect=20.0, connect_timeout=30.0)
    try:
        parent.send(np.full(128, 1.0, np.float32), 1, 5)
        p1.wait(30)
        assert p1.returncode == 37
        h = parent.isend(np.full(128, 2.0, np.float32), 1, 6)
        p2 = subprocess.Popen(
            [sys.executable, "-c", child_src, ",".join(addrs), "second"])
        deadline = time.monotonic() + 30
        while not parent.test(h):
            assert time.monotonic() < deadline, "resend never completed"
            time.sleep(0.01)
        assert parent.recv(1, 7) == b"replacement alive"
        p2.wait(30)
        assert p2.returncode == 0
    finally:
        parent.close()


def test_reconnect_mid_burst_tear_no_loss_no_dup():
    """Tear the link while a burst is in flight (frames sitting in the
    kernel send buffer are NOT delivered — the ack protocol must resend
    them and dedup the overlap): 50 frames arrive exactly once, in
    order, and every sender handle is eventually acked."""
    addrs, socks = allocate_local_addresses(2)
    out = [None, None]

    def build(r):
        out[r] = TcpTransport(r, 2, addrs, listener=socks[r],
                              reconnect=15.0)

    ts = [threading.Thread(target=build, args=(r,)) for r in (0, 1)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
    a, b = out
    try:
        def tear():
            time.sleep(0.005)
            try:
                a._peers[1].shutdown(socket.SHUT_RDWR)
            except OSError:
                pass

        killer = threading.Thread(target=tear)
        killer.start()
        handles = [a.isend(np.full(4096, i, np.float32), 1, 7)
                   for i in range(50)]
        killer.join()
        got = []
        for _ in range(50):
            buf = np.zeros(4096, np.float32)
            b.recv(0, 7, out=buf)
            got.append(int(buf[0]))
        assert got == list(range(50)), got[:10]
        deadline = time.monotonic() + 20
        for h in handles:
            while not a.test(h):
                assert time.monotonic() < deadline, "ack never released"
                time.sleep(0.002)
    finally:
        a.close()
        b.close()
