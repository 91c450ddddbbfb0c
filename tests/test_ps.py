"""Integration tests: ParamServer + ParamClient over the in-process
transport — the analog of the reference's mpirun-on-one-host test mode
(SURVEY.md section 4), with real assertions.

Topology helpers run each server's blocking event loop on its own thread
(the per-rank process analog) while clients drive from the test thread.
"""

import contextlib
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu import obs
from mpit_tpu.comm import codec as codec_mod
from mpit_tpu.comm.local import LocalRouter
from mpit_tpu.comm.transport import Handle
from mpit_tpu.optim import rules
from mpit_tpu.optim.downpour import Downpour
from mpit_tpu.optim.shells import SingleWorker
from mpit_tpu.ps import ParamClient, ParamServer, Shard, shard_layout, tags


class TestShardLayout:
    def test_even_split(self):
        assert shard_layout(12, 3) == [Shard(0, 4), Shard(4, 4), Shard(8, 4)]

    def test_remainder_goes_to_last(self):
        # floor(10/3)=3: [0,3) [3,6) [6,10) (reference pclient.lua:111-129)
        assert shard_layout(10, 3) == [Shard(0, 3), Shard(3, 3), Shard(6, 4)]

    def test_single_server_takes_all(self):
        assert shard_layout(7, 1) == [Shard(0, 7)]

    def test_errors(self):
        with pytest.raises(ValueError):
            shard_layout(2, 3)
        with pytest.raises(ValueError):
            shard_layout(10, 0)


@contextlib.contextmanager
def launch(nservers, nclients, rule="add", single_mode=False, codec=None,
           server_codec=None):
    """PS topology: servers on ranks [0, nservers) in threads, clients on
    the following ranks, driven by the caller.  Teardown force-stops any
    still-running server so a failed assertion can't leave busy-spinning
    threads behind to starve later tests.  ``codec`` sets the clients'
    announced codec; ``server_codec`` pins the servers (mismatch tests)."""
    n = nservers + nclients
    router = LocalRouter(n)
    sranks = list(range(nservers))
    cranks = list(range(nservers, n))
    servers = [
        ParamServer(r, cranks, router.endpoint(r), rule=rule,
                    single_mode=single_mode, codec=server_codec)
        for r in sranks
    ]
    threads = [threading.Thread(target=s.start, daemon=True) for s in servers]
    for t in threads:
        t.start()
    clients = [
        ParamClient(r, sranks, router.endpoint(r),
                    seed_servers=(r == cranks[0]), codec=codec)
        for r in cranks
    ]
    try:
        yield servers, clients, threads
    finally:
        for s in servers:
            s.live.stop()
        for t in threads:
            t.join(5)


def join_all(threads, timeout=30):
    for t in threads:
        t.join(timeout)
        assert not t.is_alive(), "server did not stop (stop-protocol hang)"


class TestPSBasic:
    def test_seed_push_pull_single_shard(self, rng):
        w0 = rng.normal(size=16).astype(np.float32)
        with launch(1, 1) as (servers, (client,), threads):
            param, grad = w0.copy(), np.zeros_like(w0)
            client.start(param, grad)

            # Push a delta; server plain-adds; pull back.  Per-server op
            # chaining guarantees the pull sees this client's own push.
            grad[:] = 1.0
            client.async_send_grad()
            client.async_recv_param()
            client.wait()
            np.testing.assert_allclose(param, w0 + 1.0, rtol=1e-6)

            client.stop()
            join_all(threads)
            assert servers[0].grads_applied == 1
            assert servers[0].params_served == 1

    def test_two_servers_shard_correctly(self, rng):
        w0 = rng.normal(size=10).astype(np.float32)  # shards: [0,5) [5,10)
        with launch(2, 1) as (servers, (client,), threads):
            param, grad = w0.copy(), np.zeros_like(w0)
            client.start(param, grad)

            delta = rng.normal(size=10).astype(np.float32)
            grad[:] = delta
            client.async_send_grad()
            client.async_recv_param()
            client.wait()
            np.testing.assert_allclose(param, w0 + delta, rtol=1e-5)
            # Each server holds exactly its contiguous slice.
            np.testing.assert_allclose(
                np.asarray(servers[0].param), (w0 + delta)[:5], rtol=1e-5)
            np.testing.assert_allclose(
                np.asarray(servers[1].param), (w0 + delta)[5:], rtol=1e-5)

            client.stop()
            join_all(threads)

    def test_two_clients_share_center(self, rng):
        w0 = rng.normal(size=8).astype(np.float32)
        with launch(1, 2) as (servers, (c1, c2), threads):
            p1, g1 = w0.copy(), np.zeros_like(w0)
            p2, g2 = np.zeros_like(w0), np.zeros_like(w0)
            # Clients must start concurrently (each is its own process in
            # the reference): the server's init phase waits on both, and
            # the seeder's start() blocks on the seed ack.
            t1 = threading.Thread(target=c1.start, args=(p1, g1), daemon=True)
            t2 = threading.Thread(target=c2.start, args=(p2, g2), daemon=True)
            t1.start()
            t2.start()
            t1.join(30)
            t2.join(30)
            assert not t1.is_alive() and not t2.is_alive(), "client start hung"

            # c2 pulls: sees the seed from c1.
            c2.async_recv_param()
            c2.wait()
            np.testing.assert_allclose(p2, w0, rtol=1e-6)

            # Both push deltas (awaiting acks); then c1 pulls the sum.
            g1[:] = 1.0
            c1.async_send_grad()
            c1.wait()
            g2[:] = 2.0
            c2.async_send_grad()
            c2.wait()
            c1.async_recv_param()
            c1.wait()
            np.testing.assert_allclose(p1, w0 + 3.0, rtol=1e-6)

            c1.stop()
            c2.stop()
            join_all(threads)

    def test_server_side_adam(self, rng):
        """Clients ship raw grads; servers apply Adam — result must match a
        local Adam rollout on the full vector."""
        w0 = rng.normal(size=12).astype(np.float32)
        grads = [rng.normal(size=12).astype(np.float32) for _ in range(3)]
        hp = dict(lr=1e-2, beta1=0.9, beta2=0.999, epsilon=1e-8)
        with launch(2, 1, rule=rules.make("adam", **hp)) as (servers, (client,), threads):
            param, grad = w0.copy(), np.zeros_like(w0)
            client.start(param, grad)
            for g in grads:
                grad[:] = g
                client.async_send_grad()
                client.wait()
            client.async_recv_param()
            client.wait()
            client.stop()
            join_all(threads)

        rule = rules.make("adam", **hp)
        p = jnp.asarray(w0)
        st = rule.init(p)
        for g in grads:
            p, st = rule.apply(p, jnp.asarray(g), st)
        np.testing.assert_allclose(param, np.asarray(p), rtol=1e-5)

    def test_reset_retargets_buffers(self, rng):
        w0 = rng.normal(size=6).astype(np.float32)
        with launch(1, 1) as (servers, (client,), threads):
            param, grad = w0.copy(), np.zeros_like(w0)
            client.start(param, grad)

            alt_param = np.zeros_like(w0)
            alt_grad = np.full_like(w0, 0.5)
            client.reset(alt_param, alt_grad)
            client.async_send_grad()
            client.async_recv_param()
            client.wait()
            np.testing.assert_allclose(alt_param, w0 + 0.5, rtol=1e-6)
            np.testing.assert_allclose(param, w0, rtol=1e-6)  # original untouched

            client.stop()
            join_all(threads)

    def test_reset_length_mismatch(self, rng):
        w0 = rng.normal(size=6).astype(np.float32)
        with launch(1, 1) as (servers, (client,), threads):
            client.start(w0.copy(), np.zeros_like(w0))
            with pytest.raises(ValueError):
                client.reset(np.zeros(7, np.float32), np.zeros(7, np.float32))
            client.stop()
            join_all(threads)


class TestPSWithOptimizers:
    def test_downpour_su1_end_to_end(self, rng):
        """Full stack: Downpour -> ParamClient -> LocalTransport ->
        ParamServer(plain add) matches serial SGD."""
        w0 = rng.normal(size=8).astype(np.float32)
        lr, steps = 0.1, 5
        with launch(2, 1) as (servers, (client,), threads):
            def vgf(w, target):
                return 0.5 * jnp.sum((w - target) ** 2), w - target

            opt = Downpour(vgf, client, lr=lr, su=1)
            w = opt.start(jnp.asarray(w0))
            target = jnp.zeros(8)
            for _ in range(steps):
                w, _ = opt.step(w, target)
            opt.stop()
            join_all(threads)

        ref = w0.astype(np.float64)
        for _ in range(steps):
            ref = ref - lr * ref
        np.testing.assert_allclose(np.asarray(w), ref, rtol=1e-4)

    def test_single_worker_mirror(self, rng):
        """SingleWorker pushes whole params; single_mode server mirrors them."""
        w0 = rng.normal(size=6).astype(np.float32)
        with launch(1, 1, single_mode=True) as (servers, (client,), threads):
            def vgf(w, target):
                return 0.5 * jnp.sum((w - target) ** 2), w - target

            opt = SingleWorker(vgf, client, rule="adagrad", lr=0.1)
            w = opt.start(jnp.asarray(w0))
            for _ in range(3):
                w, _ = opt.step(w, jnp.zeros(6))
            opt.stop()
            join_all(threads)
            np.testing.assert_allclose(
                np.asarray(servers[0].param), np.asarray(w), rtol=1e-5)


class TestWireCodecs:
    """INIT v2 negotiation, quantized transfers, the snapshot cache, and
    the fail-loudly paths (legacy interop / mismatch / unknown id)."""

    @pytest.mark.parametrize("codec,tol", [("bf16", 2.0**-7), ("int8", 1 / 64)])
    def test_seed_push_pull_quantized(self, rng, codec, tol):
        w0 = rng.normal(size=3000).astype(np.float32)
        with launch(2, 1, codec=codec) as (servers, (client,), threads):
            param, grad = w0.copy(), np.zeros_like(w0)
            client.start(param, grad)
            grad[:] = 1.0
            client.async_send_grad()
            client.async_recv_param()
            client.wait()
            scale = np.abs(w0).max() + 1.0
            # seed + grad + snapshot each quantize once
            np.testing.assert_allclose(param, w0 + 1.0, atol=4 * tol * scale)
            client.stop()
            join_all(threads)
            assert all(s._codecs[2].name == codec for s in servers)

    def test_env_codec_drives_negotiation(self, rng, monkeypatch):
        monkeypatch.setenv("MPIT_PS_CODEC", "bf16")
        w0 = rng.normal(size=64).astype(np.float32)
        with launch(1, 1) as (servers, (client,), threads):
            assert client.codec.name == "bf16"
            client.start(w0.copy(), np.zeros_like(w0))
            client.stop()
            join_all(threads)
            assert servers[0]._codecs[1].name == "bf16"

    def test_legacy_16_byte_init_interops_as_none(self, rng):
        """A v1 peer announcing [offset, size] must be served with the
        identity codec — the mixed-version deployment case."""
        w0 = rng.normal(size=16).astype(np.float32)
        router = LocalRouter(2)
        server = ParamServer(0, [1], router.endpoint(0))
        t = threading.Thread(target=server.start, daemon=True)
        t.start()
        try:
            wire = router.endpoint(1)
            from mpit_tpu.ps import tags

            # Hand-rolled v1 client: legacy INIT, seed, grad, pull.
            wire.send(np.asarray([0, 16], dtype=np.int64), 0, tags.INIT)
            wire.send(w0, 0, tags.PARAM_PUSH)
            wire.recv(0, tags.PARAM_PUSH_ACK)
            wire.send(np.full(16, 2.0, np.float32), 0, tags.GRAD)
            wire.recv(0, tags.GRAD_ACK)
            wire.send(tags.EMPTY, 0, tags.PARAM_REQ)
            out = np.zeros(16, np.float32)
            while not wire.iprobe(0, tags.PARAM):
                pass
            wire.recv(0, tags.PARAM, out=out)
            np.testing.assert_allclose(out, w0 + 2.0, rtol=1e-6)
            assert server._codecs[1].name == "none"
            wire.send(tags.EMPTY, 0, tags.STOP)
            join_all([t])
        finally:
            server.live.stop()

    def test_codec_mismatch_fails_loudly(self, rng):
        """A server pinned to one codec must reject a client announcing
        another at INIT — not decode frames into corrupt params."""
        from mpit_tpu.aio.scheduler import TaskError

        n = 2
        router = LocalRouter(n)
        server = ParamServer(0, [1], router.endpoint(0), codec="bf16")
        failure = []

        def run_server():
            try:
                server.start()
            except TaskError as exc:
                failure.append(exc)

        t = threading.Thread(target=run_server, daemon=True)
        t.start()
        client = ParamClient(1, [0], router.endpoint(1), codec="int8")
        w0 = rng.normal(size=8).astype(np.float32)
        client.start(w0.copy(), np.zeros_like(w0))  # INIT only (no seeding)
        t.join(10)
        assert not t.is_alive(), "mismatched server neither failed nor stopped"
        assert failure, "server accepted a mismatched codec announcement"
        assert "codec negotiation mismatch" in str(failure[0].cause)

    def test_unknown_wire_id_fails_loudly(self):
        from mpit_tpu.aio.scheduler import TaskError
        from mpit_tpu.ps import tags

        router = LocalRouter(2)
        server = ParamServer(0, [1], router.endpoint(0))
        failure = []

        def run_server():
            try:
                server.start()
            except TaskError as exc:
                failure.append(exc)

        t = threading.Thread(target=run_server, daemon=True)
        t.start()
        router.endpoint(1).send(
            np.asarray([0, 8, 99], dtype=np.int64), 0, tags.INIT)
        t.join(10)
        assert not t.is_alive()
        assert failure and "unknown codec wire id" in str(failure[0].cause)

    def test_bad_init_length_fails_loudly(self):
        from mpit_tpu.aio.scheduler import TaskError
        from mpit_tpu.ps import tags

        router = LocalRouter(2)
        server = ParamServer(0, [1], router.endpoint(0))
        failure = []

        def run_server():
            try:
                server.start()
            except TaskError as exc:
                failure.append(exc)

        t = threading.Thread(target=run_server, daemon=True)
        t.start()
        router.endpoint(1).send(
            np.asarray([0, 8, 0, 0], dtype=np.int64), 0, tags.INIT)
        t.join(10)
        assert not t.is_alive()
        assert failure and "INIT announcement" in str(failure[0].cause)

    def test_snapshot_cache_one_copy_per_version(self, rng):
        """N pulls of one committed version = one device->host copy +
        one encode; a grad apply bumps the version and invalidates."""
        w0 = rng.normal(size=256).astype(np.float32)
        with launch(1, 1, codec="int8") as (servers, (client,), threads):
            param, grad = w0.copy(), np.zeros_like(w0)
            client.start(param, grad)
            for _ in range(3):  # same version three times
                client.async_recv_param()
                client.wait()
            s = servers[0]
            assert s.snapshot_copies == 1
            assert s.snapshot_hits == 2
            grad[:] = 1.0
            client.async_send_grad()
            client.wait()
            client.async_recv_param()
            client.wait()
            assert s.snapshot_copies == 2  # new version, one new copy
            client.stop()
            join_all(threads)

    def test_mixed_codec_clients_negotiate_per_pair(self, rng):
        """codec=None servers adopt each client's announcement — a bf16
        client and a none client share one server."""
        w0 = rng.normal(size=128).astype(np.float32)
        n = 3
        router = LocalRouter(n)
        server = ParamServer(0, [1, 2], router.endpoint(0))
        t = threading.Thread(target=server.start, daemon=True)
        t.start()
        c1 = ParamClient(1, [0], router.endpoint(1), seed_servers=True,
                         codec="none")
        c2 = ParamClient(2, [0], router.endpoint(2), codec="bf16")
        p1, g1 = w0.copy(), np.zeros_like(w0)
        p2, g2 = np.zeros_like(w0), np.zeros_like(w0)
        t1 = threading.Thread(target=c1.start, args=(p1, g1), daemon=True)
        t2 = threading.Thread(target=c2.start, args=(p2, g2), daemon=True)
        t1.start(); t2.start()
        t1.join(30); t2.join(30)
        assert not t1.is_alive() and not t2.is_alive(), "client start hung"
        c2.async_recv_param()
        c2.wait()
        np.testing.assert_allclose(p2, w0, rtol=2.0**-7, atol=1e-6)
        assert server._codecs[1].name == "none"
        assert server._codecs[2].name == "bf16"
        c1.stop(); c2.stop()
        join_all([t])

    def test_int8_error_feedback_sums_over_rounds(self, rng):
        """Repeated identical grads must accumulate to ~T*g on the server
        (EF re-ships each round's quantization error), far tighter than
        T independent quantization errors."""
        w0 = np.zeros(2048, np.float32)
        g = rng.normal(size=2048).astype(np.float32)
        T = 16
        with launch(1, 1, codec="int8") as (servers, (client,), threads):
            param, grad = w0.copy(), np.zeros_like(w0)
            client.start(param, grad)
            grad[:] = g
            for _ in range(T):
                client.async_send_grad()
                client.wait()
            client.async_recv_param()
            client.wait()
            client.stop()
            join_all(threads)
        # EF bound: |sum - T*g| <= residual + one snapshot quantization,
        # each bounded by one block scale — NOT T * scale.
        scale = np.abs(g).max() * T / 127.0
        assert np.abs(param - T * g).max() <= 2.5 * scale
        assert client.residual_norm() > 0.0  # residual is live

    def test_residual_free_codecs_report_zero_norm(self, rng):
        with launch(1, 1, codec="bf16") as (servers, (client,), threads):
            client.start(np.ones(8, np.float32), np.zeros(8, np.float32))
            assert client.residual_norm() == 0.0
            client.stop()
            join_all(threads)

    def test_quantized_dtype_guard(self):
        router = LocalRouter(2)
        client = ParamClient(1, [0], router.endpoint(1), codec="int8")
        with pytest.raises(ValueError, match="float32"):
            client.start(np.zeros(8, np.float64), np.zeros(8, np.float64))


class TestPumpTaskNaming:
    def test_pump_name_refreshes_per_op(self, rng):
        """The pump task must be renamed per dequeued op — a stale
        spawn-time name misattributes later ops in error output."""
        router = LocalRouter(2, delay=2)  # ops span scheduler steps
        server = ParamServer(0, [1], router.endpoint(0))
        t = threading.Thread(target=server.start, daemon=True)
        t.start()
        try:
            client = ParamClient(1, [0], router.endpoint(1), seed_servers=True)
            w0 = rng.normal(size=8).astype(np.float32)
            param, grad = w0.copy(), np.zeros_like(w0)
            client.start(param, grad)
            names = set()
            client.async_send_grad()
            client.async_recv_param()
            task = client._pump_task[0]
            while client.sched.queue:
                names.add(task.name)
                client.ping()
            assert "pump:0:send_grad" in names
            assert "pump:0:recv_param" in names
            client.stop()
            join_all([t])
        finally:
            server.live.stop()


class TestServerCheckpointResume:
    def test_periodic_hook_writes_during_serve(self, rng, tmp_path):
        """ckpt_dir + tiny interval: snapshots land while serving, plus a
        final one at stop; the file restores cleanly."""
        from mpit_tpu.utils.checkpoint import load_server_state

        w0 = rng.normal(size=8).astype(np.float32)
        n = 2
        router = LocalRouter(n)
        server = ParamServer(
            0, [1], router.endpoint(0), rule="add",
            ckpt_dir=tmp_path, ckpt_interval=0.02,
        )
        thread = threading.Thread(target=server.start, daemon=True)
        thread.start()
        try:
            client = ParamClient(1, [0], router.endpoint(1), seed_servers=True)
            param, grad = w0.copy(), np.zeros_like(w0)
            client.start(param, grad)
            for i in range(4):
                grad[:] = i + 1.0
                client.async_send_grad()
                client.wait()
                time.sleep(0.03)  # let the interval elapse between applies
            client.stop()
            join_all([thread])
        finally:
            server.live.stop()
        assert server.ckpts_written >= 2  # periodic + final
        offset, size, param_ck, _state, meta = load_server_state(
            tmp_path / "server0_latest.npz"
        )
        assert (offset, size) == (0, 8)
        assert meta["grads_applied"] == 4
        np.testing.assert_allclose(param_ck, w0 + 1 + 2 + 3 + 4, rtol=1e-6)

    def test_adam_resume_matches_uninterrupted(self, rng, tmp_path):
        """Save server shard state mid-training, restart the topology from
        the checkpoint, finish — result must match a never-interrupted
        rollout (moments included; the reference loses these, SURVEY §5)."""
        w0 = rng.normal(size=10).astype(np.float32)
        grads = [rng.normal(size=10).astype(np.float32) for _ in range(4)]
        hp = dict(lr=1e-2, beta1=0.9, beta2=0.999, epsilon=1e-8)

        # Session 1: seed + 2 grads, checkpoint both servers, stop.
        paths = []
        with launch(2, 1, rule=rules.make("adam", **hp)) as (servers, (client,), threads):
            param, grad = w0.copy(), np.zeros_like(w0)
            client.start(param, grad)
            for g in grads[:2]:
                grad[:] = g
                client.async_send_grad()
                client.wait()
            client.stop()
            join_all(threads)
            paths = [s.save_state(tmp_path) for s in servers]

        # Session 2: restore servers, client joins WITHOUT seeding, 2 more
        # grads, pull final params.
        router = __import__("mpit_tpu.comm.local", fromlist=["LocalRouter"]).LocalRouter(3)
        servers2 = [
            ParamServer(r, [2], router.endpoint(r), rule=rules.make("adam", **hp))
            for r in (0, 1)
        ]
        for s, p in zip(servers2, paths):
            s.restore_state(p)
        threads2 = [threading.Thread(target=s.start, daemon=True) for s in servers2]
        for t in threads2:
            t.start()
        client2 = ParamClient(2, [0, 1], router.endpoint(2), seed_servers=False)
        param2, grad2 = np.zeros_like(w0), np.zeros_like(w0)
        client2.start(param2, grad2)
        for g in grads[2:]:
            grad2[:] = g
            client2.async_send_grad()
            client2.wait()
        client2.async_recv_param()
        client2.wait()
        client2.stop()
        join_all(threads2)

        # Uninterrupted reference rollout.
        rule = rules.make("adam", **hp)
        p = jnp.asarray(w0)
        st = rule.init(p)
        for g in grads:
            p, st = rule.apply(p, jnp.asarray(g), st)
        np.testing.assert_allclose(param2, np.asarray(p), rtol=1e-6, atol=1e-7)

    def test_restore_after_init_rejected(self, rng, tmp_path):
        w0 = rng.normal(size=6).astype(np.float32)
        with launch(1, 1) as (servers, (client,), threads):
            client.start(w0.copy(), np.zeros_like(w0))
            path = None
            with pytest.raises(RuntimeError):
                servers[0].restore_state(tmp_path / "nope.npz")
            path = servers[0].save_state(tmp_path)
            client.stop()
            join_all(threads)
        assert path and "server0" in path

    def test_resume_with_seeding_client_warns_not_hangs(self, rng, tmp_path):
        """A resume client mistakenly wired with seed_servers=True must not
        deadlock: the restored server consumes+acks the push (client
        authoritative for params, optimizer state kept)."""
        w0 = rng.normal(size=8).astype(np.float32)
        with launch(1, 1, rule=rules.make("adam")) as (servers, (client,), threads):
            param, grad = w0.copy(), np.zeros_like(w0)
            client.start(param, grad)
            grad[:] = 1.0
            client.async_send_grad()
            client.wait()
            client.stop()
            join_all(threads)
            path = servers[0].save_state(tmp_path)

        router = __import__("mpit_tpu.comm.local", fromlist=["LocalRouter"]).LocalRouter(2)
        server2 = ParamServer(0, [1], router.endpoint(0), rule=rules.make("adam"))
        server2.restore_state(path)
        t = threading.Thread(target=server2.start, daemon=True)
        t.start()
        client2 = ParamClient(1, [0], router.endpoint(1), seed_servers=True)
        fresh = rng.normal(size=8).astype(np.float32)
        param2, grad2 = fresh.copy(), np.zeros_like(w0)
        client2.start(param2, grad2)  # would hang before the guard
        client2.async_recv_param()
        client2.wait()
        np.testing.assert_allclose(param2, fresh, rtol=1e-6)  # client's seed won
        assert server2.grads_applied == 1  # counter restored from meta
        client2.stop()
        join_all([t])


class WireClient:
    """The unframed wire written by hand against one host server (rank
    0) from rank 1: the test decides every frame the server sees, so the
    same frames can go through a plain loop of the rule."""

    def __init__(self, rule, codec_name, size):
        self.codec = codec_mod.get(codec_name)
        self.size = size
        router = LocalRouter(2)
        self.ep = router.endpoint(1)
        self.server = ParamServer(0, [1], router.endpoint(0), rule=rule)
        self.thread = threading.Thread(target=self.server.start, daemon=True)
        self.thread.start()
        self.ep.send(np.asarray([0, size, self.codec.wire_id], np.int64),
                     0, tags.INIT)

    def frame(self, x):
        if self.codec.identity:
            return x.astype(np.float32).view(np.uint8)
        wire = np.empty(self.codec.wire_nbytes(self.size), np.uint8)
        self.codec.encode_into(x.astype(np.float32), wire)
        return wire

    def operand(self, frame):
        """What the jitted apply is handed for ``frame``."""
        if self.codec.identity:
            return jnp.asarray(frame.view(np.float32))
        return [jnp.asarray(v)
                for v in self.codec.split_wire(frame, self.size)]

    def seed(self, frame):
        self.ep.send(frame, 0, tags.PARAM_PUSH)
        self.ep.recv(0, tags.PARAM_PUSH_ACK)

    def push(self, frame):
        self.ep.send(frame, 0, tags.GRAD)
        self.ep.recv(0, tags.GRAD_ACK)

    def pull(self):
        self.ep.send(tags.EMPTY, 0, tags.PARAM_REQ)
        return np.frombuffer(self.ep.recv(0, tags.PARAM), np.uint8)

    def stop(self):
        self.ep.send(tags.EMPTY, 0, tags.STOP)
        join_all([self.thread])

    def close(self):
        self.server.live.stop()
        self.thread.join(5)


@pytest.fixture
def obs_on():
    obs.configure(enabled=True, reset=True)
    try:
        yield obs.get_recorder()
    finally:
        obs.configure(enabled=None, reset=True)


class TestDonatedApply:
    """The unchunked host apply donates the shard and the rule's slots
    (ISSUE 24): same bytes as an undonated loop, in place whenever
    nothing holds a view of the shard, a fresh output when something
    does."""

    SIZE = 1000  # not a multiple of the int8 block

    @pytest.mark.parametrize("codec_name", ["none", "int8"])
    @pytest.mark.parametrize("rule_name", sorted(rules.names()))
    def test_five_grads_bit_equal_to_undonated_loop(self, rng, rule_name,
                                                    codec_name):
        size = self.SIZE
        wc = WireClient(rule_name, codec_name, size)
        try:
            seed = wc.frame(rng.normal(size=size))
            frames = [wc.frame(rng.normal(size=size)) for _ in range(5)]
            wc.seed(seed)
            for frame in frames:
                wc.push(frame)
            wc.stop()
        finally:
            wc.close()
        server, codec = wc.server, wc.codec

        rule = rules.make(rule_name)
        if codec.identity:
            p0 = seed.view(np.float32)
            plain = jax.jit(rule.apply)
        else:
            p0 = np.empty(size, np.float32)
            codec.decode_into(seed, p0)
            plain = jax.jit(lambda p, parts, s: rule.apply(
                p, codec.decode_parts(parts, size), s))
        p = jnp.asarray(p0)
        state = rule.init(p)
        for frame in frames:
            p, state = plain(p, wc.operand(frame), state)

        assert server.grads_applied == 5
        assert np.array_equal(np.asarray(server.param), np.asarray(p))
        assert sorted(server.rule_state) == sorted(state)
        for name, leaf in state.items():
            assert np.array_equal(np.asarray(server.rule_state[name]),
                                  np.asarray(leaf)), name
        assert server.apply_inplace in (4, 5)
        assert server.metrics.counter(
            "mpit_ps_apply_inplace_total", rank=0).value \
            == server.apply_inplace

    def test_held_snapshot_view_keeps_its_bytes_and_the_apply_allocates(
            self, rng):
        size = self.SIZE
        wc = WireClient("adam", "none", size)
        try:
            server = wc.server
            seed = wc.frame(rng.normal(size=size))
            frames = [wc.frame(rng.normal(size=size)) for _ in range(3)]
            wc.seed(seed)
            wc.push(frames[0])
            first = wc.pull().view(np.float32)  # waits for the apply
            assert server.apply_inplace == 1
            # what a reader's reply task holds while its send is in
            # flight: the zero-copy view of the shard the cache serves
            view = server._snapshot_wire(wc.codec)
            assert not view.flags.owndata
            assert np.array_equal(view, first)
            wc.push(frames[1])
            second = wc.pull().view(np.float32)
            assert np.array_equal(view, first)  # its bytes stayed
            assert server.apply_inplace == 1  # declined, and counted so
            del view
            wc.push(frames[2])
            third = wc.pull().view(np.float32)
            assert server.apply_inplace == 2  # engages again
            wc.stop()
        finally:
            wc.close()
        rule = rules.make("adam")
        plain = jax.jit(rule.apply)
        p = jnp.asarray(seed.view(np.float32))
        state = rule.init(p)
        for frame, got in zip(frames, (first, second, third)):
            p, state = plain(p, wc.operand(frame), state)
            assert np.array_equal(got, np.asarray(p))
        assert server.grads_applied == 3

    def test_two_worker_traced_gang_loses_no_apply_span(self, obs_on, rng):
        """Pushes of two workers back to back with no pull between
        them: a later donated apply deletes the shard an earlier one
        produced while the recorder's waiter may still be waiting on
        it.  The waiter is handed the apply's token, not the shard."""
        rec = obs_on
        size, pushes = 1 << 22, 3
        with launch(1, 2, rule="adam") as (servers, clients, threads):
            starts = [threading.Thread(
                target=c.start, daemon=True,
                args=(np.zeros(size, np.float32), np.zeros(size, np.float32)))
                for c in clients]  # concurrently: the server waits for both
            for t in starts:
                t.start()
            for t in starts:
                t.join(30)
                assert not t.is_alive(), "client start hung"
            for k in range(pushes):
                for c in clients:
                    c.grad[:] = k + 1.0
                    c.async_send_grad()
                for c in clients:
                    c.wait()
            for c in clients:
                c.async_recv_param()
                c.wait()
            assert rec.drain(timeout=30)
            for c in clients:
                c.stop()
            join_all(threads)
            server = servers[0]
        execs = [s for s in rec.spans if s.name == "apply_exec"]
        assert len(execs) == server.grads_applied == 2 * pushes
        assert all(s.outcome == "ready" for s in execs)
        assert all([p for p, _t in s.marks] == ["queued", "exec"]
                   for s in execs)
        assert sum(s.args["inplace"] for s in execs) == server.apply_inplace
        assert server.apply_inplace >= 2 * pushes - 1
        np.testing.assert_array_equal(clients[0].param, clients[1].param)


class PostsLate:
    """Mixin over a transport: a receive is posted only once its message
    is whole — the probe-then-post order, which the shm wire serves from
    an assembly buffer with one more copy."""

    def irecv(self, src, tag, out=None):
        if out is None:
            return super().irecv(src, tag)
        return Handle(kind="recv", peer=src, tag=tag, out=out,
                      meta={"posted": None})

    def test(self, handle):
        if "posted" not in handle.meta:
            return super().test(handle)
        if handle.meta["posted"] is None:
            if handle.cancelled or not self.iprobe(handle.peer, handle.tag):
                return False
            handle.meta["posted"] = super().irecv(handle.peer, handle.tag,
                                                  out=handle.out)
        handle.done = super().test(handle.meta["posted"])
        return handle.done

    def cancel(self, handle):
        posted = handle.meta.get("posted", handle)
        if posted is not None:
            super().cancel(posted)
        handle.cancelled = True


class TestShmWireLandsInPlace:
    """Unchunked rounds over the shm wire (ISSUE 29): the server's GRAD
    receive and the client's PARAM receive are posted before their
    messages arrive, so the shards land in the frame and in the parameter
    slice straight from the ring — and the bytes are those of a wire that
    assembles every message first."""

    SIZE = 600_000       # two shards of 1.2 MB ...
    RING = 256 << 10     # ... through 256 KB rings
    ROUNDS = 3

    def run_rounds(self, name, transport_cls):
        ns = f"t_psw_{name}_{os.getpid()}"
        wires = [transport_cls(ns, r, 3, ring_bytes=self.RING)
                 for r in range(3)]
        servers = [ParamServer(r, [2], wires[r],
                               rule=rules.make("adam", lr=1e-2))
                   for r in (0, 1)]
        threads = [threading.Thread(target=s.start, daemon=True)
                   for s in servers]
        for t in threads:
            t.start()
        client = ParamClient(2, [0, 1], wires[2], seed_servers=True)
        rng = np.random.default_rng(29)
        param = rng.normal(size=self.SIZE).astype(np.float32)
        grad = np.zeros_like(param)
        try:
            client.start(param, grad)
            for _ in range(self.ROUNDS):
                grad[:] = rng.normal(size=self.SIZE).astype(np.float32)
                client.async_send_grad()
                client.async_recv_param()
                client.wait()
            client.stop()
            join_all(threads)
            assert [s.grads_applied for s in servers] == [self.ROUNDS] * 2
            return param, [w.rx_path_bytes() for w in wires]
        finally:
            for s in servers:
                s.live.stop()
            for t in threads:
                t.join(5)
            for w in wires:
                w.close()

    def test_rounds_land_direct_and_equal_the_assembled_wire(self):
        from mpit_tpu.comm.shm import ShmTransport

        class LateShm(PostsLate, ShmTransport):
            pass

        direct, paths = self.run_rounds("direct", ShmTransport)
        late, late_paths = self.run_rounds("late", LateShm)
        assert np.array_equal(direct, late)
        shard = self.SIZE // 2 * 4
        for rank, rx in enumerate(paths):
            moved = self.ROUNDS * (shard if rank < 2 else 2 * shard)
            # Every GRAD (servers) and PARAM (client) of every round ...
            assert rx["rx_direct_bytes"] >= moved, (rank, rx)
            # ... and what was assembled is acks, headers, INIT, and on a
            # server the one-shot seed if it beat its receive there.
            seed = shard if rank < 2 else 0
            assert rx["rx_assembled_bytes"] < seed + 4096, (rank, rx)
        for rx in late_paths:
            assert rx["rx_direct_bytes"] == 0
            assert rx["rx_assembled_bytes"] >= self.ROUNDS * shard
