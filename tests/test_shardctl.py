"""mpit_tpu.shardctl — versioned maps, rebalancing, live migration.

The acceptance invariants (ISSUE 5): live migration and lease-expiry
shard failover both leave final params **bitwise equal** to a fault-free
static-map run — including under deterministic drop/dup fault plans —
because the shard-scoped dedup state travels with the shard, re-routed
retries admit at-most-once on the new owner, and lockstep turns pin the
cross-client apply order (same discipline as tests/test_ft.py).
"""

import dataclasses
import threading
import tempfile

import numpy as np
import pytest

from mpit_tpu.comm.local import LocalRouter
from mpit_tpu.ft import FaultPlan, FaultyTransport, FTConfig
from mpit_tpu.ps import ParamClient, ParamServer, Shard, tags, weighted_layout
from mpit_tpu.shardctl import (
    RebalancePolicy,
    ShardController,
    ShardLoad,
    ShardMap,
)
from mpit_tpu.shardctl import wire as scwire

DATA_TAGS = frozenset({tags.GRAD, tags.PARAM_REQ, tags.PARAM_PUSH})
REPLY_TAGS = frozenset({tags.GRAD_ACK, tags.PARAM, tags.PARAM_PUSH_ACK})

FAST_FT = FTConfig(op_deadline_s=0.3, max_retries=10,
                   backoff_base_s=0.005, backoff_cap_s=0.02)


def join_all(threads, timeout=30):
    for t in threads:
        t.join(timeout)
        assert not t.is_alive(), "role thread did not stop (hang)"


# ---------------------------------------------------------------------------
# weighted_layout — Hypothesis-style property sweep (satellite)


class TestWeightedLayout:
    def _check_invariants(self, plong, shards):
        assert shards, "layout produced no shards"
        assert shards[0].offset == 0
        for prev, cur in zip(shards, shards[1:]):
            assert cur.offset == prev.end, "shards must be contiguous"
        assert shards[-1].end == plong, "shards must cover the range"
        assert all(s.size >= 1 for s in shards), "every shard nonempty"

    def test_property_sweep(self):
        """Cover-the-range / nonempty / contiguous over a seeded sweep of
        (plong, n, weights) samples — the property-test satellite."""
        rng = np.random.default_rng(1234)
        for _ in range(300):
            n = int(rng.integers(1, 9))
            plong = int(rng.integers(n, 5000))
            weights = rng.uniform(0.01, 10.0, size=n).tolist()
            shards = weighted_layout(plong, weights)
            self._check_invariants(plong, shards)
            assert len(shards) == n

    def test_proportionality(self):
        shards = weighted_layout(1000, [1.0, 3.0])
        assert shards == [Shard(0, 250), Shard(250, 750)]

    def test_remainder_goes_to_heaviest(self):
        # floors: [333, 111, 556] leave 1 spare -> heaviest (rank 2)
        shards = weighted_layout(1001, [3.0, 1.0, 5.0])
        assert sum(s.size for s in shards) == 1001
        assert shards[2].size == 557

    def test_tiny_plong_keeps_everyone_nonempty(self):
        shards = weighted_layout(3, [100.0, 0.01, 0.01])
        self._check_invariants(3, shards)

    def test_errors(self):
        with pytest.raises(ValueError):
            weighted_layout(2, [1.0, 1.0, 1.0])
        with pytest.raises(ValueError):
            weighted_layout(10, [])
        with pytest.raises(ValueError):
            weighted_layout(10, [1.0, -1.0])


# ---------------------------------------------------------------------------
# ShardMap


class TestShardMap:
    def test_initial_matches_shard_layout(self):
        m = ShardMap.initial(10, [0, 1, 2])
        assert [e.shard for e in m.entries] == [
            Shard(0, 3), Shard(3, 3), Shard(6, 4)]
        assert m.version == 0 and m.owners() == [0, 1, 2]

    def test_weighted_initial(self):
        m = ShardMap.initial(100, [5, 7], weights=[1.0, 3.0])
        assert m.entry(1).shard.size == 75 and m.owner(1) == 7

    def test_moved_bumps_version_only(self):
        m = ShardMap.initial(10, [0, 1])
        m2 = m.moved(1, 0)
        assert (m2.version, m2.owner(1)) == (1, 0)
        assert m.version == 0 and m.owner(1) == 1  # immutability
        assert [e.shard for e in m2.entries] == [e.shard for e in m.entries]

    def test_reassigned_spreads_over_survivors(self):
        m = ShardMap.initial(30, [0, 1, 2])
        m2 = m.moved(0, 1)  # rank 1 holds shards 0 and 1
        m3 = m2.reassigned(1, [0, 2])
        assert m3.version == m2.version + 1
        # both orphans land on survivors and no survivor exceeds 2 shards
        assert {m3.owner(0), m3.owner(1)} <= {0, 2}
        assert max(len(m3.shards_of(r)) for r in (0, 2)) == 2

    def test_wire_roundtrip(self):
        m = ShardMap.initial(1000, [3, 5, 9]).moved(2, 3)
        again = ShardMap.from_wire(m.to_wire())
        assert again == m
        with pytest.raises(ValueError):
            ShardMap.from_wire(np.asarray([1, 2, 3, 4], np.int64))

    def test_tiling_validated(self):
        from mpit_tpu.shardctl.shardmap import ShardEntry

        with pytest.raises(ValueError, match="tile"):
            ShardMap(0, 10, [ShardEntry(0, Shard(0, 4), 0),
                             ShardEntry(1, Shard(5, 5), 1)])


# ---------------------------------------------------------------------------
# policy


class TestRebalancePolicy:
    def _loads(self, busy):
        return {rank: {sid: ShardLoad(ops=10, busy_s=b)
                       for sid, b in shards.items()}
                for rank, shards in busy.items()}

    def test_proposes_hot_to_cold(self):
        m = ShardMap.initial(100, [0, 1])
        policy = RebalancePolicy(ratio=3.0, min_busy_s=0.01)
        loads = self._loads({0: {0: 1.0}, 1: {1: 0.1}})
        assert policy.propose(m, loads) == (0, 1)

    def test_quiet_window_proposes_nothing(self):
        m = ShardMap.initial(100, [0, 1])
        policy = RebalancePolicy(ratio=3.0, min_busy_s=0.5)
        loads = self._loads({0: {0: 0.4}, 1: {1: 0.01}})
        assert policy.propose(m, loads) is None

    def test_balanced_load_proposes_nothing(self):
        m = ShardMap.initial(100, [0, 1])
        policy = RebalancePolicy(ratio=3.0, min_busy_s=0.01)
        loads = self._loads({0: {0: 1.0}, 1: {1: 0.9}})
        assert policy.propose(m, loads) is None

    def test_disabled_policy_is_silent(self):
        m = ShardMap.initial(100, [0, 1])
        policy = RebalancePolicy(enabled=False)
        assert policy.propose(m, self._loads({0: {0: 9.0}, 1: {1: 0.0}})) \
            is None


# ---------------------------------------------------------------------------
# gang harness


def launch_sc(nservers, nclients, size, ckpt_dir=None, codec=None,
              client_plans=None, server_plan=None, client_ft=FAST_FT,
              server_ft=FAST_FT, ctl_kwargs=None):
    """Shardctl topology: servers + controller threads wired over the
    in-process router, clients driven by the test (lockstep turns)."""
    n = nservers + nclients + 1
    router = LocalRouter(n)
    sranks = list(range(nservers))
    cranks = list(range(nservers, nservers + nclients))
    ctl_rank = n - 1
    servers, threads = [], []
    for r in sranks:
        ep = router.endpoint(r)
        if server_plan is not None:
            ep = FaultyTransport(ep, server_plan)
        servers.append(ParamServer(
            r, cranks, ep, rule="add", ft=server_ft,
            controller_rank=ctl_rank, ckpt_dir=ckpt_dir,
            ckpt_interval=1e9))
        threads.append(threading.Thread(target=servers[-1].start,
                                        daemon=True))
    for t in threads:
        t.start()
    ctl = ShardController(ctl_rank, router.endpoint(ctl_rank), sranks,
                          cranks, **(ctl_kwargs or {}))
    clients = []
    for i, r in enumerate(cranks):
        ep = router.endpoint(r)
        plan = (client_plans or {}).get(i)
        if plan is not None:
            ep = FaultyTransport(ep, plan)
        clients.append(ParamClient(
            r, sranks, ep, seed_servers=(r == cranks[0]), codec=codec,
            ft=client_ft, shardctl=True, controller_rank=ctl_rank))
    return servers, clients, threads, ctl


def start_clients(clients, w0):
    params, grads, starters = [], [], []
    for c in clients:
        p = w0.copy() if not params else np.zeros_like(w0)
        g = np.zeros_like(w0)
        params.append(p)
        grads.append(g)
        starters.append(threading.Thread(target=c.start, args=(p, g),
                                         daemon=True))
    for t in starters:
        t.start()
    join_all(starters)
    return params


def lockstep(clients, gtab, rounds, hook=None):
    for r in range(rounds):
        if hook is not None:
            hook(r)
        for i, c in enumerate(clients):
            c.grad[:] = gtab[i, r]
            c.async_send_grad()
            c.wait()


def finish(clients, threads, ctl, live_threads=None):
    clients[0].async_recv_param()
    clients[0].wait()
    out = clients[0].param.copy()
    for c in clients:
        c.stop()
    join_all(live_threads if live_threads is not None else threads)
    ctl.pump()
    assert ctl.done, "controller missed client STOPs"
    return out


# ---------------------------------------------------------------------------
# end-to-end: static parity, live migration, failover — all bitwise


class TestShardctlGang:
    def _tables(self, size=48, rounds=6, nclients=2, seed=7):
        rng = np.random.default_rng(seed)
        w0 = rng.normal(size=size).astype(np.float32)
        gtab = rng.normal(size=(nclients, rounds, size)).astype(np.float32)
        return w0, gtab

    def _run(self, w0, gtab, rounds, hook=None, **kw):
        servers, clients, threads, ctl = launch_sc(2, 2, len(w0), **kw)
        start_clients(clients, w0)
        ctl.pump()  # adopt the seeder's initial map
        assert ctl.smap is not None and ctl.smap.version == 0
        lockstep(clients, gtab, rounds,
                 hook=(lambda r: hook(r, ctl, servers, threads))
                 if hook else None)
        dead = [i for i, t in enumerate(threads) if not t.is_alive()]
        live = [t for t in threads if t.is_alive() or True]
        out = finish(clients, threads, ctl,
                     live_threads=[t for i, t in enumerate(threads)
                                   if i not in dead])
        return out, servers, clients, ctl

    def test_static_map_gang_trains(self):
        w0, gtab = self._tables()
        out, servers, clients, ctl = self._run(w0, gtab, 6)
        want = w0 + gtab.sum(axis=(0, 1))
        np.testing.assert_allclose(out, want, rtol=1e-5)
        assert [s.owned_shards for s in servers] == [[0], [1]]

    def test_live_migration_is_bitwise_transparent(self):
        """One mid-run migration: final params bitwise-equal to the
        static run; the drain went through the NACK path."""
        w0, gtab = self._tables()
        static, *_ = self._run(w0, gtab, 6)

        def hook(r, ctl, servers, threads):
            if r == 3:
                assert ctl.migrate(1, 0)

        migrated, servers, clients, ctl = self._run(w0, gtab, 6, hook=hook)
        np.testing.assert_array_equal(static, migrated)
        assert servers[0].owned_shards == [0, 1]
        assert servers[1].owned_shards == []
        assert sum(int(c._m_nacks.value) for c in clients) > 0, \
            "nobody drained through NACK_MAP — the migration was free?"

    def test_live_migration_under_drop_dup_plans_stays_bitwise(self):
        """The acceptance matrix, shardctl edition: client data drops +
        dups, server reply drops, a migration mid-run — still bitwise.

        Two things of its own keep it so under a loaded machine (six
        xdist workers).  The rebalance policy is off: after the
        migration one server owns both shards, and once its busy
        seconds pass the policy's floor, which a loaded machine and the
        duplicates' work see to, the last ``pump`` proposes the move
        back to a server whose thread has already stopped and waits out
        the controller's 60 s for its DONE; the one migration under
        test is the hook's.  And an op deadline twice ``FAST_FT``'s
        with two retries more: a retry that a slow turn causes, on top
        of the plans' own, is deduplicated, ten in a row are a
        failure."""
        w0, gtab = self._tables()
        static, *_ = self._run(w0, gtab, 6)
        patient = dataclasses.replace(FAST_FT, op_deadline_s=0.6,
                                      max_retries=12)

        def hook(r, ctl, servers, threads):
            if r == 2:
                assert ctl.migrate(0, 1)

        client_plans = {
            i: FaultPlan(seed=i, drop_every=3, dup_every=4, tags=DATA_TAGS)
            for i in range(2)
        }
        server_plan = FaultPlan(seed=9, drop_every=3, tags=REPLY_TAGS)
        faulty, servers, clients, ctl = self._run(
            w0, gtab, 6, hook=hook,
            client_plans=client_plans, server_plan=server_plan,
            client_ft=patient, server_ft=patient,
            ctl_kwargs={"policy": RebalancePolicy(enabled=False)})
        np.testing.assert_array_equal(static, faulty)
        assert sum(int(s.dup_ops) for s in servers) > 0, \
            "no duplicate was ever admitted — the plan never bit"
        assert ctl.smap.version == 1 and ctl.smap.owner(0) == 1

    def test_migration_preserves_int8_error_feedback(self):
        """Quantized gang: the residual telescope survives a migration
        (encode-once staging + migrated dedup keep the applied stream
        identical), so final params match the static int8 run bitwise."""
        w0, gtab = self._tables(size=4096)

        def hook(r, ctl, servers, threads):
            if r == 3:
                assert ctl.migrate(1, 0)

        static, *_ = self._run(w0, gtab, 6, codec="int8")
        migrated, _, clients, _ = self._run(w0, gtab, 6, codec="int8",
                                            hook=hook)
        np.testing.assert_array_equal(static, migrated)
        assert any(c.residual_norm() > 0 for c in clients)

    def test_lease_expiry_failover_is_bitwise_transparent(self, tmp_path):
        """The dead-server path end-to-end: beats stop, the controller's
        lease on the server expires (fake clock), failover ADOPTs the
        shard from its checkpoint on a survivor, clients re-route via
        the broadcast map — final params bitwise vs the static run,
        under drop/dup plans."""
        w0, gtab = self._tables()
        static, *_ = self._run(w0, gtab, 6)

        now = [0.0]
        killed = []

        def hook(r, ctl, servers, threads):
            now[0] += 1.0
            if r == 3:
                import time as _time

                # The controller's lease on server 1 must be armed by a
                # real beat before the death is observable as expiry.
                t0 = _time.monotonic()
                while ctl.leases._expiry.get(1) is None:
                    ctl.pump()
                    assert _time.monotonic() - t0 < 10, "no beat arrived"
                    _time.sleep(0.01)
                # Quiesced turn boundary: checkpoint, kill, expire.
                servers[1].save_state(str(tmp_path))
                servers[1].live.stop()
                threads[1].join(10)
                assert not threads[1].is_alive()
                killed.append(1)
                ctl._drain_beats()  # the dead server's last beats
                now[0] += 100.0
                # Let the live server's next beat renew under the jumped
                # clock, so only the dead server's lease reads expired.
                t0 = _time.monotonic()
                while ctl.leases._expiry.get(0) is not None \
                        and ctl.leases._expiry[0] < now[0]:
                    ctl._drain_beats()
                    assert _time.monotonic() - t0 < 10, "no fresh beat"
                    _time.sleep(0.01)
                ctl.check_leases()
                assert ctl.smap.owner(1) == 0, "failover did not move shard"

        client_plans = {
            i: FaultPlan(seed=i, drop_every=4, dup_every=5, tags=DATA_TAGS)
            for i in range(2)
        }
        failed, servers, clients, ctl = self._run(
            w0, gtab, 6, hook=hook, ckpt_dir=str(tmp_path),
            client_plans=client_plans,
            ctl_kwargs=dict(lease_ttl_s=5.0, clock=lambda: now[0]))
        np.testing.assert_array_equal(static, failed)
        assert killed == [1]
        assert servers[0].owned_shards == [0, 1]
        # Every client adopted the failover map (the broadcast is polled
        # between rounds, so the re-route may be proactive rather than a
        # mid-op NACK/timeout re-route — either path must land on v1).
        assert all(c.smap.version == 1 for c in clients)


# ---------------------------------------------------------------------------
# controller plumbing


class TestController:
    def test_beats_feed_leases_and_window(self):
        servers, clients, threads, ctl = launch_sc(
            2, 1, 32, client_ft=FTConfig(op_deadline_s=0.3, max_retries=6,
                                         heartbeat_s=0.02,
                                         backoff_base_s=0.005,
                                         backoff_cap_s=0.02),
            server_ft=FTConfig(op_deadline_s=0.3, max_retries=6,
                               heartbeat_s=0.02, backoff_base_s=0.005,
                               backoff_cap_s=0.02))
        w0 = np.arange(32, dtype=np.float32)
        start_clients(clients, w0)
        deadline = 5.0
        import time as _time
        t0 = _time.monotonic()
        while int(ctl._m_beats.value) == 0:
            ctl.pump()
            assert _time.monotonic() - t0 < deadline, "no beat ever arrived"
            _time.sleep(0.01)
        out = finish(clients, threads, ctl)
        np.testing.assert_array_equal(out, w0)

    def test_policy_driven_rebalance_moves_the_hot_shard(self):
        """Synthetic window: feed the controller a skewed load report
        and let maybe_rebalance execute a real migration."""
        now = [0.0]
        servers, clients, threads, ctl = launch_sc(
            2, 2, 48,
            ctl_kwargs=dict(policy=RebalancePolicy(ratio=2.0,
                                                   min_busy_s=0.0,
                                                   cooldown_s=1.0),
                            clock=lambda: now[0]))
        w0 = np.arange(48, dtype=np.float32)
        start_clients(clients, w0)
        ctl.pump()
        ctl._window = {0: {0: ShardLoad(ops=50, busy_s=2.0)},
                       1: {1: ShardLoad(ops=50, busy_s=0.1)}}
        now[0] += 10.0
        assert ctl.maybe_rebalance()
        assert ctl.smap.owner(0) == 1
        gtab = np.ones((2, 2, 48), np.float32)
        lockstep(clients, gtab, 2)
        out = finish(clients, threads, ctl)
        np.testing.assert_allclose(out, w0 + 4.0, rtol=1e-6)
        assert servers[1].owned_shards == [0, 1]

    def test_migrate_refuses_noops(self):
        servers, clients, threads, ctl = launch_sc(2, 1, 32)
        w0 = np.arange(32, dtype=np.float32)
        start_clients(clients, w0)
        ctl.pump()
        assert not ctl.migrate(0, 0)  # already there
        assert not ctl.migrate(99, 1)  # unknown shard
        out = finish(clients, threads, ctl)
        np.testing.assert_array_equal(out, w0)


# ---------------------------------------------------------------------------
# guards


class TestGuards:
    def test_shardctl_without_deadlines_is_rejected(self):
        router = LocalRouter(2)
        with pytest.raises(ValueError, match="op_deadline_s"):
            ParamClient(1, [0], router.endpoint(1), shardctl=True,
                        ft=FTConfig())

    def test_mixed_legacy_and_shardctl_inits_fail_loudly(self):
        """One v4 and one legacy client on a server must not negotiate."""
        from mpit_tpu.aio import TaskError

        router = LocalRouter(3)
        server = ParamServer(0, [1, 2], router.endpoint(0), ft=FAST_FT)
        err = []

        def run_server():
            try:
                server.start()
            except TaskError as exc:
                err.append(exc)

        th = threading.Thread(target=run_server, daemon=True)
        th.start()
        sc_client = ParamClient(1, [0], router.endpoint(1), ft=FAST_FT,
                                shardctl=True)
        legacy = ParamClient(2, [0], router.endpoint(2), ft=FAST_FT)
        w = np.ones(8, np.float32)

        def start_bg(c):
            t = threading.Thread(
                target=lambda: c.start(w.copy(), np.zeros_like(w)),
                daemon=True)
            t.start()
            return t

        t1 = start_bg(sc_client)
        t2 = start_bg(legacy)
        th.join(10)
        assert err, "server accepted a mixed v4/legacy gang"
        server.live.stop()
        sc_client.live.stop()
        legacy.live.stop()
        for t in (t1, t2):
            t.join(5)
