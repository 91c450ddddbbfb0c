"""mpit_tpu.ft — fault-tolerance subsystem tests.

Every recovery path is driven by deterministic fault injection
(ft/faults.py): the FaultyTransport wrapper drops / delays / duplicates /
severs messages on a schedule that is a pure function of
(seed, src, dst, tag, per-channel count), so each failure below is the
same failure on every run.

Topology notes: client-side faults wrap the client's transport (GRAD,
PARAM_REQ, PARAM_PUSH are client sends); ack/snapshot faults wrap the
*server's* transport (GRAD_ACK, PARAM, PARAM_PUSH_ACK are server sends).
Bitwise assertions rely on lockstep turns — each client awaits its acks
before the next client ships — which pins the cross-client apply order;
FIFO channels + at-most-once dedup then make the faulty run's apply
stream identical to the fault-free one.
"""

import threading
import time

import numpy as np
import pytest

from mpit_tpu.aio import (
    DeadlineExceeded,
    Scheduler,
    TaskError,
    aio_recv,
    aio_sleep,
    deadline_at,
)
from mpit_tpu.comm.local import LocalRouter
from mpit_tpu.ft import (
    EVICTED,
    DedupTable,
    FaultPlan,
    FaultyTransport,
    FTConfig,
    LeaseRegistry,
    RetryExhausted,
    RetryPolicy,
)
from mpit_tpu.ps import ParamClient, ParamServer, tags

#: the retried data channels — INIT (the membership rendezvous) and
#: STOP/HEARTBEAT (covered by leases, not retry) stay clean.
DATA_TAGS = frozenset({tags.GRAD, tags.PARAM_REQ, tags.PARAM_PUSH})
REPLY_TAGS = frozenset({tags.GRAD_ACK, tags.PARAM, tags.PARAM_PUSH_ACK})

#: a fast retry posture for LocalRouter-speed tests
FAST_FT = FTConfig(op_deadline_s=0.25, max_retries=8,
                   backoff_base_s=0.005, backoff_cap_s=0.02)


def join_all(threads, timeout=30):
    for t in threads:
        t.join(timeout)
        assert not t.is_alive(), "role thread did not stop (hang)"


# ---------------------------------------------------------------------------
# scheduler timers


class TestSchedulerTimers:
    def test_aio_sleep_elapses(self):
        sched = Scheduler(idle_usec=0)
        t0 = time.monotonic()
        task = sched.spawn(aio_sleep(0.05), name="sleep")
        sched.wait()
        assert task.result is True
        assert time.monotonic() - t0 >= 0.05

    def test_aio_sleep_aborts_on_live_drop(self):
        from mpit_tpu.aio import LiveFlag

        live = LiveFlag()
        sched = Scheduler(idle_usec=0)
        task = sched.spawn(aio_sleep(60.0, live=live), name="sleep")
        live.stop()
        sched.wait()
        assert task.result is False

    def test_recv_deadline_raises(self):
        router = LocalRouter(2)
        sched = Scheduler(idle_usec=0)
        sched.spawn(
            aio_recv(router.endpoint(0), 1, tags.GRAD,
                     deadline=deadline_at(0.03)),
            name="recv",
        )
        with pytest.raises(TaskError) as err:
            sched.wait()
        assert isinstance(err.value.cause, DeadlineExceeded)
        assert err.value.cause.tag == tags.GRAD

    def test_deadline_at_none_passthrough(self):
        assert deadline_at(None) is None
        assert deadline_at(1.0) > time.monotonic()


# ---------------------------------------------------------------------------
# fault plan + transport


class TestFaultPlan:
    def test_parse_roundtrip(self):
        plan = FaultPlan.parse(
            "seed=7,drop_every=3,dup_every=5,delay_every=2,delay_polls=4")
        assert (plan.seed, plan.drop_every, plan.dup_every) == (7, 3, 5)
        assert plan.delay_polls == 4

    def test_parse_unknown_field_fails_loudly(self):
        with pytest.raises(ValueError, match="unknown fault-plan field"):
            FaultPlan.parse("seed=1,frobnicate=2")

    def test_every_k_counts_per_channel(self):
        plan = FaultPlan(drop_every=3)
        verdicts = [plan.decide(0, 1, tags.GRAD, n) for n in range(1, 7)]
        assert verdicts == ["pass", "pass", "drop", "pass", "pass", "drop"]
        # an independent channel has its own count
        assert plan.decide(0, 1, tags.PARAM_REQ, 1) == "pass"

    def test_rate_mode_is_seed_deterministic(self):
        plan_a = FaultPlan(seed=3, drop_rate=0.3, dup_rate=0.3)
        plan_b = FaultPlan(seed=3, drop_rate=0.3, dup_rate=0.3)
        decisions = [plan_a.decide(0, 1, tags.GRAD, n) for n in range(1, 200)]
        assert decisions == [plan_b.decide(0, 1, tags.GRAD, n)
                             for n in range(1, 200)]
        assert "drop" in decisions and "dup" in decisions
        # a different seed gives a different schedule
        other = [FaultPlan(seed=4, drop_rate=0.3, dup_rate=0.3)
                 .decide(0, 1, tags.GRAD, n) for n in range(1, 200)]
        assert decisions != other

    def test_tags_filter(self):
        plan = FaultPlan(drop_every=1, tags=frozenset({tags.GRAD}))
        assert plan.decide(0, 1, tags.GRAD, 1) == "drop"
        assert plan.decide(0, 1, tags.PARAM, 1) == "pass"
        assert plan.decide(0, 1, -5, 1) == "pass"  # internal tags never


class TestFaultyTransport:
    def _pair(self, plan):
        router = LocalRouter(2)
        return FaultyTransport(router.endpoint(0), plan), router.endpoint(1)

    def test_drop_never_delivers(self):
        src, dst = self._pair(FaultPlan(drop_every=1))
        src.send(b"x", 1, tags.GRAD)  # completes for the sender
        assert src.dropped == 1
        assert not dst.iprobe(0, tags.GRAD)

    def test_dup_delivers_twice(self):
        src, dst = self._pair(FaultPlan(dup_every=1))
        src.send(b"x", 1, tags.GRAD)
        assert dst.recv(0, tags.GRAD) == b"x"
        assert dst.recv(0, tags.GRAD) == b"x"
        assert src.duplicated == 1

    def test_delay_defers_post(self):
        src, dst = self._pair(FaultPlan(delay_every=1, delay_polls=5))
        handle = src.isend(b"x", 1, tags.GRAD)
        polls = 0
        while not src.test(handle):
            polls += 1
        assert polls >= 4
        assert dst.recv(0, tags.GRAD) == b"x"

    def test_sever_cuts_the_link(self):
        src, dst = self._pair(FaultPlan())
        src.send(b"a", 1, tags.GRAD)
        src.sever(1)
        src.send(b"b", 1, tags.GRAD)
        assert dst.recv(0, tags.GRAD) == b"a"
        assert not dst.iprobe(0, tags.GRAD)
        assert src.dropped == 1

    def test_recv_side_is_faithful(self):
        src, dst = self._pair(FaultPlan(drop_every=2))
        wrapped_dst = FaultyTransport(dst, FaultPlan(drop_every=2))
        src.send(b"x", 1, tags.GRAD)
        assert wrapped_dst.recv(0, tags.GRAD) == b"x"


# ---------------------------------------------------------------------------
# dedup + leases + retry units


class TestDedupTable:
    def test_fresh_dup_stale(self):
        t = DedupTable()
        assert t.admit(1, tags.GRAD, 0, 1) == "fresh"
        assert t.admit(1, tags.GRAD, 0, 1) == "dup"
        assert t.admit(1, tags.GRAD, 0, 2) == "fresh"
        assert t.admit(1, tags.GRAD, 0, 2) == "dup"
        # new incarnation resets the horizon
        assert t.admit(1, tags.GRAD, 1, 1) == "fresh"
        # the dead incarnation's stragglers are stale
        assert t.admit(1, tags.GRAD, 0, 3) == "stale"

    def test_channels_are_independent(self):
        t = DedupTable()
        assert t.admit(1, tags.GRAD, 0, 1) == "fresh"
        assert t.admit(1, tags.PARAM_PUSH, 0, 1) == "fresh"
        assert t.admit(2, tags.GRAD, 0, 1) == "fresh"

    def test_state_roundtrip(self):
        t = DedupTable()
        t.admit(1, tags.GRAD, 2, 7)
        t.admit(3, tags.PARAM_PUSH, 0, 4)
        t2 = DedupTable()
        t2.restore(t.state())
        assert t2.admit(1, tags.GRAD, 2, 7) == "dup"
        assert t2.admit(3, tags.PARAM_PUSH, 0, 5) == "fresh"


class TestLeaseRegistry:
    def test_expiry_only_after_first_beat(self):
        now = [0.0]
        reg = LeaseRegistry([1, 2], ttl_s=1.0, clock=lambda: now[0])
        reg.arm(1, 0, heartbeats=True)
        reg.arm(2, 0, heartbeats=False)  # never promised beats
        # nobody beat yet: nobody is on the clock (the seeding-phase
        # grace — arming at INIT would evict a slow seeder mid-push)
        now[0] = 5.0
        assert reg.expired() == []
        reg.renew(1, 0)  # first beat arms the clock
        reg.renew(2, 0)  # never promised: renew is a no-op
        now[0] = 5.5
        assert reg.expired() == []
        now[0] = 6.5
        assert reg.expired() == [1]
        reg.renew(1, 0)
        assert reg.expired() == []

    def test_stale_epoch_beat_does_not_renew(self):
        now = [0.0]
        reg = LeaseRegistry([1], ttl_s=1.0, clock=lambda: now[0])
        reg.arm(1, 5, heartbeats=True)
        reg.renew(1, 5)  # first beat: on the clock from t=0
        now[0] = 0.9
        reg.renew(1, 4)  # dead incarnation's leftover beacon
        now[0] = 1.5
        assert reg.expired() == [1]

    def test_eviction_and_rejoin_lifecycle(self):
        reg = LeaseRegistry([1, 2], ttl_s=0.0)
        reg.evict(1)
        assert reg.state(1) == EVICTED and reg.gone(1)
        assert not reg.all_done()
        reg.stop(2)
        assert reg.all_done()
        reg.rejoin(1, epoch=1)
        assert not reg.gone(1) and reg.epoch(1) == 1


class TestRetryPolicy:
    def test_backoff_caps_and_jitter_is_deterministic(self):
        cfg = FTConfig(op_deadline_s=1.0, max_retries=10,
                       backoff_base_s=0.01, backoff_cap_s=0.05)
        pol = RetryPolicy(cfg, key=3)
        seq = [pol.backoff_s(a) for a in range(1, 11)]
        assert seq == [RetryPolicy(cfg, key=3).backoff_s(a)
                       for a in range(1, 11)]
        assert max(seq) <= 0.05 * 1.5 + 1e-9
        assert seq[0] >= 0.01
        # a different key decorrelates
        assert seq != [RetryPolicy(cfg, key=4).backoff_s(a)
                       for a in range(1, 11)]


# ---------------------------------------------------------------------------
# end-to-end: retry + dedup against an injected-fault PS topology


def launch_ft(nservers, nclients, client_plans=None, server_plan=None,
              client_ft=FAST_FT, server_ft=None, rule="add", codec=None):
    """FT PS topology over LocalRouter with FaultyTransport seams.
    Returns (servers, clients, threads, client_transports)."""
    n = nservers + nclients
    router = LocalRouter(n)
    sranks = list(range(nservers))
    cranks = list(range(nservers, n))
    server_ft = server_ft or FTConfig(rejoin=True)
    servers, threads = [], []
    for r in sranks:
        ep = router.endpoint(r)
        if server_plan is not None:
            ep = FaultyTransport(ep, server_plan)
        servers.append(ParamServer(r, cranks, ep, rule=rule, ft=server_ft))
        threads.append(threading.Thread(target=servers[-1].start, daemon=True))
    for t in threads:
        t.start()
    transports, clients = [], []
    for i, r in enumerate(cranks):
        ep = router.endpoint(r)
        plan = (client_plans or {}).get(i)
        if plan is not None:
            ep = FaultyTransport(ep, plan)
        transports.append(ep)
        clients.append(ParamClient(r, sranks, ep,
                                   seed_servers=(r == cranks[0]),
                                   codec=codec, ft=client_ft))
    return servers, clients, threads, transports


def run_lockstep(clients, grads_per_round, rounds):
    """Lockstep rounds: each client ships its grad and awaits the acks
    before the next client moves — pins the cross-client apply order so
    faulty and fault-free runs are bitwise-comparable."""
    for r in range(rounds):
        for i, c in enumerate(clients):
            c.grad[:] = grads_per_round(i, r)
            c.async_send_grad()
            c.wait()


class TestRetryDedupEndToEnd:
    def _final_params(self, client_plans, server_plan, rounds=4,
                      nservers=2, nclients=2, codec=None, size=64):
        rng = np.random.default_rng(42)
        w0 = rng.normal(size=size).astype(np.float32)
        gtab = rng.normal(size=(nclients, rounds, size)).astype(np.float32)
        servers, clients, threads, transports = launch_ft(
            nservers, nclients, client_plans=client_plans,
            server_plan=server_plan, codec=codec)
        params = []
        starters = []
        for c in clients:
            p = w0.copy() if not params else np.zeros_like(w0)
            params.append(p)
            starters.append(threading.Thread(
                target=c.start, args=(p, np.zeros_like(w0)), daemon=True))
        for t in starters:
            t.start()
        join_all(starters)
        run_lockstep(clients, lambda i, r: gtab[i, r], rounds)
        clients[0].async_recv_param()
        clients[0].wait()
        for c in clients:
            c.stop()
        join_all(threads)
        stats = {
            "applied": sum(s.grads_applied for s in servers),
            "dups": sum(s.dup_ops for s in servers),
            "retries": sum(c.retries for c in clients),
        }
        return params[0].copy(), stats

    def test_drop_and_dup_run_matches_fault_free_bitwise(self):
        """The acceptance matrix: every 3rd client data message dropped,
        every 4th duplicated; every 3rd server reply dropped.  The final
        params must equal the fault-free run's final params *bitwise* —
        retry + dedup + seq-matched acks leave no trace in the math."""
        clean, clean_stats = self._final_params(None, None)
        client_plans = {
            i: FaultPlan(seed=i, drop_every=3, dup_every=4, tags=DATA_TAGS)
            for i in range(2)
        }
        server_plan = FaultPlan(seed=9, drop_every=3, tags=REPLY_TAGS)
        faulty, stats = self._final_params(client_plans, server_plan)
        np.testing.assert_array_equal(clean, faulty)
        assert stats["retries"] > 0, "the plan never actually bit"
        assert stats["dups"] > 0, "no duplicate was ever admitted"
        assert stats["applied"] == clean_stats["applied"]

    def test_int8_error_feedback_survives_retries(self):
        """Dropped replies force resends of quantized grads; the staged
        encode-once frames + server dedup must keep the error-feedback
        telescope exact: bitwise-equal params vs the fault-free int8 run."""
        clean, _ = self._final_params(None, None, codec="int8", size=2048)
        server_plan = FaultPlan(seed=5, drop_every=2, tags=REPLY_TAGS)
        faulty, stats = self._final_params(None, server_plan,
                                           codec="int8", size=2048)
        np.testing.assert_array_equal(clean, faulty)
        assert stats["retries"] > 0 and stats["dups"] > 0

    def test_exhausted_retries_fail_loudly_never_hang(self):
        """A severed server link must surface as RetryExhausted from the
        client's wait — the never-hang contract."""
        servers, clients, threads, transports = launch_ft(
            1, 1,
            client_plans={0: FaultPlan(tags=DATA_TAGS)},
            client_ft=FTConfig(op_deadline_s=0.05, max_retries=2,
                               backoff_base_s=0.005, backoff_cap_s=0.01),
        )
        (client,), (ct,) = clients, transports
        w0 = np.ones(8, np.float32)
        param, grad = w0.copy(), np.zeros_like(w0)
        client.start(param, grad)
        ct.sever(0)
        grad[:] = 1.0
        client.async_send_grad()
        t0 = time.monotonic()
        with pytest.raises(TaskError) as err:
            client.wait()
        assert isinstance(err.value.cause, RetryExhausted)
        assert time.monotonic() - t0 < 10.0
        for s in servers:
            s.live.stop()
        join_all(threads)

    def test_param_read_retries_and_discards_stale_snapshots(self):
        """Dropped PARAM replies: the read retries (same seq) and a later
        duplicate snapshot must not satisfy a newer request."""
        server_plan = FaultPlan(seed=2, drop_every=2,
                                tags=frozenset({tags.PARAM}))
        servers, clients, threads, _ = launch_ft(1, 1,
                                                 server_plan=server_plan)
        (client,) = clients
        w0 = np.arange(16, dtype=np.float32)
        param, grad = w0.copy(), np.zeros_like(w0)
        client.start(param, grad)
        for i in range(4):
            grad[:] = 1.0
            client.async_send_grad()
            client.async_recv_param()
            client.wait()
            np.testing.assert_array_equal(param, w0 + (i + 1))
        assert client.retries > 0
        client.stop()
        join_all(threads)


# ---------------------------------------------------------------------------
# heartbeats, leases, eviction, rejoin


HB_FT = FTConfig(heartbeat_s=0.02, op_deadline_s=0.5, max_retries=4,
                 backoff_base_s=0.005, backoff_cap_s=0.02)


class TestHeartbeatLeaseEviction:
    def test_heartbeats_flow_and_renew(self):
        servers, clients, threads, _ = launch_ft(
            1, 1, client_ft=HB_FT,
            server_ft=FTConfig(lease_ttl_s=0.5, rejoin=True))
        (client,) = clients
        w0 = np.ones(8, np.float32)
        client.start(w0.copy(), np.zeros_like(w0))
        deadline = time.monotonic() + 5
        while servers[0].heartbeats_seen < 3 and time.monotonic() < deadline:
            client.ping()
            time.sleep(0.005)
        assert servers[0].heartbeats_seen >= 3
        assert client.heartbeats_sent >= 3
        client.stop()
        join_all(threads)

    def test_lease_expiry_evicts_without_stalling_survivors(self):
        """The acceptance scenario: one client goes silent; its lease
        expires; the server evicts it, keeps serving the survivor, and
        the stop protocol completes without the dead client's STOP."""
        servers, clients, threads, transports = launch_ft(
            1, 2,
            client_plans={1: FaultPlan()},  # wrap c2 so we can sever it
            client_ft=HB_FT,
            server_ft=FTConfig(lease_ttl_s=0.15, rejoin=True))
        c1, c2 = clients
        w0 = np.ones(8, np.float32)
        bufs = [(w0.copy(), np.zeros_like(w0)),
                (np.zeros_like(w0), np.zeros_like(w0))]
        starters = [threading.Thread(target=c.start, args=bufs[i], daemon=True)
                    for i, c in enumerate(clients)]
        for t in starters:
            t.start()
        join_all(starters)
        # the lease arms on c2's first delivered beat (not at INIT —
        # arming before the seeding phase would evict mid-seed); wait for
        # c2's own, not for two beats of anyone's: on a loaded machine
        # both may be c1's, and a lease never armed never runs out
        deadline = time.monotonic() + 10
        while (not servers[0].leases.armed(c2.rank)
               and time.monotonic() < deadline):
            c2.ping()
            c2.wait()
            time.sleep(0.005)
        transports[1].sever(0)  # c2 "crashes": nothing reaches the server
        deadline = time.monotonic() + 10
        while (servers[0].leases.state(clients[1].rank) != EVICTED
               and time.monotonic() < deadline):
            c1.ping()
            time.sleep(0.005)
        assert servers[0].leases.state(c2.rank) == EVICTED
        assert c2.rank not in servers[0].grad_bufs  # staging released
        # survivor is unaffected
        p1, g1 = bufs[0]
        g1[:] = 2.0
        c1.async_send_grad()
        c1.async_recv_param()
        c1.wait()
        np.testing.assert_array_equal(p1, w0 + 2.0)
        c1.stop()
        join_all(threads)  # completes with only the survivor's STOP
        assert servers[0].leases.evictions == 1

    def test_evicted_client_rejoins_with_bumped_epoch(self):
        servers, clients, threads, transports = launch_ft(
            1, 2, client_plans={1: FaultPlan()}, client_ft=HB_FT,
            server_ft=FTConfig(lease_ttl_s=0.15, rejoin=True))
        c1, c2 = clients
        w0 = np.ones(8, np.float32)
        bufs = [(w0.copy(), np.zeros_like(w0)),
                (np.zeros_like(w0), np.zeros_like(w0))]
        starters = [threading.Thread(target=c.start, args=bufs[i], daemon=True)
                    for i, c in enumerate(clients)]
        for t in starters:
            t.start()
        join_all(starters)
        bufs[1][1][:] = 1.0
        c2.async_send_grad()
        c2.wait()
        deadline = time.monotonic() + 10
        while servers[0].heartbeats_seen < 2 and time.monotonic() < deadline:
            c2.ping()
            c2.wait()
            time.sleep(0.005)
        transports[1].sever(0)  # crash
        deadline = time.monotonic() + 10
        while (servers[0].leases.state(c2.rank) != EVICTED
               and time.monotonic() < deadline):
            c1.ping()
            time.sleep(0.005)
        assert servers[0].leases.state(c2.rank) == EVICTED
        # the restarted incarnation: same rank, epoch + 1, no seeding
        c2b = ParamClient(
            c2.rank, [0], transports[1].inner,
            ft=FTConfig(heartbeat_s=0.02, op_deadline_s=0.5, max_retries=4,
                        backoff_base_s=0.005, epoch=1))
        p2b, g2b = np.zeros_like(w0), np.zeros_like(w0)
        starter = threading.Thread(target=c2b.start, args=(p2b, g2b),
                                   daemon=True)
        starter.start()
        join_all([starter], timeout=10)
        # start() returns when the INIT is delivered, not when the
        # server's listener has taken it
        deadline = time.monotonic() + 10
        while servers[0].rejoins < 1 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert servers[0].rejoins == 1
        c2b.async_recv_param()
        c2b.wait()
        np.testing.assert_array_equal(p2b, w0 + 1.0)  # pre-crash state kept
        g2b[:] = 3.0
        c2b.async_send_grad()
        c2b.wait()
        p1, g1 = bufs[0]
        c1.async_recv_param()
        c1.wait()
        np.testing.assert_array_equal(p1, w0 + 4.0)
        c1.stop()
        c2b.stop()
        join_all(threads)


# ---------------------------------------------------------------------------
# server checkpoint / restart


class TestServerRestart:
    def test_restart_resumes_retried_ops_without_double_apply(self, tmp_path):
        """Kill the server after a checkpoint; the client's in-flight
        retry lands on the restarted process.  The checkpointed dedup
        table must admit the already-applied op as DUP, and the op issued
        into the void must apply exactly once."""
        router = LocalRouter(2)
        s1 = ParamServer(0, [1], router.endpoint(0), rule="adam")
        t = threading.Thread(target=s1.start, daemon=True)
        t.start()
        client = ParamClient(
            1, [0], router.endpoint(1), seed_servers=True,
            ft=FTConfig(op_deadline_s=0.2, max_retries=30,
                        backoff_base_s=0.01, backoff_cap_s=0.05))
        w0 = np.ones(12, np.float32)
        param, grad = w0.copy(), np.zeros_like(w0)
        client.start(param, grad)
        grad[:] = 1.0
        client.async_send_grad()
        client.wait()
        s1.live.stop()
        t.join(5)
        path = s1.save_state(tmp_path)
        assert "server0_" in str(path)  # stamped version
        # ops into the void: retried until the replacement serves them
        client.async_send_grad()
        client.async_recv_param()
        s2 = ParamServer(0, [1], router.endpoint(0), rule="adam",
                         ft=FTConfig(rejoin=True))
        s2.restore_state(tmp_path / "server0_latest.npz")
        t2 = threading.Thread(target=s2.start, daemon=True)
        t2.start()
        client.wait()
        client.stop()
        join_all([t2])
        assert s2.grads_applied == 2  # restored count + exactly one more

    def test_stamped_history_is_pruned(self, tmp_path):
        from mpit_tpu.utils.checkpoint import save_server_state

        for _ in range(6):
            save_server_state(tmp_path, 0, 0, 4, np.zeros(4, np.float32),
                              {}, keep=3)
            time.sleep(0.002)  # distinct millisecond stamps
        stamped = [p for p in tmp_path.glob("server0_*.npz")
                   if p.name[len("server0_"):-len(".npz")].isdigit()]
        assert len(stamped) == 3
        assert (tmp_path / "server0_latest.npz").exists()

    def test_checkpoint_meta_carries_ft_state(self, tmp_path):
        servers, clients, threads, _ = launch_ft(1, 1, client_ft=FAST_FT)
        (client,) = clients
        w0 = np.ones(8, np.float32)
        param, grad = w0.copy(), np.zeros_like(w0)
        client.start(param, grad)
        grad[:] = 1.0
        client.async_send_grad()
        client.wait()
        client.stop()
        join_all(threads)
        path = servers[0].save_state(tmp_path)
        from mpit_tpu.utils.checkpoint import load_server_state

        *_rest, meta = load_server_state(path)
        assert meta["clients"]["1"]["framed"] is True
        assert meta["dedup"]  # the admitted seqs are recorded
        s2 = ParamServer(0, [1], LocalRouter(2).endpoint(0))
        s2.restore_state(path)
        assert s2.dedup.admit(1, tags.GRAD, 0, 1) == "dup"


# ---------------------------------------------------------------------------
# the property test: any {drop, delay, dup} plan completes bitwise or
# fails loudly — never hangs


# ---------------------------------------------------------------------------
# chaos soak: SIGKILL a live worker process mid-DOWNPOUR, supervisor
# restarts it, it rejoins via INIT v3, the run converges


@pytest.mark.slow
def test_chaos_soak_sigkill_worker_rejoins_and_converges(tmp_path, monkeypatch):
    """np=4 DOWNPOUR gang over TCP with the FT posture on.  The
    supervisor SIGKILLs worker rank 3 mid-run and respawns it as epoch 1
    (MPIT_FT_REJOIN): it re-announces via INIT v3, pulls the live center,
    and finishes training.  Both workers must land in the fault-free
    loss envelope (the bar the non-chaos np4 topology tests assert)."""
    import socket

    from mpit_tpu.ft.supervisor import RestartPolicy, supervise_gang
    from mpit_tpu.train.launch import LAUNCH_DEFAULTS, device_env_overrides

    socks = [socket.socket() for _ in range(4)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    addrs = ",".join(f"127.0.0.1:{s.getsockname()[1]}" for s in socks)
    for s in socks:
        s.close()
    # TCP reconnect window: the restarted rank re-binds its address and
    # redials; peers re-handshake instead of failing loudly.
    monkeypatch.setenv("MPIT_TCP_RECONNECT_S", "60")
    cfg = LAUNCH_DEFAULTS.merged(
        # epochs sized so the +12s kill lands mid-training and the
        # surviving worker is still running through the whole restart
        # cycle (~0.15s/epoch on the 1-core CI box).
        np=4, opt="downpour", lr=0.2, su=1, epochs=300, batch=64, side=8,
        master_freq=2, device_policy="cpu", transport="tcp",
        tcp_addrs=addrs,
        # Lease TTL comfortably above the restart cycle: the replacement
        # normally rejoins while still ACTIVE (generation supersede); if
        # a slow box pushes past the TTL, eviction-then-rejoin also works.
        ft_heartbeat_s=0.25, ft_lease_ttl_s=20.0, ft_op_deadline_s=5.0,
        supervise=2,
        server_ckpt_dir=str(tmp_path), server_ckpt_interval=2.0,
    )
    results = supervise_gang(
        "mpit_tpu.train.launch", cfg, timeout=600,
        policy=RestartPolicy(max_restarts=2, restart_delay_s=0.5),
        env_overrides=device_env_overrides(cfg, 4),
        server_ranks=[0, 2],
        chaos_kill_rank=3, chaos_kill_after_s=12.0,
    )
    roles = {r: v["role"] for r, v in results.items()}
    assert roles == {0: "server", 1: "worker", 2: "server", 3: "worker"}
    workers = [v for v in results.values() if v["role"] == "worker"]
    # the fault-free envelope from the np4 topology tests
    assert all(w["final_test_err"] < 0.8 for w in workers)
    assert all(v["grads_applied"] > 0 for v in results.values()
               if v["role"] == "server")


@pytest.mark.parametrize("seed", range(5))
def test_property_fault_plans_never_hang(seed):
    """Seed-deterministic random plans over {drop, delay, dup} on <= 3
    clients: the run either completes with bitwise-correct final params
    or raises (RetryExhausted / TaskError) — and always finishes inside
    the hard timeout.  INIT stays clean (membership is the supervisor's
    problem, not retry's); STOP loss is covered by lease eviction."""
    rng = np.random.default_rng(seed)
    nclients = int(rng.integers(1, 4))
    rounds = 3
    size = 32
    w0 = rng.normal(size=size).astype(np.float32)
    gtab = rng.normal(size=(nclients, rounds, size)).astype(np.float32)

    def run(client_plans, server_plan, box):
        servers, clients = [], []
        try:
            servers, clients, threads, _ = launch_ft(
                2, nclients, client_plans=client_plans,
                server_plan=server_plan,
                client_ft=FTConfig(heartbeat_s=0.02, op_deadline_s=0.15,
                                   max_retries=6, backoff_base_s=0.005,
                                   backoff_cap_s=0.02),
                server_ft=FTConfig(lease_ttl_s=1.0, rejoin=True))
            params = []
            starters = []
            for i, c in enumerate(clients):
                p = w0.copy() if i == 0 else np.zeros(size, np.float32)
                g = np.zeros(size, np.float32)
                params.append((p, g))
                starters.append(threading.Thread(
                    target=c.start, args=(p, g), daemon=True))
            for t in starters:
                t.start()
            join_all(starters, timeout=20)
            for r in range(rounds):
                for i, c in enumerate(clients):
                    params[i][1][:] = gtab[i, r]
                    c.async_send_grad()
                    c.wait()
            clients[0].async_recv_param()
            clients[0].wait()
            for c in clients:
                c.stop()
            join_all(threads, timeout=20)
            box["params"] = params[0][0].copy()
        except (TaskError, RetryExhausted, AssertionError) as exc:
            box["error"] = exc  # loud is an acceptable outcome
            for c in clients:
                c.live.stop()
            for s in servers:
                s.live.stop()

    clean: dict = {}
    run(None, None, clean)
    assert "params" in clean, f"fault-free run failed: {clean.get('error')}"

    client_plans = {
        i: FaultPlan(seed=seed * 17 + i, drop_rate=0.08, dup_rate=0.08,
                     delay_rate=0.15, delay_polls=4, tags=DATA_TAGS)
        for i in range(nclients)
    }
    server_plan = FaultPlan(seed=seed * 31 + 7, drop_rate=0.08,
                            dup_rate=0.08, delay_rate=0.15, delay_polls=4,
                            tags=REPLY_TAGS)
    box: dict = {}
    worker = threading.Thread(target=run,
                              args=(client_plans, server_plan, box),
                              daemon=True)
    worker.start()
    worker.join(90)  # the hard timeout: a hang is the one forbidden outcome
    assert not worker.is_alive(), "faulty run HUNG (never-hang contract broken)"
    if "params" in box:
        np.testing.assert_array_equal(clean["params"], box["params"])
    else:
        assert "error" in box  # failed loudly
