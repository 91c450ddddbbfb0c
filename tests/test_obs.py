"""mpit_tpu.obs — metrics registry, op spans, Chrome-trace export.

Three layers of assertion:

1. the registry/recorder primitives (bucketing math, exposition format,
   the null-object disabled path — including a microbenchmark proving
   "disabled" really is a no-op object, not a branch tree);
2. deterministic counters: under a seeded every-k fault plan the
   retry/dedup/drop counters on both ends must match the arithmetic of
   the plan *exactly* (computed by replaying ``FaultPlan.decide``, not
   eyeballed), and a trace export round-trips through the validator;
3. attribution: a dropped-then-retried op is findable in the exported
   trace with its [epoch, seq] identity and retry count.

Obs global state is process-wide, so every test that enables it goes
through the ``obs_on`` fixture (enable + reset, restore after).
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from mpit_tpu import obs
from mpit_tpu.aio import EXEC, Scheduler, aio_sleep
from mpit_tpu.comm.local import LocalRouter
from mpit_tpu.ft import FaultPlan, FaultyTransport, FTConfig, RetryExhausted
from mpit_tpu.obs import flight as obs_flight
from mpit_tpu.obs import metrics as obs_metrics
from mpit_tpu.obs import profile as obs_profile
from mpit_tpu.obs import spans as obs_spans
from mpit_tpu.obs import statusd as obs_statusd
from mpit_tpu.obs import top as obs_top
from mpit_tpu.obs import trace as obs_trace
from mpit_tpu.obs.__main__ import main as obs_cli
from mpit_tpu.ps import ParamClient, ParamServer, tags

DATA_TAGS = frozenset({tags.GRAD, tags.PARAM_REQ, tags.PARAM_PUSH})

#: fast retry posture for LocalRouter-speed gangs (mirrors test_ft.py)
FAST_FT = FTConfig(op_deadline_s=0.25, max_retries=8,
                   backoff_base_s=0.005, backoff_cap_s=0.02)


@pytest.fixture
def obs_on():
    obs.configure(enabled=True, reset=True)
    try:
        yield obs.get_registry()
    finally:
        obs.configure(enabled=None, reset=True)


def join_all(threads, timeout=30):
    for t in threads:
        t.join(timeout)
        assert not t.is_alive(), "role thread did not stop (hang)"


# ---------------------------------------------------------------------------
# registry primitives


class TestRegistry:
    def test_counter_gauge_histogram(self):
        reg = obs_metrics.Registry()
        c = reg.counter("mpit_x_total", rank=1)
        c.inc()
        c.inc(4)
        assert c.value == 5
        assert reg.counter("mpit_x_total", rank=1) is c  # get-or-create
        assert reg.counter("mpit_x_total", rank=2) is not c
        g = reg.gauge("mpit_depth")
        g.set(7)
        g.add(-2)
        assert g.value == 5
        h = reg.histogram("mpit_h_seconds")
        for v in (0.75, 1.5, 3.0):
            h.observe(v)
        assert h.count == 3 and h.vmax == 3.0 and h.vmin == 0.75

    def test_log2_bucketing_is_exact(self):
        # [2^(e-1), 2^e) lands in the bucket whose key is e.
        assert obs_metrics.bucket_index(0.75) == \
            0 - obs_metrics.HIST_LO_EXP  # (0.5, 1.0) -> exponent 0
        assert obs_metrics.bucket_index(1.0) == 1 - obs_metrics.HIST_LO_EXP
        assert obs_metrics.bucket_index(0.0) == 0
        assert obs_metrics.bucket_index(-5.0) == 0
        assert obs_metrics.bucket_index(float(2 ** 40)) == \
            obs_metrics.HIST_BUCKETS - 1  # clamped top
        h = obs_metrics.Histogram("h")
        h.observe(0.75)
        snap = h.snapshot()
        assert snap["buckets"] == {0: 1}

    def test_kind_collision_fails_loudly(self):
        reg = obs_metrics.Registry()
        reg.counter("mpit_k")
        with pytest.raises(TypeError, match="already registered"):
            reg.histogram("mpit_k")

    def test_snapshot_and_exposition(self):
        reg = obs_metrics.Registry()
        reg.counter("mpit_c_total", peer=3).inc(2)
        reg.histogram("mpit_h").observe(1.5)
        snap = reg.snapshot()
        assert snap['mpit_c_total{peer="3"}'] == 2
        assert snap["mpit_h"]["count"] == 1
        text = reg.exposition()
        assert 'mpit_c_total{peer="3"} 2' in text
        assert "mpit_h_count 1" in text
        assert 'le="+Inf"' in text
        assert "mpit_c_total" in reg.format_summary(prefix="mpit_c")
        assert "mpit_h" not in reg.format_summary(prefix="mpit_c")

    def test_timer_context_observes(self):
        reg = obs_metrics.Registry()
        with reg.timer("mpit_t_seconds", codec="int8"):
            pass
        h = reg.histogram("mpit_t_seconds", codec="int8")
        assert h.count == 1 and h.total >= 0.0

    def test_counter_incs_are_thread_safe_enough(self):
        reg = obs_metrics.Registry()
        c = reg.counter("mpit_mt_total")
        threads = [threading.Thread(
            target=lambda: [c.inc() for _ in range(10000)])
            for _ in range(4)]
        for t in threads:
            t.start()
        join_all(threads)
        assert c.value == 40000


class TestDisabledPath:
    def test_disabled_registry_is_the_null_object(self):
        assert not obs.obs_enabled()
        reg = obs.get_registry()
        assert reg is obs_metrics.NULL_REGISTRY
        assert reg.counter("x") is obs_metrics.NULL
        assert reg.histogram("y", a=1) is obs_metrics.NULL
        assert reg.timer("z") is obs_metrics.NULL
        rec = obs_spans.get_recorder()
        assert rec is obs_spans.NULL_RECORDER
        assert rec.op("GRAD", peer=1) is obs_spans.NULL_SPAN
        assert rec.task_begin("t") is None
        assert rec.open_ops() == []
        assert rec.wire("rx", 0, 1, 2, 0.0, 1.0) is None and rec.spans == ()
        # the flight recorder is the shared null object too
        fl = obs_flight.get_flight()
        assert fl is obs_flight.NULL_FLIGHT
        fl.record("op", name="GRAD")
        assert fl.dump("anything") is None and fl.events == ()
        # the CPU profiler is the shared null object too: no clock
        # reads, no samples, nothing to snapshot
        prof = obs_profile.get_profiler()
        assert prof is obs_profile.NULL_PROFILER
        assert not prof.enabled
        assert prof.cpu_now() == 0.0
        prof.step("t", 0.5)
        prof.sample(3)
        assert prof.samples == () and prof.cpu_seconds == 0.0
        assert prof.top_tasks() == []
        # and no statusd endpoint (no socket) without MPIT_OBS_HTTP
        assert obs_statusd.maybe_start(0) is None
        # nothing accumulates anywhere
        obs_metrics.NULL.inc(10)
        obs_metrics.NULL.observe(1.0)
        assert obs_metrics.NULL.value == 0
        assert reg.snapshot() == {} and reg.exposition() == ""

    def test_disabled_path_microbenchmark(self):
        """The no-op-object claim, measured: 200k disabled counter incs
        plus 20k disabled op-span lifecycles plus 20k disabled
        flight-recorder records plus 20k disabled profiler step/sample
        pairs must finish far inside a generous absolute budget
        (>= 5 µs/op would still pass — real cost is tens of ns).
        Catches anyone replacing the null objects — the registry's,
        the span recorder's, the flight recorder's, or the CPU
        profiler's — with env reads or clock calls per operation."""
        reg = obs.get_registry()
        c = reg.counter("mpit_bench_total")
        rec = obs_spans.get_recorder()
        fl = obs_flight.get_flight()
        prof = obs_profile.get_profiler()
        t0 = time.perf_counter()
        for _ in range(200_000):
            c.inc()
        for _ in range(20_000):
            sp = rec.op("GRAD", peer=1, side="client")
            sp.mark("encode")
            sp.end("ok")
        for _ in range(20_000):
            fl.record("op", name="GRAD", outcome="ok")
        for _ in range(20_000):
            prof.step("t", prof.cpu_now())
            prof.sample(0)
        # the wire's null paths (PR 34): no span, no meter, no record
        meter = rec.wire_meter(None, None)
        assert meter is obs_spans.NULL_METER and rec.sleep(0.0) == 0.0
        for _ in range(20_000):
            rec.wire("tx", 0, 1, 2, 0.0, 0.0, bytes=1 << 20)
            meter.start()
            meter.note(sp)
            rec.seen_ready(sp)
        elapsed = time.perf_counter() - t0
        assert elapsed < 1.2, (
            f"disabled-path overhead {elapsed:.3f}s for 340k ops — the "
            "null objects are no longer no-ops")

    def test_configure_flips_and_restores(self):
        obs.configure(enabled=True, reset=True)
        try:
            assert obs.obs_enabled()
            assert obs.get_registry() is not obs_metrics.NULL_REGISTRY
            assert obs_spans.get_recorder().enabled
        finally:
            obs.configure(enabled=None, reset=True)
        assert not obs.obs_enabled()

    def test_registry_or_local_always_counts(self):
        reg = obs.registry_or_local()
        assert reg.enabled
        c = reg.counter("mpit_local_total")
        c.inc()
        assert c.value == 1


# ---------------------------------------------------------------------------
# spans + trace export


class TestSpans:
    def test_op_span_records_phases_and_histogram(self, obs_on):
        rec = obs_spans.get_recorder()
        sp = rec.op("GRAD", peer=3, side="client", epoch=0)
        sp.mark("encode")
        sp.mark("send")
        sp.note(seq=7)
        sp.end("ok", retries=1)
        sp.end("ignored")  # idempotent
        assert len(rec.spans) == 1
        done = rec.spans[0]
        assert done.outcome == "ok"
        assert done.args["seq"] == 7 and done.args["retries"] == 1
        assert [p for p, _ in done.marks] == ["encode", "send"]
        h = obs_on.histogram("mpit_ps_op_seconds", op="GRAD", side="client")
        assert h.count == 1

    def test_scheduler_records_task_lifecycles(self, obs_on):
        sched = Scheduler(idle_usec=0)
        sched.spawn(aio_sleep(0.01), name="nap")
        sched.wait()
        rec = obs_spans.get_recorder()
        names = [name for name, _, _, state, _cpu in rec.tasks]
        assert "nap" in names
        assert obs_on.counter("mpit_aio_steps_total").value > 0
        assert obs_on.counter("mpit_aio_tasks_total").value >= 1


class TestTraceExport:
    def test_round_trip_and_balance(self, obs_on, tmp_path):
        rec = obs_spans.get_recorder()
        for i in range(3):
            sp = rec.op("GRAD", peer=0, side="client", epoch=0, seq=i + 1)
            sp.mark("send")
            sp.end("ok")
        tok = rec.task_begin("svc")
        rec.task_end(tok, "svc", "DONE")
        path = obs_trace.write_rank_trace(str(tmp_path / "t.json"), 7,
                                          role="client")
        stats = obs_trace.validate_trace(path)
        assert stats["ops"] == 3 and stats["tasks"] == 1
        obj = json.load(open(path))
        assert obj["otherData"]["ranks"]["7"]["role"] == "client"
        # merged file validates too and keeps the pid
        merged = str(tmp_path / "m.json")
        obs_trace.merge_traces(merged, [path])
        assert obs_trace.validate_trace(merged)["pids"] == 1

    def test_validator_rejects_malformed(self, tmp_path):
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"traceEvents": [
            {"ph": "E", "name": "GRAD", "pid": 0, "tid": 1, "ts": 1.0}]}))
        with pytest.raises(ValueError, match="no open B"):
            obs_trace.validate_trace(str(bad))
        bad.write_text(json.dumps({"traceEvents": [
            {"ph": "B", "name": "GRAD", "pid": 0, "tid": 1, "ts": 1.0}]}))
        with pytest.raises(ValueError, match="unclosed"):
            obs_trace.validate_trace(str(bad))
        bad.write_text(json.dumps({"nope": 1}))
        with pytest.raises(ValueError, match="traceEvents"):
            obs_trace.validate_trace(str(bad))

    def test_cli_entry(self, obs_on, tmp_path, capsys):
        rec = obs_spans.get_recorder()
        sp = rec.op("PARAM", peer=0)
        sp.end("ok")
        path = obs_trace.write_rank_trace(str(tmp_path / "t.json"), 0)
        assert obs_trace.main([path]) == 0
        assert obs_trace.main([str(tmp_path / "missing.json")]) == 1


# ---------------------------------------------------------------------------
# back-compat: the utils/timers fold


class TestTimersFold:
    def test_utils_reexports_are_the_obs_objects(self):
        from mpit_tpu import utils
        from mpit_tpu.obs import timers as obs_timers

        assert utils.PhaseTimers is obs_timers.PhaseTimers
        assert utils.trace_annotation is obs_timers.trace_annotation
        assert utils.profiler_trace is obs_timers.profiler_trace
        assert obs.PhaseTimers is obs_timers.PhaseTimers

    def test_phase_timers_still_work(self):
        tm = obs.PhaseTimers()
        with tm.phase("feval"):
            pass
        assert tm.count["feval"] == 1


# ---------------------------------------------------------------------------
# deterministic counters under seeded fault plans (2s/2c gang)


def launch_gang(nservers, nclients, client_plans=None,
                client_ft=FAST_FT, server_ft=None):
    """FT PS topology over LocalRouter with FaultyTransport client seams
    (the test_ft.py harness shape, trimmed to what these tests need)."""
    n = nservers + nclients
    router = LocalRouter(n)
    sranks, cranks = list(range(nservers)), list(range(nservers, n))
    server_ft = server_ft or FTConfig(rejoin=True)
    servers, threads = [], []
    for r in sranks:
        servers.append(ParamServer(r, cranks, router.endpoint(r), rule="add",
                                   ft=server_ft))
        threads.append(threading.Thread(target=servers[-1].start, daemon=True))
    for t in threads:
        t.start()
    clients, transports = [], []
    for i, r in enumerate(cranks):
        ep = router.endpoint(r)
        plan = (client_plans or {}).get(i)
        if plan is not None:
            ep = FaultyTransport(ep, plan)
        transports.append(ep)
        clients.append(ParamClient(r, sranks, ep,
                                   seed_servers=(r == cranks[0]),
                                   ft=client_ft))
    return servers, clients, threads, transports


def run_gang(servers, clients, threads, rounds, size=64):
    rng = np.random.default_rng(7)
    starters = []
    params = []
    for c in clients:
        p = (rng.normal(size=size).astype(np.float32)
             if not params else np.zeros(size, np.float32))
        params.append(p)
        starters.append(threading.Thread(
            target=c.start, args=(p, np.zeros(size, np.float32)),
            daemon=True))
    for t in starters:
        t.start()
    join_all(starters)
    for r in range(rounds):
        for c in clients:
            c.grad[:] = rng.normal(size=size).astype(np.float32)
            c.async_send_grad()
            c.wait()
    for c in clients:
        c.stop()
    join_all(threads)


def simulate_grad_channel(plan, src, dst, rounds):
    """Replay the plan's arithmetic for one (client -> server) GRAD
    channel under the retry protocol: a dropped data frame times out and
    is resent (the resend advances the per-channel count), a passed or
    duplicated frame is acked.  Returns (sends, drops, dups)."""
    sends = drops = dups = 0
    n = 0
    for _ in range(rounds):
        while True:
            n += 1
            sends += 1
            verdict = plan.decide(src, dst, tags.GRAD, n)
            if verdict == "drop":
                drops += 1
                continue  # deadline fires, client resends
            if verdict == "dup":
                dups += 1
            break  # delivered (possibly twice) -> acked
    return sends, drops, dups


class TestDeterministicCounters:
    def test_drop_plan_counters_match_plan_arithmetic(self):
        """Every-3rd GRAD dropped on each client->server channel: the
        transport drop counters, the client retry counters and the
        server dedup counters must equal the replayed plan arithmetic
        exactly — not approximately."""
        rounds, nservers, nclients = 6, 2, 2
        plans = {i: FaultPlan(seed=i, drop_every=3,
                              tags=frozenset({tags.GRAD}))
                 for i in range(nclients)}
        servers, clients, threads, transports = launch_gang(
            nservers, nclients, client_plans=plans)
        run_gang(servers, clients, threads, rounds)
        for i, (c, tr) in enumerate(zip(clients, transports)):
            want_drops = want_retries = 0
            for dst in range(nservers):
                _, drops, dups = simulate_grad_channel(
                    plans[i], c.rank, dst, rounds)
                assert dups == 0
                want_drops += drops
                # every dropped GRAD costs exactly one resend
                want_retries += drops
            assert tr.dropped == want_drops
            assert c.retries == want_retries
            assert want_drops > 0  # the plan actually fired
        # drops never reach the server: no dups, no stale, all applied
        assert sum(s.dup_ops for s in servers) == 0
        assert sum(s.stale_drops for s in servers) == 0
        # one GRAD per (client, server) pair per round (sharded vector)
        assert (sum(s.grads_applied for s in servers)
                == rounds * nclients * nservers)

    def test_dup_plan_counters_match_plan_arithmetic(self):
        """Every-2nd data frame duplicated: the server's dup counter
        must equal the transports' duplication counters exactly (each
        injected duplicate is admitted DUP and re-acked), with zero
        retries — duplication never stalls the op."""
        rounds, nservers, nclients = 5, 2, 2
        plans = {i: FaultPlan(seed=i, dup_every=2, tags=DATA_TAGS)
                 for i in range(nclients)}
        servers, clients, threads, transports = launch_gang(
            nservers, nclients, client_plans=plans)
        run_gang(servers, clients, threads, rounds)
        injected = sum(tr.duplicated for tr in transports)
        assert injected > 0
        assert sum(s.dup_ops for s in servers) == injected
        assert sum(c.retries for c in clients) == 0
        assert (sum(s.grads_applied for s in servers)
                == rounds * nclients * nservers)

    def test_fault_plan_env_spec_drives_the_same_counters(self, monkeypatch):
        """The env-spec path (MPIT_FT_FAULT_PLAN) parses to the same
        plan object the direct tests use — the deterministic-counter
        contract holds for env-configured gangs too."""
        monkeypatch.setenv("MPIT_FT_FAULT_PLAN",
                           f"seed=0,drop_every=3,tags={tags.GRAD}")
        plan = FaultPlan.from_env()
        assert plan == FaultPlan(seed=0, drop_every=3,
                                 tags=frozenset({tags.GRAD}))


# ---------------------------------------------------------------------------
# the acceptance scenario: fault-injected gang -> attributable trace


class TestFaultTraceAttribution:
    def test_dropped_then_retried_op_is_attributable(self, obs_on, tmp_path):
        """2s/2c gang under an every-k drop plan with obs enabled: the
        exported Chrome trace must contain the retried GRAD op's span
        with its [epoch, seq] identity and retry count, the trace must
        validate (balanced B/E), and the drop/retry/dup counters must
        match the plan arithmetic."""
        rounds, nservers, nclients = 4, 2, 2
        plans = {0: FaultPlan(seed=0, drop_every=2,
                              tags=frozenset({tags.GRAD}))}
        servers, clients, threads, transports = launch_gang(
            nservers, nclients, client_plans=plans)
        run_gang(servers, clients, threads, rounds)
        # counters match the plan arithmetic on both ends
        want_drops = want_retries = 0
        for dst in range(nservers):
            _, drops, _ = simulate_grad_channel(
                plans[0], clients[0].rank, dst, rounds)
            want_drops += drops
            want_retries += drops
        assert transports[0].dropped == want_drops > 0
        assert clients[0].retries == want_retries
        assert sum(s.dup_ops for s in servers) == 0  # drops, not dups
        # export + validate
        path = obs_trace.write_rank_trace(
            str(tmp_path / "trace.json"), rank=clients[0].rank, role="worker")
        stats = obs_trace.validate_trace(path)
        assert stats["ops"] > 0
        # the retried op is attributable: a GRAD span with retries >= 1
        # carrying its [epoch, seq] identity and per-attempt phases
        obj = json.load(open(path))
        begins = {}
        retried = None
        for ev in obj["traceEvents"]:
            if ev["ph"] == "B" and ev["name"] == "GRAD":
                begins[(ev["tid"], ev["ts"])] = ev
                if ev["args"].get("retries", 0) >= 1:
                    retried = ev
        assert retried is not None, "no retried GRAD span in the trace"
        assert retried["args"]["epoch"] == 0
        assert retried["args"]["seq"] >= 1
        assert retried["args"]["peer"] in range(nservers)
        # its phase events exist on the same tid, including the backoff
        phases = {ev["name"] for ev in obj["traceEvents"]
                  if ev["ph"] == "X" and ev["tid"] == retried["tid"]}
        assert "GRAD.backoff" in phases and "GRAD.send" in phases
        # server-side spans recorded the applies (same process here, so
        # the shared recorder holds both sides)
        server_grads = [sp for sp in obs_spans.get_recorder().spans
                        if sp.name == "GRAD"
                        and sp.args.get("side") == "server"]
        assert (sum(1 for sp in server_grads if sp.outcome == "applied")
                == rounds * nclients * nservers)


# ---------------------------------------------------------------------------
# gradient staleness: deterministic counts under a sequential schedule

#: staleness-tracking retry posture (FAST_FT + the header extension)
STALE_FT = FTConfig(op_deadline_s=0.25, max_retries=8,
                    backoff_base_s=0.005, backoff_cap_s=0.02,
                    staleness=True)


def run_sequential(servers, clients, threads, rounds, size=64):
    """Drive every round from ONE thread in a fixed interleave — all
    clients read, then all clients write, in client order — so the
    server-side apply order (and with it every staleness value) is a
    pure function of (nservers, nclients, rounds), replayable exactly.
    Starts stay threaded (the INIT rendezvous needs every client
    announcing before phase 2)."""
    rng = np.random.default_rng(7)
    starters, params = [], []
    for c in clients:
        p = (rng.normal(size=size).astype(np.float32)
             if not params else np.zeros(size, np.float32))
        params.append(p)
        starters.append(threading.Thread(
            target=c.start, args=(p, np.zeros(size, np.float32)),
            daemon=True))
    for t in starters:
        t.start()
    join_all(starters)
    for _ in range(rounds):
        for c in clients:
            c.async_recv_param()
            c.wait()
        for c in clients:
            c.grad[:] = rng.normal(size=size).astype(np.float32)
            c.async_send_grad()
            c.wait()
    for c in clients:
        c.stop()
    join_all(threads)


def replay_staleness(nservers, nclients, rounds):
    """The sequential schedule's staleness arithmetic: version starts at
    1 per server (the seed push), every applied grad bumps it, and each
    client's basis is the version at its read.  Returns
    {(client_idx, server_rank): {staleness_value: count}}."""
    version = [1] * nservers
    basis = [[0] * nservers for _ in range(nclients)]
    out = {}
    for _ in range(rounds):
        for ci in range(nclients):
            for s in range(nservers):
                basis[ci][s] = version[s]
        for ci in range(nclients):
            for s in range(nservers):
                stal = version[s] - basis[ci][s]
                pair = out.setdefault((ci, s), {})
                pair[stal] = pair.get(stal, 0) + 1
                version[s] += 1
    return out


def expected_bucket_dict(values):
    """{staleness_value: n} -> the exact Histogram.snapshot() buckets."""
    out = {}
    for v, n in values.items():
        key = obs_metrics.bucket_index(float(v)) + obs_metrics.HIST_LO_EXP
        out[key] = out.get(key, 0) + n
    return out


class TestStalenessDeterministic:
    def _assert_exact(self, obs_on, servers, clients, rounds,
                      nservers, nclients):
        want = replay_staleness(nservers, nclients, rounds)
        for (ci, s), values in want.items():
            hist = obs_on.histogram("mpit_ps_grad_staleness",
                                    rank=s, client=clients[ci].rank)
            snap = hist.snapshot()
            assert snap["count"] == sum(values.values()), (ci, s, snap)
            assert snap["sum"] == float(sum(v * n
                                            for v, n in values.items()))
            assert snap["buckets"] == expected_bucket_dict(values), \
                (ci, s, snap["buckets"])

    def test_fault_free_counts_match_replay_exactly(self, obs_on):
        """2s/2c, sequential schedule: client 0's grads land at
        staleness 0, client 1's at 1 (client 0's apply intervenes
        between its read and its write) — bucket-exact."""
        rounds, nservers, nclients = 5, 2, 2
        servers, clients, threads, _ = launch_gang(
            nservers, nclients, client_ft=STALE_FT)
        run_sequential(servers, clients, threads, rounds)
        self._assert_exact(obs_on, servers, clients, rounds,
                           nservers, nclients)

    def test_drop_plan_staleness_and_retries_match_replay(self, obs_on):
        """Every-2nd GRAD dropped on client 0: the retry machinery must
        be *invisible* to staleness — the op applies exactly once at the
        same schedule position — while the retry counters match the
        replayed plan arithmetic.  Both exact, same run."""
        rounds, nservers, nclients = 4, 2, 2
        plans = {0: FaultPlan(seed=0, drop_every=2,
                              tags=frozenset({tags.GRAD}))}
        servers, clients, threads, transports = launch_gang(
            nservers, nclients, client_plans=plans, client_ft=STALE_FT)
        run_sequential(servers, clients, threads, rounds)
        self._assert_exact(obs_on, servers, clients, rounds,
                           nservers, nclients)
        want_drops = want_retries = 0
        for dst in range(nservers):
            _, drops, _ = simulate_grad_channel(
                plans[0], clients[0].rank, dst, rounds)
            want_drops += drops
            want_retries += drops
        assert transports[0].dropped == want_drops > 0
        assert clients[0].retries == want_retries
        assert sum(s.dup_ops for s in servers) == 0

    def test_delay_plan_staleness_matches_replay(self, obs_on):
        """Every-2nd GRAD delayed (inside the deadline): delivery order
        per channel is preserved, nothing retries, and the staleness
        histogram still equals the replay exactly."""
        rounds, nservers, nclients = 4, 2, 2
        plans = {i: FaultPlan(seed=i, delay_every=2, delay_polls=3,
                              tags=frozenset({tags.GRAD}))
                 for i in range(nclients)}
        servers, clients, threads, transports = launch_gang(
            nservers, nclients, client_plans=plans, client_ft=STALE_FT)
        run_sequential(servers, clients, threads, rounds)
        self._assert_exact(obs_on, servers, clients, rounds,
                           nservers, nclients)
        assert sum(tr.delayed for tr in transports) > 0
        assert sum(c.retries for c in clients) == 0

    def test_legacy_init_negotiates_extension_off(self, obs_on):
        """Mixed gang: a staleness-tracking framed client and a plain
        legacy (v1 INIT) client on one server.  The extension must be
        per pair — 24-byte headers for the tracker, the byte-identical
        16/0-byte legacy wire for the other — and only the tracker
        grows a staleness histogram."""
        rounds, nservers = 2, 2
        n = nservers + 2
        router = LocalRouter(n)
        sranks, cranks = list(range(nservers)), list(range(nservers, n))
        servers, threads = [], []
        for r in sranks:
            servers.append(ParamServer(r, cranks, router.endpoint(r),
                                       rule="add", ft=FTConfig(rejoin=True)))
            threads.append(threading.Thread(target=servers[-1].start,
                                            daemon=True))
        for t in threads:
            t.start()
        clients = [
            ParamClient(cranks[0], sranks, router.endpoint(cranks[0]),
                        seed_servers=True, ft=STALE_FT),
            ParamClient(cranks[1], sranks, router.endpoint(cranks[1]),
                        seed_servers=False, ft=FTConfig()),  # legacy v1
        ]
        assert clients[0]._stale and clients[0]._hdr == 24
        assert not clients[1]._stale and clients[1]._hdr == 0
        run_sequential(servers, clients, threads, rounds)
        for s in servers:
            assert s._stale_track[cranks[0]] is True
            assert s._stale_track.get(cranks[1], False) is False
        assert (sum(s.grads_applied for s in servers)
                == rounds * 2 * nservers)
        stale_keys = [k for k in obs_on.snapshot()
                      if k.startswith("mpit_ps_grad_staleness")]
        assert stale_keys  # the tracker produced histograms...
        assert all(f'client="{cranks[0]}"' in k for k in stale_keys), \
            stale_keys  # ...and the legacy client none

    def test_staleness_without_framing_is_inert(self):
        """FTConfig(staleness=True) with no op deadline: nothing to
        extend — the client keeps the headerless legacy wire."""
        cfg = FTConfig(staleness=True)
        assert not cfg.stale_track
        router = LocalRouter(2)
        client = ParamClient(1, [0], router.endpoint(1), ft=cfg)
        assert not client._stale and client._hdr == 0


# ---------------------------------------------------------------------------
# statusd: the live introspection endpoint


def _http_get(port, route):
    import urllib.error

    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{route}", timeout=5) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as exc:
        return exc.code, exc.read()


class TestStatusd:
    def test_endpoints_serve_metrics_status_trace(self, obs_on):
        obs_on.counter("mpit_bench_total", rank=7).inc(3)
        rec = obs_spans.get_recorder()
        done = rec.op("PARAM", peer=0, side="client", epoch=0, seq=4)
        done.end("ok")
        open_span = rec.op("GRAD", peer=1, side="client", epoch=0, seq=5)
        open_span.mark("send")
        obs.register_status_provider("probe", lambda: {"hello": 1})
        srv = obs_statusd.StatusServer(0, rank=3, role="worker")
        try:
            code, body = _http_get(srv.port, "/metrics")
            assert code == 200
            assert 'mpit_bench_total{rank="7"} 3' in body.decode()
            code, body = _http_get(srv.port, "/status")
            status = json.loads(body)
            assert (status["rank"], status["role"]) == (3, "worker")
            assert status["probe"] == {"hello": 1}
            inflight = status["inflight_ops"]
            assert len(inflight) == 1 and inflight[0]["op"] == "GRAD"
            assert inflight[0]["seq"] == 5
            assert inflight[0]["phase"] == "send"
            assert inflight[0]["elapsed_s"] >= 0
            code, body = _http_get(srv.port, "/trace")
            stats = obs_trace.validate_trace(json.loads(body))
            assert stats["ops"] == 1  # the finished span; open ones wait
            code, _ = _http_get(srv.port, "/nope")
            assert code == 404
        finally:
            srv.close()
            open_span.end("ok")

    def test_maybe_start_env_gating(self, obs_on, monkeypatch):
        monkeypatch.delenv("MPIT_OBS_HTTP", raising=False)
        assert obs_statusd.maybe_start(0) is None
        monkeypatch.setenv("MPIT_OBS_HTTP", "0")  # port 0 = OS-assigned
        srv = obs_statusd.maybe_start(0, role="server")
        try:
            assert srv is not None and srv.port > 0
            _, body = _http_get(srv.port, "/status")
            assert json.loads(body)["role"] == "server"
        finally:
            srv.close()

    def test_provider_failure_is_contained(self, obs_on):
        def boom():
            raise RuntimeError("provider died")

        obs.register_status_provider("boom", boom)
        srv = obs_statusd.StatusServer(0, rank=1)
        try:
            code, body = _http_get(srv.port, "/status")
            assert code == 200
            assert "provider died" in json.loads(body)["boom"]["error"]
        finally:
            srv.close()

    def test_roles_register_providers_when_obs_on(self, obs_on):
        router = LocalRouter(2)
        server = ParamServer(0, [1], router.endpoint(0), rule="add")
        client = ParamClient(1, [0], router.endpoint(1))
        section = obs_statusd._PROVIDERS["server0"]()
        assert section["role"] == "server"
        assert section["clients"]["1"]["state"] == "active"
        section = obs_statusd._PROVIDERS["client1"]()
        assert section["role"] == "client" and section["rank"] == 1
        assert server is not None and client is not None


# ---------------------------------------------------------------------------
# flight recorder: ring, dumps, failure-path triggers


class TestFlightRecorder:
    def test_ring_is_bounded_and_dump_validates(self, obs_on, tmp_path,
                                                monkeypatch):
        monkeypatch.setenv("MPIT_OBS_FLIGHT", str(tmp_path))
        fl = obs_flight.get_flight()
        fl.set_identity(rank=5, role="worker")
        for i in range(obs_flight.CAPACITY + 40):
            fl.record("op", name="GRAD", seq=i)
        assert len(fl.events) == obs_flight.CAPACITY  # bounded ring
        path = fl.dump("unit_test", tasks=[("recv_grad:1.g0", "EXEC")],
                       note="hello")
        assert path and str(tmp_path) in path
        stats = obs_flight.validate_dump(path)
        assert stats["reason"] == "unit_test" and stats["rank"] == 5
        assert stats["events"] == obs_flight.CAPACITY
        assert stats["tasks"] == 1
        # CLI validation agrees
        assert obs_cli(["flight", path]) == 0
        # a second dump never overwrites the first
        path2 = fl.dump("unit_test")
        assert path2 != path

    def test_validator_rejects_malformed(self, tmp_path):
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"schema": "nope"}))
        with pytest.raises(ValueError, match="schema"):
            obs_flight.validate_dump(str(bad))
        bad.write_text(json.dumps({
            "schema": "mpit_flight/1", "reason": "x", "pid": 1,
            "wall_time": 1.0, "events": [{"kind": "op"}], "metrics": {}}))
        with pytest.raises(ValueError, match="numeric t"):
            obs_flight.validate_dump(str(bad))
        assert obs_cli(["flight", str(bad)]) == 1

    def test_retry_exhausted_dumps_flight(self, obs_on, tmp_path,
                                          monkeypatch):
        """A severed server makes the client's GRAD exhaust its retries:
        the raise must leave a validated flight dump on disk carrying
        the retry_exhausted event and the live task table."""
        monkeypatch.setenv("MPIT_OBS_FLIGHT", str(tmp_path))
        fast = FTConfig(op_deadline_s=0.05, max_retries=1,
                        backoff_base_s=0.005, backoff_cap_s=0.01)
        plans = {0: FaultPlan(sever_after=0)}  # every send dropped
        servers, clients, threads, _ = launch_gang(
            1, 1, client_plans=plans, client_ft=fast)
        client = clients[0]
        with pytest.raises(Exception) as exc_info:
            client.start(np.zeros(8, np.float32), np.zeros(8, np.float32))
        assert isinstance(
            getattr(exc_info.value, "cause", exc_info.value),
            RetryExhausted)
        for role in clients + servers:
            role.live.stop()
        join_all(threads)
        dumps = sorted(tmp_path.glob("mpit_flight_*retry_exhausted*.json"))
        assert dumps, list(tmp_path.iterdir())
        stats = obs_flight.validate_dump(str(dumps[0]))
        assert stats["reason"] == "retry_exhausted"
        obj = json.load(open(dumps[0]))
        assert any(ev["kind"] == "retry_exhausted" for ev in obj["events"])

    def test_scheduler_watchdog_dumps_on_stall(self, obs_on, tmp_path,
                                               monkeypatch):
        """A queue that idles past stall_s without completing one task
        trips the watchdog exactly once per stall episode, and the dump
        carries the stuck task table."""
        monkeypatch.setenv("MPIT_OBS_FLIGHT", str(tmp_path))
        sched = Scheduler(idle_usec=500, stall_s=0.01)

        def parked():
            while True:
                yield EXEC

        sched.spawn(parked(), name="stuck_service")
        deadline = time.monotonic() + 10
        fl = obs_flight.get_flight()
        while fl.last_dump_path is None and time.monotonic() < deadline:
            sched.ping_pass()
        assert fl.last_dump_path, "watchdog never dumped"
        stats = obs_flight.validate_dump(fl.last_dump_path)
        assert stats["reason"] == "scheduler_stall"
        obj = json.load(open(fl.last_dump_path))
        assert ["stuck_service", "EXEC"] in obj["tasks"]
        assert obs_on.counter("mpit_aio_stall_dumps_total").value == 1
        # one dump per episode: more idle passes must not re-dump
        first = fl.last_dump_path
        for _ in range(50):
            sched.ping_pass()
        assert fl.last_dump_path == first

    def test_eviction_dumps_flight(self, obs_on, tmp_path, monkeypatch):
        """A client that beats once and then goes silent is evicted on
        lease expiry — and the reaper leaves a reason=eviction dump."""
        monkeypatch.setenv("MPIT_OBS_FLIGHT", str(tmp_path))
        servers, clients, threads, _ = launch_gang(
            1, 2, client_ft=FTConfig(heartbeat_s=0.01),
            server_ft=FTConfig(lease_ttl_s=0.15, rejoin=True))
        c0, c1 = clients
        starters = [threading.Thread(
            target=c.start,
            args=(np.zeros(16, np.float32), np.zeros(16, np.float32)),
            daemon=True) for c in clients]
        for t in starters:
            t.start()
        join_all(starters)  # both announced
        # The lease arms at the first beat: make c1 beat once (ping
        # emits + pumps the beacon), then go silent; c0 keeps beating
        # via ping until the reaper evicts c1 and dumps.
        for _ in range(20):
            c1.ping()
        time.sleep(0.02)
        deadline = time.monotonic() + 20
        while not any(tmp_path.glob("mpit_flight_*eviction*.json")):
            assert time.monotonic() < deadline, "eviction never dumped"
            c0.ping()
            time.sleep(0.005)
        c0.stop()
        c1.live.stop()
        join_all(threads)
        dump = sorted(tmp_path.glob("mpit_flight_*eviction*.json"))[0]
        stats = obs_flight.validate_dump(str(dump))
        assert stats["reason"] == "eviction"
        assert servers[0].leases.state(c1.rank) == "evicted"


# ---------------------------------------------------------------------------
# mpit top: exposition parsing + the aggregator read path


class TestTop:
    def test_parse_exposition(self):
        text = ('mpit_ps_grads_applied_total{rank="0"} 42\n'
                '# comment\n'
                'mpit_ps_grad_staleness_sum{client="2",rank="0"} 7\n'
                'mpit_ps_grad_staleness_count{client="2",rank="0"} 14\n'
                'garbage line\n'
                'mpit_shardctl_map_version 3\n')
        samples = obs_top.parse_exposition(text)
        assert obs_top.metric_sum(
            samples, "mpit_ps_grads_applied_total") == 42
        assert obs_top.metric_sum(
            samples, "mpit_ps_grads_applied_total", rank=0) == 42
        assert obs_top.hist_mean(
            samples, "mpit_ps_grad_staleness") == 0.5
        assert obs_top.metric_sum(samples, "mpit_shardctl_map_version") == 3

    def test_poll_rank_and_table(self, obs_on):
        obs_on.counter("mpit_ps_grads_applied_total", rank=0).inc(10)
        obs_on.counter("mpit_ps_params_served_total", rank=0).inc(5)
        obs_on.histogram("mpit_ps_grad_staleness", rank=0,
                         client=2).observe(2.0)
        obs_on.counter("mpit_ft_retries_total", rank=0).inc(3)
        srv = obs_statusd.StatusServer(0, rank=0, role="server")
        try:
            sample = obs_top.poll_rank("127.0.0.1", srv.port)
            assert sample["status"]["role"] == "server"
            row = obs_top._rank_row(0, sample, None, None)
            assert row["ops_total"] == 15
            assert row["staleness_mean"] == 2.0
            assert row["retries"] == 3
            table = obs_top.render_table([row, {"rank": 1, "up": False}])
            assert "server" in table and "(down)" in table
        finally:
            srv.close()

    def test_cli_once_json(self, obs_on, capsys):
        obs_on.counter("mpit_ps_grads_applied_total", rank=0).inc(1)
        srv = obs_statusd.StatusServer(0, rank=0, role="server")
        try:
            rc = obs_top.main(["--np", "1", "--base-port", str(srv.port),
                               "--iters", "1", "--json", "--min-up", "1"])
        finally:
            srv.close()
        assert rc == 0
        snap = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert snap["ranks"][0]["up"] and snap["ranks"][0]["ops_total"] == 1
        # a dead endpoint with --min-up fails loudly
        rc = obs_top.main(["--np", "1", "--base-port", str(srv.port),
                           "--iters", "1", "--json", "--min-up", "1"])
        assert rc == 1


# ---------------------------------------------------------------------------
# the merge subcommand: leftover parts from a crashed gang


class TestMergeSubcommand:
    def test_merge_assembles_leftover_parts(self, obs_on, tmp_path,
                                            capsys):
        rec = obs_spans.get_recorder()
        for i in range(2):
            sp = rec.op("GRAD", peer=0, side="client", seq=i + 1)
            sp.end("ok")
        base = str(tmp_path / "crashed.json")
        obs_trace.write_rank_trace(obs_trace.part_path(base, 0), 0,
                                   role="server")
        obs_trace.write_rank_trace(obs_trace.part_path(base, 3), 3,
                                   role="worker")
        assert obs_cli(["merge", base]) == 0
        stats = obs_trace.validate_trace(base)
        assert stats["pids"] == 2
        # parts kept by default (postmortem material)
        assert sorted(tmp_path.glob("crashed.json.rank*.json"))
        obj = json.load(open(base))
        assert set(obj["otherData"]["ranks"]) == {"0", "3"}

    def test_merge_without_parts_errors(self, tmp_path):
        assert obs_cli(["merge", str(tmp_path / "none.json")]) == 1

    def test_default_subcommand_still_validates(self, obs_on, tmp_path):
        path = obs_trace.write_rank_trace(str(tmp_path / "t.json"), 0)
        assert obs_cli([path]) == 0
        assert obs_cli(["validate", path]) == 0


# ---------------------------------------------------------------------------
# process-gang smoke: per-rank parts merged by the launcher (slow)


@pytest.mark.slow
def test_gang_merges_rank_traces(tmp_path, monkeypatch):
    """np=3 process gang with MPIT_OBS_TRACE: every child writes a part,
    the parent merges them, the merged trace validates and carries one
    pid per rank plus per-rank metrics riders."""
    from mpit_tpu.train.launch import LAUNCH_DEFAULTS, launch_processes

    trace_path = str(tmp_path / "gang_trace.json")
    monkeypatch.setenv("MPIT_OBS_TRACE", trace_path)
    cfg = LAUNCH_DEFAULTS.merged(
        np=3, opt="downpour", epochs=1, model="linear", side=8,
        batch=64, master_freq=2, device_policy="cpu",
    )
    results = launch_processes(cfg, timeout=600)
    assert set(results) == {0, 1, 2}
    stats = obs_trace.validate_trace(trace_path)
    assert stats["pids"] == 3 and stats["events"] > 0
    obj = json.load(open(trace_path))
    ranks = obj["otherData"]["ranks"]
    assert set(ranks) == {"0", "1", "2"}
    server_metrics = ranks["0"]["metrics"]
    assert any(k.startswith("mpit_ps_grads_applied_total")
               for k in server_metrics)
    assert not list(tmp_path.glob("gang_trace.json.rank*")), \
        "part files should be cleaned up after the merge"
