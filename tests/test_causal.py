"""Causal op tracing — clock estimator, FLAG_TIMING wire, joiner,
latency decomposition, critical path.

Three layers of assertion:

1. the clock estimator and wire-layout primitives (offset recovery on
   constructed exchanges, minimum-RTT filtering, header sizes);
2. deterministic joiner behavior on **synthetic** two-rank traces with
   a known injected clock skew: the recovered offset lands within the
   rtt/2 bound, every phase is non-negative, and the decomposition sums
   to the op's client wall time exactly;
3. round trips on **real** gangs (LocalRouter 2s/2c, FLAG_TIMING on):
   every completed framed op joins, the wire-level estimator state
   rides the trace, a drop plan's retry attempts appear as separate
   attempt chains matching the plan arithmetic, and legacy peers
   negotiate the extension off per pair.
"""

import json
import threading

import numpy as np
import pytest

from mpit_tpu import obs
from mpit_tpu.comm.local import LocalRouter
from mpit_tpu.ft import (
    ACK_TIMING_WORDS,
    FLAG_FRAMED,
    FLAG_TIMING,
    FaultPlan,
    FaultyTransport,
    FTConfig,
    hdr_bytes,
    pack_reply_stamps,
    pack_tx_stamp,
    reply_hdr_bytes,
    unpack_reply_stamps,
    unpack_tx_stamp,
)
from mpit_tpu.obs import causal as obs_causal
from mpit_tpu.obs import clock as obs_clock
from mpit_tpu.obs import trace as obs_trace
from mpit_tpu.ps import ParamClient, ParamServer, tags

#: fast retry posture with the timing extension on (LocalRouter speed)
TIMED_FT = FTConfig(op_deadline_s=0.25, max_retries=8,
                    backoff_base_s=0.005, backoff_cap_s=0.02, timing=True)


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
# clock estimator + wire primitives


class TestClockEstimator:
    def test_symmetric_exchange_recovers_offset_exactly(self):
        clock = obs_clock.PeerClock()
        # peer clock = local + 5000us; 100us each way, 30us turnaround
        t1 = 1_000_000
        assert clock.add(t1, t1 + 100 + 5000, t1 + 130 + 5000, t1 + 230)
        assert clock.offset_us == pytest.approx(5000.0)
        assert clock.uncertainty_us == pytest.approx(100.0)  # rtt/2

    def test_asymmetry_error_stays_within_rtt_bound(self):
        clock = obs_clock.PeerClock()
        skew, out, back = -7000, 20, 380  # pathological asymmetry
        t1 = 2_000_000
        clock.add(t1, t1 + out + skew, t1 + out + skew + 10,
                  t1 + out + 10 + back)
        assert abs(clock.offset_us - skew) <= clock.uncertainty_us

    def test_min_rtt_sample_wins(self):
        clock = obs_clock.PeerClock()
        t1 = 1_000_000
        clock.add(t1, t1 + 500, t1 + 510, t1 + 1010)          # rtt 1000
        assert clock.rtt_us == pytest.approx(1000.0)
        assert clock.add(t1 + 5000, t1 + 5100, t1 + 5110, t1 + 5210)
        assert clock.rtt_us == pytest.approx(200.0)           # better won
        # a worse later sample does not displace the best
        assert not clock.add(t1 + 9000, t1 + 9400, t1 + 9410, t1 + 9810)
        assert clock.rtt_us == pytest.approx(200.0)

    def test_garbage_exchange_rejected(self):
        clock = obs_clock.PeerClock()
        # negative rtt: echoed stamp from a different attempt
        assert not clock.add(2_000_000, 1_000_000, 3_000_000, 2_000_100)
        assert clock.samples == 1 and clock.accepted == 0

    def test_drift_aging_lets_fresh_samples_replace_stale_best(self):
        clock = obs_clock.PeerClock()
        t1 = 1_000_000
        clock.add(t1, t1 + 50, t1 + 60, t1 + 110)             # rtt 100
        # 10 s later, a 500us-rtt sample: aged best = 100 + 10*100ppm
        # = 1100us, so the fresh one wins despite the larger rtt.
        t2 = t1 + 10_000_000
        assert clock.add(t2, t2 + 250, t2 + 260, t2 + 510)
        assert clock.rtt_us == pytest.approx(500.0)

    def test_estimator_registry_snapshot(self):
        est = obs_clock.ClockEstimator()
        est.add_exchange(0, 1_000_000, 1_000_100, 1_000_110, 1_000_210)
        obs_clock.register("clienttest", est)
        snap = obs_clock.snapshot_all()
        assert "clienttest" in snap and "0" in snap["clienttest"]
        obs_clock.reset()
        assert "clienttest" not in obs_clock.snapshot_all()


class TestTimingWire:
    def test_header_sizes(self):
        assert hdr_bytes(False, False) == 16
        assert hdr_bytes(True, False) == 24
        assert hdr_bytes(False, True) == 24
        assert hdr_bytes(True, True) == 32
        assert reply_hdr_bytes(False, True) == 40
        assert reply_hdr_bytes(True, True) == 48
        assert ACK_TIMING_WORDS == 5
        assert FLAG_TIMING == 8 and not (FLAG_TIMING & (FLAG_FRAMED | 6))

    def test_tx_stamp_roundtrip_last_header_word(self):
        buf = np.zeros(64, np.uint8)
        for hdr in (24, 32):
            pack_tx_stamp(buf, hdr, 123456789)
            assert unpack_tx_stamp(buf, hdr) == 123456789
            # the stamp never touches [epoch, seq]
            assert buf[:16].view(np.int64).tolist() == [0, 0]

    def test_reply_stamps_roundtrip(self):
        buf = np.zeros(64, np.uint8)
        pack_reply_stamps(buf, 24, 1, 2, 3)
        assert unpack_reply_stamps(buf, 24) == (1, 2, 3)

    def test_timing_without_framing_is_inert(self):
        cfg = FTConfig(timing=True)
        assert not cfg.timing_track
        router = LocalRouter(2)
        client = ParamClient(1, [0], router.endpoint(1), ft=cfg)
        assert not client._timing and client._hdr == 0


# ---------------------------------------------------------------------------
# synthetic traces: known skew in, recovered offset + clean phases out


def synth_trace(skew_us: float, n_ops: int = 3, clock_meta=None) -> dict:
    """A two-rank trace: client rank 3 drives ``n_ops`` GRADs against
    server rank 0 whose clock runs ``skew_us`` ahead.  Wire is 50us
    out / 50us back, apply 300us, per-op spacing 10ms."""
    events = []
    for i in range(n_ops):
        c0 = 1_000_000.0 + i * 10_000
        send_done = c0 + 300
        s_recv = send_done + 50 + skew_us          # server clock
        s_ack = s_recv + 20 + 300                  # after queue + apply
        ack_done = s_ack - skew_us + 50            # client clock
        events += [
            {"ph": "B", "name": "GRAD", "cat": "ps_op", "pid": 3, "tid": 1,
             "ts": c0, "args": {"rank": 3, "peer": 0, "side": "client",
                                "epoch": 0, "seq": i + 1}},
            {"ph": "X", "name": "GRAD.encode", "cat": "ps_phase", "pid": 3,
             "tid": 1, "ts": c0, "dur": 100.0},
            {"ph": "X", "name": "GRAD.send", "cat": "ps_phase", "pid": 3,
             "tid": 1, "ts": c0 + 100, "dur": 200.0},
            {"ph": "X", "name": "GRAD.ack", "cat": "ps_phase", "pid": 3,
             "tid": 1, "ts": send_done, "dur": ack_done - send_done},
            {"ph": "E", "name": "GRAD", "cat": "ps_op", "pid": 3, "tid": 1,
             "ts": ack_done, "args": {"outcome": "ok"}},
            {"ph": "B", "name": "GRAD", "cat": "ps_op", "pid": 0, "tid": 1,
             "ts": s_recv, "args": {"rank": 0, "peer": 3, "side": "server",
                                    "epoch": 0, "seq": i + 1}},
            {"ph": "X", "name": "GRAD.apply", "cat": "ps_phase", "pid": 0,
             "tid": 1, "ts": s_recv + 20, "dur": 300.0},
            {"ph": "X", "name": "GRAD.ack", "cat": "ps_phase", "pid": 0,
             "tid": 1, "ts": s_ack, "dur": 10.0},
            {"ph": "E", "name": "GRAD", "cat": "ps_op", "pid": 0, "tid": 1,
             "ts": s_ack + 10, "args": {"outcome": "applied"}},
        ]
    events.sort(key=lambda e: e["ts"])
    other = {}
    if clock_meta is not None:
        other["clock"] = clock_meta
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": other}


class TestSyntheticJoin:
    @pytest.mark.parametrize("skew_us", [0.0, 37_000.0, -250_000.0])
    def test_injected_skew_recovered_within_bound(self, skew_us):
        report = obs_causal.analyze(synth_trace(skew_us))
        assert report["ops"]["join_rate"] == 1.0
        assert report["violations"] == []
        (entry,) = report["offsets"]
        assert entry["source"] == "derived"
        # symmetric synthetic wire => the NTP estimate is exact up to
        # the turnaround; always within the rtt/2 bound
        assert abs(entry["offset_us"] - skew_us) <= entry["uncertainty_us"]
        assert abs(entry["offset_us"] - skew_us) <= 200.0

    def test_phases_nonnegative_and_sum_to_wall(self):
        report = obs_causal.analyze(synth_trace(37_000.0))
        for d in report["chains"]:
            assert d["joined"]
            for phase, value in d["phases"].items():
                assert value >= 0.0, (phase, value)
            assert sum(d["phases"].values()) == pytest.approx(
                d["wall_us"], abs=d["uncertainty_us"] + 1.0)

    def test_recorded_wire_offsets_preferred(self):
        meta = {"client3": {"0": {"offset_us": 37_000.0,
                                  "uncertainty_us": 25.0, "rtt_us": 50.0,
                                  "samples": 8, "accepted": 4}}}
        report = obs_causal.analyze(synth_trace(37_000.0, clock_meta=meta))
        (entry,) = report["offsets"]
        assert entry["source"] == "wire"
        assert entry["offset_us"] == 37_000.0
        assert report["violations"] == []

    def test_flow_events_pair_and_validate(self, tmp_path):
        path = tmp_path / "synth.json"
        path.write_text(json.dumps(synth_trace(1000.0, n_ops=2)))
        out = tmp_path / "flow.json"
        n = obs_causal.emit_flow(str(path), str(out))
        assert n == 2 * 2 * 2  # request + reply arrow per op, s+f each
        obj = json.loads(out.read_text())
        starts = [e for e in obj["traceEvents"] if e["ph"] == "s"]
        finishes = [e for e in obj["traceEvents"] if e["ph"] == "f"]
        assert {e["id"] for e in starts} == {e["id"] for e in finishes}
        assert all(e.get("bp") == "e" for e in finishes)
        # the merged file still validates (s/f are well-formed events)
        obs_trace.validate_trace(obj)

    def test_beyond_uncertainty_negative_phase_is_a_violation(self):
        # Claim a tiny-uncertainty offset that is wrong by 30ms: the
        # wire/ack segments go negative far beyond the claimed bound.
        meta = {"client3": {"0": {"offset_us": 0.0, "uncertainty_us": 5.0,
                                  "rtt_us": 10.0, "samples": 8,
                                  "accepted": 4}}}
        report = obs_causal.analyze(synth_trace(-30_000.0, clock_meta=meta))
        assert report["violations"]

    def test_cli_json_and_min_join_gate(self, tmp_path, capsys):
        from mpit_tpu.obs.__main__ import main as obs_cli

        path = tmp_path / "synth.json"
        path.write_text(json.dumps(synth_trace(500.0)))
        assert obs_cli(["analyze", str(path), "--json",
                        "--min-join", "0.95"]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["ops"]["join_rate"] == 1.0
        assert payload["critical_path"]["client"] == 3
        # drop the server half: every completed op is unjoined => rc 1
        obj = synth_trace(500.0)
        obj["traceEvents"] = [
            e for e in obj["traceEvents"]
            if (e.get("args") or {}).get("side") != "server"
            and e.get("pid") != 0]
        path2 = tmp_path / "halved.json"
        path2.write_text(json.dumps(obj))
        assert obs_cli(["analyze", str(path2), "--min-join", "0.95"]) == 1


# ---------------------------------------------------------------------------
# real gangs: round trip, retries, legacy interop


def launch_timed_gang(nservers=2, nclients=2, client_plans=None,
                      client_ft=TIMED_FT):
    n = nservers + nclients
    router = LocalRouter(n)
    sranks, cranks = list(range(nservers)), list(range(nservers, n))
    servers, threads = [], []
    for r in sranks:
        servers.append(ParamServer(r, cranks, router.endpoint(r), rule="add",
                                   ft=FTConfig(rejoin=True)))
        threads.append(threading.Thread(target=servers[-1].start,
                                        daemon=True))
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


def run_rounds(servers, clients, threads, rounds, size=64):
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


class TestGangRoundTrip:
    def test_timed_gang_trace_joins_and_decomposes(self, obs_on, tmp_path):
        """The acceptance scenario: a real 2s/2c gang on the FLAG_TIMING
        wire, trace exported and analyzed — every completed framed op
        joins, every phase is non-negative, sums hold, and the trace
        carries the wire-level estimator state."""
        servers, clients, threads, _ = launch_timed_gang()
        run_rounds(servers, clients, threads, rounds=4)
        path = str(tmp_path / "gang.json")
        obs_trace.write_rank_trace(path, rank=0, role="gang")
        report = obs_causal.analyze(path)
        assert report["ops"]["completed"] > 0
        assert report["ops"]["join_rate"] == 1.0
        assert report["violations"] == []
        # wire-level estimator state rode the trace (every client had
        # accepted exchanges against every server)
        sources = {(e["client"], e["server"]): e["source"]
                   for e in report["offsets"]}
        for c in (2, 3):
            for s in (0, 1):
                assert sources.get((c, s)) == "wire", sources
        for d in report["chains"]:
            assert all(v >= 0.0 for v in d["phases"].values())
            assert sum(d["phases"].values()) == pytest.approx(
                d["wall_us"], abs=max(d["uncertainty_us"], 1.0) + 1.0)
        # both halves' stamps landed on the client spans
        obj = json.load(open(path))
        stamped = [e for e in obj["traceEvents"]
                   if e["ph"] == "B" and "srv_recv_us" in
                   (e.get("args") or {})]
        assert stamped
        assert (obj["otherData"]["clock"].keys()
                >= {"client2", "client3"})

    def test_estimator_offset_near_zero_same_process(self, obs_on):
        """All ranks share one process => true offset is 0; the
        estimator must land within its own uncertainty (and sane
        absolute bounds)."""
        servers, clients, threads, _ = launch_timed_gang()
        run_rounds(servers, clients, threads, rounds=4)
        for c in clients:
            for srank in (0, 1):
                clock = c._clock.peers[srank]
                assert clock.accepted > 0
                assert abs(clock.offset_us) <= clock.uncertainty_us + 1.0
        # the clock gauge surfaced
        keys = [k for k in obs_on.snapshot()
                if k.startswith("mpit_clock_offset_us")]
        assert len(keys) == 4  # 2 clients x 2 servers


def simulate_grad_channel(plan, src, dst, rounds):
    """Replay the plan arithmetic for one client->server GRAD channel
    (the test_obs.py harness contract): dropped frames time out and
    resend; passed/duplicated frames ack."""
    sends = drops = dups = 0
    n = 0
    for _ in range(rounds):
        while True:
            n += 1
            sends += 1
            verdict = plan.decide(src, dst, tags.GRAD, n)
            if verdict == "drop":
                drops += 1
                continue
            if verdict == "dup":
                dups += 1
            break
    return sends, drops, dups


class TestDropPlanAttempts:
    def test_retry_attempts_appear_as_separate_attempt_chains(
            self, obs_on, tmp_path):
        """Every-2nd GRAD dropped on client 0's channels: each dropped
        op's chain must carry exactly 1 + resends attempt segments (the
        backoff marks split them), matching the replayed plan
        arithmetic — and the analyzer attributes the dead attempts to
        the ``retry`` phase."""
        rounds, nservers = 4, 2
        plans = {0: FaultPlan(seed=0, drop_every=2,
                              tags=frozenset({tags.GRAD}))}
        servers, clients, threads, transports = launch_timed_gang(
            client_plans=plans)
        run_rounds(servers, clients, threads, rounds)
        want_retries = sum(
            simulate_grad_channel(plans[0], clients[0].rank, dst, rounds)[1]
            for dst in range(nservers))
        assert clients[0].retries == want_retries > 0
        path = str(tmp_path / "drop.json")
        obs_trace.write_rank_trace(path, rank=0, role="gang")
        events, _ = obs_causal.load_trace(path)
        chains, _ = obs_causal.join_spans(obs_causal.extract_spans(events))
        grad_chains = [c for c in chains
                       if c.op == "GRAD" and c.key[1] == clients[0].rank]
        assert grad_chains
        retried = [c for c in grad_chains
                   if c.client.args.get("retries", 0) >= 1]
        assert retried, "the drop plan produced no retried GRAD chain"
        total_attempts = 0
        for chain in grad_chains:
            attempts = chain.attempts()
            assert len(attempts) == 1 + int(
                chain.client.args.get("retries", 0) or 0)
            assert chain.joined  # the surviving attempt reached a server
            total_attempts += len(attempts)
        n_ops = rounds * nservers
        assert total_attempts == n_ops + want_retries
        report = obs_causal.analyze(path)
        assert report["violations"] == []
        by_key = {(d["client"], d["server"], d["seq"]): d
                  for d in report["chains"] if d["op"] == "GRAD"}
        for chain in retried:
            d = by_key[(chain.key[1], chain.key[2][1], chain.key[4])]
            assert d["phases"]["retry"] > 0.0


class TestLegacyInterop:
    def test_legacy_peers_negotiate_timing_off_per_pair(self, obs_on):
        """Mixed gang: a FLAG_TIMING client and a plain legacy (v1)
        client on the same servers.  The extension is per pair — the
        legacy pair's acks stay 16-byte [epoch, seq]-free legacy wire
        (2-word ack staging, no echo service), only the timed client
        grows estimator state, and the gang completes with every grad
        applied."""
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
                        seed_servers=True, ft=TIMED_FT),
            ParamClient(cranks[1], sranks, router.endpoint(cranks[1]),
                        seed_servers=False, ft=FTConfig()),  # legacy v1
        ]
        assert clients[0]._timing and clients[0]._hdr == 24
        assert clients[0]._hdr_rx == 40
        assert not clients[1]._timing and clients[1]._hdr == 0
        run_rounds(servers, clients, threads, rounds)
        for s in servers:
            assert s._timing[cranks[0]] is True
            assert s._timing.get(cranks[1], False) is False
            # ack staging sized per negotiation: timing tail vs legacy
            assert s._ack_send[cranks[0]].size == ACK_TIMING_WORDS
            assert cranks[1] not in s._ack_send  # legacy: 0-byte acks
        assert clients[0]._clock.peers and all(
            c.accepted for c in clients[0]._clock.peers.values())
        assert not clients[1]._clock.peers
        assert (sum(s.grads_applied for s in servers)
                == rounds * 2 * nservers)

    def test_heartbeat_echo_refreshes_clock_while_idle(self, obs_on):
        """Beats flow during ping()/wait() even with no op in flight;
        with FLAG_TIMING each is echoed and the estimator accumulates
        samples from the heartbeat stream alone."""
        import time as _time

        ft = FTConfig(op_deadline_s=0.25, heartbeat_s=0.01, timing=True,
                      backoff_base_s=0.005, backoff_cap_s=0.02)
        servers, clients, threads, _ = launch_timed_gang(client_ft=ft)
        run_rounds_started = False
        try:
            rng = np.random.default_rng(7)
            starters, params = [], []
            for c in clients:
                p = (rng.normal(size=64).astype(np.float32)
                     if not params else np.zeros(64, np.float32))
                params.append(p)
                starters.append(threading.Thread(
                    target=c.start, args=(p, np.zeros(64, np.float32)),
                    daemon=True))
            for t in starters:
                t.start()
            join_all(starters)
            run_rounds_started = True
            before = {s: clients[0]._clock.peer(s).samples for s in (0, 1)}
            deadline = _time.monotonic() + 5.0
            while _time.monotonic() < deadline:
                for c in clients:
                    c.ping()
                if all(clients[0]._clock.peer(s).samples > before[s] + 2
                       for s in (0, 1)):
                    break
                _time.sleep(0.002)
            for s in (0, 1):
                assert clients[0]._clock.peer(s).samples > before[s], \
                    "no heartbeat-echo clock samples while idle"
        finally:
            if run_rounds_started:
                for c in clients:
                    c.stop()
                join_all(threads)


# ---------------------------------------------------------------------------
# flight-dump causal chain + top columns


class TestFlightCausalChain:
    def test_open_op_marks_and_clock_ride_the_dump(self, obs_on, tmp_path,
                                                   monkeypatch):
        monkeypatch.setenv("MPIT_OBS_FLIGHT", str(tmp_path))
        rec = obs.get_recorder()
        span = rec.op("GRAD", peer=0, side="client", rank=3, epoch=0, seq=9)
        span.mark("encode")
        span.mark("send")
        span.mark("backoff")
        est = obs_clock.ClockEstimator()
        est.add_exchange(0, 1_000_000, 1_000_100, 1_000_110, 1_000_210)
        obs_clock.register("client3", est)
        flight = obs.get_flight()
        path = flight.dump("stall_test")
        span.end("exhausted")
        dump = json.load(open(path))
        (op,) = [o for o in dump["inflight_ops"] if o["op"] == "GRAD"]
        assert [m[0] for m in op["marks"]] == ["encode", "send", "backoff"]
        assert all(isinstance(m[1], float) for m in op["marks"])
        assert op["phase"] == "backoff" and op["seq"] == 9
        assert dump["clock"]["client3"]["0"]["accepted"] == 1
        obs.validate_dump(path)  # schema stays valid with the additions


class TestTopColumns:
    def test_hist_quantile_from_exposition(self):
        from mpit_tpu.obs import top as obs_top
        from mpit_tpu.obs.metrics import Registry

        reg = Registry()
        h = reg.histogram("mpit_ps_op_seconds", op="GRAD", side="client")
        for v in [0.001] * 98 + [3.0, 3.5]:
            h.observe(v)
        samples = obs_top.parse_exposition(reg.exposition())
        p50 = obs_top.hist_quantile(samples, "mpit_ps_op_seconds", 0.50)
        p99 = obs_top.hist_quantile(samples, "mpit_ps_op_seconds", 0.99)
        assert p50 is not None and p50 <= 0.002
        assert p99 is not None and p99 >= 2.0
        assert obs_top.hist_quantile(samples, "mpit_nonexistent", 0.99) is None

    def test_rank_row_has_p99_and_sendq_columns(self):
        from mpit_tpu.obs import top as obs_top
        from mpit_tpu.obs.metrics import Registry

        reg = Registry()
        reg.histogram("mpit_ps_op_seconds", op="GRAD",
                      side="client").observe(0.004)
        reg.gauge("mpit_tcp_send_queue_depth", rank=1, peer=0).set(3)
        reg.gauge("mpit_tcp_send_queue_depth", rank=1, peer=2).set(4)
        sample = {"metrics": obs_top.parse_exposition(reg.exposition()),
                  "status": {"role": "worker"}, "port": 1}
        row = obs_top._rank_row(1, sample, None, None)
        assert row["p99_s"] is not None and row["p99_s"] >= 0.004
        assert row["send_queue"] == 7
        table = obs_top.render_table([row])
        assert "p99ms" in table and "sendq" in table


# ---------------------------------------------------------------------------
# the shm wire's own spans: tx joined to rx by (src, dst, msg_id)


class TestWireJoin:
    """``join_wire`` on real shm endpoints (PR 34): the wire's identity
    is exact whichever way a message was received."""

    RING = 1 << 20
    BIG = 6 << 20

    @pytest.fixture
    def wires(self, obs_on):
        import os

        from mpit_tpu.comm.shm import ShmTransport

        ns = f"t_wj_{os.getpid()}"
        ends = [ShmTransport(ns, r, 3, ring_bytes=self.RING)
                for r in range(3)]
        yield ends
        for t in ends:
            t.close()

    @staticmethod
    def move(src, dst, tag, seed, receive):
        data = np.random.default_rng(seed).integers(
            0, 256, TestWireJoin.BIG, dtype=np.uint8)
        send = src.isend(data, dst.rank, tag)
        out = receive(src, dst, tag, send)
        np.testing.assert_array_equal(out, data)

    @staticmethod
    def posted(src, dst, tag, send):
        out = np.zeros(TestWireJoin.BIG, np.uint8)
        recv = dst.irecv(src.rank, tag, out=out)
        while not all([src.test(send), dst.test(recv)]):  # poll both
            pass
        return out

    @staticmethod
    def assembled(src, dst, tag, send):
        while not dst.iprobe(src.rank, tag):  # whole before anyone asks
            src.test(send)
        return TestWireJoin.posted(src, dst, tag, send)

    @staticmethod
    def cancelled_then_reposted(src, dst, tag, send):
        first = dst.irecv(src.rank, tag,
                          out=np.zeros(TestWireJoin.BIG, np.uint8))
        for _ in range(2):  # part of it lands in the first buffer
            src.test(send)
            assert not dst.test(first)
        dst.cancel(first)
        return TestWireJoin.posted(src, dst, tag, send)

    def test_every_way_of_receiving_joins_end_to_end(self, wires, tmp_path):
        a, b, c = wires
        ways = [(a, b, self.posted, 1), (a, b, self.assembled, 0),
                (b, a, self.cancelled_then_reposted, 0),
                (c, a, self.posted, 1), (a, c, self.assembled, 0)]
        for seed, (src, dst, receive, _direct) in enumerate(ways):
            self.move(src, dst, 7, seed, receive)
        path = str(tmp_path / "wire.json")
        obs_trace.write_rank_trace(path, rank=0, role="gang")
        stats = obs_trace.validate_trace(path)
        assert stats["ops"] == 2 * len(ways)
        events = obs_causal.load_trace(path)[0]
        assert obs_causal.extract_spans(events) == []  # no op span is one
        spans = obs_causal.extract_spans(events, cat="wire")
        pairs, loose = obs_causal.join_wire(spans)
        assert not loose and len(pairs) == len(ways)
        # rank a sent three messages, b and c one each: msg_id alone
        # would confuse them, (src, dst, msg_id) cannot
        got = sorted((tx.args["rank"], rx.args["rank"], tx.args["msg_id"])
                     for tx, rx in pairs)
        assert got == sorted(
            (src.rank, dst.rank, n)
            for (src, dst, _r, _direct), n in zip(ways, (1, 2, 1, 1, 3)))
        # which way each went is the endpoints' own count, not the span's
        direct = {r.rank: r.rx_path_bytes()["rx_direct_bytes"]
                  for r in wires}
        assert direct == {
            r.rank: self.BIG * sum(d for _s, dst, _r, d in ways
                                   if dst is r) for r in wires}
        for tx, rx in pairs:
            assert (tx.name, rx.name) == ("tx", "rx")
            assert tx.args["msg_id"] == rx.args["msg_id"]
            assert tx.args["bytes"] == rx.args["bytes"] == self.BIG
            # published by the sender before the receiver had it whole
            assert tx.t0 <= rx.t1 and tx.t1 <= rx.t1

    def test_an_end_without_its_other_end_is_left_over(self, wires):
        a, b, _c = wires
        self.move(a, b, 7, 9, self.posted)
        rec = obs.get_recorder()
        lone = [sp for sp in rec.spans if sp.name == "tx"]
        events = obs_trace.chrome_events(rec, pid=0)
        spans = obs_causal.extract_spans(events, cat="wire")
        pairs, loose = obs_causal.join_wire(
            [s for s in spans if s.name == "tx"])
        assert not pairs and len(loose) == len(lone) == 1
