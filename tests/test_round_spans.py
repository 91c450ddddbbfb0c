"""The sync round's span tree (PR 23): the shells' ``round`` spans and
their phases, the ordinal join on the unframed wire, ``apply_exec``
against the GRAD ack, the obs-off path, the anchor onto a profiler
timeline, and the benchmark's readers of all of it on a hand-worked
fixture.  In-process thread gangs and constructed fixtures only.
"""

import contextlib
import gc
import json
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import spec as spec_mod
from chipbench.layers import spantree
from mpit_tpu import obs
from mpit_tpu.comm.local import LocalRouter
from mpit_tpu.obs import causal
from mpit_tpu.obs import trace as obs_trace
from mpit_tpu.optim.downpour import Downpour
from mpit_tpu.optim.easgd import EAMSGD
from mpit_tpu.optim.shells import RuleShell
from mpit_tpu.ps import ParamClient, ParamServer

SIZE = 64
TARGET = jnp.linspace(-1.0, 1.0, SIZE)


def quad(w, target):
    """Loss and gradient of 0.5 |w - target|^2."""
    d = w - target
    return 0.5 * jnp.sum(d * d), d


@pytest.fixture
def obs_on():
    obs.configure(enabled=True, reset=True)
    try:
        yield obs.get_recorder()
    finally:
        obs.configure(enabled=None, reset=True)


@contextlib.contextmanager
def gang(nservers, nclients, rule="add"):
    """Servers on threads, clients driven by the caller, unframed wire."""
    n = nservers + nclients
    router = LocalRouter(n)
    sranks, cranks = list(range(nservers)), list(range(nservers, n))
    servers = [ParamServer(r, cranks, router.endpoint(r), rule=rule)
               for r in sranks]
    threads = [threading.Thread(target=s.start, daemon=True)
               for s in servers]
    for t in threads:
        t.start()
    clients = [ParamClient(r, sranks, router.endpoint(r),
                           seed_servers=(r == cranks[0])) for r in cranks]
    try:
        yield servers, clients
    finally:
        for s in servers:
            s.live.stop()
        for t in threads:
            t.join(10)
            assert not t.is_alive(), "server thread did not stop"


OPTIMIZERS = {
    "rule-su1": lambda pc: RuleShell(quad, pc, su=1),
    "rule-su2": lambda pc: RuleShell(quad, pc, su=2),
    "downpour": lambda pc: Downpour(quad, pc, lr=0.1, su=1),
    "easgd": lambda pc: EAMSGD(quad, pc, lr=0.1, mva=0.2, su=1),
}


def phase_spans(span):
    """[(phase, begin, end)] of a recorder span."""
    ends = [t for _p, t in span.marks[1:]] + [span.t1]
    return [(p, t, e) for (p, t), e in zip(span.marks, ends)]


@pytest.mark.parametrize("name", sorted(OPTIMIZERS))
def test_round_children_tile_and_ops_carry_the_round(obs_on, name):
    rec = obs_on
    steps = 3
    with gang(2, 1) as (_servers, (pc,)):
        opt = OPTIMIZERS[name](pc)
        w = opt.start(jnp.zeros(SIZE))
        for _ in range(steps * getattr(opt, "su", 1)):
            w, _loss = opt.step(w, TARGET)
        opt.stop()
    rounds = [s for s in rec.spans if s.name == "round"]
    assert [s.args["round"] for s in rounds] == list(range(steps))
    assert opt.rounds == steps
    for span in rounds:
        parts = phase_spans(span)
        # the children tile the parent: the first begins with it, each
        # begins where the one before ends, the last ends with it
        assert parts[0][1] == span.t0 and parts[-1][2] == span.t1
        assert all(a[2] == b[1] for a, b in zip(parts, parts[1:]))
        assert {"wait_backward", "d2h", "stage", "exchange", "h2d",
                "telemetry"} == {p for p, _b, _e in parts}
        # the client ops of the round carry its number and begin inside
        # one of its exchange phases, one GRAD and one PARAM per server
        ops = [s for s in rec.spans if s.args.get("side") == "client"
               and s.args.get("round") == span.args["round"]]
        assert sorted(s.name for s in ops) == ["GRAD"] * 2 + ["PARAM"] * 2
        exchanges = [(b, e) for p, b, e in parts if p == "exchange"]
        assert all(any(b <= s.t0 <= e for b, e in exchanges) for s in ops)
    # the exported trace nests the phases under the B/E pair by name
    events = obs_trace.chrome_events(rec, pid=0)
    names = {e["name"] for e in events if e.get("cat") == "ps_phase"}
    assert {"round.d2h", "round.exchange", "round.h2d"} <= names
    # one definition of the sync time: the sum of the exchange phases
    want = sum(e - b for s in rounds for p, b, e in phase_spans(s)
               if p == "exchange")
    assert opt.sync_seconds == pytest.approx(want) and want > 0


class CountingClient:
    """The optimizer tests' in-process plain-add server, one shard."""

    def start(self, param, grad):
        self.param, self.grad = param, grad
        self.center = param.copy()

    reset = start

    def async_send_grad(self):
        self.center += self.grad

    def async_recv_param(self):
        np.copyto(self.param, self.center)

    def ping(self):
        pass

    wait = stop = ping


@pytest.mark.parametrize("name", sorted(OPTIMIZERS))
def test_obs_off_makes_no_span_and_reads_only_the_plain_timer(monkeypatch,
                                                              name):
    """With obs off a round creates no span, takes no fence and reads
    the clock only for ``sync_seconds``: one ``time.monotonic`` pair
    around each exchange (EASGD's round has two), which times the
    client's wait as the round span's ``exchange`` phase would."""
    nap = 0.01

    class SlowClient(CountingClient):
        def wait(self):
            time.sleep(nap)

    obs.configure(enabled=False, reset=True)
    try:
        opt = OPTIMIZERS[name](SlowClient())
        w = opt.start(jnp.zeros(SIZE))
        for _ in range(2 * getattr(opt, "su", 1)):  # compile everything
            w, _loss = opt.step(w, TARGET)
        before = opt.sync_seconds
        reads = []
        me = threading.get_ident()  # not a thread another test left behind

        def counted(real, clock):
            def read():
                if threading.get_ident() == me:
                    reads.append(clock)
                return real()
            return read

        for clock in ("monotonic", "monotonic_ns", "time", "perf_counter"):
            monkeypatch.setattr(time, clock,
                                counted(getattr(time, clock), clock))
        for _ in range(2 * getattr(opt, "su", 1)):
            w, _loss = opt.step(w, TARGET)
        monkeypatch.undo()
        exchanges = 2 if name == "easgd" else 1  # a round
        assert reads == ["monotonic"] * (2 * exchanges * 2)
        assert opt._spans is obs.NULL_RECORDER
        assert obs.get_recorder().spans == ()
        assert opt.rounds == 4
        assert 2 * nap <= opt.sync_seconds - before < 2 * nap + 0.5
    finally:
        obs.configure(enabled=None, reset=True)


# -- the streamed round (PR 27): the same tree, what its phases mean ----------


@pytest.mark.parametrize("nservers, streamed", [(1, 0), (2, 2)])
def test_the_six_phases_tile_a_streamed_round(obs_on, monkeypatch, nservers,
                                              streamed):
    from mpit_tpu.optim import sync

    monkeypatch.setattr(sync, "PIECE_BYTES", 10 * 4)  # several a shard
    rec = obs_on
    with gang(nservers, 1) as (_servers, (pc,)):
        opt = RuleShell(quad, pc, su=1)
        w = opt.start(jnp.zeros(SIZE))
        for _ in range(3):
            w, _loss = opt.step(w, TARGET)
        opt.stop()
    rounds = [s for s in rec.spans if s.name == "round"]
    assert len(rounds) == 3 and len(opt._stream.cut) == nservers
    assert opt.rounds == 3
    for span in rounds:
        parts = phase_spans(span)
        assert [p for p, _b, _e in parts] == [
            "wait_backward", "d2h", "stage", "exchange", "h2d", "telemetry"]
        assert parts[0][1] == span.t0 and parts[-1][2] == span.t1
        assert all(a[2] == b[1] for a, b in zip(parts, parts[1:]))
        assert not {"pieces", "shards_streamed"} & set(span.args)
        # a ``d2h`` copy span a piece, the round's own
        pieces = [s for s in rec.spans if s.name == "d2h"
                  and s.args["round"] == span.args["round"]]
        assert len(pieces) == -(-SIZE // nservers // 10) * nservers
        assert len({s.args["shard"] for s in pieces}) == max(streamed, 1)
        # the ops still begin inside the exchange, and carry the round
        (exchange,) = [(b, e) for p, b, e in parts if p == "exchange"]
        ops = [s for s in rec.spans if s.args.get("side") == "client"
               and s.args.get("round") == span.args["round"]]
        assert sorted(s.name for s in ops) == (
            ["GRAD"] * nservers + ["PARAM"] * nservers)
        assert all(exchange[0] <= s.t0 and s.t1 <= exchange[1] for s in ops)
        assert all("gated_ms" in s.args for s in ops if s.name == "GRAD")
    want = sum(e - b for s in rounds for p, b, e in phase_spans(s)
               if p == "exchange")
    assert opt.sync_seconds == pytest.approx(want) and want > 0


def test_gated_ms_lies_outside_the_grad_span(obs_on):
    """The wait for a shard's staging is measured before its GRAD span
    opens: the span keeps meaning the wire and the server."""
    rec = obs_on
    hold = 0.25
    with gang(2, 1) as (_servers, (pc,)):
        opt = RuleShell(quad, pc, su=1)
        w = opt.start(jnp.zeros(SIZE))
        stream = opt._stream
        second = stream.cut[1]
        asked = []

        def slow_gate(shard):
            if shard.offset != second.offset:
                return stream.staged(shard)
            asked.append(time.monotonic())
            held = asked[-1] - asked[0] < hold
            return 0 if held else stream.staged(shard)

        pc.stream_shards(slow_gate, stream.landed)
        w, _loss = opt.step(w, TARGET)
        opt.stop()
    grads = {s.args["peer"]: s for s in rec.spans
             if s.name == "GRAD" and s.args["side"] == "client"}
    held, free = grads[1], grads[0]
    assert held.args["gated_ms"] >= 1e3 * hold
    assert held.t0 >= asked[0] + hold          # it opened after the wait
    assert held.t1 - held.t0 < hold / 2        # and does not contain it
    assert free.args["gated_ms"] < 1e3 * hold / 2
    # the round's exchange does contain it: the worker waited there
    (span,) = [s for s in rec.spans if s.name == "round"]
    assert span.phase_seconds("exchange") >= hold


def test_obs_off_a_streamed_round_reads_the_clock_twice_and_fences_nothing(
        monkeypatch):
    import jax

    obs.configure(enabled=False, reset=True)
    try:
        with gang(2, 1) as (_servers, (pc,)):
            opt = RuleShell(quad, pc, su=1)
            w = opt.start(jnp.zeros(SIZE))
            for _ in range(2):  # compile everything
                w, _loss = opt.step(w, TARGET)
            assert opt._stream is not None and len(opt._stream.cut) == 2
            me = threading.current_thread()
            reads, fences = [], []
            for clock in ("monotonic", "monotonic_ns", "time",
                          "perf_counter"):
                real = getattr(time, clock)

                def counted(real=real, clock=clock):
                    if threading.current_thread() is me:  # not the servers
                        reads.append(clock)
                    return real()

                monkeypatch.setattr(time, clock, counted)
            real_fence = jax.block_until_ready
            monkeypatch.setattr(
                jax, "block_until_ready",
                lambda x: fences.append(1) or real_fence(x))
            before = opt.sync_seconds
            for _ in range(2):
                w, _loss = opt.step(w, TARGET)
            monkeypatch.undo()
            assert reads == ["monotonic"] * 4  # a pair a round
            assert fences == []
            assert opt._spans is obs.NULL_RECORDER
            assert obs.get_recorder().spans == ()
            assert opt.rounds == 4 and opt.sync_seconds > before
            opt.stop()
    finally:
        obs.configure(enabled=None, reset=True)


def test_client_and_server_spans_join_by_ordinal_unframed(obs_on):
    rec = obs_on
    rounds = 3
    with gang(2, 2) as (_servers, clients):
        bufs = [(np.zeros(SIZE, np.float32), np.zeros(SIZE, np.float32))
                for _ in clients]
        starters = [threading.Thread(target=c.start, args=b, daemon=True)
                    for c, b in zip(clients, bufs)]
        for t in starters:
            t.start()
        for t in starters:
            t.join(30)
        for _ in range(rounds):
            for c in clients:
                c.grad[:] = 1.0
                c.async_send_grad()
                c.async_recv_param()
                c.wait()
        for c in clients:
            c.stop()
    spans = causal.extract_spans(obs_trace.chrome_events(rec, pid=0))
    assert not any("seq" in s.args for s in spans)  # no wire identity
    chains, _unkeyed = causal.join_spans(spans)
    for op in ("GRAD", "PARAM"):
        mine = [c for c in chains if c.op == op]
        # one chain per (client, server, round), every one with both halves
        assert len(mine) == 2 * 2 * rounds
        assert all(c.joined and c.key[3] == causal.ORDINAL for c in mine)
        assert sorted({c.key[4] for c in mine}) == list(range(rounds))
        for chain in mine:
            client, server = chain.client, chain.server
            assert client.args["n"] == server.args["n"] == chain.key[4]
            assert client.args["rank"] == server.args["peer"]
            assert client.args["peer"] == server.args["rank"]
            # one process, one clock: the server's half begins after the
            # client sent and before the client's half ends
            assert client.mark_ts("send", last=False) <= server.t0 \
                <= client.t1
    grads = [s for s in spans if s.name == "GRAD"]
    assert all(s.args["bytes"] == SIZE // 2 * 4 for s in grads)
    execs = [s for s in spans if s.name == "apply_exec"]
    assert len(execs) == 2 * 2 * rounds
    assert all([p for p, _t, _d in s.phases] == ["queued", "exec"]
               and s.outcome == "ready" for s in execs)


@pytest.mark.parametrize("ids, want", [
    (("boot-a", "boot-a"), {(1, 0): 150e6, (0, 1): -150e6}),
    (("boot-a", "boot-b"), {}),
])
def test_ranks_on_one_clock_differ_by_their_epoch_offsets(ids, want):
    other = {"ranks": {
        "1": {"epoch_offset": 100.0, "clock_id": ids[0]},
        "0": {"epoch_offset": 250.0, "clock_id": ids[1]},
        "7": {"role": "from a program that records no offset"}}}
    assert causal.shared_clock_offsets(other) == want
    table = causal.OffsetTable([], other)
    if want:
        assert table.lookup(1, 0) == (150e6, 0.0, "monotonic")
    else:
        assert table.lookup(1, 0)[2] == "none"


class SlowResult:
    """A stubbed apply's result: ready ``delay`` seconds after it is
    first waited for."""

    def __init__(self, value, delay):
        self.value, self.delay = value, delay

    def block_until_ready(self):
        time.sleep(self.delay)
        return self

    def unsafe_buffer_pointer(self):  # a fresh output, never in place
        return id(self)

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.value, dtype)


@pytest.mark.parametrize("delay", [0.3])
def test_apply_exec_ends_after_the_ack_and_does_not_delay_it(obs_on, delay):
    rec = obs_on
    with gang(1, 1) as ((server,), (pc,)):
        server._apply_for = lambda codec: (
            lambda param, grad, state: (
                SlowResult(np.asarray(param) + np.asarray(grad), delay),
                state))
        # the stub's result stands for its own token: what is waited on
        server._apply_token = lambda: server.param
        pc.start(np.zeros(SIZE, np.float32), np.zeros(SIZE, np.float32))
        pc.grad[:] = 1.0
        pc.async_send_grad()
        pc.wait()  # returns at the ack
        acked = time.monotonic()
        assert rec.drain(timeout=10)
        pc.stop()
    grad_client = next(s for s in rec.spans if s.name == "GRAD"
                       and s.args["side"] == "client")
    grad_server = next(s for s in rec.spans if s.name == "GRAD"
                       and s.args["side"] == "server")
    exec_span = next(s for s in rec.spans if s.name == "apply_exec")
    # the ack did not wait for the apply, recording or not
    assert grad_client.t1 - grad_client.t0 < delay / 2
    assert grad_client.t1 <= acked
    assert [p for p, _t in grad_server.marks] == ["copy", "dispatch", "ack"]
    # the apply's execution ends after the ack, in a span of its own
    assert exec_span.t1 > grad_server.t1 and exec_span.t1 > grad_client.t1
    assert exec_span.t1 - exec_span.t0 >= delay
    assert exec_span.phase_seconds("exec") >= delay
    assert exec_span.args["grad_n"] == grad_server.args["n"] == 0
    assert exec_span.outcome == "ready"


def waiters():
    return {t for t in threading.enumerate()
            if t.name == "obs-ready-waiter"}


def test_a_replaced_recorder_ends_its_waiter_once_it_has_drained():
    """``while True: items.get()`` left one thread for every recorder
    ever made."""
    before, recorders = waiters(), []
    try:
        for _ in range(10):
            obs.configure(enabled=True, reset=True)
            rec = obs.get_recorder()
            rec.end_when_ready(rec.op("apply_exec", side="server"),
                               SlowResult(0, 0.05))
            recorders.append(rec)
        mine = waiters() - before
        for rec in recorders:  # what was handed over has drained
            assert rec.drain(timeout=10)
            assert [s.outcome for s in rec.spans] == ["ready"]
        deadline = time.monotonic() + 10
        while len(waiters() - before) > 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(waiters() - before) <= 1  # the recorder that stands
    finally:
        obs.configure(enabled=None, reset=True)
    for thread in mine:
        thread.join(10)
    assert not waiters() - before


def test_a_dropped_recorder_takes_its_waiter_with_it():
    before = waiters()
    rec = obs.SpanRecorder()  # nobody's global, never closed
    rec.end_when_ready(rec.op("apply_exec", side="server"), jnp.ones(3))
    assert rec.drain(timeout=10)
    (thread,) = waiters() - before
    del rec
    gc.collect()  # its spans hold it
    thread.join(10)
    assert not thread.is_alive()


# -- a hand-made profiler trace and the benchmark's readers -------------------
#
# One traced round.  Profiler clock (ns): the window is [0, 1_000_000]
# (bench.batch then bench.dispatch); the chip runs the step's program
# jit_loss over [50k, 950k] with three operations, [50k, 150k] under
# head_loss, [150k, 200k] under attn and [900k, 950k] under the transpose
# of head_loss, and then another program, jit_shipped_norm over
# [955k, 975k], whose one operation [960k, 970k] also sits under a scope
# called head_loss and must not count: busy 210k, idle 790k in the gaps
# [0, 50k], [200k, 900k], [950k, 960k] and [970k, 1000k].  The
# ``mpit.round`` annotation begins at 200_000 ns with mono_ns
# 5_000_200_000, so the monotonic clock is the profiler's plus 5 s.  The
# round (monotonic ms after 5 s): wait_backward 0.20-0.25, d2h 0.25-0.30,
# stage 0.30-0.35, exchange 0.35-0.85, h2d 0.85-0.90, telemetry
# 0.90-0.95; the client's GRAD 0.35-0.60 (send mark at 0.36) and PARAM
# 0.35-0.80, 500 bytes each; the server's GRAD begins at 0.40 and copies
# until 0.48; its apply_exec is queued 0.50-0.52 and runs 0.52-0.75.
# Leaves cover [200k, 800k] and [850k, 950k] of the profiler's timeline,
# so 50k of the middle gap, the first gap and the last two are unnamed:
# 140k of 790k, 17.72%.  Per MB of the 500 bytes: GRAD op 0.25 ms, PARAM
# op 0.45, the server's copy 0.08, apply_exec 0.23, each times 2000.

WORKER, SERVER = 1, 0
EPOCH_OFFSET = {WORKER: 100.0, SERVER: 250.0}
HAND_WORKED = {
    "shell_round_ms_p50": 0.5,
    "d2h_ms_p50": 0.1,
    "h2d_ms_p50": 0.05,
    "param_op_ms_p50": 0.45,
    "grad_queue_ms_p50": 0.04,
    "server_apply_ms_p50": 0.23,
    "idle_unnamed_pct": 100 * 140 / 790,
    "head_loss_ms_per_step": 0.15,
    "push_direct_pct": 75.0,  # 375 of the GRAD's 500 bytes read from pieces
}


def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    """One protobuf field: a varint for an int, else length-delimited."""
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    data = value.encode() if isinstance(value, str) else value
    return varint(number << 3 | 2) + varint(len(data)) + data


def xplane(name, lines, stat_names, metadata_stats=None):
    """An XPlane: ``lines`` is {line name: [(event name, start_ns,
    duration_ns, {stat: int})]}; ``metadata_stats`` {event name: {stat:
    str}} puts string stats on the events' metadata."""
    stat_id = {s: i + 1 for i, s in enumerate(stat_names)}
    names = sorted({ev[0] for evs in lines.values() for ev in evs})
    meta_id = {n: i + 1 for i, n in enumerate(names)}
    body = field(2, name)
    for k, (line, events) in enumerate(lines.items()):
        packed = field(1, k + 1) + field(2, line) + field(3, 0)
        for ev_name, start, dur, stats in events:
            event = (field(1, meta_id[ev_name]) + field(2, start * 1000)
                     + field(3, dur * 1000))
            for stat, value in stats.items():
                event += field(4, field(1, stat_id[stat]) + field(4, value))
            packed += field(4, event)
        body += field(3, packed)
    for n, i in meta_id.items():
        meta = field(1, i) + field(2, n)
        for stat, text in (metadata_stats or {}).get(n, {}).items():
            meta += field(5, field(1, stat_id[stat]) + field(5, text))
        body += field(4, field(1, i) + field(2, meta))
    for stat, i in stat_id.items():
        body += field(5, field(1, i) + field(2, field(1, i) + field(2, stat)))
    return field(1, body)


@pytest.fixture
def traced_run(tmp_path):
    """The ``run`` a reader is given, for the trace described above."""
    ops = {"%fusion.1 = f32[8]{0} fusion()": "jit(loss)/head_loss/dot_general:",
           "%fusion.2 = f32[8]{0} fusion()":
               "jit(loss)/DecoderBlock_0/attn/dot_general:",
           "%fusion.3 = f32[8]{0} fusion()":
               "jit(loss)/transpose(jvp(head_loss))/mul:",
           "%fusion.4 = f32[]{} fusion()":
               "jit(shipped_norm)/head_loss/reduce_sum:"}
    a, b, c, d = ops
    space = xplane(
        "/device:TPU:0",
        {"XLA Ops": [(a, 50_000, 100_000, {}), (b, 150_000, 50_000, {}),
                     (c, 900_000, 50_000, {}), (d, 960_000, 10_000, {})],
         "XLA Modules": [("jit_loss(1)", 50_000, 900_000, {}),
                         ("jit_shipped_norm(2)", 955_000, 20_000, {})]},
        ["tf_op"], {n: {"tf_op": s} for n, s in ops.items()})
    space += xplane(
        "/host:CPU",
        {"python": [("bench.batch", 0, 100_000, {}),
                    ("bench.dispatch", 100_000, 900_000, {}),
                    ("mpit.round", 200_000, 750_000,
                     {"round": 4, "mono_ns": 5_000_200_000})]},
        ["round", "mono_ns"])
    trace_dir = tmp_path / "device_trace" / "plugins" / "profile" / "t"
    trace_dir.mkdir(parents=True)
    (trace_dir / "host.xplane.pb").write_bytes(space)

    events = []

    def span(pid, tid, name, begin_ms, phases, end_ms, **args):
        us = lambda ms: (5.0 + ms / 1e3 + EPOCH_OFFSET[pid]) * 1e6
        events.append({"ph": "B", "cat": "ps_op", "name": name, "pid": pid,
                       "tid": tid, "ts": us(begin_ms), "args": args})
        marks = phases + [("", end_ms)]
        for (phase, at), (_next, until) in zip(marks, marks[1:]):
            events.append({"ph": "X", "cat": "ps_phase", "pid": pid,
                           "tid": tid, "name": f"{name}.{phase}",
                           "ts": us(at), "dur": us(until) - us(at)})
        events.append({"ph": "E", "cat": "ps_op", "name": name, "pid": pid,
                       "tid": tid, "ts": us(end_ms),
                       "args": {"outcome": "ok"}})

    span(WORKER, 1, "round", 0.20,
         [("wait_backward", 0.20), ("d2h", 0.25), ("stage", 0.30),
          ("exchange", 0.35), ("h2d", 0.85), ("telemetry", 0.90)], 0.95,
         side="worker", round=4, rank=WORKER, n=4, direct_bytes=375)
    span(WORKER, 2, "GRAD", 0.35,
         [("encode", 0.35), ("send", 0.36), ("ack", 0.38)], 0.60,
         side="client", rank=WORKER, peer=SERVER, round=4, n=4, bytes=500)
    span(WORKER, 3, "PARAM", 0.35, [("send", 0.35), ("recv", 0.37)], 0.80,
         side="client", rank=WORKER, peer=SERVER, round=4, n=4, bytes=500)
    span(SERVER, 1, "GRAD", 0.40,
         [("copy", 0.40), ("dispatch", 0.48), ("ack", 0.50)], 0.55,
         side="server", rank=SERVER, peer=WORKER, n=4, bytes=500)
    span(SERVER, 2, "apply_exec", 0.50, [("queued", 0.50), ("exec", 0.52)],
         0.75, side="server", rank=SERVER, peer=WORKER, n=4, grad_n=4)
    events.sort(key=lambda e: e["ts"])
    obs_path = tmp_path / "obs_trace.json"
    obs_path.write_text(json.dumps({
        "traceEvents": events,
        "otherData": {"ranks": {
            str(r): {"epoch_offset": off, "clock_id": "one-host"}
            for r, off in EPOCH_OFFSET.items()}}}))
    return {
        "obs_trace": str(obs_path),
        "results": {WORKER: {"chipbench": {"marks": {}}},
                    SERVER: {"chipbench": {"marks": {}}}},
        "summary": {"window": [5.0, 5.001], "worker_ranks": [WORKER]},
        "reduction": {"step_module": "jit_loss", "step_module_runs": 1},
    }


def reader(name):
    root = spec_mod.ROOT
    return spec_mod.load_reader(root, spec_mod.load_bench(root), name)


@pytest.mark.parametrize("name", sorted(HAND_WORKED))
def test_reader_gives_the_hand_worked_value(traced_run, name):
    assert reader(name)(traced_run) == pytest.approx(HAND_WORKED[name],
                                                     rel=1e-6)


@pytest.mark.parametrize("name", sorted(HAND_WORKED))
def test_reader_gives_none_on_a_run_without_spans(tmp_path, name):
    """A program that predates the spans (PR 23's parent): a merged
    trace with client op spans and nothing else, no device trace."""
    events = [
        {"ph": "B", "cat": "ps_op", "name": "GRAD", "pid": WORKER, "tid": 1,
         "ts": 105.0004e6, "args": {"side": "client", "peer": SERVER}},
        {"ph": "E", "cat": "ps_op", "name": "GRAD", "pid": WORKER, "tid": 1,
         "ts": 105.0006e6, "args": {"outcome": "ok"}}]
    path = tmp_path / "obs_trace.json"
    path.write_text(json.dumps({"traceEvents": events}))
    run = {"obs_trace": str(path),
           "results": {WORKER: {"chipbench": {"marks": {
               "epoch_offset": 100.0}}}},
           "summary": {"window": [5.0, 5.001], "worker_ranks": [WORKER]},
           "reduction": {"step_module": "jit_loss", "step_module_runs": 4}}
    assert reader(name)(run) is None
    assert reader(name)({**run, "obs_trace": None}) is None


def test_readers_print_the_parts_per_mb_and_the_step_by_scope(traced_run,
                                                              capsys):
    """``bytes``, ``grad_n``, ``queued`` and the scopes other than
    ``head_loss`` are read here: the lines two readers print before
    their result."""
    tree = spantree.load(traced_run)
    assert spantree.per_mb(tree) == pytest.approx({
        "GRAD op": 500.0, "PARAM op": 900.0, "server copy": 160.0,
        "apply_exec": 460.0, "queued ms": 0.02})
    ((client, server, applied), (param, pserver, none)) = sorted(
        spantree.round_ops(tree), key=lambda m: m[0].name)
    assert (client.name, server.side, applied.name) == (
        "GRAD", "server", "apply_exec")
    assert param.name == "PARAM" and pserver is None and none is None
    assert spantree.scope_ms_per_step(traced_run) == pytest.approx({
        "step": 0.9, "head_loss": 0.15, "attn": 0.05})
    reader("server_apply_ms_p50")(traced_run)
    reader("head_loss_ms_per_step")(traced_run)
    out = capsys.readouterr().out
    assert "ms per MB of the spans' own bytes: GRAD op 500.000" in out
    assert "by scope: step 0.900, head_loss 0.150, attn 0.050" in out


def test_one_name_under_two_stacks_is_ambiguous_not_misattributed(tmp_path):
    """Two programs that own an operation of the same HLO text: the
    plane's metadata then holds the name twice, and neither stack is
    taken.  Another plane's metadata does not count at all."""
    def plane(name, stacks):
        body = field(2, name)
        for i, (op, stack) in enumerate(stacks):
            meta = field(1, i + 1) + field(2, op) + field(
                5, field(1, 1) + field(5, stack))
            body += field(4, field(1, i + 1) + field(2, meta))
        body += field(5, field(1, 1) + field(2, field(1, 1)
                                             + field(2, "tf_op")))
        return field(1, body)

    path = tmp_path / "two.xplane.pb"
    path.write_bytes(
        plane("/device:TPU:0", [("%add.1", "jit(loss)/head_loss/add:"),
                                ("%add.1", "jit(shipped_norm)/add:"),
                                ("%mul.2", "jit(loss)/mlp/mul:")])
        + plane("/device:TPU:1", [("%mul.2", "jit(loss)/attn/mul:")]))
    assert spantree.op_scopes(str(path), "/device:TPU:0") == {
        "%add.1": spantree.AMBIGUOUS, "%mul.2": "jit(loss)/mlp/mul:"}
    assert spantree.op_scopes(str(path), "/device:TPU:1") == {
        "%mul.2": "jit(loss)/attn/mul:"}


def test_anchor_maps_monotonic_stamps_to_the_microsecond(traced_run):
    path = spantree.xplane_path(traced_run)
    rows = spantree.anchors(path)
    assert rows == [(4, 200_000.0, 5_000_200_000.0)]
    tree = spantree.load(traced_run)
    (span,) = tree.rounds()
    # the round span's begin is the annotation's begin on the profiler's
    # clock, and every phase boundary lands where the fixture put it
    begin = spantree.to_profiler_ns(rows, tree.mono(span, span.t0))
    assert abs(begin - 200_000.0) < 1_000
    ends = [spantree.to_profiler_ns(rows, tree.mono(span, ts + dur))
            for _p, ts, dur in span.phases]
    want = [250_000, 300_000, 350_000, 850_000, 900_000, 950_000]
    assert all(abs(a - b) < 1_000 for a, b in zip(ends, want))
    # two rounds 3 s apart whose offsets differ by 2 us: the nearest
    # round's offset is used, and the drift is reported
    two = rows + [(5, 3_000_202_000.0, 8_000_200_000.0)]
    assert spantree.to_profiler_ns(two, 7.9) == pytest.approx(
        2_900_002_000.0, abs=1)
    assert spantree.drift_us(two) == pytest.approx(2.0)


# -- apply_exec's end stamp (PR 34) --------------------------------------------


@pytest.mark.parametrize("seen_first", [True, False])
def test_apply_exec_ends_at_the_earlier_of_waiter_and_wait_apply(obs_on,
                                                                  seen_first):
    """The waiter's end stamp waits for the interpreter lock; a role
    thread that waited on the same result says when it saw it ready, and
    the span ends at the earlier of the two, whichever thread writes
    last; ``end_from`` names the stamp that stands."""
    rec = obs_on
    span = rec.op("apply_exec", peer=1, side="server", rank=0)
    span.mark("queued")
    if seen_first:
        # the role thread's stamp is there when the waiter ends the span
        rec.end_when_ready(span, SlowResult(0, 0.2))
        time.sleep(0.05)
        rec.seen_ready(span)
        seen = span.seen_ready
        assert span.t1 is None
        assert rec.drain(timeout=10)
        assert span.args["end_from"] == "wait_apply"
        # never before the span's last mark (``exec``, the waiter's own)
        assert span.t1 == max(seen, span.marks[-1][1])
        assert span.t1 - span.t0 < 0.15
    else:
        # the waiter was on time: a later look changes nothing
        rec.end_when_ready(span, SlowResult(0, 0.01))
        assert rec.drain(timeout=10)
        ended = span.t1
        rec.seen_ready(span)
        assert span.t1 == ended and span.args["end_from"] == "waiter"
    assert [p for p, _t in span.marks] == ["queued", "exec"]


def test_a_pull_that_waits_on_the_apply_stamps_its_end(obs_on):
    """The server thread's ``wait_apply`` ends when the apply is ready:
    that instant is also ``apply_exec``'s end (``seen_ready``), so the
    span cannot outlast the ``snapshot`` mark that follows the wait,
    unless its own last mark does: ``exec`` is the waiter thread's, which
    stamps it when it gets the interpreter lock, under load after the
    pull has gone on, and a span never ends before its last mark (it
    then ends at that mark, an ``exec`` phase of no length)."""
    rec = obs_on
    with gang(1, 1) as ((_server,), (pc,)):
        pc.start(np.zeros(SIZE, np.float32), np.zeros(SIZE, np.float32))
        for _ in range(3):
            pc.grad[:] = 1.0
            pc.async_send_grad()
            pc.async_recv_param()
            pc.wait()
        assert rec.drain(timeout=10)
        pc.stop()
    execs = [s for s in rec.spans if s.name == "apply_exec"]
    pulls = [s for s in rec.spans if s.name == "PARAM"
             and s.args["side"] == "server"]
    assert len(execs) == len(pulls) == 3
    for exec_span, pull in zip(execs, pulls):
        assert exec_span.args["end_from"] in ("waiter", "wait_apply")
        waited = dict(pull.marks)
        assert exec_span.seen_ready is not None
        assert waited["wait_apply"] <= exec_span.seen_ready <= (
            waited["snapshot"])
        began = dict(exec_span.marks)["exec"]
        if began <= waited["snapshot"]:
            assert exec_span.t1 <= waited["snapshot"]
        else:  # the waiter came late: the span ends where ``exec`` began
            assert exec_span.t1 == began
            assert exec_span.args["end_from"] == "wait_apply"


# -- the wire meter (PR 34) ----------------------------------------------------


def test_wire_meter_notes_what_an_endpoint_did_between_two_looks(obs_on):
    import os

    from mpit_tpu.aio import EXEC, Scheduler
    from mpit_tpu.comm.shm import ShmTransport

    ns = f"t_meter_{os.getpid()}"
    a, b = (ShmTransport(ns, r, 2, ring_bytes=1 << 20) for r in (0, 1))
    try:
        sched = Scheduler(idle_usec=1000)
        meter = obs_on.wire_meter(a, sched)
        data = np.arange(1 << 20, dtype=np.float32)
        out = np.zeros_like(data)
        recv, send = b.irecv(0, 4, out=out), a.isend(data, 1, 4)

        def until_sent():
            while not all([a.test(send), b.test(recv)]):
                yield EXEC

        def other():  # keeps the queue busy, so a pass backs off
            while not send.done:
                yield EXEC

        sched.spawn(other())
        task = sched.spawn(until_sent())
        meter.start()
        sched.wait_for(task)
        span = obs_on.op("GRAD", peer=1, side="client", rank=0)
        meter.note(span)
        got = {k: v for k, v in span.args.items()
               if k.startswith(("wire_", "sched_"))}
        # a second note covers only what came after the first: taken at
        # once, before the endpoints close (a close joins the helper
        # threads and unmaps the rings, which under load outlasts the
        # 4 MB send the first note covers)
        meter.note(span)
        again = dict(span.args)
        span.end()
    finally:
        a.close()
        b.close()
    assert sorted(got) == ["sched_sleep_ms", "wire_poll_ms",
                           "wire_rx_copy_ms", "wire_span_ms",
                           "wire_tx_copy_ms"]
    assert got["wire_tx_copy_ms"] > 0 and got["wire_rx_copy_ms"] == 0
    assert got["wire_poll_ms"] >= 0
    assert got["sched_sleep_ms"] == pytest.approx(sched.sleep_s * 1e3)
    assert got["wire_tx_copy_ms"] + got["wire_poll_ms"] + (
        got["sched_sleep_ms"]) <= got["wire_span_ms"]
    assert again["wire_tx_copy_ms"] == 0
    assert again["wire_span_ms"] < got["wire_span_ms"]


def test_wire_meter_on_a_wire_without_totals_and_with_obs_off():
    from mpit_tpu.aio import Scheduler
    from mpit_tpu.obs import spans as obs_spans

    assert obs_spans.NULL_RECORDER.wire_meter(None, None) is (
        obs_spans.NULL_METER)
    obs_spans.NULL_METER.start()
    obs_spans.NULL_METER.note(obs_spans.NULL_SPAN)
    obs.configure(enabled=True, reset=True)
    try:
        rec = obs.get_recorder()
        meter = rec.wire_meter(LocalRouter(2).endpoint(0), Scheduler())
        span = rec.op("GRAD", peer=1, side="server", rank=0)
        meter.note(span)
        assert span.args["sched_sleep_ms"] == 0.0
        assert "wire_poll_ms" not in span.args
        span.end()
    finally:
        obs.configure(enabled=None, reset=True)


# -- a two-server gang over shm: the wire's spans and their readers (PR 34) ----

WIRE_METRICS = ("wire_copy_ms_p50", "push_blocked_ms_p50",
                "server_away_ms_p50", "pull_blocked_ms_p50",
                "client_away_ms_p50")
GANG_STEPS = 6


@pytest.fixture(scope="module")
def wire_gang_run(tmp_path_factory):
    """One process gang on the CPU, servers 0 and 2 and worker 1 over
    the shm wire, a 9.2 MB vector (4.99 and 4.23 MB shards), with
    ``MPIT_OBS_TRACE``: the ``run`` a reader is given, windowed from the
    second round on."""
    import os

    from mpit_tpu.train.launch import LAUNCH_DEFAULTS, launch_processes

    tmp = tmp_path_factory.mktemp("wire_gang")
    path = str(tmp / "obs_trace.json")
    # The ranks' compiled programs go to a cache of the gang's own: what
    # an LM gang leaves in the checkout's cache aborts jax's dispatch in
    # tests/test_mesh_launch.py (.claude/skills/verify, Gotchas).
    before = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(tmp / "jax_cache")
    os.environ["MPIT_OBS_TRACE"] = path
    try:
        cfg = LAUNCH_DEFAULTS.merged(
            np=3, lm=1, lm_d_model=128, lm_heads=4, lm_layers=1, lm_seq=64,
            lm_vocab=8192, batch=2, lm_steps=GANG_STEPS,
            lm_eval_every=GANG_STEPS, opt="rmsprop", lr=1e-3,
            device_policy="cpu")
        results = launch_processes(cfg, timeout=600)
    finally:
        del os.environ["MPIT_OBS_TRACE"]
        if before is None:
            del os.environ["JAX_COMPILATION_CACHE_DIR"]
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = before
    assert set(results) == {0, 1, 2}
    run = {"obs_trace": path,
           "results": {r: {"chipbench": {"marks": {}}} for r in results},
           "summary": {"window": [0.0, float("inf")], "worker_ranks": [1]},
           "reduction": {}}
    tree = spantree.load(dict(run))
    second = sorted(tree.rounds(), key=lambda r: r.t0)[1]
    run["summary"]["window"][0] = tree.mono(second, second.t0) - 1e-6
    return run


def test_gang_every_windowed_round_has_its_eight_wire_spans(wire_gang_run):
    from chipbench.layers import wiretree

    obs_trace.validate_trace(wire_gang_run["obs_trace"])
    wire = wiretree.load(wire_gang_run)
    tree = wire.tree
    assert len(wire.rounds) == GANG_STEPS - 1
    assert wire.unmatched == 0
    assert wiretree.tile_error_pct(wire) < 1.0
    assert len(wire.messages) == 4 * len(wire.rounds)  # 8 ends a round
    halves = {(c.name, c.args["round"], c.args["peer"]): (c, s)
              for c, s, _a in spantree.round_ops(tree)}
    eps = 1e-4  # s: the op's own marks are Python's, the wire's native
    for op, k, tx, rx in wire.messages:
        server = rx.pid if op == "GRAD" else tx.pid
        client_op, server_op = halves[(op, k, server)]
        mine, theirs = (tx, rx) if op == "GRAD" else (rx, tx)
        assert mine.pid == 1 and theirs.pid == server
        # the client's end lies inside the client's op span
        assert tree.mono(client_op, client_op.t0) - eps <= (
            tree.mono(mine, mine.t0))
        assert tree.mono(mine, mine.t1) <= (
            tree.mono(client_op, client_op.t1) + eps)
        if op == "GRAD":
            # the server has the frame before its GRAD span opens
            assert tree.mono(theirs, theirs.t1) <= (
                tree.mono(server_op, server_op.t0) + eps)
        else:
            # the server's copy into its ring is its ``send`` phase
            send = next(ts for name, ts, _d in server_op.phases
                        if name == "send")
            assert tree.mono(server_op, send) - eps <= (
                tree.mono(theirs, theirs.t0))
            assert tree.mono(theirs, theirs.t1) <= (
                tree.mono(server_op, server_op.t1) + eps)
    by_round = {}
    for _op, k, _tx, _rx in wire.messages:
        by_round[k] = by_round.get(k, 0) + 2
    assert set(by_round.values()) == {8}


def test_gang_round_args_tile_the_exchange(wire_gang_run):
    from chipbench.layers import wiretree

    wire = wiretree.load(wire_gang_run)
    for r in wire.rounds:
        exchange = spantree.phase_ms(r, "exchange")
        parts = [r.args[key] for key in wiretree.ROUND_ARGS]
        assert all(p >= 0 for p in parts)
        assert r.args["wire_tx_copy_ms"] > 0 and r.args["wire_rx_copy_ms"] > 0
        assert sum(parts) <= exchange
        assert "wire_span_ms" not in r.args  # the phase is the stretch
    parts = wiretree.exchange_parts(wire)
    assert parts["interpreter_ms"] >= 0
    # the servers note the same deltas on their op spans
    noted = [s for s in wire.tree.spans if s.side == "server"
             and s.name in ("GRAD", "PARAM") and wire.tree.in_window(s)]
    assert len(noted) == 4 * len(wire.rounds)
    for s in noted:
        assert sum(s.args[key] for key in wiretree.ROUND_ARGS) <= (
            s.args["wire_span_ms"])


@pytest.mark.parametrize("name", WIRE_METRICS)
def test_gang_wire_reader_returns_a_number(wire_gang_run, name, capsys):
    value = reader(name)(wire_gang_run)
    assert isinstance(value, float) and value >= 0
    printed = capsys.readouterr().out
    if name == "wire_copy_ms_p50":
        assert value > 0
        lines = [ln for ln in printed.splitlines()
                 if ln.startswith("chipbench: wire: ")]
        # two ops x two servers x two ends, two servers x two ops, the
        # client's exchange, and the check of the tracing itself
        assert len(lines) == 8 + 4 + 1 + 1
        assert "client exchange" in lines[-2] and "interpreter" in lines[-2]
        assert "0 wire spans of the window without their other end" in (
            lines[-1])
    else:
        assert printed == ""


def test_gang_cores_stamps_pass_their_own_check(wire_gang_run, capsys):
    """PR 67 on the program's own trace: every windowed round carries the
    worker's ``cpu_ms``, the servers' metered stretches tile their time
    and each ``exec`` lies in two of them, every rank's part says whose
    threads it had, and the table's last line is the check."""
    from chipbench.layers import coretree

    run = dict(wire_gang_run)
    cores = coretree.load(run)
    assert len(cores.rounds) == GANG_STEPS - 1
    assert coretree.check_faults(cores) == []
    assert sorted(cores.census) == [0, 1, 2]
    for census in cores.census.values():
        assert census["threads"] >= 1 and census["by_name"]
    rows = coretree.round_rows(cores)
    for row in rows:
        assert row["worker_ms"] >= 0.0
        # a push's ack comes in the middle of the exchange; a reply is
        # noted as the worker has it, a moment before or after
        ended = sorted((st.pid, st.op) for st in row["stretches"])
        assert {(0, "GRAD"), (2, "GRAD")} <= set(ended) <= {
            (0, "GRAD"), (0, "PARAM"), (2, "GRAD"), (2, "PARAM")}
        assert len(ended) == len(set(ended))
    assert sum(len(r["stretches"]) for r in rows) >= 4 * len(rows) - 1
    applied = [a for a in coretree.applies(cores) if a["round"] is not None]
    assert len(applied) == 2 * len(cores.rounds)
    assert all(a["cpu_ms"] is not None and a["end_from"] in (
        "waiter", "wait_apply") for a in applied)
    assert all((a["waiter_late_ms"] is not None)
               == (a["end_from"] == "wait_apply") for a in applied)
    assert coretree.print_table(cores)
    lines = capsys.readouterr().out.splitlines()
    assert all(ln.startswith("chipbench: cores: ") for ln in lines)
    assert lines[-1].startswith("chipbench: cores: check passes")


@pytest.mark.parametrize("name", ["apply_cores_p50", "exchange_cores_p50",
                                  "crew_spin_pct"])
def test_gang_cores_reader_returns_a_number(wire_gang_run, name):
    from chipbench.layers import coretree

    value = reader(name)(dict(wire_gang_run))
    crew = coretree.crew_rows(coretree.load(dict(wire_gang_run)))
    if name == "crew_spin_pct" and not any(c > 0 for c, _s in crew.values()):
        assert value is None  # this host leaves a gang of three no helper
    else:
        assert isinstance(value, float) and value >= 0


@pytest.mark.parametrize("name", WIRE_METRICS)
def test_wire_reader_gives_none_where_no_transport_ran(traced_run, name):
    """A local cell has no transport and the parent of PR 34 no wire
    span: a merged trace without one, and no merged trace at all."""
    assert reader(name)(dict(traced_run)) is None
    assert reader(name)({**traced_run, "obs_trace": None}) is None


# -- the push that follows the staging (PR 40): ``push_early_pct`` ------------


def test_gang_push_early_reader_gives_the_early_share(wire_gang_run, capsys):
    """The gang's GRADs are the slices themselves over shm, so their
    sends follow the staging: every windowed round's ``tx`` spans carry
    ``early_bytes`` within their ``bytes`` and ``unready_ms`` within
    ``away_ms``, and the reader gives the median round's share."""
    from chipbench.layers import wiretree

    wire = wiretree.load(dict(wire_gang_run))
    pushes = [tx for op, _k, tx, _rx in wire.messages if op == "GRAD"]
    assert len(pushes) == 2 * len(wire.rounds)
    for tx in pushes:
        assert 0 <= tx.args["early_bytes"] <= tx.args["bytes"]
        assert 0.0 <= tx.args["unready_ms"] <= tx.args["away_ms"] + 1e-9
    value = reader("push_early_pct")(wire_gang_run)
    by_round = {}
    for op, k, tx, _rx in wire.messages:
        if op == "GRAD":
            early, total = by_round.get(k, (0, 0))
            by_round[k] = (early + tx.args["early_bytes"],
                           total + tx.args["bytes"])
    import statistics

    assert value == pytest.approx(statistics.median(
        100.0 * early / total for early, total in by_round.values()))
    assert 0.0 <= value <= 100.0
    assert capsys.readouterr().out == ""
    # the servers' sends have no mark, and say so
    for op, _k, tx, _rx in wire.messages:
        if op == "PARAM":
            assert tx.args["early_bytes"] == 0
            assert tx.args["unready_ms"] == 0.0


def test_push_early_reader_gives_none_without_the_spans(traced_run,
                                                        wire_gang_run,
                                                        tmp_path):
    """No transport, no merged trace, and a program from before PR 40,
    whose ``tx`` spans say nothing of ``early_bytes``: None each time,
    and nothing raised."""
    import json

    read = reader("push_early_pct")
    assert read(dict(traced_run)) is None
    assert read({**traced_run, "obs_trace": None}) is None
    with open(wire_gang_run["obs_trace"]) as fh:
        trace = json.load(fh)
    for event in trace["traceEvents"]:
        for key in ("early_bytes", "unready_ms"):
            event.get("args", {}).pop(key, None)
    older = tmp_path / "older.json"
    older.write_text(json.dumps(trace))
    run = {k: v for k, v in wire_gang_run.items() if not k.startswith("_")}
    assert read({**run, "obs_trace": str(older)}) is None


# -- the push that reads the pieces (PR 45): ``push_direct_pct`` ---------------


def test_gang_push_direct_reader_says_the_whole_push_read_the_pieces(
        wire_gang_run, capsys):
    """The gang's GRADs are the slices themselves over shm, so both
    shards' sends read the pieces where they landed: every windowed
    round's ``direct_bytes`` is the sum of its two GRADs' ``bytes``, the
    stream's thread copied nothing, and the reader says 100."""
    tree = spantree.load(dict(wire_gang_run))
    rounds = tree.rounds()
    assert len(rounds) >= 3
    for r in rounds:
        pushed = sum(s.args["bytes"] for s in tree.named("GRAD", "client")
                     if s.args.get("round") == r.args["round"])
        assert r.args["direct_bytes"] == pushed > 0
        assert not any(key.startswith("stage_") for key in r.args)
    assert reader("push_direct_pct")(wire_gang_run) == 100.0
    assert capsys.readouterr().out == ""


def test_push_direct_reader_gives_none_for_a_program_without_the_counter(
        traced_run, wire_gang_run, tmp_path):
    """No merged trace, and a program from before PR 45, whose ``round``
    spans say nothing of ``direct_bytes``: None each time, nothing
    raised; a round whose push went by the mirror reads 0."""
    import json

    read = reader("push_direct_pct")
    assert read({**traced_run, "obs_trace": None}) is None
    with open(wire_gang_run["obs_trace"]) as fh:
        trace = json.load(fh)
    for event in trace["traceEvents"]:
        event.get("args", {}).pop("direct_bytes", None)
    older = tmp_path / "older.json"
    older.write_text(json.dumps(trace))
    run = {k: v for k, v in wire_gang_run.items() if not k.startswith("_")}
    assert read({**run, "obs_trace": str(older)}) is None
    for event in trace["traceEvents"]:
        if event.get("name") == "round" and event.get("ph") in ("B", "E"):
            event.setdefault("args", {})["direct_bytes"] = 0
    mirror = tmp_path / "mirror.json"
    mirror.write_text(json.dumps(trace))
    assert read({**run, "obs_trace": str(mirror)}) == 0.0


# -- the round's host copies as spans of their own (PR 48) ---------------------
#
# The hand-worked round above with its passes over the host's memory
# (monotonic ms after 5 s; the profiler's clock is 5 s behind).  Two
# pieces of 250 bytes go down: the first's span is 0.25-0.30 (issue from
# 0.25, wait 0.26, hand 0.29, issue 0.295, held 0.296, issue 0.297; its
# cut was dispatched at 0.255, two in flight), the second's 0.30-0.34
# (issue 0.30, wait 0.305, hand 0.33, issue 0.335, held 0.336, issue
# 0.338; cut at 0.258, one in flight).  The DMA's passes are therefore
# 0.255-0.29 and 0.29-0.33 (the engine takes the second when the first
# has landed): 0.075 ms for 500 bytes.  The thread: wait 0.055, hand
# 0.010, held 0.003, issue 0.022 ms, so issue is 0.022 of 0.087 not held.
# The GRAD's ``tx`` copies 300 bytes 0.36-0.40 and 200 bytes 0.42-0.45,
# the server's ``rx`` all 500 0.38-0.47; the sweep (``exec`` 0.52-0.75)
# moves 7 x 500; the PARAM's ``tx`` copies 0.76-0.79 and the worker's
# ``rx`` 0.77-0.80; the upload's two pieces are 0.85-0.87 and 0.87-0.88,
# closed at 0.90.  Memory traffic: 500 + 2000 + 2000 + 3500 + 500 = 8500
# bytes, 17 a byte of the vector, over a union of 0.075 + 0.11 + 0.23 +
# 0.04 + 0.03 = 0.485 ms.  One copier at work: 0.415 ms and 500 + 300 +
# 222.2 + 222.2 + 3500 + 333.3 + 333.3 + 500 = 5911.1 bytes; two: 0.02 +
# 0.03 + 0.02 = 0.07 ms and 522.2 + 733.3 + 1333.3 = 2588.9 bytes; never
# three.  All of it lies in the device's middle idle gap (200-900 us of
# 790 us idle): 485 us under a host pass, 61.39%.

COPIED = {
    "host_passes_per_byte": 17.0,
    "host_copy_gbps_p50": 8500 / 0.485e-3 / 1e9,
    "copy_gbps_at_1": (5911 + 1 / 9) / 0.415e-3 / 1e9,
    "copy_gbps_at_2": (2588 + 8 / 9) / 0.07e-3 / 1e9,
    "copy_gbps_at_3plus": None,
    "stage_dma_gbps_p50": 500 / 0.075e-3 / 1e9,
    "stage_issue_share_pct": 100 * 0.022 / 0.087,
    "exchange_sleep_apply_ms_p50": 0.2,
    "exchange_sleep_staging_ms_p50": 0.04,
    "idle_by_host_pass_pct": 100 * 485 / 790,
}
PS_CELLS = ["c111m-ps1w-su1", "c1.3b-ps1w-su8", "olmoe-l1-ps1w-su1"]


@pytest.fixture
def copied_run(traced_run):
    """``traced_run`` with the round's host copies in its merged trace."""
    with open(traced_run["obs_trace"]) as fh:
        trace = json.load(fh)
    events = trace["traceEvents"]
    us = lambda pid, ms: (5.0 + ms / 1e3 + EPOCH_OFFSET[pid]) * 1e6
    ns = lambda ms: round((5.0 + ms / 1e3) * 1e9)

    def span(cat, pid, tid, name, begin, phases, end, **args):
        events.append({"ph": "B", "cat": cat, "name": name, "pid": pid,
                       "tid": tid, "ts": us(pid, begin), "args": args})
        marks = phases + [("", end)]
        for (phase, at), (_next, until) in zip(marks, marks[1:]):
            events.append({"ph": "X", "cat": "ps_phase", "pid": pid,
                           "tid": tid, "name": f"{name}.{phase}",
                           "ts": us(pid, at),
                           "dur": us(pid, until) - us(pid, at)})
        events.append({"ph": "E", "cat": cat, "name": name, "pid": pid,
                       "tid": tid, "ts": us(pid, end),
                       "args": {"outcome": "ok"}})

    piece = dict(round=4, shard=0, rank=WORKER, thread="stream", streams=1,
                 bytes=250)
    span("copy", WORKER, 10, "d2h", 0.25,
         [("issue", 0.25), ("wait", 0.26), ("hand", 0.29), ("issue", 0.295),
          ("held", 0.296), ("issue", 0.297)], 0.30,
         lo=0, in_flight=2, issued_ms=0.005, **piece, **{"pass": "d2h"})
    span("copy", WORKER, 10, "d2h", 0.30,
         [("issue", 0.30), ("wait", 0.305), ("hand", 0.33), ("issue", 0.335),
          ("held", 0.336), ("issue", 0.338)], 0.34,
         lo=62, in_flight=1, issued_ms=0.047, **piece, **{"pass": "d2h"})
    span("copy", WORKER, 10, "h2d", 0.85, [], 0.87, lo=0, **piece,
         **{"pass": "h2d"})
    span("copy", WORKER, 10, "h2d", 0.87, [], 0.88, lo=62, **piece,
         **{"pass": "h2d"})
    span("copy", WORKER, 11, "h2d_shard", 0.85, [], 0.90, round=4, shard=0,
         rank=WORKER, thread="stream", bytes=500, pieces=2, ready=1)

    def wire(pid, tid, name, begin, end, peer, tag, msg_id, copies):
        span("wire", pid, tid, name, begin, [], end, rank=pid, peer=peer,
             tag=tag, msg_id=msg_id, bytes=500, copies_merged=0,
             copies=[[ns(a), ns(b), n] for a, b, n in copies],
             **({"round": 4} if pid == WORKER else {}))

    wire(WORKER, 20, "tx", 0.36, 0.45, SERVER, 2, 1,
         [(0.36, 0.40, 300), (0.42, 0.45, 200)])
    wire(SERVER, 20, "rx", 0.38, 0.47, WORKER, 2, 1, [(0.38, 0.47, 500)])
    wire(SERVER, 21, "tx", 0.76, 0.79, WORKER, 5, 1, [(0.76, 0.79, 500)])
    wire(WORKER, 21, "rx", 0.77, 0.80, SERVER, 5, 1, [(0.77, 0.80, 500)])
    for event in events:
        if event["ph"] == "B" and event["name"] == "apply_exec":
            event["args"]["bytes_moved"] = 3500
        if event["ph"] == "B" and event["name"] == "round":
            event["args"].update(
                sleep_staging_ms=0.04, sleep_apply_ms=0.2,
                sleep_drain_ms=0.01, sleep_pull_ms=0.02, sched_sleep_ms=0.27)
    events.sort(key=lambda e: e["ts"])
    with open(traced_run["obs_trace"], "w") as fh:
        json.dump(trace, fh)
    obs_trace.validate_trace(traced_run["obs_trace"])
    return traced_run


@pytest.mark.parametrize("name", sorted(COPIED))
def test_copy_reader_gives_the_hand_worked_value(copied_run, name):
    value = reader(name)(copied_run)
    if COPIED[name] is None:
        assert value is None
    else:
        assert value == pytest.approx(COPIED[name], rel=1e-6)


@pytest.mark.parametrize("name", sorted(COPIED))
def test_copy_reader_gives_none_for_a_program_without_the_spans(
        traced_run, wire_gang_run, tmp_path, name):
    """A run without a transport or a merged trace, and the parent of
    PR 48: the gang's own trace with every ``copy`` span taken out."""
    read = reader(name)
    assert read(dict(traced_run)) is None
    assert read({**traced_run, "obs_trace": None}) is None
    with open(wire_gang_run["obs_trace"]) as fh:
        trace = json.load(fh)
    trace["traceEvents"] = [e for e in trace["traceEvents"]
                            if e.get("cat") != "copy"]
    older = tmp_path / "older.json"
    older.write_text(json.dumps(trace))
    run = {k: v for k, v in wire_gang_run.items() if not k.startswith("_")}
    assert read({**run, "obs_trace": str(older)}) is None


def test_copy_readers_print_their_tables(copied_run, capsys):
    for name in sorted(COPIED):
        reader(name)(copied_run)
    out = [ln[len("chipbench: copies: "):] for ln in
           capsys.readouterr().out.splitlines()
           if ln.startswith("chipbench: copies: ")]
    assert out[0].startswith("1 at work: 0.0004 s") and "apply 55%" in out[0]
    assert out[1].startswith("2 at work: 0.0001 s")
    assert "ring_in+ring_out 100%" in out[1]
    assert out[2] == "3plus at work: 0.0000 s, 0.000 GB, no GB/s"
    # what one copier of a kind got alone and in company: the DMA 500
    # bytes in 0.075 ms, the rings' senders 633.3 bytes in 0.03 ms alone
    # and 1366.7 in 0.07 ms beside a receiver
    assert out[3] == ("a d2h pass's own GB/s of traffic with 1, 2, 3plus "
                      "at work: 0.01, -, -")
    assert out[4].endswith("at work: 0.02, 0.02, -") and "ring_in" in out[4]
    assert out[8] == (
        "1 rounds; memory traffic a byte of the vector and round, by pass "
        "(median): d2h 1.00, ring_in 4.00, ring_out 4.00, apply 7.00, "
        "h2d 1.00")
    assert out[9] == ("4 wire spans hold 5 copy intervals, 0 more merged "
                      "over a gap; 10 passes in all, 5 copy spans")
    assert any(ln.startswith("client asleep in exchange") and
               "staging 0.04, apply 0.20, drain 0.01, pull 0.02" in ln
               for ln in out)
    assert any("idle while apply: 0.0002 s (29.1%)" in ln for ln in out)
    assert any(ln.startswith("idle 0.0008 s: 61.39% under a host pass, "
                             "20.89% under none but a leaf") for ln in out)
    assert any(ln.startswith("stream, the round of the median DMA rate: "
                             "2 pieces") and "{1: 1, 2: 1}" in ln
               for ln in out)
    assert any(ln.startswith("upload of shard 0: 2 pieces") and
               "1 of them to the parameters whole" in ln for ln in out)


def test_every_new_entry_names_the_three_ps_cells_and_a_known_layer():
    bench = spec_mod.load_bench(spec_mod.ROOT)
    entries = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in COPIED}
    for name in COPIED:
        entry = entries[name]
        assert entry["workloads"] == PS_CELLS
        assert entry["moves"] == "tokens_per_s" and entry["layer"] in layers
        assert reader(name) is not None
    # added together, in this order, behind everything the file had
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("host_passes_per_byte")
    assert at >= 57 and names[at:at + len(COPIED)] == [
        "host_passes_per_byte", "host_copy_gbps_p50", "copy_gbps_at_1",
        "copy_gbps_at_2", "copy_gbps_at_3plus", "stage_dma_gbps_p50",
        "stage_issue_share_pct", "exchange_sleep_apply_ms_p50",
        "exchange_sleep_staging_ms_p50", "idle_by_host_pass_pct"]


# (a) the stream thread's piece spans against the sums they replace


def test_piece_spans_tile_the_staging_and_sum_to_what_the_round_args_read(
        obs_on, monkeypatch):
    """A round's ``d2h`` spans tile the stream thread's time from before
    its first cut until the payload is whole, their bytes are the
    payload's, and their phases sum to what the four ``stage_*_ms``
    args of the ``round`` span read before they went: the parent's sums
    are computed here from the thread's own clock reads, which come in a
    fixed order (one before the first cuts, one a cut, and a piece its
    pop, landing, hand-over, free, room and end with its cut between
    the last two)."""
    from mpit_tpu.optim import sync

    monkeypatch.setattr(sync, "PIECE_BYTES", 10 * 4)
    reads = []
    real = time.monotonic

    def logged():
        t = real()
        if threading.current_thread().name == "mpit-round-stream":
            reads.append(t)
        return t

    with gang(2, 1) as (_servers, (pc,)):
        opt = RuleShell(quad, pc, su=1)
        w = opt.start(jnp.zeros(SIZE))
        w, _loss = opt.step(w, TARGET)  # compiles the cuts and pastes
        monkeypatch.setattr(time, "monotonic", logged)
        w, _loss = opt.step(w, TARGET)
        monkeypatch.undo()
        whole = opt._stream.pieces
        opt.stop()
    pieces = [s for s in obs_on.spans if s.name == "d2h"
              and s.args["round"] == 1]
    n, first = len(whole), min(sync.IN_FLIGHT, len(whole))
    assert len(pieces) == n == 8
    # the spans tile: each begins where the one before ended
    assert pieces[0].t0 == reads[0]
    assert all(a.t1 == b.t0 for a, b in zip(pieces, pieces[1:]))
    for span in pieces:
        assert span.marks[0] == ("issue", span.t0)
        assert [p for p, _t in span.marks] == [
            "issue", "wait", "hand", "issue", "held", "issue"]
        stamps = [t for _p, t in span.marks] + [span.t1]
        assert stamps == sorted(stamps)
        assert span.args["pass"] == "d2h" and span.args["thread"] == "stream"
        assert span.args["streams"] == 3  # the local wire: by the mirror
    assert sum(s.args["bytes"] for s in pieces) == SIZE * 4
    assert [s.args["in_flight"] for s in pieces] == (
        [first] * (n - first + 1) + list(range(first - 1, 0, -1)))
    assert [(s.args["shard"], s.args["lo"]) for s in pieces] == [
        (shard, lo) for shard, lo, _hi in whole]
    # the parent's four sums, from the same thread's clock reads
    at = 1 + first
    want = dict.fromkeys(("wait", "copy", "held", "issue"), 0.0)
    lead = 0.0
    for i, span in enumerate(pieces):
        cut = 1 if first + i < n else 0  # a cut's stamp, while any is left
        pop, host, staged, freed, room = reads[at:at + 5]
        end = reads[at + 5 + cut]
        at += 6 + cut
        want["wait"] += host - pop
        want["copy"] += staged - host
        want["held"] += room - freed
        want["issue"] += freed - staged + end - room
        lead += pop - span.t0
        assert span.t1 == end
        assert span.args["issued_ms"] == pytest.approx(
            (pop - reads[1 + i]) * 1e3 if i < first
            else (pop - reads[(1 + first) + 7 * (i - first) + 5]) * 1e3)
    got = dict.fromkeys(sync.STAGE_PHASES, 0.0)
    for span in pieces:
        ends = [t for _p, t in span.marks[1:]] + [span.t1]
        for (phase, t), end in zip(span.marks, ends):
            got[phase] += end - t
    assert got["wait"] == pytest.approx(want["wait"], abs=1e-9)
    assert got["hand"] == pytest.approx(want["copy"], abs=1e-9)
    assert got["held"] == pytest.approx(want["held"], abs=1e-9)
    # ``issue`` also holds what the sums left out: the first cuts and
    # the pop of each piece
    assert got["issue"] == pytest.approx(want["issue"] + lead, abs=1e-9)
    # the uploads: a span a piece, a closing span a shard, the last to
    # the fence the shell takes while recording
    ups = [s for s in obs_on.spans if s.name == "h2d"
           and s.args["round"] == 1]
    assert sum(s.args["bytes"] for s in ups) == SIZE * 4 and len(ups) == n
    closing = [s for s in obs_on.spans if s.name == "h2d_shard"
               and s.args["round"] == 1]
    assert [s.args["ready"] for s in closing] == [0, 1]
    assert [s.args["pieces"] for s in closing] == [4, 4]
    (r,) = [s for s in obs_on.spans if s.name == "round"
            and s.args["round"] == 1]
    assert closing[1].t1 == dict(r.marks)["telemetry"]
    assert "pass" not in closing[0].args
    obs_trace.validate_trace({"traceEvents": obs_trace.chrome_events(
        obs_on, pid=0)})


# (b), (c), (d) on the gang over shm: passes a byte, intervals, sleeps


def test_gang_copy_bytes_by_pass_are_the_shard_bytes_times_the_passes(
        wire_gang_run):
    """Identity codec, rmsprop (three state slots: nine streams): a
    round's ``bytes`` by pass over the vector's bytes are 1 down, 2 into
    a ring, 2 out of one, the rule's streams, 1 up, so a byte moves
    1 + 4 + 4 + 9 + 1 times over the host's memory."""
    from chipbench.layers import copytree
    from mpit_tpu.optim import rules

    copies = copytree.load(dict(wire_gang_run))
    assert len(copies.rounds) == GANG_STEPS - 1
    streams = 3 + 2 * rules.state_slots("rmsprop")
    clean = 0
    for r, mine, own in copies.rounds:
        pushed = sum(s.args["bytes"] for s in copies.tree.named(
            "GRAD", "client") if s.args.get("round") == r.args["round"])
        vector = copies.prog.vector_bytes(1, mine)
        assert vector == pushed == sum(
            s.args["bytes"] for s in own if s.name == "d2h")
        by_pass = {kind: sum(c.bytes for c in mine if c.kind == kind)
                   for kind in copies.prog.PASSES}
        swept = by_pass.pop("apply")
        assert by_pass == {"d2h": vector, "ring_in": 2 * vector,
                           "ring_out": 2 * vector, "h2d": vector}
        # (on a loaded host the recorder's waiter may stamp a sweep's
        # ``exec`` after its round has ended, ROADMAP M13: that round
        # then reads a shard's sweep short and a later one long)
        if swept == streams * vector:
            clean += 1
            assert {c.rank for c in mine} == {0, 1, 2}
            assert sum(c.moved for c in mine) == (2 + 8 + streams) * vector
    assert clean >= 1
    # every sweep of the run is there once, whichever round it fell in
    sweeps = [c for c in copies.passes if c.kind == "apply"]
    assert len(sweeps) == 2 * GANG_STEPS
    assert sum(c.bytes for c in sweeps) == streams * vector * GANG_STEPS
    if clean > len(copies.rounds) // 2:
        assert reader("host_passes_per_byte")(wire_gang_run) == 10 + streams
    assert reader("host_copy_gbps_p50")(wire_gang_run) > 0
    table = copies.table()
    assert sum(row["bytes"] for row in table.values()) == pytest.approx(
        sum(c.moved for _r, mine, _o in copies.rounds for c in mine
            if c.t1 > c.t0))
    assert reader("copy_gbps_at_1")(wire_gang_run) > 0
    assert reader("stage_dma_gbps_p50")(wire_gang_run) > 0
    assert 0.0 <= reader("stage_issue_share_pct")(wire_gang_run) <= 100.0


def test_gang_wire_copy_intervals_lie_in_their_span_apart_and_bounded(
        wire_gang_run):
    from chipbench.layers import wiretree

    wire = wiretree.load(dict(wire_gang_run))
    assert wire.messages
    for _op, _k, tx, rx in wire.messages:
        for span in (tx, rx):
            runs = span.args["copies"]
            assert 1 <= len(runs) <= 64 and span.args["copies_merged"] == 0
            lo, hi = (wire.tree.mono(span, t) * 1e9
                      for t in (span.t0, span.t1))
            assert lo - 1e3 <= runs[0][0] and runs[-1][1] <= hi + 1e3
            assert all(b <= e for b, e, _n in runs)
            assert all(a[1] <= b[0] for a, b in zip(runs, runs[1:]))
            assert sum(n for _b, _e, n in runs) == span.args["bytes"]
            # what lies between the chunks of a run is not copying
            # (a thread taken off its core between two chunks of a run
            # stretches the run and not ``copy_ms``: no upper bound but
            # the span's own length holds on a loaded host)
            copying = sum(e - b for b, e, _n in runs) / 1e6
            assert span.args["copy_ms"] <= copying + 1e-6
            assert copying <= (hi - lo) / 1e6 + 1e-3
            assert not {"chunks", "refused", "overlap_chunks",
                        "direct"} & set(span.args)


def test_gang_named_sleeps_sum_to_the_schedulers_sleep(wire_gang_run,
                                                       capsys):
    from chipbench.layers import copytree
    from mpit_tpu.ps.client import SLEEP_REASONS

    copies = copytree.load(dict(wire_gang_run))
    for r, _mine, _own in copies.rounds:
        named = [r.args[f"sleep_{reason}_ms"] for reason in SLEEP_REASONS]
        assert all(ms >= 0.0 for ms in named)
        assert sum(named) == pytest.approx(r.args["sched_sleep_ms"],
                                           abs=1e-6)
    assert SLEEP_REASONS == copytree.SLEEPS
    for name in ("exchange_sleep_apply_ms_p50",
                 "exchange_sleep_staging_ms_p50"):
        assert reader(name)(wire_gang_run) >= 0.0
    assert "client asleep in exchange" in capsys.readouterr().out
    # the servers' schedulers name nothing: their spans carry the sum alone
    assert not any(key.startswith("sleep_") for s in copies.tree.spans
                   if s.side == "server" for key in s.args)


def test_the_obs_cli_prints_the_host_copies_of_a_merged_trace(wire_gang_run,
                                                              capsys):
    from mpit_tpu.obs import __main__ as obs_cli

    obs_cli.main(["analyze", wire_gang_run["obs_trace"]])
    out = capsys.readouterr().out
    # (19.00 times, unless a loaded host stamped a sweep a round late)
    assert f"host copies over {GANG_STEPS} round(s): a byte of the vector " \
        "moves 1" in out
    assert "  1 at work: " in out and "  3plus at work: " in out
    assert "client sleeps in exchange (median ms): apply " in out


# (e) with obs off: no clock on the stream's thread, no interval buffer


def test_obs_off_the_streams_thread_reads_no_clock_and_makes_no_span(
        monkeypatch):
    from mpit_tpu.optim import sync

    monkeypatch.setattr(sync, "PIECE_BYTES", 10 * 4)
    obs.configure(enabled=False, reset=True)
    try:
        with gang(2, 1) as (_servers, (pc,)):
            opt = RuleShell(quad, pc, su=1)
            w = opt.start(jnp.zeros(SIZE))
            w, _loss = opt.step(w, TARGET)
            real = time.monotonic

            def refused():
                if threading.current_thread().name == "mpit-round-stream":
                    raise AssertionError("the stream's thread read the clock")
                return real()

            monkeypatch.setattr(time, "monotonic", refused)
            for _ in range(2):
                w, _loss = opt.step(w, TARGET)  # raises what the thread did
            monkeypatch.undo()
            assert opt._stream.closing is None
            assert pc.sched.why is None and pc.sched.sleep_by == {}
            opt.stop()
        assert obs.get_recorder().spans == ()
    finally:
        obs.configure(enabled=None, reset=True)


# (f) the concurrency sweep on hand-made intervals

SWEEPS = {
    # one copier: 1 s, its 8 bytes
    "alone": ([("d2h", 0.0, 1.0, 8)],
              {"1": (1.0, 8.0, {"d2h": 1.0})}),
    # two that touch but never overlap stay in class 1
    "touching": ([("d2h", 0.0, 1.0, 8), ("h2d", 1.0, 3.0, 4)],
                 {"1": (3.0, 12.0, {"d2h": 1.0, "h2d": 2.0})}),
    # 0-4 at 2 B/s, 1-3 at 3 B/s, 2-6 at 1 B/s: 0-1 one, 1-2 two, 2-3
    # three, 3-4 two, 4-6 one
    "stairs": ([("ring_in", 0.0, 4.0, 8), ("apply", 1.0, 3.0, 6),
                ("ring_out", 2.0, 6.0, 4)],
               {"1": (3.0, 2.0 + 2.0, {"ring_in": 1.0, "ring_out": 2.0}),
                "2": (2.0, 5.0 + 3.0, {"ring_in+apply": 1.0,
                                       "ring_in+ring_out": 1.0}),
                "3plus": (1.0, 6.0, {"ring_in+ring_out+apply": 1.0})}),
    # four at once are still the last class; a gap belongs to none
    "four": ([("ring_in", 0.0, 1.0, 1), ("ring_in", 0.0, 1.0, 1),
              ("ring_out", 0.0, 1.0, 1), ("apply", 0.0, 1.0, 1),
              ("h2d", 5.0, 6.0, 2)],
             {"1": (1.0, 2.0, {"h2d": 1.0}),
              "3plus": (1.0, 4.0, {"ring_in+ring_out+apply": 1.0})}),
}


@pytest.mark.parametrize("case", sorted(SWEEPS))
def test_the_concurrency_sweep_gives_the_hand_computed_classes(case):
    from mpit_tpu.obs import copies

    rows, want = SWEEPS[case]
    passes = [copies.Copy(0, "t", kind, t0, t1, n, n)
              for kind, t0, t1, n in rows]
    table = copies.by_class(passes)
    for k in copies.CLASSES:
        seconds, nbytes, met = want.get(k, (0.0, 0.0, {}))
        assert table[k]["seconds"] == pytest.approx(seconds)
        assert table[k]["bytes"] == pytest.approx(nbytes)
        assert table[k]["passes"] == pytest.approx(met)
        # each kind's own share: together the class's bytes, and as many
        # copier-seconds as copiers were at work
        assert sum(b for _s, b in table[k]["own"].values()) == (
            pytest.approx(nbytes))
        assert sum(s for s, _b in table[k]["own"].values()) >= seconds
    assert sum(row["bytes"] for row in table.values()) == pytest.approx(
        sum(p.moved for p in passes))
    assert copies.union_seconds(passes) == pytest.approx(
        sum(row["seconds"] for row in table.values()))
    # the same sweep under intervals of another clock: idle gaps
    gaps = [(-1.0, 0.5), (2.5, 5.5)]
    by_pass = copies.overlap_by_passes(passes, gaps)
    assert sum(by_pass.values()) == pytest.approx(1.5 + 3.0)
    assert by_pass["none"] >= 1.0  # before the first pass began


def test_merging_ranks_of_two_clocks_is_refused(tmp_path):
    parts = []
    for rank, clock in ((0, "boot-a"), (1, "boot-b")):
        path = tmp_path / f"t.rank{rank}.json"
        path.write_text(json.dumps({
            "traceEvents": [], "otherData": {"ranks": {str(rank): {
                "epoch_offset": 1.0, "clock_id": clock}}}}))
        parts.append(str(path))
    with pytest.raises(ValueError, match="different monotonic clocks"):
        obs_trace.merge_traces(str(tmp_path / "t.json"), parts)
    # one clock, or a part that names none, merges as before
    (tmp_path / "t.rank1.json").write_text(json.dumps({
        "traceEvents": [], "otherData": {"ranks": {"1": {
            "epoch_offset": 2.0, "clock_id": "boot-a"}}}}))
    assert obs_trace.merge_traces(str(tmp_path / "t.json"), parts) == 0
