"""CPU/utilization attribution plane (obs/profile.py).

Four layers of assertion:

1. the profiler primitive: step attribution (negative deltas clamped,
   never negative totals), throttled counter-track sampling, the
   metric bindings (``mpit_sched_cpu_seconds_total`` /
   ``mpit_sched_runq``), and the enablement contract — profiling is
   OFF even when obs is on, and the disabled object is the shared
   null singleton;
2. scheduler integration: a CPU-burning task run under profiling
   carries ``cpu_s`` on the Task, ``cpu_us`` on its recorded
   lifecycle, and an attribution row in the profiler;
3. deterministic counter-track round trips: samples written by the
   trace exporter validate as ``ph:"C"`` events, survive a merge with
   per-rank (pid) tracks kept distinct, and surface in
   ``analyze_trace``;
4. the offline report: cpu attribution is non-negative and
   sums-to-wall by construction (clamping both directions), and the
   ``profile`` CLI round-trips --json / --require-counters, while
   flight dumps for ``scheduler_stall`` carry a well-formed resources
   section (validate_dump enforces the shape);
5. the process's cores (PR 67): with obs off no stamp of
   ``time.process_time``, no thread and nothing under ``/proc`` opened;
   on, ``cpu_ms`` on an ``apply_exec`` span's ``exec`` and on every
   stretch of the wire's meter, ``waiter_late_ms`` where a role thread
   saw the result first, the census of a rank's threads by name (on a
   made-up ``/proc`` and on this process's own), and a rank's part
   that carries it through a merge.  None asserts a duration: a test
   waits for a counter to have moved.
"""

import json
import os
import threading
import time

import pytest

from mpit_tpu import obs
from mpit_tpu.aio import Scheduler
from mpit_tpu.comm import pool as comm_pool
from mpit_tpu.obs import causal as obs_causal
from mpit_tpu.obs import flight as obs_flight
from mpit_tpu.obs import metrics as obs_metrics
from mpit_tpu.obs import profile as obs_profile
from mpit_tpu.obs import spans as obs_spans
from mpit_tpu.obs import trace as obs_trace
from mpit_tpu.obs.__main__ import main as obs_cli


@pytest.fixture
def prof_on():
    """obs + profiling forced on, everything reset on the way out.
    Order matters: obs.configure(reset=True) clears the profile
    override too, so the profile flip comes second."""
    obs.configure(enabled=True, reset=True)
    obs_profile.configure(enabled=True, reset=True)
    try:
        yield obs_profile.get_profiler()
    finally:
        obs.configure(enabled=None, reset=True)


@pytest.fixture
def no_pool():
    """The sampler's tracks depend on whether the process has a pool
    (any client with a codec builds one): ask for none."""
    comm_pool.close()
    yield
    comm_pool.close()


def burn_task(rounds=40, width=4000):
    """A generator task that does real arithmetic per step — enough
    thread-time to stamp, few enough steps to stay fast."""
    acc = 0
    for _ in range(rounds):
        acc += sum(i * i for i in range(width))
        yield
    return acc


# ---------------------------------------------------------------------------
# the profiler primitive + enablement


class TestProfilerPrimitive:
    def test_profiling_off_even_when_obs_on(self):
        obs.configure(enabled=True, reset=True)
        try:
            assert obs.obs_enabled()
            assert not obs_profile.profile_enabled()
            assert obs_profile.get_profiler() is obs_profile.NULL_PROFILER
        finally:
            obs.configure(enabled=None, reset=True)

    def test_env_enablement_implies_obs(self, monkeypatch):
        monkeypatch.setenv(obs_profile.PROFILE_ENV, "1")
        # MPIT_OBS_PROFILE alone turns obs on (like a trace request)
        assert obs_metrics.obs_enabled()
        assert obs_profile.profile_enabled()
        monkeypatch.setenv(obs_profile.PROFILE_ENV, "0")
        assert not obs_profile.profile_enabled()

    def test_step_attributes_and_counts(self, prof_on):
        prof = prof_on
        prof.step("apply", 0.010)
        prof.step("apply", 0.005)
        prof.step("encode", 0.002)
        prof.step("noise", -0.5)  # foreign-thread stamp: dropped
        prof.step("noise", 0.0)
        assert prof.task_cpu["apply"] == pytest.approx(0.015)
        assert "noise" not in prof.task_cpu
        assert prof.cpu_seconds == pytest.approx(0.017)
        reg = obs.get_registry()
        c = reg.counter("mpit_sched_cpu_seconds_total")
        assert c.value == pytest.approx(0.017)
        top = prof.top_tasks(1)
        assert top == [["apply", pytest.approx(15000.0)]]

    def test_sample_emits_tracks_and_throttles(self, prof_on):
        prof = prof_on
        prof._interval = 0.0  # deterministic: no rate cap
        prof.step("t", 0.001)
        prof.sample(3)
        tracks = {track for _, track, _ in prof.samples}
        # no pool in this process path — the scheduler tracks only
        assert {"sched_runq", "task_cpu"} <= tracks
        assert prof.last_runq == 3
        g = obs.get_registry().gauge("mpit_sched_runq")
        assert g.value == 3
        # throttle: a huge interval means the next call is a no-op
        n = len(prof.samples)
        prof._interval = 3600.0
        prof.sample(9)
        assert len(prof.samples) == n and prof.last_runq == 3

    def test_cpu_now_is_a_real_clock(self, prof_on):
        t0 = prof_on.cpu_now()
        sum(i * i for i in range(50_000))
        assert prof_on.cpu_now() >= t0

    def test_resource_snapshot_sections(self, prof_on):
        prof_on.step("hot", 0.004)
        prof_on._interval = 0.0
        prof_on.sample(2)
        snap = obs_profile.resource_snapshot()
        assert snap["sched"] == {"runq": 2,
                                 "cpu_seconds": pytest.approx(0.004)}
        assert ["hot", pytest.approx(4000.0)] in snap["top_tasks"]
        obs.configure(enabled=None, reset=True)
        # disabled: no sched/top sections (pool may exist from other
        # tests — pool-only is legal, so only assert the absence)
        snap = obs_profile.resource_snapshot()
        assert "sched" not in snap and "top_tasks" not in snap


# ---------------------------------------------------------------------------
# scheduler integration


class TestSchedulerStamping:
    def test_tasks_carry_cpu(self, prof_on):
        prof = prof_on
        prof._interval = 0.0
        sched = Scheduler(idle_usec=0)
        sched.spawn(burn_task(), name="burn")
        sched.wait()
        assert prof.task_cpu.get("burn", 0.0) > 0.0
        assert prof.cpu_seconds > 0.0
        rec = obs_spans.get_recorder()
        rows = {name: cpu for name, _, _, _, cpu in rec.tasks}
        assert rows["burn"] > 0.0
        # the ping pass sampled the run queue at least once
        assert any(track == "sched_runq" for _, track, _ in prof.samples)

    def test_disabled_scheduler_stamps_nothing(self):
        obs.configure(enabled=True, reset=True)  # obs on, profiling off
        try:
            sched = Scheduler(idle_usec=0)
            sched.spawn(burn_task(rounds=3), name="burn")
            sched.wait()
            rec = obs_spans.get_recorder()
            rows = {name: cpu for name, _, _, _, cpu in rec.tasks}
            assert rows["burn"] == 0.0
        finally:
            obs.configure(enabled=None, reset=True)


# ---------------------------------------------------------------------------
# counter-track round trips


def _sampled_trace(tmp_path, prof, rank, n=4):
    """Write one rank's trace after n deterministic samples."""
    prof._interval = 0.0
    for i in range(n):
        prof.step(f"task{rank}", 0.001)
        prof.sample(i)
    path = str(tmp_path / f"trace.rank{rank}.json")
    obs_trace.write_rank_trace(path, rank=rank, role="server")
    return path


class TestCounterTracks:
    def test_the_pools_tracks_come_with_it_and_go_at_its_close(
            self, prof_on, no_pool):
        prof = prof_on
        prof._interval = 0.0
        pool = comm_pool.configure(2)
        for i in range(2):  # utilization is a difference of two samples
            prof.sample(i)
        assert {track for _, track, _ in prof.samples} == (
            {"sched_runq", "task_cpu"} if pool.serial
            else set(obs_profile.TRACKS))
        comm_pool.close()
        assert comm_pool.current_pool() is None
        prof.samples.clear()
        prof.sample(2)
        assert {track for _, track, _ in prof.samples} == {
            "sched_runq", "task_cpu"}
        assert comm_pool.current_pool() is None  # observed, not built

    def test_round_trip_validates(self, prof_on, no_pool, tmp_path):
        path = _sampled_trace(tmp_path, prof_on, rank=0)
        stats = obs_trace.validate_trace(path)
        assert stats["counters"] >= 8  # 2 tracks x 4 samples
        with open(path) as fh:
            events = json.load(fh)["traceEvents"]
        cs = [ev for ev in events if ev.get("ph") == "C"]
        assert cs and all(ev["cat"] == "resource" and ev["tid"] == 0
                          and isinstance(ev["args"]["value"], (int, float))
                          for ev in cs)
        assert {ev["name"] for ev in cs} == {"sched_runq", "task_cpu"}

    def test_malformed_counter_rejected(self, prof_on, tmp_path):
        path = _sampled_trace(tmp_path, prof_on, rank=0)
        with open(path) as fh:
            obj = json.load(fh)
        for ev in obj["traceEvents"]:
            if ev.get("ph") == "C":
                ev["args"] = {}  # strip the value
                break
        with pytest.raises(ValueError, match="without numeric args.value"):
            obs_trace.validate_trace(obj)

    def test_merge_keeps_per_rank_tracks_distinct(self, prof_on, tmp_path):
        p0 = _sampled_trace(tmp_path, prof_on, rank=0)
        p1 = _sampled_trace(tmp_path, prof_on, rank=1)
        merged = str(tmp_path / "trace.json")
        obs_trace.merge_traces(merged, [p0, p1])
        assert obs_trace.validate_trace(merged)["counters"] > 0
        with open(merged) as fh:
            events = json.load(fh)["traceEvents"]
        by_pid = {}
        for ev in events:
            if ev.get("ph") == "C":
                by_pid.setdefault(ev["pid"], set()).add(ev["name"])
        # counters are keyed per pid: both ranks keep their own tracks
        assert set(by_pid) == {0, 1}
        assert all("sched_runq" in tracks for tracks in by_pid.values())
        report = obs_profile.analyze_trace(merged)
        assert report["counter_events"] > 0
        assert report["ranks"]["0"]["counter_samples"]["task_cpu"] >= 4


# ---------------------------------------------------------------------------
# cpu attribution math (non-negative, sums-to-wall by construction)


def _synthetic_span_events(cpu_encode, cpu_span):
    """One client GRAD span: 100us encode phase + 300us total wall,
    with the given cpu riders (possibly out of range — the clamp is
    the thing under test)."""
    return [
        {"ph": "B", "cat": "ps_op", "name": "GRAD", "pid": 0, "tid": 1,
         "ts": 1000.0, "args": {"side": "client", "peer": 1}},
        {"ph": "X", "cat": "ps_phase", "name": "GRAD.encode", "pid": 0,
         "tid": 1, "ts": 1000.0, "dur": 100.0,
         "args": {"cpu_us": cpu_encode}},
        {"ph": "E", "cat": "ps_op", "name": "GRAD", "pid": 0, "tid": 1,
         "ts": 1300.0, "args": {"outcome": "ok", "cpu_us": cpu_span}},
    ]


class TestCpuAttribution:
    @pytest.mark.parametrize("cpu_encode,cpu_span", [
        (40.0, 250.0),     # in range
        (500.0, 900.0),    # rider above wall: clamps to wall
        (-30.0, -1.0),     # negative rider: clamps to zero
    ])
    def test_non_negative_and_sums_to_wall(self, cpu_encode, cpu_span):
        spans = obs_causal.extract_spans(
            _synthetic_span_events(cpu_encode, cpu_span))
        attr = obs_causal.cpu_attribution(spans)
        rows = attr["GRAD/client"]
        for row in rows.values():
            assert row["cpu_us"] >= 0.0 and row["off_cpu_us"] >= 0.0
            assert row["cpu_us"] + row["off_cpu_us"] == \
                pytest.approx(row["wall_us"])
        assert rows["encode"]["wall_us"] == pytest.approx(100.0)
        assert rows["encode"]["cpu_us"] == \
            pytest.approx(min(max(cpu_encode, 0.0), 100.0))
        assert rows["(span)"]["wall_us"] == pytest.approx(300.0)
        assert rows["(span)"]["cpu_us"] == \
            pytest.approx(min(max(cpu_span, 0.0), 300.0))

    def test_no_riders_means_none(self):
        events = _synthetic_span_events(10.0, 20.0)
        for ev in events:
            ev.get("args", {}).pop("cpu_us", None)
        spans = obs_causal.extract_spans(events)
        assert obs_causal.cpu_attribution(spans) is None

    def test_analyze_trace_ops_table(self):
        trace = {"traceEvents": _synthetic_span_events(40.0, 250.0),
                 "otherData": {}}
        report = obs_profile.analyze_trace(trace)
        op = report["ops"]["GRAD/client"]
        assert op["count"] == 1
        assert op["cpu_us"] + op["off_cpu_us"] == \
            pytest.approx(op["wall_us"])
        assert report["cpu_phases"]["GRAD/client"]["encode"]["cpu_us"] == \
            pytest.approx(40.0)


# ---------------------------------------------------------------------------
# the profile CLI


class TestProfileCLI:
    def test_report_and_json(self, prof_on, tmp_path, capsys):
        rec = obs_spans.get_recorder()
        sp = rec.op("GRAD", peer=1, side="client", epoch=0)
        sp.mark("encode")
        sp.end("ok")
        path = _sampled_trace(tmp_path, prof_on, rank=0)
        assert obs_cli(["profile", path, "--require-counters"]) == 0
        out = capsys.readouterr().out
        assert "counter sample" in out and "rank 0" in out
        assert obs_cli(["profile", path, "--json"]) == 0
        report = json.loads(capsys.readouterr().out)
        assert report["counter_events"] >= 8
        assert "GRAD/client" in report["ops"]

    def test_require_counters_gates(self, tmp_path, capsys):
        obs.configure(enabled=True, reset=True)  # profiling OFF
        try:
            path = str(tmp_path / "bare.json")
            obs_trace.write_rank_trace(path, rank=0)
        finally:
            obs.configure(enabled=None, reset=True)
        assert obs_cli(["profile", path]) == 0
        capsys.readouterr()
        assert obs_cli(["profile", path, "--require-counters"]) == 1

    def test_unreadable_trace_is_rc2(self, tmp_path):
        assert obs_cli(["profile", str(tmp_path / "missing.json")]) == 2


# ---------------------------------------------------------------------------
# flight-dump resources section


class TestFlightResources:
    def test_stall_dump_carries_resources(self, prof_on, tmp_path,
                                          monkeypatch):
        monkeypatch.setenv(obs_flight.ENV_DIR, str(tmp_path))
        prof_on.step("stuck", 0.003)
        prof_on._interval = 0.0
        prof_on.sample(1)
        fl = obs_flight.get_flight()
        fl.record("task", name="stuck", state="RUNNING")
        path = fl.dump("scheduler_stall")
        assert obs_flight.validate_dump(path)["reason"] == "scheduler_stall"
        with open(path) as fh:
            obj = json.load(fh)
        assert obj["resources"]["sched"]["runq"] == 1
        assert obj["resources"]["top_tasks"][0][0] == "stuck"

    def test_validator_enforces_shape(self, prof_on, tmp_path, monkeypatch):
        monkeypatch.setenv(obs_flight.ENV_DIR, str(tmp_path))
        path = obs_flight.get_flight().dump("scheduler_stall")
        with open(path) as fh:
            good = json.load(fh)
        bad = dict(good)
        bad.pop("resources")
        with pytest.raises(ValueError, match="no resources section"):
            obs_flight.validate_dump(bad)
        bad = json.loads(json.dumps(good))
        bad["resources"]["pool"] = {"threads": 4}  # missing depth/busy
        with pytest.raises(ValueError, match="resources.pool"):
            obs_flight.validate_dump(bad)
        bad = json.loads(json.dumps(good))
        bad["resources"]["sched"] = {"runq": 0}  # missing cpu_seconds
        with pytest.raises(ValueError, match="resources.sched"):
            obs_flight.validate_dump(bad)
        bad = json.loads(json.dumps(good))
        bad["resources"]["top_tasks"] = [["t"]]  # not a [name, cpu] pair
        with pytest.raises(ValueError, match="top_tasks"):
            obs_flight.validate_dump(bad)
        # other reasons never require the section
        other = json.loads(json.dumps(good))
        other["reason"] = "retry_exhausted"
        other.pop("resources")
        assert obs_flight.validate_dump(other)["reason"] == "retry_exhausted"


# ---------------------------------------------------------------------------
# the process's cores: exact stamps, and a census of the threads at exit


def proc_opens(monkeypatch):
    """Every path under /proc that is opened, listed or stat'ed, and
    every reading of ``time.process_time``, from here on."""
    import builtins

    seen = []

    def spy(real):
        def wrapped(path, *a, **kw):
            if isinstance(path, (str, bytes, os.PathLike)) and \
                    os.fspath(path).startswith("/proc"):
                seen.append(os.fspath(path))
            return real(path, *a, **kw)
        return wrapped

    def clock():
        seen.append("process_time")
        return 0.0

    monkeypatch.setattr(builtins, "open", spy(builtins.open))
    for name in ("open", "listdir", "stat"):
        monkeypatch.setattr(os, name, spy(getattr(os, name)))
    monkeypatch.setattr(time, "process_time", clock)
    return seen


@pytest.fixture
def recording(tmp_path, monkeypatch):
    """A trace asked for, as the benchmark's traced run asks: obs on,
    ``MPIT_OBS_PROFILE`` not."""
    monkeypatch.setenv(obs_metrics.TRACE_ENV, str(tmp_path / "t.json"))
    monkeypatch.delenv(obs_profile.PROFILE_ENV, raising=False)
    obs.configure(enabled=None, reset=True)
    try:
        yield obs_spans.get_recorder()
    finally:
        obs.configure(enabled=None, reset=True)


class Spinner:
    """A thread that burns a core until told to stop."""

    def __enter__(self):
        self.halt = threading.Event()
        self.thread = threading.Thread(target=self._spin, daemon=True)
        self.thread.start()
        return self

    def _spin(self):
        while not self.halt.is_set():
            sum(i * i for i in range(2000))

    def __exit__(self, *exc):
        self.halt.set()
        self.thread.join()


class TestCoresOff:
    def test_obs_off_no_thread_no_proc_read_no_clock(self, monkeypatch):
        """With obs off the recorder and its meter are the null ones: a
        scheduler's run, a metered stretch and a span handed to be ended
        when ready start no thread, touch nothing under /proc and read
        no ``process_time``."""
        assert not obs.obs_enabled()
        seen = proc_opens(monkeypatch)
        before = set(threading.enumerate())
        rec = obs_spans.get_recorder()
        assert rec is obs_spans.NULL_RECORDER
        meter = rec.wire_meter(None, None)
        assert meter is obs_spans.NULL_METER
        sched = Scheduler(idle_usec=0)
        sched.spawn(burn_task(rounds=3), name="burn")
        sched.wait()
        meter.start()
        span = rec.op("apply_exec", peer=1, side="server", rank=0)
        meter.note(span)
        rec.end_when_ready(span, None)
        rec.seen_ready(span)
        assert seen == []
        assert set(threading.enumerate()) == before

    def test_the_stamps_follow_the_recorder_not_the_profile_switch(
            self, recording):
        """A trace request alone (no ``MPIT_OBS_PROFILE``) is enough for
        ``cpu_ms``, and the per-step ``thread_time`` stamps stay off."""
        assert not obs_profile.profile_enabled()
        assert recording.enabled
        assert recording._prof is obs_profile.NULL_PROFILER
        meter = recording.wire_meter(None, Scheduler())
        span = recording.op("GRAD", peer=1, side="server", rank=0)
        meter.note(span)
        assert span.args["cpu_ms"] >= 0.0 and span.cpu0 is None
        span.end()


class TestCoresStamps:
    def test_process_cpu_counts_every_thread(self):
        """The clock the stamps read rises while another thread spins
        and this one sleeps (waited for, not timed)."""
        with Spinner():
            start = obs_profile.process_cpu()
            own = time.thread_time()
            deadline = time.monotonic() + 30.0
            while obs_profile.process_cpu() - start < 0.05:
                assert time.monotonic() < deadline
                time.sleep(0.005)
            assert time.thread_time() - own < obs_profile.process_cpu() - start

    def test_a_metered_stretch_carries_the_process_cpu(self, recording):
        meter = recording.wire_meter(None, Scheduler())
        meter.start()
        with Spinner():
            begin = obs_profile.process_cpu()
            deadline = time.monotonic() + 30.0
            while obs_profile.process_cpu() - begin < 0.03:
                assert time.monotonic() < deadline
                time.sleep(0.005)
        span = recording.op("PARAM", peer=1, side="server", rank=0)
        meter.note(span)
        first = span.args["cpu_ms"]
        assert first >= 30.0
        # cores over the stretch: no more than the process could have had
        assert first <= span.args["wire_span_ms"] * (
            len(os.sched_getaffinity(0)) + 1) + 20.0
        meter.note(span)  # the next stretch begins where this one ended
        assert span.args["cpu_ms"] < first
        assert "crew_copy_ms" not in span.args  # no transport, no helpers
        span.end()

    def test_apply_exec_carries_the_process_cpu_of_its_exec(self, recording):
        import jax.numpy as jnp

        rec = recording
        span = rec.op("apply_exec", peer=1, side="server", rank=0)
        span.mark("queued")
        rec.end_when_ready(span, jnp.ones(8) * 2.0)
        assert rec.drain(timeout=10)
        assert span.args["end_from"] == "waiter"
        assert span.args["cpu_ms"] >= 0.0
        assert "waiter_late_ms" not in span.args
        assert span.cpu0 is None  # no one thread's CPU on a handed span

    @pytest.mark.parametrize("seen_at,end_from,cpu_ms,late_ms", [
        (0.25, "wait_apply", 250.0, 750.0),  # the role thread saw it first
        (2.0, "waiter", 8000.0, None),       # the waiter did
        (None, "waiter", 8000.0, None),      # nobody waited on it
    ], ids=["seen_first", "seen_after", "not_seen"])
    def test_the_earlier_stamp_ends_the_span_with_its_own_cpu(
            self, recording, seen_at, end_from, cpu_ms, late_ms):
        """``exec`` from 0 to the waiter's stamp at 1 s; a role thread
        that saw the result at ``seen_at``: the span ends at the earlier
        one, with the process's CPU at that one, and says how late the
        waiter's came."""
        span = recording.op("apply_exec", peer=1, side="server", rank=0)
        span.mark("queued")
        span.mark("exec")
        begin = span.marks[-1][1]
        span.exec_cpu = [1.0, 9.0, 1.25]  # exec mark, waiter's end, seen
        span.end("ready", end_from="waiter")
        span.t1 = begin + 1.0
        if seen_at is not None:
            span.seen_ready = begin + seen_at
        obs_spans._end_no_later_than_seen(span)
        assert span.t1 == begin + min(seen_at or 1.0, 1.0)
        assert span.args["end_from"] == end_from
        assert span.args["cpu_ms"] == pytest.approx(cpu_ms)
        assert span.args.get("waiter_late_ms") == (
            None if late_ms is None else pytest.approx(late_ms))

    def test_the_crews_two_run_from_note_to_note(self, recording):
        """A helper spins on past a stretch's last copy, so a ``start``
        drops nothing of the crew's totals, as it does of the rest."""

        class Wire:
            totals = {"tx_copy": 0.0, "rx_copy": 0.0, "progress": 0.0,
                      "crew_copy": 0.0, "crew_spin": 0.0}

            def wire_totals(self):
                return dict(self.totals)

        wire = Wire()
        meter = recording.wire_meter(wire, Scheduler())
        wire.totals.update(tx_copy=0.5, progress=0.5, crew_copy=0.25,
                           crew_spin=0.001)
        meter.start()
        wire.totals.update(tx_copy=0.75, progress=0.75, crew_copy=0.375,
                           crew_spin=0.002)
        span = recording.op("round", peer=1, side="worker", rank=1)
        meter.note(span, stretch=False)
        assert span.args["wire_tx_copy_ms"] == pytest.approx(250.0)
        assert span.args["crew_copy_ms"] == pytest.approx(375.0)
        assert span.args["crew_spin_ms"] == pytest.approx(2.0)
        assert "wire_span_ms" not in span.args
        span.end()


def fake_task_dir(tmp_path, threads):
    """A ``/proc/self/task`` of ``(tid, name, utime, stime)`` rows."""
    for tid, name, utime, stime in threads:
        task = tmp_path / str(tid)
        task.mkdir()
        cells = ["S"] + ["0"] * 10 + [str(utime), str(stime)] + ["0"] * 30
        (task / "stat").write_text(f"{tid} ({name}) " + " ".join(cells))
    return str(tmp_path)


class TestThreadCensus:
    @pytest.mark.parametrize("threads,want", [
        ([(1, "python3", 100, 20), (2, "python3", 5, 0)],
         {"python": (2, 125)}),
        ([(1, "tf_XLAEigen/7", 10, 0), (2, "tf_XLAEigen/12", 30, 0),
          (3, "mpit-crew", 2, 1)],
         {"tf_XLAEigen": (2, 40), "mpit-crew": (1, 3)}),
        ([(1, "a (b) c", 1, 1)], {"a (b) c": (1, 2)}),  # a name with ")"
        ([(1, "pjrt-tpu-3", 0, 0), (2, "pjrt-tpu-11", 0, 4)],
         {"pjrt-tpu": (2, 4)}),
    ], ids=["interpreter", "pool_and_crew", "parenthesis", "index_cut"])
    def test_threads_are_counted_by_name_with_their_cpu(
            self, tmp_path, monkeypatch, threads, want):
        monkeypatch.setattr(obs_profile, "TASK_DIR",
                            fake_task_dir(tmp_path, threads))
        census = obs_profile.thread_census()
        tick = census["clock_tick_ms"]
        assert census["threads"] == len(threads)
        assert census["affinity"] == len(os.sched_getaffinity(0))
        assert census["by_name"] == {
            name: {"threads": n, "cpu_ms": ticks * tick}
            for name, (n, ticks) in want.items()}

    def test_the_names_that_ran_most_are_kept_in_that_order(
            self, tmp_path, monkeypatch):
        threads = [(tid, f"name{chr(97 + tid)}", tid, 0)
                   for tid in range(obs_profile.CENSUS_NAMES + 5)]
        monkeypatch.setattr(obs_profile, "TASK_DIR",
                            fake_task_dir(tmp_path, threads))
        census = obs_profile.thread_census()
        assert census["threads"] == len(threads)
        kept = list(census["by_name"])
        assert len(kept) == obs_profile.CENSUS_NAMES
        assert kept[0] == f"name{chr(97 + len(threads) - 1)}"
        cpu = [row["cpu_ms"] for row in census["by_name"].values()]
        assert cpu == sorted(cpu, reverse=True)

    def test_a_thread_that_ended_meanwhile_is_left_out(self, tmp_path,
                                                       monkeypatch):
        root = fake_task_dir(tmp_path, [(1, "python3", 1, 0)])
        (tmp_path / "2").mkdir()  # listed, and gone before it is read
        (tmp_path / "3").mkdir()
        (tmp_path / "3" / "stat").write_text("3 (torn")
        monkeypatch.setattr(obs_profile, "TASK_DIR", root)
        assert obs_profile.thread_census()["threads"] == 1

    def test_without_a_proc_the_census_is_empty(self, tmp_path, monkeypatch):
        monkeypatch.setattr(obs_profile, "TASK_DIR", str(tmp_path / "none"))
        assert obs_profile.thread_census() == {}

    def test_this_process_counts_a_copy_helper_under_its_name(
            self, monkeypatch):
        """The real ``/proc``: a helper thread of an shm endpoint is
        ``mpit-crew`` (``comm/native/transport.cpp``), there while the
        endpoint is open."""
        from mpit_tpu.comm import shm

        if not os.path.isdir(obs_profile.TASK_DIR):
            pytest.skip("no /proc/self/task on this host")
        monkeypatch.setattr(shm, "copy_helpers", lambda cores, ranks: 2)
        wire = shm.ShmTransport(f"t_census_{os.getpid()}", 0, 1,
                                ring_bytes=1 << 20)
        try:
            deadline = time.monotonic() + 10.0
            while "mpit-crew" not in obs_profile.thread_census()["by_name"]:
                assert time.monotonic() < deadline  # it names itself first
            census = obs_profile.thread_census()
            assert census["by_name"]["mpit-crew"]["threads"] == 2
            assert census["threads"] >= 3
        finally:
            wire.close()
        assert "mpit-crew" not in obs_profile.thread_census()["by_name"]


class TestCoresExport:
    def part(self, tmp_path, rank):
        path = str(tmp_path / f"part{rank}.json")
        rec = obs_spans.get_recorder()
        span = rec.op("GRAD", peer=1, side="server", rank=rank)
        rec.wire_meter(None, Scheduler()).note(span)
        span.end()
        obs_trace.write_rank_trace(path, rank, role="server")
        return path

    def test_a_part_says_once_whose_threads_they_were(self, recording,
                                                      tmp_path):
        path = self.part(tmp_path, 0)
        stats = obs_trace.validate_trace(path)
        assert stats["ops"] >= 1
        with open(path) as fh:
            obj = json.load(fh)
        cores = obj["otherData"]["ranks"]["0"]["cores"]
        assert cores["affinity"] == len(os.sched_getaffinity(0))
        assert cores["threads"] >= 1 and cores["by_name"]
        # no counter track of the cores: the plane's four alone
        names = {ev["name"] for ev in obj["traceEvents"]
                 if ev.get("ph") == "C"}
        assert names <= set(obs_profile.TRACKS)
        begun = [ev for ev in obj["traceEvents"]
                 if ev.get("ph") == "B" and ev["name"] == "GRAD"]
        ended = [ev for ev in obj["traceEvents"]
                 if ev.get("ph") == "E" and ev["name"] == "GRAD"]
        assert any("cpu_ms" in ev.get("args", {}) for ev in begun + ended)

    def test_two_ranks_parts_merge_with_their_census_apart(
            self, recording, tmp_path):
        parts = [self.part(tmp_path, rank) for rank in (0, 1)]
        merged = str(tmp_path / "merged.json")
        obs_trace.merge_traces(merged, parts)
        obs_trace.validate_trace(merged)
        with open(merged) as fh:
            obj = json.load(fh)
        ranks = obj["otherData"]["ranks"]
        assert all("by_name" in ranks[r]["cores"] for r in "01")
