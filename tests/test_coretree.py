"""The readers of the host's cores (``chipbench/layers/coretree.py``, PR
67) on a merged trace made by hand: two servers (ranks 0 and 2), one
worker (rank 1), two rounds in the window, every ``cpu_ms`` a stamp
pair's difference chosen so that each number a reader returns is one
worked out by hand below.

All times are monotonic seconds; a rank's exported timestamps are wall
microseconds, its own ``epoch_offset`` added (each rank has another, as
each process does).  The affinity set has 4 cores.

    round 5: 10.0 .. 12.0, exchange 10.5 .. 11.5, the worker's cpu_ms 2000
    round 6: 12.0 .. 14.0, exchange 12.5 .. 13.5, the worker's cpu_ms 2600

    a server's metered stretches (an op span's end, less wire_span_ms):
    r0 GRAD   9.40 .. 10.90  cpu 600     r2 GRAD   9.45 .. 10.95  cpu 500
    r0 PARAM 10.90 .. 11.40  cpu 900     r2 PARAM 10.95 .. 11.45  cpu 1000
    r0 GRAD  11.40 .. 12.90  cpu 400     r2 GRAD  11.45 .. 12.95  cpu 500
    r0 PARAM 12.90 .. 13.40  cpu 600     r2 PARAM 12.95 .. 13.45  cpu 500

    round 5: the servers 3000 (1900 in stretches begun inside the
      exchange), with the worker 5000 ms over 1000 ms: 5.0 cores (3.9)
    round 6: the servers 2000 (1100), with the worker 4600: 4.6 (3.7)
    exchange_cores_p50 = median(5.0, 4.6) = 4.8

    apply_exec   exec           cpu_ms  end_from    cores
    r0 round 5   10.8 .. 11.0     800   wait_apply  4.0
    r2 round 5   10.9 .. 11.3    1200   waiter      3.0  (left out: the
                                         round has one ended on time)
    r0 round 6   12.8 .. 13.0     400   waiter      2.0
    r2 round 6   12.9 .. 13.1     600   waiter      3.0
    apply_cores_p50 = median(4.0, 2.0, 3.0) = 3.0

    the helpers: the worker's rounds 300 copy + 100 spin each; r0's
      GRAD stretches 100 + 0, its PARAM stretches 100 + 100; r2 has none
    crew_spin_pct = 100 * (200 + 200) / (600 + 400 + 400) = 28.57
"""

import json

import pytest

from chipbench import spec as spec_mod
from chipbench.layers import coretree
from mpit_tpu.obs import trace as obs_trace

OFFSETS = {0: 1000.0, 1: 2000.0, 2: 3000.0}
READERS = ("apply_cores_p50", "exchange_cores_p50", "crew_spin_pct")
PS_CELLS = ["c111m-ps1w-su1", "c1.3b-ps1w-su8", "olmoe-l1-ps1w-su1"]


def us(pid, t):
    return (t + OFFSETS[pid]) * 1e6


def span(pid, tid, name, t0, t1, args, phases=()):
    out = [{"ph": "B", "cat": "ps_op", "name": name, "pid": pid, "tid": tid,
            "ts": us(pid, t0), "args": dict(args)}]
    for phase, lo, hi in phases:
        out.append({"ph": "X", "cat": "ps_phase", "name": f"{name}.{phase}",
                    "pid": pid, "tid": tid, "ts": us(pid, lo),
                    "dur": (hi - lo) * 1e6})
    out.append({"ph": "E", "cat": "ps_op", "name": name, "pid": pid,
                "tid": tid, "ts": us(pid, t1), "args": {"outcome": "ok"}})
    return out


#: (pid, op, end, wire_span_ms, cpu_ms, crew copy, crew spin)
STRETCHES = [
    (0, "GRAD", 10.90, 1500.0, 600.0, 100.0, 0.0),
    (0, "PARAM", 11.40, 500.0, 900.0, 100.0, 100.0),
    (0, "GRAD", 12.90, 1500.0, 400.0, 100.0, 0.0),
    (0, "PARAM", 13.40, 500.0, 600.0, 100.0, 100.0),
    (2, "GRAD", 10.95, 1500.0, 500.0, 0.0, 0.0),
    (2, "PARAM", 11.45, 500.0, 1000.0, 0.0, 0.0),
    (2, "GRAD", 12.95, 1500.0, 500.0, 0.0, 0.0),
    (2, "PARAM", 13.45, 500.0, 500.0, 0.0, 0.0),
]
#: (pid, queued from, exec from, exec to, cpu_ms, end_from, waiter_late_ms)
APPLIES = [
    (0, 10.7, 10.8, 11.0, 800.0, "wait_apply", 30.0),
    (2, 10.7, 10.9, 11.3, 1200.0, "waiter", None),
    (0, 12.7, 12.8, 13.0, 400.0, "waiter", None),
    (2, 12.7, 12.9, 13.1, 600.0, "waiter", None),
]


def events(cpu=True):
    ev = []
    for k, t0, worker_ms in ((5, 10.0, 2000.0), (6, 12.0, 2600.0)):
        args = {"round": k, "rank": 1, "side": "worker", "n": k,
                "sched_sleep_ms": 1.0}
        if cpu:
            args.update(cpu_ms=worker_ms, crew_copy_ms=300.0,
                        crew_spin_ms=100.0)
        ev += span(1, 1, "round", t0, t0 + 2.0, args,
                   [("wait_backward", t0, t0 + 0.5),
                    ("exchange", t0 + 0.5, t0 + 1.5),
                    ("h2d", t0 + 1.5, t0 + 2.0)])
    for n, (pid, op, end, wall, cpu_ms, copy, spin) in enumerate(STRETCHES):
        args = {"peer": 1, "side": "server", "rank": pid, "n": n // 2 % 2,
                "wire_span_ms": wall, "sched_sleep_ms": 0.0}
        if cpu:
            args.update(cpu_ms=cpu_ms, crew_copy_ms=copy, crew_spin_ms=spin)
        ev += span(pid, 2 if op == "GRAD" else 3, op, end - 0.1, end, args,
                   [("send", end - 0.1, end)])
    for n, (pid, begin, lo, hi, cpu_ms, end_from, late) in enumerate(APPLIES):
        args = {"peer": 1, "side": "server", "rank": pid, "n": n // 2,
                "grad_n": n // 2}
        if cpu:
            args.update(cpu_ms=cpu_ms, end_from=end_from)
            if late is not None:
                args["waiter_late_ms"] = late
        ev += span(pid, 4, "apply_exec", begin, hi, args,
                   [("queued", begin, lo), ("exec", lo, hi)])
    return sorted(ev, key=lambda e: e["ts"])


def trace(edit=None, cpu=True):
    ranks = {}
    for pid, off in OFFSETS.items():
        ranks[str(pid)] = {"role": "worker" if pid == 1 else "server",
                           "epoch_offset": off, "clock_id": "one-host"}
        if cpu:
            ranks[str(pid)]["cores"] = {
                "affinity": 4, "threads": 45, "clock_tick_ms": 10.0,
                "by_name": {"python": {"threads": 20, "cpu_ms": 4e4},
                            "tf_XLAEigen": {"threads": 13, "cpu_ms": 1e3}}}
    obj = {"traceEvents": events(cpu), "displayTimeUnit": "ms",
           "otherData": {"ranks": ranks, "clock": {}}}
    if edit is not None:
        edit(obj)
    return obj


def run_of(tmp_path, obj):
    path = tmp_path / "obs_trace.json"
    path.write_text(json.dumps(obj))
    return {"obs_trace": str(path), "results": {0: {}, 1: {}, 2: {}},
            "summary": {"worker_ranks": [1], "window": [9.5, 20.0]}}


def read(name, run):
    bench = spec_mod.load_bench()
    return spec_mod.load_reader(spec_mod.ROOT, bench, name)(run)


def begins(obj, name, pid=None):
    return [e for e in obj["traceEvents"]
            if e["ph"] == "B" and e["name"] == name
            and pid in (None, e["pid"])]


def test_the_hand_made_trace_is_one_the_program_would_accept():
    stats = obs_trace.validate_trace(trace())
    assert stats["pids"] == 3 and stats["ops"] == 14
    obs_trace.validate_trace(trace(cpu=False))


@pytest.mark.parametrize("name,want", [
    ("apply_cores_p50", 3.0),
    ("exchange_cores_p50", 4.8),
    ("crew_spin_pct", 100.0 * 400.0 / 1400.0),
])
def test_a_reader_returns_the_number_worked_out_by_hand(tmp_path, name,
                                                        want):
    assert read(name, run_of(tmp_path, trace())) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_a_parents_trace_reads_none_and_prints_nothing(tmp_path, name,
                                                       capsys):
    """A program that stamps no ``cpu_ms`` (the parent of PR 67): its
    spans and phases are all there, and nothing is read."""
    assert read(name, run_of(tmp_path, trace(cpu=False))) is None
    assert capsys.readouterr().out == ""


def a_gap(obj):
    """A server's meter missed an op: its stretches no longer tile."""
    gone = begins(obj, "PARAM", pid=0)[0]
    obj["traceEvents"] = [
        e for e in obj["traceEvents"]
        if not (e["pid"] == 0 and e["name"].startswith("PARAM")
                and gone["ts"] <= e["ts"] <= gone["ts"] + 0.1e6 + 1)]


def too_much(obj):
    """500 ms on 4 cores cannot hold 2,500 ms of CPU."""
    begins(obj, "PARAM", pid=2)[0]["args"]["cpu_ms"] = 2500.0


def exec_over_its_stretches(obj):
    """An ``exec`` of 400 ms that ran more than its two stretches did."""
    begins(obj, "apply_exec", pid=2)[0]["args"]["cpu_ms"] = 1590.0


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("edit,says", [
    (a_gap, "r0's stretches cover 2.000 s of 2.500 s"),
    (too_much, "r2 PARAM ran 2500.0 ms of CPU in 500.0 ms on 4 cores"),
    (exec_over_its_stretches,
     "r2 exec in round 0 ran 1590.0 ms of CPU, its stretches 1500.0"),
], ids=["a_gap", "too_much", "exec_over_its_stretches"])
def test_a_failed_check_reads_none_and_says_where(tmp_path, capsys, name,
                                                  edit, says):
    run = run_of(tmp_path, trace(edit))
    assert read(name, run) is None
    out = capsys.readouterr().out
    if name == "exchange_cores_p50":  # the one that prints the table
        assert "check FAILS" in out and says in out
    else:
        assert "null: the table's check fails" in out
    assert says in "; ".join(coretree.check_faults(coretree.load(run)))


def test_the_judged_applies_are_the_ones_ended_on_time(tmp_path):
    cores = coretree.load(run_of(tmp_path, trace()))
    chosen, on_time, total = coretree.judged_applies(cores)
    assert (len(chosen), on_time, total) == (3, 1, 4)
    assert [(a["pid"], a["round"]) for a in chosen] == [(0, 0), (0, 1),
                                                        (2, 1)]


def test_a_round_is_its_workers_cpu_and_the_stretches_that_end_in_it(
        tmp_path):
    cores = coretree.load(run_of(tmp_path, trace()))
    rows = coretree.round_rows(cores)
    assert [(r["round"], r["worker_ms"], r["servers_ms"], r["inside_ms"])
            for r in rows] == [(5, 2000.0, 3000.0, 1900.0),
                               (6, 2600.0, 2000.0, 1100.0)]
    assert [(st.pid, st.op) for st in rows[0]["stretches"]] == [
        (0, "GRAD"), (2, "GRAD"), (0, "PARAM"), (2, "PARAM")]
    assert rows[0]["stretches"][0].lo == pytest.approx(9.4)


def test_the_helpers_time_is_kept_a_rank(tmp_path):
    cores = coretree.load(run_of(tmp_path, trace()))
    assert coretree.crew_rows(cores) == {1: (600.0, 200.0),
                                         0: (400.0, 200.0), 2: (0.0, 0.0)}


@pytest.mark.parametrize("edit", [
    lambda obj: [e["args"].update(crew_copy_ms=0.0, crew_spin_ms=0.0)
                 for e in obj["traceEvents"] if "crew_copy_ms" in e.get(
                     "args", {})],
    lambda obj: [(e["args"].pop("crew_copy_ms"), e["args"].pop(
        "crew_spin_ms")) for e in obj["traceEvents"]
        if "crew_copy_ms" in e.get("args", {})],
], ids=["no_helper", "a_wire_without_totals"])
def test_without_a_helper_the_spin_reads_none(tmp_path, edit):
    run = run_of(tmp_path, trace(edit))
    assert read("crew_spin_pct", run) is None
    assert read("exchange_cores_p50", run) == pytest.approx(4.8)


def test_the_table_ends_in_the_check(tmp_path, capsys):
    run = run_of(tmp_path, trace())
    assert read("exchange_cores_p50", run) == pytest.approx(4.8)
    lines = [ln[len("chipbench: cores: "):]
             for ln in capsys.readouterr().out.splitlines()]
    assert all(ln for ln in lines) and len(lines) > 12
    assert lines[0].startswith("r0 at exit: 45 threads on 4 cores")
    assert lines[0].endswith("python x20 40000 ms, tf_XLAEigen x13 1000 ms")
    text = "\n".join(lines)
    assert "the whole exchange, a round (means of 2): 1000.0 ms" in text
    assert "  r1 exchange: 1000.0, 1000.0, 2300.0, 2.30, 300.0, 100.0" in text
    # a GRAD stretch of 1.5 s of which 0.4 s lie in the exchange
    assert "  r0 GRAD (1.00 a round): 1500.0, 400.0, 500.0, 0.33, 100.0, 0.0" \
        in text
    assert "  r2 PARAM (1.00 a round): 500.0, 500.0, 750.0, 1.50, 0.0, 0.0" \
        in text
    assert "  r0 exec, inside those (1.00 a round): 200.0, 200.0, 600.0, " \
        "3.00, -, -" in text
    assert ("  round 5: 2000.0 ms, exchange 1000.0 ms, cpu ms the worker "
            "2000.0, the servers 3000.0 (1900.0 in stretches begun inside "
            "it): 5.00 cores") in text
    assert ("  apply r0 round 0: queued 100.0 ms, exec 200.0 ms, end_from "
            "wait_apply, waiter_late_ms 30.0, cpu_ms 800.0") in text
    assert ("  r0: 2 applies in the windowed rounds by end_from: wait_apply "
            "1, waiter 1; where wait_apply ended it the waiter's stamp "
            "came 30.0 ms later") in text
    assert "  r2: 2 applies in the windowed rounds by end_from: waiter 2" \
        in text
    assert lines[-2].startswith("check passes")
    assert lines[-1].startswith(
        "exchange_cores_p50 over 2 windowed rounds: the worker 2.30 cores; "
        "with the servers' stretches that lie whole inside the exchange "
        "3.80, with all that end in it 4.80")


def test_the_entries_list_the_three_ps_cells_at_the_end():
    bench = spec_mod.load_bench(spec_mod.ROOT)
    mine = bench["per_layer"][-3:]
    assert [m["name"] for m in mine] == list(READERS)
    assert [(m["unit"], m["better"], m["source"]) for m in mine] == [
        ("cores", "higher", "program_span"),
        ("cores", "lower", "program_span"),
        ("%", "lower", "program_counter")]
    for m in mine:
        assert m["layer"] == "L2 servers + wire"
        assert m["moves"] == "tokens_per_s" and m["workloads"] == PS_CELLS
    perf = (spec_mod.ROOT / "PERF.md").read_text()
    layers = perf[perf.index("## 3. Layers"):perf.index("## 4. Cells")]
    for name in READERS:
        assert f"`{name}`" in layers
