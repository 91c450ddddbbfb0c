"""Shared by the readers of the host's cores (PR 67): the process's own
CPU (``time.process_time``, all threads) that the program stamps at the
two ends of a stretch while it records spans, as the arg ``cpu_ms``: on
the first worker's ``round`` spans over their ``exchange`` phase and on
the servers' GRAD and PARAM op spans over the stretch since the op
before (``mpit_tpu/obs/spans.py`` ``WireMeter``; a server's stretches
tile its time), and on the servers' ``apply_exec`` spans over ``exec``
(``_ReadyWaiter``); beside them the copy helpers' own ``crew_copy_ms``
and ``crew_spin_ms`` on the metered spans, ``end_from`` and
``waiter_late_ms`` on ``apply_exec``, and what each rank's part says
once under ``otherData.ranks[<rank>].cores`` (``obs/profile.py``
``thread_census``).  Nothing is sampled and nothing interpolated: a
number here is a difference of two stamps of one clock over an interval
the spans give.  Built on ``spantree`` (the tree, the clocks, the first
worker's rounds that lie whole in the window).  Not a reader itself: no
``read``.

Everything here returns None (or nothing) where the program stamped no
``cpu_ms``, as the parent of PR 67 does not; nothing raises for that.
A reader also returns None, and says so, where the table's check fails.
"""

from __future__ import annotations

import json
import statistics
from typing import Any, Dict, List, Optional, Tuple

from chipbench.layers import spantree

CACHE_KEY = "_coretree"  # on the run dict: one parse for all readers
Interval = Tuple[float, float]
#: a rank's stretches may leave this share of its time uncovered, and an
#: ``exec``'s CPU may pass its two stretches' by this share
CHECK_PCT = 3.0
ROUND_LINES = 48  # the table's lines a round: the longest rounds
METERED = ("GRAD", "PARAM")  # the server op spans the wire's meter notes


def say(line: str) -> None:
    print(f"chipbench: cores: {line}", flush=True)


class Stretch:
    """One metered stretch of a server: it ends where its op span does
    and began ``wire_span_ms`` before."""

    __slots__ = ("pid", "op", "lo", "hi", "args")

    def __init__(self, pid: int, op: str, hi: float, args: Dict[str, Any]):
        self.pid, self.op, self.hi, self.args = pid, op, hi, args
        self.lo = hi - float(args["wire_span_ms"]) / 1e3

    @property
    def cpu_ms(self) -> float:
        return float(self.args["cpu_ms"])


class Cores:
    """The stamped stretches of one run beside its span tree."""

    def __init__(self, tree: spantree.Tree, census: Dict[int, Dict[str, Any]]):
        self.tree = tree
        #: pid -> that rank's ``otherData.ranks[<rank>].cores``
        self.census = census
        #: the windowed rounds whose ``exchange`` carries ``cpu_ms``
        self.rounds = [r for r in tree.rounds()
                       if r.args.get("cpu_ms") is not None
                       and self.interval(r, "exchange") is not None]
        #: every server's metered stretches of the whole run, by time
        self.stretches = sorted(
            (Stretch(s.pid, s.name, tree.mono(s, s.t1), s.args)
             for s in tree.spans
             if s.side == "server" and s.name in METERED
             and s.args.get("cpu_ms") is not None
             and s.args.get("wire_span_ms") is not None),
            key=lambda st: st.hi)
        self._applies: Optional[List[Dict[str, Any]]] = None
        self._ok: Optional[bool] = None

    def interval(self, span: Any, phase: Optional[str] = None
                 ) -> Optional[Interval]:
        """``span``'s extent, or its first phase ``phase``'s, monotonic s."""
        if phase is None:
            return (self.tree.mono(span, span.t0), self.tree.mono(span, span.t1))
        for name, ts, dur in span.phases:
            if name == phase:
                return (self.tree.mono(span, ts),
                        self.tree.mono(span, ts + dur))
        return None

    def ending_in(self, lo: float, hi: float) -> List[Stretch]:
        return [st for st in self.stretches if lo <= st.hi <= hi]

    def affinity(self) -> Optional[int]:
        sizes = [int(c["affinity"]) for c in self.census.values()
                 if c.get("affinity")]
        return sizes[0] if sizes else None

    def ok(self) -> bool:
        """The table's check, made once and silently for a reader that
        comes before the table."""
        if self._ok is None:
            self._ok = not check_faults(self)
        return self._ok


def load(run: Dict[str, Any]) -> Optional[Cores]:
    """The run's cores, parsed once; None where no span says ``cpu_ms``."""
    if CACHE_KEY not in run:
        run[CACHE_KEY] = _load(run)
    return run[CACHE_KEY]


def _load(run: Dict[str, Any]) -> Optional[Cores]:
    tree = spantree.load(run)
    if tree is None or not any("cpu_ms" in s.args for s in tree.spans):
        return None
    with open(run["obs_trace"]) as fh:
        ranks = (json.load(fh).get("otherData") or {}).get("ranks") or {}
    census = {int(rank): dict(rec["cores"]) for rank, rec in ranks.items()
              if isinstance(rec, dict) and isinstance(rec.get("cores"), dict)}
    return Cores(tree, census)


def checked(run: Dict[str, Any]) -> Optional[Cores]:
    """:func:`load` for a reader: None as well where the check fails."""
    cores = load(run)
    if cores is not None and not cores.ok():
        say("null: the table's check fails (its last line says where)")
        return None
    return cores


# -- the servers' applies -------------------------------------------------------


def applies(cores: Cores) -> List[Dict[str, Any]]:
    """Every server's windowed ``apply_exec`` with an ``exec`` phase:
    ``pid``, ``exec`` (its interval), ``queued_ms``, and ``cpu_ms``,
    ``end_from`` and ``waiter_late_ms`` as the span says them (None in a
    program that does not), and ``round``, the index of the windowed
    round its middle lies in (None: in none)."""
    if cores._applies is not None:
        return cores._applies
    spans = [cores.interval(r) for r in cores.rounds]
    out = []
    for span in cores.tree.named("apply_exec", "server"):
        iv = cores.interval(span, "exec")
        if iv is None:
            continue
        mid = (iv[0] + iv[1]) / 2
        k = next((i for i, (lo, hi) in enumerate(spans) if lo <= mid <= hi),
                 None)
        out.append({"pid": span.pid, "exec": iv, "round": k,
                    "queued_ms": spantree.phase_ms(span, "queued"),
                    "cpu_ms": span.args.get("cpu_ms"),
                    "end_from": span.args.get("end_from"),
                    "waiter_late_ms": span.args.get("waiter_late_ms")})
    cores._applies = sorted(out, key=lambda a: a["exec"][0])
    return cores._applies


def judged_applies(cores: Cores) -> Tuple[List[Dict[str, Any]], int, int]:
    """The applies ``apply_cores_p50`` is taken over: of each windowed
    round those a role thread ended on time (``end_from``
    ``wait_apply``) where the round has one, else all of the round's;
    with how many of them ``wait_apply`` ended and how many there are
    in the rounds in all."""
    by_round: Dict[int, List[Dict[str, Any]]] = {}
    for a in applies(cores):
        if a["round"] is not None:
            by_round.setdefault(a["round"], []).append(a)
    chosen, on_time = [], 0
    for _k, mine in sorted(by_round.items()):
        exact = [a for a in mine if a["end_from"] == "wait_apply"]
        on_time += len(exact)
        chosen += exact or mine
    return chosen, on_time, sum(len(m) for m in by_round.values())


def apply_cores(cores: Cores) -> Optional[float]:
    chosen, on_time, total = judged_applies(cores)
    ratios = [float(a["cpu_ms"]) / ((a["exec"][1] - a["exec"][0]) * 1e3)
              for a in chosen
              if a["cpu_ms"] is not None and a["exec"][1] > a["exec"][0]]
    if not ratios:
        return None
    say(f"apply_cores_p50 over {len(ratios)} applies of {total} in the "
        f"windowed rounds ({on_time} ended by wait_apply)")
    return statistics.median(ratios)


# -- the gang's demand ----------------------------------------------------------


def round_rows(cores: Cores) -> List[Dict[str, Any]]:
    """A windowed round: its extent, its ``exchange``, the worker's CPU
    inside that, and the servers' stretches that end with it (inside the
    round: a server notes its reply a moment before or after the worker
    has it), all of them and those that also began inside the
    exchange."""
    rows = []
    for r in cores.rounds:
        lo, hi = cores.interval(r, "exchange")
        mine = cores.ending_in(*cores.interval(r))
        rows.append({
            "round": r.args.get("round"), "span": cores.interval(r),
            "exchange": (lo, hi), "worker_ms": float(r.args["cpu_ms"]),
            "servers_ms": sum(st.cpu_ms for st in mine),
            "inside_ms": sum(st.cpu_ms for st in mine if st.lo >= lo),
            "stretches": mine})
    return rows


def exchange_cores(cores: Cores) -> Optional[float]:
    """The gang's CPU over the first worker's ``exchange``, in cores:
    the worker's inside the phase, and each server's over its stretches
    that end with it (from its last reply of the round before: what a
    server ran while the worker computed is in it)."""
    rows = [row for row in round_rows(cores)
            if row["exchange"][1] > row["exchange"][0]]
    if not rows:
        return None
    wall = [(row["exchange"][1] - row["exchange"][0]) * 1e3 for row in rows]
    whole = [(row["worker_ms"] + row["servers_ms"]) / ms
             for row, ms in zip(rows, wall)]
    inside = [(row["worker_ms"] + row["inside_ms"]) / ms
              for row, ms in zip(rows, wall)]
    say(f"exchange_cores_p50 over {len(rows)} windowed rounds: the worker "
        f"{statistics.median(r['worker_ms'] / ms for r, ms in zip(rows, wall)):.2f}"
        f" cores; with the servers' stretches that lie whole inside the "
        f"exchange {statistics.median(inside):.2f}, with all that end in it "
        f"{statistics.median(whole):.2f}")
    return statistics.median(whole)


def crew_rows(cores: Cores) -> Dict[int, Tuple[float, float]]:
    """pid -> the helpers' (copy ms, spin ms) over the windowed rounds:
    the first worker's from its rounds' spans, a server's from its
    stretches that end inside those rounds."""
    out: Dict[int, Tuple[float, float]] = {}

    def add(pid: int, args: Dict[str, Any]) -> None:
        if args.get("crew_copy_ms") is not None:
            copy, spin = out.get(pid, (0.0, 0.0))
            out[pid] = (copy + float(args["crew_copy_ms"]),
                        spin + float(args.get("crew_spin_ms") or 0.0))

    for r in cores.rounds:
        add(r.pid, r.args)
        for st in cores.ending_in(*cores.interval(r)):
            add(st.pid, st.args)
    return out


def crew_spin(cores: Cores) -> Optional[float]:
    """Of the copy helpers' time on a core in the windowed rounds, all
    ranks, the share they spun with no part to take."""
    rows = crew_rows(cores)
    copy = sum(c for c, _s in rows.values())
    spin = sum(s for _c, s in rows.values())
    return 100.0 * spin / (copy + spin) if copy + spin > 0.0 else None


# -- the table ------------------------------------------------------------------


def check_faults(cores: Cores) -> List[str]:
    """What is wrong with the stamps, if anything.  A server's stretches
    tile its time from the first that ends in a windowed round to the
    last (gaps within :data:`CHECK_PCT`); no stretch, ``exchange`` or
    ``exec`` ran more CPU than the affinity set's cores times its wall
    (one clock tick of room); and an ``exec``'s CPU is no more than that
    of its server's stretches it lies in (two pairs of stamps of one
    clock; :data:`CHECK_PCT` and a tick of room)."""
    faults: List[str] = []
    if not cores.rounds:
        return ["no windowed round carries cpu_ms"]
    lo = cores.interval(cores.rounds[0])[0]
    hi = cores.interval(cores.rounds[-1])[1]
    cores_n = cores.affinity() or 0
    tick = max([float(c.get("clock_tick_ms") or 0.0)
                for c in cores.census.values()] + [0.0])
    by_pid: Dict[int, List[Stretch]] = {}
    for st in cores.ending_in(lo, hi):
        by_pid.setdefault(st.pid, []).append(st)
    for pid, mine in sorted(by_pid.items()):
        covered = sum(st.hi - st.lo for st in mine[1:])
        extent = mine[-1].hi - mine[0].hi
        if extent > 0 and abs(covered - extent) > extent * CHECK_PCT / 100:
            faults.append(f"r{pid}'s stretches cover {covered:.3f} s of "
                          f"{extent:.3f} s")
    bounded = [(f"r{st.pid} {st.op}", st.cpu_ms, (st.hi - st.lo) * 1e3)
               for mine in by_pid.values() for st in mine]
    bounded += [(f"round {row['round']} exchange", row["worker_ms"],
                 (row["exchange"][1] - row["exchange"][0]) * 1e3)
                for row in round_rows(cores)]
    for a in applies(cores):
        if a["cpu_ms"] is None or a["round"] is None:
            continue
        a_lo, a_hi = a["exec"]
        bounded.append((f"r{a['pid']} exec", float(a["cpu_ms"]),
                        (a_hi - a_lo) * 1e3))
        over = [st for st in by_pid.get(a["pid"], ())
                if st.hi > a_lo and st.lo < a_hi]
        if over and over[0].lo <= a_lo and over[-1].hi >= a_hi:
            room = sum(st.cpu_ms for st in over)
            if float(a["cpu_ms"]) > room * (1 + CHECK_PCT / 100) + tick:
                faults.append(
                    f"r{a['pid']} exec in round {a['round']} ran "
                    f"{a['cpu_ms']:.1f} ms of CPU, its stretches {room:.1f}")
    if cores_n:
        faults += [f"{what} ran {cpu:.1f} ms of CPU in {wall:.1f} ms on "
                   f"{cores_n} cores" for what, cpu, wall in bounded
                   if cpu > cores_n * wall + tick]
    return faults


def _mean(values: List[float]) -> str:
    return format(statistics.fmean(values), ".1f") if values else "-"


def _census_lines(cores: Cores) -> None:
    for pid, c in sorted(cores.census.items()):
        names = ", ".join(
            f"{name} x{row['threads']} {row['cpu_ms']:.0f} ms"
            for name, row in (c.get("by_name") or {}).items())
        say(f"r{pid} at exit: {c.get('threads')} threads on "
            f"{c.get('affinity')} cores (clock tick "
            f"{c.get('clock_tick_ms')} ms); by name, CPU since it began: "
            + names)


def _stretch_lines(cores: Cores, rows: List[Dict[str, Any]]) -> None:
    """Rows rank x stretch, a windowed round (means): wall ms, of it
    inside the exchange, CPU ms, cores, the helpers' copy and spin ms."""
    n = len(rows)
    wall = [(r["exchange"][1] - r["exchange"][0]) * 1e3 for r in rows]
    say(f"the whole exchange, a round (means of {n}): {_mean(wall)} ms; "
        "rank stretch: wall ms, of it in the exchange, cpu ms, cores, "
        "crew copy ms, crew spin ms")
    worker = cores.rounds[0].pid
    crew = [(float(r.args.get("crew_copy_ms") or 0.0),
             float(r.args.get("crew_spin_ms") or 0.0)) for r in cores.rounds]
    cpu = [r["worker_ms"] for r in rows]
    say(f"  r{worker} exchange: {_mean(wall)}, {_mean(wall)}, {_mean(cpu)}, "
        f"{statistics.fmean(cpu) / statistics.fmean(wall):.2f}, "
        f"{_mean([c for c, _s in crew])}, {_mean([s for _c, s in crew])}")
    keyed: Dict[Tuple[int, str], List[Tuple[float, float, Stretch]]] = {}
    for row in rows:
        lo, hi = row["exchange"]
        for st in row["stretches"]:
            keyed.setdefault((st.pid, st.op), []).append(
                ((st.hi - st.lo) * 1e3, (st.hi - max(st.lo, lo)) * 1e3, st))
    for (pid, op), got in sorted(keyed.items()):
        walls = [w for w, _i, _st in got]
        cpus = [st.cpu_ms for _w, _i, st in got]
        say(f"  r{pid} {op} ({len(got) / n:.2f} a round): {_mean(walls)}, "
            f"{_mean([i for _w, i, _st in got])}, {_mean(cpus)}, "
            f"{statistics.fmean(cpus) / statistics.fmean(walls):.2f}, "
            f"{_mean([float(st.args.get('crew_copy_ms') or 0.0) for _w, _i, st in got])}, "
            f"{_mean([float(st.args.get('crew_spin_ms') or 0.0) for _w, _i, st in got])}")
    execs: Dict[int, List[Dict[str, Any]]] = {}
    for a in applies(cores):
        if a["round"] is not None and a["cpu_ms"] is not None:
            execs.setdefault(a["pid"], []).append(a)
    for pid, mine in sorted(execs.items()):
        walls = [(a["exec"][1] - a["exec"][0]) * 1e3 for a in mine]
        cpus = [float(a["cpu_ms"]) for a in mine]
        say(f"  r{pid} exec, inside those ({len(mine) / n:.2f} a round): "
            f"{_mean(walls)}, {_mean(walls)}, {_mean(cpus)}, "
            f"{statistics.fmean(cpus) / max(statistics.fmean(walls), 1e-9):.2f}"
            ", -, -")


def _apply_lines(cores: Cores) -> None:
    """M13: who ended each ``exec``, and how late the waiter's own stamp
    came where a role thread's ended it."""
    rows = [a for a in applies(cores) if a["round"] is not None]
    if not rows:
        return
    by_pid: Dict[int, List[Dict[str, Any]]] = {}
    for a in rows:
        by_pid.setdefault(a["pid"], []).append(a)
    for pid, mine in sorted(by_pid.items()):
        for a in mine[:3]:
            lo, hi = a["exec"]
            cpu, late = a["cpu_ms"], a["waiter_late_ms"]
            say(f"  apply r{pid} round {a['round']}: queued "
                f"{a['queued_ms']:.1f} ms, exec {(hi - lo) * 1e3:.1f} ms, "
                f"end_from {a['end_from']}, waiter_late_ms "
                f"{'-' if late is None else format(late, '.1f')}, cpu_ms "
                f"{'-' if cpu is None else format(cpu, '.1f')}")
        by_end: Dict[str, int] = {}
        for a in mine:
            by_end[str(a["end_from"])] = by_end.get(str(a["end_from"]), 0) + 1
        late = [float(a["waiter_late_ms"]) for a in mine
                if a["waiter_late_ms"] is not None]
        say(f"  r{pid}: {len(mine)} applies in the windowed rounds by "
            "end_from: "
            + ", ".join(f"{k} {n}" for k, n in sorted(by_end.items()))
            + (f"; where wait_apply ended it the waiter's stamp came "
               f"{statistics.median(late):.1f} ms later (median; "
               f"{max(late):.1f} at most): what exec overstates by where "
               "the waiter ends it" if late else ""))


def print_table(cores: Cores) -> bool:
    """One table a traced run (the ``chipbench: cores:`` lines); what
    its check says."""
    _census_lines(cores)
    rows = round_rows(cores)
    if rows:
        _stretch_lines(cores, rows)
    longest = sorted(rows, key=lambda r: r["span"][0] - r["span"][1]
                     )[:ROUND_LINES]
    if len(longest) < len(rows):
        say(f"the {ROUND_LINES} longest of {len(rows)} windowed rounds, in "
            "order of time:")
    for row in sorted(longest, key=lambda r: r["span"][0]):
        ex = (row["exchange"][1] - row["exchange"][0]) * 1e3
        say(f"  round {row['round']}: "
            f"{(row['span'][1] - row['span'][0]) * 1e3:.1f} ms, exchange "
            f"{ex:.1f} ms, cpu ms the worker {row['worker_ms']:.1f}, the "
            f"servers {row['servers_ms']:.1f} "
            f"({row['inside_ms']:.1f} in stretches begun inside it): "
            f"{(row['worker_ms'] + row['servers_ms']) / max(ex, 1e-9):.2f} "
            "cores")
    _apply_lines(cores)
    faults = check_faults(cores)
    cores._ok = not faults
    say("check " + ("passes" if not faults else "FAILS") + " (a server's "
        f"stretches tile its time within {CHECK_PCT:.0f}%, nothing ran more "
        "CPU than the cores times its wall, no exec more than the stretches "
        "it lies in)" + ("" if not faults else ": " + "; ".join(faults[:8])))
    return not faults
