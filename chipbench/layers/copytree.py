"""Shared by the readers of the round's host copies (PR 48): every pass
of a piece of the vector over the host's memory, as the program itself
puts them in one form (``mpit_tpu/obs/copies.py`` ``records``: the
stream thread's ``d2h`` and ``h2d`` piece spans of category ``copy``,
the ``copies`` intervals of the shm wire's ``tx`` and ``rx`` spans, the
``exec`` phase of the servers' ``apply_exec`` spans with their
``bytes_moved``), from the merged Chrome trace the gang writes under
``MPIT_OBS_TRACE``, on the monotonic clock all ranks of the host share,
booked under the first worker's rounds that lie whole in the window
(``spantree.Tree.rounds``) by where a pass's middle lies.  Built on
``spantree`` (the tree, the clocks, the anchors onto the device trace)
and on the program's own span parser (``obs/causal.py``
``extract_spans``, which ``spantree`` and ``wiretree`` use too).  Not a
reader itself: no ``read``.

Everything here returns None (or nothing) where the program recorded no
``copy`` span, as the parent of PR 48 does not and a cell without a
parameter server never will; nothing raises for that.
"""

from __future__ import annotations

import json
import statistics
from typing import Any, Dict, List, Optional, Tuple

from chipbench import reduce as reduce_mod
from chipbench.layers import spantree

CACHE_KEY = "_copytree"  # on the run dict: one parse for all readers
GB = 1e9
SLEEPS = ("staging", "apply", "drain", "pull")


class Copies:
    """The passes of one run and its windowed rounds."""

    def __init__(self, prog: Any, tree: spantree.Tree, passes: list,
                 pieces: list, rounds: List[Tuple[Any, list, list]],
                 wire_runs: Tuple[int, int, int]):
        #: the program's module, ``mpit_tpu.obs.copies``
        self.prog = prog
        self.tree = tree
        #: every pass of the run (``prog.Copy``), all rounds, all ranks
        self.passes = passes
        #: every span of category ``copy``
        self.pieces = pieces
        #: (``round`` span, the passes in it, its own piece spans) of the
        #: first worker's rounds in the window that staged a payload
        self.rounds = rounds
        #: the run's wire spans, their copy intervals, and how many more
        #: there were before intervals were merged over a gap
        self.wire_runs = wire_runs
        self._table: Optional[Dict[str, Dict[str, Any]]] = None

    def per_round(self, fn) -> List[float]:
        return [v for v in (fn(r, mine, pieces)
                            for r, mine, pieces in self.rounds)
                if v is not None]

    def table(self) -> Dict[str, Dict[str, Any]]:
        """The concurrency table over the windowed rounds together."""
        if self._table is None:
            self._table = self.prog.add_tables(
                self.prog.by_class(mine) for _r, mine, _p in self.rounds)
        return self._table


def load(run: Dict[str, Any]) -> Optional[Copies]:
    """The run's host copies, parsed once; None without any."""
    if CACHE_KEY not in run:
        run[CACHE_KEY] = _load(run)
    return run[CACHE_KEY]


def _load(run: Dict[str, Any]) -> Optional[Copies]:
    tree = spantree.load(run)
    if tree is None:
        return None
    try:
        from mpit_tpu.obs import causal, copies as prog
    except ImportError:
        return None  # a program that predates the copy spans
    with open(run["obs_trace"]) as fh:
        events = json.load(fh).get("traceEvents", [])
    pieces = causal.extract_spans(events, cat="copy")
    if not pieces:
        return None
    wire = causal.extract_spans(events, cat="wire")
    passes = prog.records(pieces, wire, tree.spans, tree.mono)
    wire_runs = (len(wire), sum(len(s.args.get("copies") or ()) for s in wire),
                 sum(int(s.args.get("copies_merged", 0)) for s in wire))
    worker = tree.first_worker
    rounds = []
    for r in tree.rounds():
        mine = prog.within(passes, tree.mono(r, r.t0), tree.mono(r, r.t1))
        own = [s for s in pieces if s.pid == worker
               and s.args.get("round") == r.args.get("round")]
        if prog.vector_bytes(worker, mine):
            rounds.append((r, mine, own))
    if not rounds:
        return None
    return Copies(prog, tree, passes, pieces, rounds, wire_runs)


def median(values: List[float]) -> Optional[float]:
    return statistics.median(values) if values else None


def class_gbps(copies: Copies, k: str) -> Optional[float]:
    return copies.prog.gbps(copies.table()[k])


def say(line: str) -> None:
    print(f"chipbench: copies: {line}", flush=True)


def print_classes(copies: Copies) -> None:
    """The concurrency table and the passes by name: the lines the five
    L2 metrics are cut from."""
    worker = copies.tree.first_worker
    for line in copies.prog.class_lines(copies.table()):
        say(line)
    by_kind: Dict[str, List[float]] = {}
    for _r, mine, _p in copies.rounds:
        vector = copies.prog.vector_bytes(worker, mine)
        for kind in copies.prog.PASSES:
            by_kind.setdefault(kind, []).append(
                sum(c.moved for c in mine if c.kind == kind) / vector)
    say(f"{len(copies.rounds)} rounds; memory traffic a byte of the vector "
        "and round, by pass (median): " + ", ".join(
            f"{kind} {statistics.median(vals):.2f}"
            for kind, vals in by_kind.items()))
    say("{} wire spans hold {} copy intervals, {} more merged over a gap; "
        "{} passes in all, {} copy spans".format(
            *copies.wire_runs, len(copies.passes), len(copies.pieces)))


def stage_rows(copies: Copies) -> List[Dict[str, Any]]:
    return [row for row in (copies.prog.stage(pieces)
                            for _r, _m, pieces in copies.rounds)
            if row is not None]


def print_stage(copies: Copies) -> None:
    """The stream thread's piece table: the round of the median DMA
    rate, the cuts in flight at pop over all windowed rounds, and the
    uploads by shard."""
    rows = stage_rows(copies)
    if not rows:
        return
    mid = sorted(rows, key=lambda r: r["bytes"] / max(r["dma_s"], 1e-12))[
        len(rows) // 2]
    say("stream, the round of the median DMA rate: "
        + copies.prog.stage_line(mid))
    flights: Dict[int, int] = {}
    for row in rows:
        for k, n in row["in_flight"].items():
            flights[k] = flights.get(k, 0) + n
    total = sum(flights.values())
    say(f"stream, {len(rows)} rounds, {total} pieces by cuts in flight at "
        "pop: " + ", ".join(f"{k}: {100.0 * n / total:.1f}%"
                            for k, n in sorted(flights.items())))
    shards: Dict[int, List[Any]] = {}
    for _r, _m, pieces in copies.rounds:
        for s in pieces:
            if s.name == "h2d_shard":
                shards.setdefault(int(s.args.get("shard", -1)), []).append(s)
    for shard, spans in sorted(shards.items()):
        ms = statistics.median((s.t1 - s.t0) / 1e3 for s in spans)
        ready = sum(int(s.args.get("ready", 0)) for s in spans)
        say(f"upload of shard {shard}: {spans[0].args.get('pieces')} pieces, "
            f"{float(spans[0].args.get('bytes', 0)) / 1e6:.1f} MB, "
            f"{ms:.2f} ms (median of {len(spans)}; {ready} of them to the "
            "parameters whole on the device, the rest their dispatch alone)")


def sleeps(copies: Copies) -> Dict[str, List[float]]:
    """The client's named sleeps in ``exchange``, ms by round."""
    out: Dict[str, List[float]] = {}
    for r, _m, _p in copies.rounds:
        for name in SLEEPS:
            key = f"sleep_{name}_ms"
            if key in r.args:
                out.setdefault(name, []).append(float(r.args[key]))
    return out


def print_sleeps(copies: Copies) -> None:
    named = sleeps(copies)
    if not named:
        return
    total = [float(r.args.get("sched_sleep_ms", 0.0))
             for r, _m, _p in copies.rounds]
    say("client asleep in exchange, median ms by what the pending ops "
        "waited for: " + ", ".join(
            f"{name} {statistics.median(vals):.2f}"
            for name, vals in named.items())
        + f"; sched_sleep_ms {statistics.median(total):.2f}")


def idle_by_pass(run: Dict[str, Any], copies: Copies
                 ) -> Optional[Dict[str, float]]:
    """The first worker's device idle time over the traced window (ns)
    by the set of host passes running meanwhile, any rank's, with the
    passes mapped onto the device trace's clock by the ``mpit.round``
    anchors; ``leaf`` is what of ``none`` a leaf of the span tree covers
    (``spantree.leaf_intervals``: the worker was in a named phase with
    no copy running, as in ``wait_backward`` or a step's dispatch)."""
    path = spantree.xplane_path(run)
    if path is None:
        return None
    rows = spantree.anchors(path)
    gaps = spantree.device_idle(run)
    if not rows or not gaps:
        return None
    lo, hi = gaps[0][0], gaps[-1][1]
    on_device = []
    for c in copies.passes:
        t0 = spantree.to_profiler_ns(rows, c.t0)
        t1 = spantree.to_profiler_ns(rows, c.t1)
        if t1 > lo and t0 < hi and t1 > t0:
            on_device.append(c._replace(t0=t0, t1=t1))
    out = copies.prog.overlap_by_passes(on_device, gaps)
    named = reduce_mod.union(
        [(c.t0, c.t1) for c in on_device]
        + [(spantree.to_profiler_ns(rows, a), spantree.to_profiler_ns(rows, b))
           for a, b in spantree.leaf_intervals(copies.tree)])
    covered = sum(e - s for start, end in gaps
                  for s, e in reduce_mod.clip(named, start, end))
    idle = sum(end - start for start, end in gaps)
    out["leaf"] = max(covered - (idle - out.get("none", 0.0)), 0.0)
    out["idle"] = idle
    return out
