"""Shared by the readers of the program's own spans (PR 23): the sync
round's span tree from the merged Chrome trace the gang writes under
``MPIT_OBS_TRACE``, put back on the host's monotonic clock, joined
client to server by the program's own joiner (``mpit_tpu/obs/causal.py``,
by ordinal on the unframed wire), and anchored on the device trace's
clock by the ``mpit.round`` annotations.  Not a reader itself: no
``read``.

Clocks.  A span's exported ``ts`` is wall microseconds of its rank;
minus that rank's ``epoch_offset`` (``otherData.ranks``, or the rank's
own ``marks`` in a program that predates it) it is seconds on
``time.monotonic``, which every process of the gang shares and on which
the benchmark's window is given.  The device trace has another clock.
Each traced round's ``mpit.round`` annotation carries the monotonic
stamp of its own begin (``mono_ns``), so its profiler timestamp minus
that stamp is the offset between the two clocks at that round; a
monotonic time is mapped with the offset of the nearest round, and the
offsets' range over the traced rounds is the drift.

Everything here returns None (or an empty list) where the program
recorded no such span, as the parent of PR 23 does not; nothing raises
for that.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import pathlib
import statistics
from typing import Any, Dict, List, Optional, Tuple

from chipbench import reduce as reduce_mod, spec as spec_mod

Interval = Tuple[float, float]
CACHE_KEY = "_spantree"  # on the run dict: one parse for all readers
MB = 1e6  # bytes


class Tree:
    """The spans of one run on the monotonic clock (seconds)."""

    def __init__(self, spans: list, chains: list,
                 offsets: Dict[int, float], first_worker: int,
                 window: Tuple[float, float]):
        self.spans = spans
        self.chains = chains
        self.offsets = offsets
        self.first_worker = first_worker
        self.window = window

    def mono(self, span: Any, ts_us: float) -> float:
        """An exported timestamp of ``span``'s rank, in monotonic s."""
        return ts_us / 1e6 - self.offsets.get(span.pid, 0.0)

    def in_window(self, span: Any) -> bool:
        lo, hi = self.window
        return lo <= self.mono(span, span.t0) and \
            self.mono(span, span.t1) <= hi

    def rounds(self) -> list:
        """The first worker's ``round`` spans that lie in the window."""
        return [s for s in self.spans
                if s.name == "round" and s.pid == self.first_worker
                and self.in_window(s)]

    def named(self, name: str, side: str) -> list:
        return [s for s in self.spans if s.name == name
                and s.side == side and self.in_window(s)]


def phase_ms(span: Any, *phases: str) -> float:
    """Milliseconds ``span`` spent in the named phases."""
    return sum(dur for name, _ts, dur in span.phases
               if name in phases) / 1e3


def median_ms(values: List[float]) -> Optional[float]:
    return statistics.median(values) if values else None


def load(run: Dict[str, Any]) -> Optional[Tree]:
    """The run's tree, parsed once; None without a merged trace."""
    if CACHE_KEY in run:
        return run[CACHE_KEY]
    run[CACHE_KEY] = tree = _load(run)
    return tree


def _load(run: Dict[str, Any]) -> Optional[Tree]:
    path = run.get("obs_trace")
    if not path or not os.path.exists(path):
        return None
    from mpit_tpu.obs import causal

    with open(path) as fh:
        obj = json.load(fh)
    spans = causal.extract_spans(obj.get("traceEvents", []))
    chains, _unkeyed = causal.join_spans(spans)
    other = obj.get("otherData") or {}
    offsets: Dict[int, float] = {}
    for rank, res in run["results"].items():
        marks = res.get("chipbench", {}).get("marks", {})
        if "epoch_offset" in marks:
            offsets[int(rank)] = float(marks["epoch_offset"])
    for rank, info in (other.get("ranks") or {}).items():
        if isinstance(info, dict) and "epoch_offset" in info:
            offsets[int(rank)] = float(info["epoch_offset"])
    summary = run["summary"]
    return Tree(spans, chains, offsets, summary["worker_ranks"][0],
                tuple(summary["window"]))


def joined(tree: Tree, op: str) -> List[Tuple[Any, Any]]:
    """(client span, server span) of every ``op`` chain the program's
    joiner made whose client half lies in the window."""
    out = []
    for chain in tree.chains:
        if chain.op == op and chain.joined and \
                tree.in_window(chain.client):
            out.append((chain.client, chain.server))
    return out


# -- the device trace ---------------------------------------------------------


def xplane_path(run: Dict[str, Any]) -> Optional[str]:
    """The first worker's ``.xplane.pb``: the runner keeps it beside the
    merged trace, under ``device_trace/``."""
    path = run.get("obs_trace")
    if not path:
        return None
    found = sorted(glob.glob(str(
        pathlib.Path(path).parent / "device_trace" / "plugins" / "profile"
        / "*" / "*.xplane.pb")))
    return found[0] if found else None


def anchors(path: str) -> List[Tuple[int, float, float]]:
    """``[round, profiler_ns, mono_ns]`` of every ``mpit.round``
    annotation in the trace's host plane."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for event in line.events:
                if event.name != "mpit.round":
                    continue
                stats = dict(event.stats)
                if "mono_ns" in stats:
                    out.append((int(stats.get("round", -1)),
                                float(event.start_ns),
                                float(stats["mono_ns"])))
    return sorted(out, key=lambda a: a[2])


def to_profiler_ns(anchor_rows: List[Tuple[int, float, float]],
                   mono_s: float) -> float:
    """A monotonic time (s) on the profiler's clock (ns), by the anchor
    of the nearest round."""
    mono_ns = mono_s * 1e9
    _k, prof, mono = min(anchor_rows, key=lambda a: abs(a[2] - mono_ns))
    return mono_ns + (prof - mono)


def drift_us(anchor_rows: List[Tuple[int, float, float]]) -> float:
    """The range of the clock offset over the traced rounds."""
    offsets = [prof - mono for _k, prof, mono in anchor_rows]
    return (max(offsets) - min(offsets)) / 1e3


def round_ops(tree: Tree) -> List[Tuple[Any, Optional[Any], Optional[Any]]]:
    """The tree under the first worker's rounds in the window: ``(client
    op span, server span, apply_exec span)`` of every client op that
    carries one of those rounds' ``round=k``.  The server half is the
    one the program's joiner paired with it by the channel ordinal
    ``n``; a GRAD's ``apply_exec`` is the one on that server whose
    ``grad_n`` is the server GRAD span's ``n``.  None where a half is
    missing."""
    worker = tree.first_worker
    rounds = {r.args.get("round") for r in tree.rounds()}
    server_of = {id(c.client): c.server for c in tree.chains if c.joined}
    applies = {(s.pid, s.args.get("peer"), s.args.get("grad_n")): s
               for s in tree.spans if s.name == "apply_exec"}
    out = []
    for span in tree.spans:
        if span.pid != worker or span.side != "client" or \
                span.args.get("round") not in rounds:
            continue
        server = server_of.get(id(span))
        applied = None
        if server is not None and span.name == "GRAD":
            applied = applies.get((server.pid, worker, server.args.get("n")))
        out.append((span, server, applied))
    return out


def leaf_intervals(tree: Tree) -> List[Interval]:
    """Monotonic intervals (s) of the tree's leaves: the phases of the
    first worker's ``round`` spans other than ``exchange`` (whose
    children are the client's ops), and the phases of everything
    :func:`round_ops` finds under those rounds."""
    spans = [(r, [p for p in r.phases if p[0] != "exchange"])
             for r in tree.rounds()]
    for members in round_ops(tree):
        spans += [(m, m.phases) for m in members if m is not None]
    return [(tree.mono(span, ts), tree.mono(span, ts + dur))
            for span, phases in spans for _name, ts, dur in phases]


def per_mb(tree: Tree) -> Dict[str, float]:
    """Median ms per MB, each op over the ``bytes`` its own span
    carries (no size is kept by hand): the client's GRAD and PARAM op
    spans, the server GRAD span's ``copy`` phase, and ``apply_exec``'s
    ``exec`` phase over the bytes of the GRAD it applied (joined by
    ``grad_n``); with the median ``queued`` phase of ``apply_exec``, the
    time an apply stood behind the one before it, in ms.  All ranks, the
    window."""
    rows: Dict[str, List[float]] = {}

    def add(key: str, ms: float, span: Any) -> None:
        if span.args.get("bytes"):
            rows.setdefault(key, []).append(ms * MB / span.args["bytes"])

    for op in ("GRAD", "PARAM"):
        for span in tree.named(op, "client"):
            add(f"{op} op", (span.t1 - span.t0) / 1e3, span)
    grads = {(s.pid, s.args.get("peer"), s.args.get("n")): s
             for s in tree.named("GRAD", "server")}
    for span in grads.values():
        add("server copy", phase_ms(span, "copy"), span)
    queued = []
    for span in tree.named("apply_exec", "server"):
        grad = grads.get((span.pid, span.args.get("peer"),
                          span.args.get("grad_n")))
        if grad is not None:
            add("apply_exec", phase_ms(span, "exec"), grad)
            queued.append(phase_ms(span, "queued"))
    out = {key: statistics.median(values) for key, values in rows.items()}
    if queued:
        out["queued ms"] = statistics.median(queued)
    return out


def traced_chip(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The trace's first chip: ``plane`` (its name), its ``ops`` and
    ``modules`` events ``(name, start_ns, duration_ns)`` and the traced
    window ``lo``, ``hi`` as ``chipbench/reduce.py`` bounds it, first to
    last ``bench.*`` annotation.  Parsed once a run."""
    key = CACHE_KEY + "_chip"
    if key not in run:
        run[key] = None
        path = xplane_path(run)
        events = reduce_mod.load(path) if path else None
        if events and events["annotations"] and events["chips"]:
            plane = sorted(events["chips"])[0]
            run[key] = {"plane": plane, **events["chips"][plane],
                        "lo": min(a[1] for a in events["annotations"]),
                        "hi": max(a[2] for a in events["annotations"])}
    return run[key]


def device_idle(run: Dict[str, Any]) -> Optional[List[Interval]]:
    """The idle gaps (profiler ns) of that chip over the traced window."""
    chip = traced_chip(run)
    if chip is None:
        return None
    lo, hi = chip["lo"], chip["hi"]
    busy = reduce_mod.union(reduce_mod.clip(
        [(s, s + d) for _n, s, d in chip["ops"]], lo, hi))
    return reduce_mod.complement(busy, lo, hi)


# -- the model's layer names on the device operations ---------------------------
#
# The name stack of an operation is read by ``chipbench/reduce.py``
# (:func:`chipbench.reduce.op_scopes`), which books the Mosaic kernels by
# it as well; the names stay reachable here for the readers.

AMBIGUOUS = reduce_mod.AMBIGUOUS
op_scopes = reduce_mod.op_scopes


def model_scopes(run: Dict[str, Any]) -> List[str]:
    """The scopes this run's device time is booked under: those its
    cell's configuration lists (``scopes`` in the configuration's file);
    a run that names no cell, as a test's hand-made one, is read under
    every scope any committed configuration lists."""
    cell = run.get("cell")
    if cell is not None:
        return list(cell.config["scopes"])
    return spec_mod.all_scopes()


def scope_ms_per_step(run: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Device ms per traced micro-step by the model's layer: the
    operations of the first chip that ran inside a run of the step's
    program (the ``XLA Modules`` events of ``reduction.step_module`` in
    the traced window, so another program's operations never count),
    each under the innermost of the configuration's scopes
    (:func:`model_scopes`) in its name stack; the rest under
    ``unscoped``, names of :data:`AMBIGUOUS` stack under
    ``ambiguous``, and the programs' own time under ``step``.  None
    without a device trace or a run of the step's program."""
    chip = traced_chip(run)
    module = run["reduction"].get("step_module")
    if chip is None or not module:
        return None
    steps = sorted((s, s + d) for name, s, d in chip["modules"]
                   if reduce_mod.module_short_name(name) == module
                   and chip["lo"] <= s and s + d <= chip["hi"])
    if not steps:
        return None
    starts = [s for s, _e in steps]
    scopes = op_scopes(xplane_path(run), chip["plane"])
    pattern = reduce_mod.scope_pattern(model_scopes(run))
    total: Dict[str, float] = {"step": sum(e - s for s, e in steps)}
    for name, start, dur in chip["ops"]:
        at = bisect.bisect_right(starts, start) - 1
        if at < 0 or start >= steps[at][1]:
            continue
        stack = scopes.get(name, "")
        found = pattern.findall(stack) if pattern else []
        key = ("ambiguous" if stack == AMBIGUOUS
               else found[-1] if found else "unscoped")
        total[key] = total.get(key, 0.0) + dur
    return {key: ns / 1e6 / len(steps) for key, ns in total.items()}
