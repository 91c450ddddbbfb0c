"""From a profiler trace (``.xplane.pb``) to numbers: the device's busy
union and idle share over the traced window, time per device operation,
time of the Mosaic kernels, all together and under each of the model's
scopes, time of the step's program, and the idle gaps named by what the
host was doing.  Kept with the benchmark so that
every PR computes the same number in the same way; checked by the
self-check against ``chipbench/fixtures/steps4.xplane.pb``.

What a TPU trace holds (read off the fixture by hand, PR 22): a plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event
per executed program, named ``jit_<fn>(<fingerprint>)``) and ``XLA Ops``
(one event per HLO operation, named by its HLO text, ``%name = ...``; a
Pallas kernel compiled by Mosaic carries
``custom_call_target="tpu_custom_call"``), and a plane ``/host:CPU``
whose thread lines hold the ``TraceAnnotation`` events by name.  Times
are nanoseconds on one clock; the device's and the host's differ by
about a millisecond, which is far under the gaps that are named here.

Needs jax only for ``jax.profiler.ProfileData``; initialises no backend.
"""

from __future__ import annotations

import collections
import re
import statistics
from typing import Any, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
ANNOTATION_PREFIX = "bench."
MOSAIC_MARK = "tpu_custom_call"
SMALL_GAP_NS = 10_000.0  # shorter gaps are the device's own op-to-op slack


_HLO = re.compile(r"^%(\S+) = (.*?) ([a-z][a-z\-]*)\(")


def op_label(hlo_text: str) -> str:
    """``%fusion.2 = f32[8,2048]{...} fusion(...), kind=kLoop`` ->
    ``[fusion] f32[8,2048]{...}``: what the operation is and the start of
    its result's shape, without its own name, so that the same operation
    of every layer falls under one label and a reader of ``breakdown``
    can tell a matrix product from a copy."""
    match = _HLO.match(hlo_text)
    if not match:
        return hlo_text.split(" = ", 1)[0].lstrip("%")[:80]
    _name, shape, opcode = match.groups()
    if MOSAIC_MARK in hlo_text:
        opcode = "mosaic kernel"
    return f"[{opcode}] {shape[:64]}"


def module_short_name(name: str) -> str:
    """``jit_loss(1751...)`` -> ``jit_loss``."""
    return name.split("(", 1)[0]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def complement(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    gaps, at = [], lo
    for start, end in busy:
        if start > at:
            gaps.append((at, start))
        at = max(at, end)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def load(path: str) -> Dict[str, Any]:
    """The events the reduction uses, as plain tuples."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips: Dict[str, Dict[str, list]] = {}
    annotations: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = chips.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    chip["ops"] = [(e.name, e.start_ns, e.duration_ns)
                                   for e in line.events]
                elif line.name == "XLA Modules":
                    chip["modules"] = [(e.name, e.start_ns, e.duration_ns)
                                       for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                annotations += [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                    if e.name.startswith(ANNOTATION_PREFIX)]
    return {"chips": chips, "annotations": sorted(annotations,
                                                  key=lambda a: a[1])}


def _host_activity(annotations: Sequence[Tuple[str, float, float]],
                   at: float) -> str:
    """What the host was doing at time ``at``: the innermost benchmark
    annotation that covers it.  Inside ``bench.dispatch`` (the program's
    ``opt.step``) the time before the proxy's ``bench.ps_round`` is the
    wait for the backward and the d2h, the time after it the h2d."""
    inner: Optional[Tuple[str, float, float]] = None
    for ann in annotations:
        if ann[1] <= at < ann[2] and (inner is None or ann[1] >= inner[1]):
            inner = ann
    if inner is None:
        return "outside the benchmark's annotations"
    if inner[0] != "bench.dispatch":
        return inner[0]
    for name, start, end in annotations:
        if name == "bench.ps_round" and inner[1] <= start and end <= inner[2]:
            return ("bench.dispatch.before_ps_round" if at < start
                    else "bench.dispatch.after_ps_round")
    return inner[0]


# -- the model's layer names on the device operations ---------------------------
#
# ``jax.profiler.ProfileData`` gives an event's own stats but not those of
# its metadata, and the name ``jax.named_scope`` gave an operation
# (``jit(loss)/.../head_loss/dot_general``) is a stat of the event's
# metadata in the device plane.  So the plane's metadata is read straight
# from the file: the few fields of ``XSpace`` that are needed, decoded by
# hand (tsl/profiler/protobuf/xplane.proto; only varints and
# length-delimited fields occur in them).

SCOPE_STAT = "tf_op"  # the metadata stat that holds an operation's name stack
AMBIGUOUS = "?"  # an event name that two name stacks claim in one plane


def scope_pattern(scopes: Sequence[str]) -> Optional["re.Pattern[str]"]:
    """Finds the model's scopes, as the configuration's file lists them,
    in a name stack (``transpose(jvp(attn))`` holds ``attn``); None for
    no scopes."""
    if not scopes:
        return None
    return re.compile(r"\b(%s)\b" % "|".join(re.escape(s) for s in scopes))


def _varint(buf: bytes, at: int) -> Tuple[int, int]:
    value, shift = 0, 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, at


def _fields(buf: bytes):
    """(field number, wire type, value) of one protobuf message."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire == 1:
            value, at = buf[at:at + 8], at + 8
        elif wire == 5:
            value, at = buf[at:at + 4], at + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane message")
        yield number, wire, value




def op_scopes(path: str, plane_name: str) -> Dict[str, str]:
    """``{event name: name stack}`` for the operations of the device
    plane ``plane_name``: an ``XLA Ops`` event's name (its HLO text) to
    the name jax gave the operation, scopes included.  A name that two
    metadata entries of the plane give different stacks (the same HLO
    text in two programs) maps to :data:`AMBIGUOUS`.  Empty where the
    plane holds no such stat."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out: Dict[str, str] = {}
    for number, wire, plane in _fields(space):
        if number != 1 or wire != 2:
            continue
        name, metadata, stat_names = "", [], {}
        for f, w, value in _fields(plane):
            if f == 2 and w == 2:
                name = bytes(value).decode("utf-8", "replace")
            elif f == 4 and w == 2:
                metadata.append(value)
            elif f == 5 and w == 2:
                entry = dict((n, v) for n, _w, v in _fields(value))
                meta = dict((n, v) for n, _w, v in _fields(entry.get(2, b"")))
                stat_names[meta.get(1, entry.get(1))] = \
                    bytes(meta.get(2, b"")).decode("utf-8", "replace")
        if name != plane_name:
            continue
        for entry in metadata:
            event_name, stack = "", ""
            for f, w, value in _fields(entry):
                if f != 2 or w != 2:
                    continue
                for g, gw, part in _fields(value):
                    if g == 2 and gw == 2:
                        event_name = bytes(part).decode("utf-8", "replace")
                    elif g == 5 and gw == 2:
                        stat = dict((n, v) for n, _w, v in _fields(part))
                        if stat_names.get(stat.get(1)) == SCOPE_STAT:
                            text = stat.get(5)
                            if text is None and 7 in stat:  # a reference
                                text = stat_names.get(stat[7], "").encode()
                            stack = bytes(text or b"").decode(
                                "utf-8", "replace")
            if event_name and stack:
                known = out.setdefault(event_name, stack)
                if known != stack:
                    out[event_name] = AMBIGUOUS
    return out



def reduce_trace(path: str, step_module: str,
                 scopes: Sequence[str] = ()) -> Dict[str, Any]:
    """Everything the per-layer readers and ``breakdown`` take from one
    trace.  Seconds unless the key says otherwise.  With several chips
    in the trace, busy time is averaged over them.  ``step_module`` is
    the name of the micro-step's program as the mix's file gives it
    (``jit_loss``); a trace that holds no run of it gives no step time,
    and ``modules`` says which programs it does hold.

    ``scopes`` are the model's, from the configuration's file.  Beside
    ``mosaic_s`` and ``mosaic_calls``, every Mosaic kernel together,
    ``mosaic_by_scope`` has ``{scope: [calls, seconds]}`` of the Mosaic
    calls whose name stack (:func:`op_scopes`) holds exactly that one
    scope: a kernel family's time is the time under its scope
    (``arithmetic/<module>.py`` ``kernels``), so one block's kernels are
    not booked as another's.  A call whose stack holds none of the
    scopes, or two of them, or whose name two stacks claim, counts for no
    family: ``mosaic_no_family`` has ``[label, calls, seconds]`` of
    those."""
    events = load(path)
    pattern = scope_pattern(scopes)
    annotations = events["annotations"]
    if not annotations or not events["chips"]:
        return {"ok": False, "why": "no device plane or no bench.* "
                "annotation in the trace", "chips": len(events["chips"]),
                "annotations": len(annotations)}
    lo = min(a[1] for a in annotations)
    hi = max(a[2] for a in annotations)
    busy_s: List[float] = []
    op_time: Dict[str, float] = collections.Counter()
    op_count: Dict[str, int] = collections.Counter()
    mosaic_ns = 0.0
    mosaic_calls = 0
    by_scope: Dict[str, List[float]] = {}  # scope -> [calls, ns]
    no_family: Dict[str, List[float]] = {}  # label -> [calls, ns]
    module_ns: Dict[str, List[float]] = collections.defaultdict(list)
    gap_by_activity: Dict[str, float] = collections.Counter()
    longest_gap_ns = 0.0
    for plane_name, chip in events["chips"].items():
        stacks = op_scopes(path, plane_name) if pattern else {}
        spans = clip([(s, s + d) for _n, s, d in chip["ops"]], lo, hi)
        busy = union(spans)
        busy_s.append(sum(e - s for s, e in busy) / 1e9)
        for name, start, dur in chip["ops"]:
            if start + dur <= lo or start >= hi:
                continue
            label = op_label(name)
            op_time[label] += dur / 1e9
            op_count[label] += 1
            if MOSAIC_MARK in name:
                mosaic_ns += dur
                mosaic_calls += 1
                stack = stacks.get(name, "")
                found = (set(pattern.findall(stack))
                         if pattern and stack != AMBIGUOUS else set())
                row = (by_scope.setdefault(found.pop(), [0, 0.0])
                       if len(found) == 1
                       else no_family.setdefault(label, [0, 0.0]))
                row[0] += 1
                row[1] += dur
        for name, start, dur in chip["modules"]:
            if lo <= start and start + dur <= hi:
                module_ns[module_short_name(name)].append(dur)
        for start, end in complement(busy, lo, hi):
            longest_gap_ns = max(longest_gap_ns, end - start)
            if end - start < SMALL_GAP_NS:
                gap_by_activity["between device ops (<10us each)"] += \
                    (end - start) / 1e9
                continue
            # cut the gap where the host's activity changes
            cuts = sorted({start, end} | {t for _n, s, e in annotations
                                          for t in (s, e) if start < t < end})
            for a, b in zip(cuts, cuts[1:]):
                gap_by_activity[_host_activity(annotations, (a + b) / 2)] += \
                    (b - a) / 1e9
    n_chips = len(events["chips"])
    step_runs = module_ns.get(step_module, [])
    top = lambda table: [[k, v] for k, v in sorted(
        table.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "ok": True,
        "chips": n_chips,
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_s) / n_chips,
        "idle_pct": 100.0 * (1.0 - sum(busy_s) / n_chips / ((hi - lo) / 1e9)),
        "step_module": step_module,
        "step_module_runs": len(step_runs),
        "modules": {m: [len(runs), sum(runs) / 1e6]  # runs, total ms
                    for m, runs in module_ns.items()},
        "step_module_ms_p50": (statistics.median(step_runs) / 1e6
                               if step_runs else None),
        "mosaic_s": mosaic_ns / 1e9 / n_chips,
        "mosaic_calls": mosaic_calls,
        "mosaic_by_scope": {scope: [int(calls), ns / 1e9 / n_chips]
                            for scope, (calls, ns) in by_scope.items()},
        "mosaic_no_family": [[label, int(calls), ns / 1e9 / n_chips]
                             for label, (calls, ns) in no_family.items()],
        "longest_gap_s": longest_gap_ns / 1e9,
        "device_ops": top({f"{k} x{op_count[k]}": v / n_chips
                           for k, v in op_time.items()}),
        "idle_gaps": top({k: v / n_chips for k, v in gap_by_activity.items()}),
    }


if __name__ == "__main__":
    import json
    import sys

    print(json.dumps(reduce_trace(sys.argv[1], sys.argv[2], sys.argv[3:]),
                     indent=1))
