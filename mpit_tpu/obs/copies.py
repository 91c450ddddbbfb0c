"""The round's passes over the host's memory, from the merged trace.

A sync round of a PS gang moves every byte of the vector over the host's
memory several times, on several threads of several ranks at once: the
chip's DMA lands the payload piece by piece (``d2h``), the client's
thread copies it into the servers' rings (``ring_in``), each server
copies it out (``ring_out``), each server's update rule sweeps its shard
(``apply``), the fresh parameters go back through the rings
(``ring_in`` on the server, ``ring_out`` on the client) and up to the
chip (``h2d``).  Each of these is recorded where it happens
(docs/OBSERVABILITY.md, *The round's host copies*); this module puts
them in one form, a :class:`Copy`, and answers what no single record
can: how many bytes a byte of the vector costs the host's memory a
round, what the memory gave all copiers together, and what it gave when
one, two, or three and more of them were at work at once.

A :class:`Copy` comes from one of three records:

- a ``copy`` span with a ``pass`` (``optim/sync.py``: the stream
  thread's ``d2h`` and ``h2d`` piece spans).  A ``d2h`` piece's pass is
  the DMA's, not the thread's: it runs from the later of the cut's
  dispatch (``issued_ms`` before the span's ``wait``) and the landing of
  the piece before it (the engine takes them in order) to the end of
  ``wait``; where the piece was also copied into the mirror
  (``streams`` 3), ``hand`` is a second copy of twice its bytes.  An
  ``h2d`` piece's pass is the span: the dispatch of its ``device_put``
  and paste.
- an interval of a ``wire`` span's ``copies`` (``comm/shm.py``): a
  ``tx``'s are ``ring_in``, an ``rx``'s ``ring_out``, at the native
  stamps.
- the ``exec`` phase of a server's ``apply_exec`` span with
  ``bytes_moved`` (``ps/server.py``): ``apply``.

``bytes`` is what the pass copied (for ``apply`` what the sweep read and
wrote) and ``moved`` what that cost the memory: a DMA's landing writes
its bytes and an upload reads them (1 stream), a ``memcpy`` reads and
writes them (2), a record that knows better says so (``streams``,
``bytes_moved``).  All times are seconds on the monotonic clock the
ranks of one host share (``obs/trace.py`` refuses to merge ranks of
different clocks).
"""

from __future__ import annotations

from typing import (Any, Callable, Dict, Iterable, List, NamedTuple,
                    Optional, Tuple)

#: the closed list of ``pass`` names, in the order a byte meets them
PASSES = ("d2h", "ring_in", "ring_out", "apply", "h2d")
#: memory streams of a copied byte, by pass, where the record says none
STREAMS = {"d2h": 1, "ring_in": 2, "ring_out": 2, "h2d": 1}
#: the classes of the concurrency table: copiers at work at once
CLASSES = ("1", "2", "3plus")
GB = 1e9


class Copy(NamedTuple):
    """One pass of some bytes over the host's memory."""

    rank: int
    thread: str
    kind: str  # one of PASSES
    t0: float
    t1: float
    bytes: int
    moved: int  # bytes of memory traffic: ``bytes`` times its streams


def _landings(piece_spans: Iterable[Any]):
    """The DMA's pass of every ``d2h`` piece span, in the order the
    engine took them: ``(span, begin, landed, waited)`` in the spans' own
    exported microseconds, a thread's pieces each from the later of its
    cut's dispatch and the landing of the piece before it to the end of
    its ``wait`` (``waited``: that phase's length)."""
    landed: Dict[Tuple[int, str], float] = {}
    for span in sorted(piece_spans, key=lambda s: s.t0):
        if span.args.get("pass") != "d2h":
            continue
        ts, dur = next(((ts, dur) for name, ts, dur in span.phases
                        if name == "wait"), (span.t0, span.t1 - span.t0))
        key = (span.pid, str(span.args.get("thread", "")))
        issued = ts - float(span.args.get("issued_ms", 0.0)) * 1e3
        begin = min(max(issued, landed.get(key, issued)), ts + dur)
        landed[key] = ts + dur
        yield span, begin, ts + dur, dur


def records(copy_spans: Iterable[Any], wire_spans: Iterable[Any],
            op_spans: Iterable[Any],
            mono: Callable[[Any, float], float]) -> List[Copy]:
    """Every pass the three kinds of span hold (``obs/causal.py``
    ``extract_spans`` of categories ``copy``, ``wire`` and ``ps_op``),
    in order of begin.  ``mono(span, ts_us)`` takes an exported
    timestamp of ``span``'s rank to monotonic seconds."""
    out: List[Copy] = []
    copy_spans = list(copy_spans)
    for span, begin, landed, _waited in _landings(copy_spans):
        nbytes = int(span.args.get("bytes", 0))
        out.append(Copy(span.pid, "dma", "d2h", mono(span, begin),
                        mono(span, landed), nbytes, nbytes))
        more = int(span.args.get("streams", 1)) - 1
        for name, ts, dur in span.phases:
            if name == "hand" and more > 0:  # copied into the mirror too
                out.append(Copy(span.pid, str(span.args.get("thread", "")),
                                "d2h", mono(span, ts), mono(span, ts + dur),
                                nbytes, nbytes * more))
    for span in copy_spans:
        kind = span.args.get("pass")
        if kind == "d2h" or kind not in PASSES:
            continue  # above; or a span that closes others (``h2d_shard``)
        nbytes = int(span.args.get("bytes", 0))
        out.append(Copy(span.pid, str(span.args.get("thread", "")), kind,
                        mono(span, span.t0), mono(span, span.t1), nbytes,
                        nbytes * int(span.args.get("streams",
                                                   STREAMS.get(kind, 1)))))
    for span in wire_spans:
        kind = "ring_in" if span.name == "tx" else "ring_out"
        for begin, end, nbytes in span.args.get("copies") or ():
            out.append(Copy(span.pid, "wire", kind, begin * 1e-9,
                            end * 1e-9, int(nbytes),
                            int(nbytes) * STREAMS[kind]))
    for span in op_spans:
        moved = span.args.get("bytes_moved")
        if span.name != "apply_exec" or not moved:
            continue
        for name, ts, dur in span.phases:
            if name == "exec":
                out.append(Copy(span.pid, "apply", "apply", mono(span, ts),
                                mono(span, ts + dur), int(moved),
                                int(moved)))
    return sorted(out, key=lambda c: c.t0)


def vector_bytes(worker: int, copies: List[Copy]) -> int:
    """The payload a round of ``worker`` staged: what its DMA landed."""
    return sum(c.bytes for c in copies if c.kind == "d2h"
               and c.thread == "dma" and c.rank == worker)


def within(copies: List[Copy], lo: float, hi: float) -> List[Copy]:
    """The passes whose middle lies in ``[lo, hi]`` (a round's span)."""
    return [c for c in copies if lo <= (c.t0 + c.t1) / 2 <= hi]


def stretches(copies: List[Copy]) -> List[Tuple[float, float, List[Copy]]]:
    """The time from the first pass's begin to the last one's end, cut
    at every begin and end: ``(from, to, the passes at work between)``,
    in order, the stretches in which none is at work included."""
    edges = sorted({t for c in copies for t in (c.t0, c.t1)})
    starts = sorted(copies, key=lambda c: c.t0)
    out, active, at = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while at < len(starts) and starts[at].t0 <= a:
            active.append(starts[at])
            at += 1
        active = [c for c in active if c.t1 > a]
        out.append((a, b, [c for c in active if c.t0 < c.t1]))
    return out


def label(active: Iterable[Copy]) -> str:
    """The set of passes at work, by name in :data:`PASSES`' order."""
    kinds = {c.kind for c in active}
    return "+".join(p for p in PASSES if p in kinds) or "none"


def union_seconds(copies: List[Copy]) -> float:
    """How long at least one of the passes was at work."""
    return sum(b - a for a, b, active in stretches(copies) if active)


def by_class(copies: List[Copy]) -> Dict[str, Dict[str, Any]]:
    """The concurrency table of some passes: for ``1``, ``2`` and
    ``3plus`` copiers at work at once, the ``seconds`` that was so, the
    ``bytes`` of memory traffic in them (a pass's ``moved`` spread
    evenly over its length), the seconds by the set of passes that met
    (``passes``: :func:`label` -> s) and each kind of pass's own seconds
    and bytes there (``own``: kind -> ``[s, bytes]``: what one copier of
    that kind got in that company)."""
    table = {k: {"seconds": 0.0, "bytes": 0.0, "passes": {}, "own": {}}
             for k in CLASSES}
    for a, b, active in stretches(copies):
        if not active:
            continue
        row = table[CLASSES[min(len(active), 3) - 1]]
        row["seconds"] += b - a
        for c in active:
            share = c.moved * (b - a) / (c.t1 - c.t0)
            row["bytes"] += share
            own = row["own"].setdefault(c.kind, [0.0, 0.0])
            own[0] += b - a
            own[1] += share
        met = label(active)
        row["passes"][met] = row["passes"].get(met, 0.0) + (b - a)
    return table


def add_tables(tables: Iterable[Dict[str, Dict[str, Any]]]
               ) -> Dict[str, Dict[str, Any]]:
    """The sum of several rounds' tables of :func:`by_class`."""
    total = by_class([])
    for table in tables:
        for k, row in table.items():
            total[k]["seconds"] += row["seconds"]
            total[k]["bytes"] += row["bytes"]
            for met, s in row["passes"].items():
                total[k]["passes"][met] = (
                    total[k]["passes"].get(met, 0.0) + s)
            for kind, (s, nbytes) in row["own"].items():
                own = total[k]["own"].setdefault(kind, [0.0, 0.0])
                own[0] += s
                own[1] += nbytes
    return total


def gbps(row: Dict[str, Any]) -> Optional[float]:
    return row["bytes"] / row["seconds"] / GB if row["seconds"] > 0 else None


def class_lines(table: Dict[str, Dict[str, Any]]) -> List[str]:
    """The concurrency table as lines: class, seconds, GB, GB/s, and
    which passes met in it (the three that met longest, with their
    share of the class's time)."""
    lines = []
    for k in CLASSES:
        row = table[k]
        rate = gbps(row)
        met = sorted(row["passes"].items(), key=lambda kv: -kv[1])[:3]
        lines.append(
            f"{k} at work: {row['seconds']:.4f} s, "
            f"{row['bytes'] / GB:.3f} GB, "
            + (f"{rate:.2f} GB/s" if rate is not None else "no GB/s")
            + (": " + ", ".join(
                f"{name} {100.0 * s / row['seconds']:.0f}%"
                for name, s in met) if met else ""))
    for kind in PASSES:
        rates = [f"{table[k]['own'][kind][1] / table[k]['own'][kind][0] / GB:.2f}"
                 if table[k]["own"].get(kind, [0.0])[0] > 0 else "-"
                 for k in CLASSES]
        lines.append(f"a {kind} pass's own GB/s of traffic with "
                     f"{', '.join(CLASSES)} at work: {', '.join(rates)}")
    return lines


def overlap_by_passes(copies: List[Copy],
                      gaps: Iterable[Tuple[float, float]]
                      ) -> Dict[str, float]:
    """The length of ``gaps`` (intervals on the passes' clock, in order
    and apart) by the set of passes at work meanwhile: :func:`label` ->
    length, ``none`` where no pass ran."""
    cuts = stretches(copies)
    out: Dict[str, float] = {}
    at = 0
    for lo, hi in gaps:
        covered = 0.0
        while at < len(cuts) and cuts[at][1] <= lo:
            at += 1
        i = at
        while i < len(cuts) and cuts[i][0] < hi:
            a, b, active = cuts[i]
            piece = min(b, hi) - max(a, lo)
            if piece > 0:
                met = label(active)
                out[met] = out.get(met, 0.0) + piece
                covered += piece
            i += 1
        if hi - lo > covered:  # before the first pass or after the last
            out["none"] = out.get("none", 0.0) + (hi - lo) - covered
    return out


# -- the stream's thread, piece by piece --------------------------------------


def stage(piece_spans: List[Any]) -> Optional[Dict[str, Any]]:
    """What the stream's thread did over some ``d2h`` piece spans (a
    round's): ``pieces``, ``bytes``, the ms in each phase
    (``wait_ms``, ``hand_ms``, ``held_ms``, ``issue_ms``), ``dma_s``
    (the engine's time: every piece from the later of its cut's
    dispatch and the landing before it to its own landing),
    ``in_flight`` (pieces by the cuts outstanding when they were
    popped) and ``waited`` (pieces the thread had to wait 50 us or more
    for).  None without a span."""
    spans = [s for s in piece_spans if s.args.get("pass") == "d2h"]
    if not spans:
        return None
    out: Dict[str, Any] = {
        "pieces": len(spans),
        "bytes": sum(int(s.args.get("bytes", 0)) for s in spans),
        "dma_s": 0.0, "waited": 0, "in_flight": {}}
    for phase in ("wait", "hand", "held", "issue"):
        out[f"{phase}_ms"] = sum(
            dur for s in spans for name, _ts, dur in s.phases
            if name == phase) / 1e3
    for s, begin, landed, waited in _landings(spans):
        out["dma_s"] += (landed - begin) / 1e6
        out["waited"] += waited >= 50.0
        k = int(s.args.get("in_flight", 0))
        out["in_flight"][k] = out["in_flight"].get(k, 0) + 1
    return out


def stage_line(row: Dict[str, Any]) -> str:
    busy = row["wait_ms"] + row["hand_ms"] + row["issue_ms"]
    flights = ", ".join(f"{k}: {n}" for k, n in sorted(
        row["in_flight"].items()))
    return (
        f"{row['pieces']} pieces, {row['bytes'] / 1e6:.1f} MB: wait "
        f"{row['wait_ms']:.2f} + hand {row['hand_ms']:.2f} + held "
        f"{row['held_ms']:.2f} + issue {row['issue_ms']:.2f} ms; the DMA "
        f"{row['dma_s'] * 1e3:.2f} ms = "
        f"{row['bytes'] / max(row['dma_s'], 1e-12) / GB:.2f} GB/s; issue "
        f"{100.0 * row['issue_ms'] / max(busy, 1e-12):.1f}% of the thread's "
        f"time not held; waited for {row['waited']} pieces; in flight at "
        f"pop {{{flights}}}")


# -- a merged trace, without the benchmark ------------------------------------


def section(events: List[dict], other: dict) -> Optional[Dict[str, Any]]:
    """The host copies of a merged trace for ``python -m mpit_tpu.obs
    analyze``: over every worker round that holds a ``d2h`` piece span,
    the passes a byte makes, what the memory gave while any copier ran,
    the concurrency table, the stream's thread, and the client's sleeps
    by name.  None where the trace holds no ``copy`` span."""
    from mpit_tpu.obs import causal

    copy_spans = causal.extract_spans(events, cat="copy")
    if not copy_spans:
        return None
    offsets = {}
    for rank, info in (other.get("ranks") or {}).items():
        if isinstance(info, dict) and "epoch_offset" in info:
            offsets[int(rank)] = float(info["epoch_offset"])

    def mono(span: Any, ts_us: float) -> float:
        return ts_us / 1e6 - offsets.get(span.pid, 0.0)

    ops = causal.extract_spans(events)
    copies = records(copy_spans, causal.extract_spans(events, cat="wire"),
                     ops, mono)
    tables, passes, rates, stages, sleeps = [], [], [], [], []
    for r in (s for s in ops if s.name == "round"):
        mine = within(copies, mono(r, r.t0), mono(r, r.t1))
        vector = vector_bytes(r.pid, mine)
        if not vector:
            continue
        moved = sum(c.moved for c in mine)
        tables.append(by_class(mine))
        passes.append(moved / vector)
        rates.append(moved / max(union_seconds(mine), 1e-12) / GB)
        row = stage([s for s in copy_spans if s.pid == r.pid
                     and s.args.get("round") == r.args.get("round")])
        if row is not None:
            stages.append(row)
        sleeps.append({k: float(v) for k, v in r.args.items()
                       if k.startswith("sleep_") and k.endswith("_ms")})
    if not tables:
        return None
    return {"rounds": len(tables), "passes_per_byte": passes,
            "gbps": rates, "classes": add_tables(tables),
            "stage": stages, "sleeps": sleeps}


def render(sec: Dict[str, Any]) -> List[str]:
    import statistics

    lines = [
        f"host copies over {sec['rounds']} round(s): a byte of the vector "
        f"moves {statistics.median(sec['passes_per_byte']):.2f} times "
        f"(median), {statistics.median(sec['gbps']):.2f} GB/s while any "
        "copier ran"]
    lines += ["  " + line for line in class_lines(sec["classes"])]
    if sec["stage"]:
        mid = sorted(sec["stage"], key=lambda row: row["wait_ms"])[
            len(sec["stage"]) // 2]
        lines.append("  stream (the round of the median wait): "
                     + stage_line(mid))
    named = sorted({k for row in sec["sleeps"] for k in row})
    if named:
        lines.append("  client sleeps in exchange (median ms): " + ", ".join(
            f"{k[len('sleep_'):-len('_ms')]} "
            f"{statistics.median(row.get(k, 0.0) for row in sec['sleeps']):.2f}"
            for k in named))
    return lines
