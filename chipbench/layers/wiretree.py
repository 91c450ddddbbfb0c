"""Shared by the readers of the shm wire's own spans (PR 34): one
``wire`` span a message of 1 MB or more and end (``tx``, ``rx``) in the
merged Chrome trace the gang writes under ``MPIT_OBS_TRACE``, stamped by
the native transport on ``CLOCK_MONOTONIC`` (``mpit_tpu/comm/shm.py``),
joined ``tx`` to ``rx`` by the program's own joiner on the wire's
identity ``(src, dst, msg_id)`` (``mpit_tpu/obs/causal.py``
``join_wire``), and booked under the first worker's rounds that lie
whole in the window (``spantree.Tree.rounds``): the client's spans carry
their round's ``round=k``, a server's span takes the round of the
client end it is joined to.  Not a reader itself: no ``read``.

A message's args tile its flight: ``copy_ms`` + ``blocked_ms`` +
``away_ms`` is a ``tx``'s first attempt to its last chunk published,
``copy_ms`` + ``starved_ms`` + ``away_ms`` an ``rx``'s first chunk
published to message whole (``flight_ms`` either way).  The ``round``
span carries what the client's one thread did during ``exchange``:
``wire_tx_copy_ms``, ``wire_rx_copy_ms``, ``wire_poll_ms`` and
``sched_sleep_ms``.

Everything here returns None (or nothing) where the program recorded no
such span, as the parent of PR 34 does not and a cell without a
transport never will; nothing raises for that.
"""

from __future__ import annotations

import json
import statistics
from typing import Any, Dict, List, Optional, Tuple

from chipbench.layers import spantree

CACHE_KEY = "_wiretree"  # on the run dict: one parse for all readers
MB = 1e6  # bytes
PARTS = {"tx": ("copy_ms", "blocked_ms", "away_ms"),
         "rx": ("copy_ms", "starved_ms", "away_ms")}
ROUND_ARGS = ("wire_tx_copy_ms", "wire_rx_copy_ms", "wire_poll_ms",
              "sched_sleep_ms")


class Wire:
    """The wire spans of one run under its windowed rounds."""

    def __init__(self, tree: spantree.Tree, rounds: List[Any],
                 messages: List[Tuple[str, int, Any, Any]],
                 unmatched: int):
        self.tree = tree
        #: the first worker's ``round`` spans in the window
        self.rounds = rounds
        #: (op, round, tx span, rx span) of every joined message of a
        #: windowed round; op is ``GRAD`` (client to server) or ``PARAM``
        self.messages = messages
        #: wire spans of those rounds left without their other end
        self.unmatched = unmatched

    def longest(self, op: str, end: str, key: str) -> List[float]:
        """Per windowed round, the largest ``key`` over the ``end``
        (``tx`` or ``rx``) spans of the round's ``op`` messages."""
        by_round: Dict[int, float] = {}
        for name, k, tx, rx in self.messages:
            if name == op:
                span = tx if end == "tx" else rx
                value = float(span.args.get(key, 0.0))
                by_round[k] = max(by_round.get(k, value), value)
        return list(by_round.values())


def load(run: Dict[str, Any]) -> Optional[Wire]:
    """The run's wire spans, parsed once; None without any."""
    if CACHE_KEY not in run:
        run[CACHE_KEY] = _load(run)
    return run[CACHE_KEY]


def _load(run: Dict[str, Any]) -> Optional[Wire]:
    tree = spantree.load(run)
    if tree is None:
        return None
    from mpit_tpu.obs import causal

    join = getattr(causal, "join_wire", None)
    if join is None:
        return None  # a program that predates the wire's spans
    from mpit_tpu.ps import tags

    with open(run["obs_trace"]) as fh:
        events = json.load(fh).get("traceEvents", [])
    spans = causal.extract_spans(events, cat="wire")
    if not spans:
        return None
    ops = {tags.GRAD: "GRAD", tags.PARAM: "PARAM"}
    rounds = tree.rounds()
    windowed = {r.args.get("round") for r in rounds}
    worker = tree.first_worker
    pairs, loose = join(spans)

    def round_of(tx: Any, rx: Any) -> Optional[int]:
        client = tx if tx.pid == worker else rx
        return client.args.get("round") if client.pid == worker else None

    messages = [(ops[tx.args.get("tag")], round_of(tx, rx), tx, rx)
                for tx, rx in pairs
                if tx.args.get("tag") in ops
                and round_of(tx, rx) in windowed]
    unmatched = sum(1 for s in loose if s.pid == worker
                    and s.args.get("round") in windowed)
    return Wire(tree, rounds, messages, unmatched)


def median(values: List[float]) -> Optional[float]:
    return statistics.median(values) if values else None


def tile_error_pct(wire: Wire) -> float:
    """The worst of the messages' ``|copy + blocked or starved + away -
    flight|`` over their flight, both ends, in percent."""
    worst = 0.0
    for _op, _k, tx, rx in wire.messages:
        for span in (tx, rx):
            flight = float(span.args.get("flight_ms", 0.0))
            parts = sum(float(span.args.get(p, 0.0))
                        for p in PARTS[span.name])
            if flight > 0:
                worst = max(worst, 100.0 * abs(parts - flight) / flight)
    return worst


def exchange_parts(wire: Wire) -> Optional[Dict[str, float]]:
    """Median ms over the windowed rounds of the client thread's
    ``exchange``: in the copies into the servers' rings and out of its
    own, polling the rings, asleep in the scheduler's back-off, and the
    rest of the phase, which is the interpreter's (scheduler,
    coroutines, codec)."""
    rows = [r for r in wire.rounds if "wire_poll_ms" in r.args]
    if not rows:
        return None
    out = {key: statistics.median(float(r.args[key]) for r in rows)
           for key in ROUND_ARGS}
    out["exchange_ms"] = statistics.median(
        spantree.phase_ms(r, "exchange") for r in rows)
    out["interpreter_ms"] = statistics.median(
        spantree.phase_ms(r, "exchange")
        - sum(float(r.args[key]) for key in ROUND_ARGS) for r in rows)
    return out


def table(wire: Wire) -> List[str]:
    """The lines the metrics are cut from: for GRAD and PARAM, per
    server and end, the medians over the windowed rounds of ``flight``
    and its parts in ms [ms per MB of the message's own bytes]; the
    servers' threads between two closes of their op spans; the client's
    ``exchange``; and how well the parts tile and the ends join."""
    lines = []
    groups: Dict[Tuple[str, int, str], List[Any]] = {}
    for op, _k, tx, rx in wire.messages:
        server = rx.pid if op == "GRAD" else tx.pid
        groups.setdefault((op, server, "tx"), []).append(tx)
        groups.setdefault((op, server, "rx"), []).append(rx)
    for (op, server, end), spans in sorted(groups.items()):
        mb = statistics.median(s.args["bytes"] for s in spans) / MB
        cells = []
        for key in ("flight_ms",) + PARTS[end]:
            ms = statistics.median(float(s.args.get(key, 0.0))
                                   for s in spans)
            cells.append(f"{key[:-3]} {ms:.2f} [{ms / mb:.4f}]")
        lines.append(f"{op} server {server} {end} of rank "
                     f"{spans[0].pid}, {mb:.1f} MB, {len(spans)} messages: "
                     + ", ".join(cells))
    noted: Dict[Tuple[int, str], List[Any]] = {}
    for span in wire.tree.spans:
        if span.side == "server" and "wire_poll_ms" in span.args \
                and wire.tree.in_window(span):
            noted.setdefault((span.pid, span.name), []).append(span)
    for (server, op), spans in sorted(noted.items()):
        cells = [f"{key} {statistics.median(float(s.args[key]) for s in spans):.2f}"
                 for key in ("wire_span_ms",) + ROUND_ARGS]
        lines.append(f"server {server} thread up to the close of its {op} "
                     "span: " + ", ".join(cells))
    parts = exchange_parts(wire)
    if parts is not None:
        lines.append(
            "client exchange {exchange_ms:.2f} = tx_copy "
            "{wire_tx_copy_ms:.2f} + rx_copy {wire_rx_copy_ms:.2f} + poll "
            "{wire_poll_ms:.2f} + sleep {sched_sleep_ms:.2f} + interpreter "
            "{interpreter_ms:.2f}".format(**parts))
    lines.append(f"{len(wire.rounds)} rounds, {len(wire.messages)} joined "
                 f"messages, {wire.unmatched} wire spans of the window "
                 "without their other end, parts tile the flight to "
                 f"{tile_error_pct(wire):.4f}% at worst")
    return lines
