"""Cuts a few rounds out of a traced run's merged span trace
(``.chipbench_runs/<run>/obs_trace.json``), so that the readers of the
program's own spans can be held to a recorded trace that is small enough
to commit.  Run where the traced run ran:

    python3 -m chipbench.fixtures.trim_obs_trace <obs_trace.json> <out.json> [rounds]

It takes ``rounds`` consecutive ``round`` spans of the first worker from
the middle of the run (2 unless told) and keeps every event of every rank
that lies between the first one's begin and the last one's end on the
monotonic clock the ranks share, a span only if it lies there whole (a
begin without its end, or an end without its begin, is dropped, and its
phases with it).  ``otherData.ranks`` keeps each rank's role, epoch offset
and clock id; ``otherData.fixture`` says what a reader's ``run`` needs:
``window`` (monotonic seconds: the cut, a microsecond wider), and
``worker_ranks``.  ``chipbench/fixtures/copies2.obs_trace.json`` is the
cut of a traced run of ``c111m-ps1w-su1`` (``copies2.expected.json``
says which, and what the readers gave on it).
"""

from __future__ import annotations

import json
import sys
from typing import Any, Dict, List


def trim(obj: Dict[str, Any], rounds: int = 2) -> Dict[str, Any]:
    events: List[dict] = obj["traceEvents"]
    ranks = (obj.get("otherData") or {}).get("ranks") or {}
    offset = {int(r): float(info["epoch_offset"])
              for r, info in ranks.items()}
    mono = lambda ev: ev["ts"] / 1e6 - offset.get(ev["pid"], 0.0)
    begins = [ev for ev in events if ev.get("ph") == "B"
              and ev.get("name") == "round" and ev.get("cat") == "ps_op"]
    worker = min(ev["pid"] for ev in begins)
    mine = sorted((ev for ev in begins if ev["pid"] == worker), key=mono)
    first = len(mine) // 2
    picked = mine[first:first + rounds]
    lo = mono(picked[0]) - 1e-6
    last_end = min(ev["ts"] for ev in events if ev.get("ph") == "E"
                   and ev.get("name") == "round" and ev["pid"] == worker
                   and ev["ts"] > picked[-1]["ts"])
    hi = last_end / 1e6 - offset.get(worker, 0.0) + 1e-6
    kept: List[dict] = []
    open_at: Dict[tuple, List[int]] = {}
    for ev in sorted(events, key=lambda e: e.get("ts", -1.0)):
        if ev.get("ph") == "M":
            kept.append(ev)
            continue
        if ev.get("ph") == "C" or not lo <= mono(ev) <= hi:
            continue
        if ev.get("ph") == "X" and mono(ev) + ev.get("dur", 0.0) / 1e6 > hi:
            continue
        key = (ev["pid"], ev["tid"])
        if ev["ph"] == "B":
            open_at.setdefault(key, []).append(len(kept))
        elif ev["ph"] == "E":
            if not open_at.get(key):
                continue  # its begin lies before the cut
            open_at[key].pop()
        kept.append(ev)
    dangling = {i for stack in open_at.values() for i in stack}
    dropped = {(kept[i]["pid"], kept[i]["tid"], kept[i]["ts"])
               for i in dangling}
    out = []
    for i, ev in enumerate(kept):
        if i in dangling:
            continue  # its end lies after the cut
        if ev.get("ph") == "X" and any(
                pid == ev["pid"] and tid == ev["tid"] and ts <= ev["ts"]
                for pid, tid, ts in dropped):
            continue  # a phase of a span that was dropped
        out.append(ev)
    return {
        "traceEvents": out, "displayTimeUnit": "ms",
        "otherData": {
            "ranks": {r: {k: info.get(k) for k in
                          ("role", "epoch_offset", "clock_id")}
                      for r, info in ranks.items()},
            "fixture": {"window": [lo, hi], "worker_ranks": [worker],
                        "rounds": [ev["args"]["round"] for ev in picked]}}}


def main(argv: List[str]) -> int:
    with open(argv[0]) as fh:
        obj = json.load(fh)
    small = trim(obj, int(argv[2]) if len(argv) > 2 else 2)
    with open(argv[1], "w") as fh:
        json.dump(small, fh, separators=(",", ":"))
    print(f"{argv[1]}: {len(small['traceEvents'])} of "
          f"{len(obj['traceEvents'])} events, rounds "
          f"{small['otherData']['fixture']['rounds']}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
