"""L2 servers + wire: the client's GRAD op span per shard, from
``encode`` through ``send`` to ``ack``: the wire plus that server's
apply.  The median over the window of the program's own op spans, read
from the merged Chrome trace the gang writes under ``MPIT_OBS_TRACE``
(``cat`` ``ps_op``, ``B``/``E`` pairs per ``pid``/``tid``, wall
microseconds)."""

import json
import os
import statistics


def read(run):
    path = run.get("obs_trace")
    if not path or not os.path.exists(path):
        return None
    with open(path) as fh:
        events = json.load(fh)["traceEvents"]
    first = run["first_worker"]
    offset = first["chipbench"]["marks"]["epoch_offset"]
    lo, hi = ((t + offset) * 1e6 for t in run["summary"]["window"])
    begun, spans = {}, []
    for ev in events:
        if ev.get("cat") != "ps_op" or ev.get("name") != "GRAD":
            continue
        key = (ev["pid"], ev["tid"])
        if ev["ph"] == "B":
            begun[key] = ev
        elif ev["ph"] == "E" and key in begun:
            start = begun.pop(key)
            if start["args"].get("side") == "client" and \
                    lo <= start["ts"] and ev["ts"] <= hi:
                spans.append((ev["ts"] - start["ts"]) / 1e3)
    return statistics.median(spans) if spans else None
