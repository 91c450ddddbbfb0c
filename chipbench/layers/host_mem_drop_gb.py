"""L2 servers + wire: MemAvailable at the opening of the window minus
its minimum inside the window, from the runner's 2 Hz poll of
``/proc/meminfo``.  The early warning for the loss of host memory that
PR 21 met with the chunked int8 composition (PERF.md section 7)."""


def read(run):
    lo, hi = run["summary"]["window"]
    inside = [avail for t, avail in run["mem_samples"] if lo <= t <= hi]
    before = [avail for t, avail in run["mem_samples"] if t <= lo]
    if not inside:
        return None
    at_open = before[-1] if before else inside[0]
    return (at_open - min(inside)) / 1e9
