"""L3 shell + client: from the first ``async_*`` call of a sync round to
the return of ``wait``, the median over the first worker's rounds in the
window; the benchmark's timing proxy at the ``ParamClientAPI`` boundary."""

import statistics


def read(run):
    first = run["first_worker"]
    lo, hi = run["summary"]["window"]
    rounds = [1e3 * (t1 - t0) for t0, t1 in first["chipbench_worker"]["rounds"]
              if lo <= t0 and t1 <= hi]
    return statistics.median(rounds) if rounds else None
