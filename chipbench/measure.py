"""From the ranks' records to the end-to-end metrics and ``correct``.
Pure arithmetic on what ``chipbench/child.py`` reports; no jax.

Definitions (PERF.md section 2):

- ``tokens_per_s``: the median, over the sync rounds that lie whole in
  the window, of a round's prediction targets over the round's seconds
  (from the end of the round before it to its own fenced end, so the
  rounds tile the worker's time); the sum of that median over workers.
  A worker's window opens after its set-up and closes at the end of the
  first whole sync round that ends after ``--seconds``; with several
  workers the rounds counted are those inside the interval common to all
  windows.  A median of rounds, not tokens over the window's seconds,
  because on a host whose cores are shared a run loses whole seconds to
  a few rounds (PERF.md section 6, PR 22: the driver read spreads of 4.0
  and 5.9% on the window's mean); what the median leaves out is the
  per-layer ``round_stall_pct``, and the window's mean goes on an
  earlier line.
- ``step_ms_p50``: the median wall time of a fenced micro-step in the
  window, over all workers; only in the cells ``BENCHMARK.json`` lists
  for it, where a micro-step is a round and the chip sets its time.
- ``loss_at_budget``: mean training loss of the four micro-steps that
  end at the mix's ``token_budget``, counted per worker from step 0 with
  warm-up; the mean over workers.  ``correct`` wants it under the run's
  own first loss (step 0, before any update) by the mix's
  ``min_learning_nats``, a fixed number of nats: half of what the mix
  learnt by its budget when it was recorded.
- ``setup_s``: from the start of the command to the opening of the
  (common) window.
"""

from __future__ import annotations

import math
import statistics
from typing import Any, Dict, List, Tuple

class RunFailed(RuntimeError):
    """The run cannot be reported: no result line, exit code 1."""


def worker_ranks(results: Dict[int, Dict[str, Any]]) -> List[int]:
    return sorted(r for r, res in results.items()
                  if res.get("role") in ("worker", "local"))


def common_window(results: Dict[int, Dict[str, Any]]) -> Tuple[float, float]:
    marks = [results[r]["chipbench"]["marks"] for r in worker_ranks(results)]
    return (max(m["window_open"] for m in marks),
            min(m["window_close"] for m in marks))


def tokens_in(rows: List[List[Any]], tokens_per_step: int, lo: float,
              hi: float) -> float:
    total = 0.0
    for _k, t0, t1, _loss, _end in rows:
        inside = min(t1, hi) - max(t0, lo)
        if inside > 0 and t1 > t0:
            total += tokens_per_step * inside / (t1 - t0)
    return total


def round_rates(rows: List[List[Any]], tokens_per_step: int, lo: float,
                hi: float) -> List[List[float]]:
    """``[seconds, tokens/s]`` of each sync round that lies whole in
    ``[lo, hi]``: its micro-steps' spans, which tile the worker's time,
    and their tokens.  A traced run steps over the profiler's start and
    stop, so a round there is the sum of its spans, not end minus
    begin."""
    out: List[List[float]] = []
    seconds, steps, begin = 0.0, 0, None
    for _k, t0, t1, _loss, ends_round in rows:
        begin = t0 if begin is None else begin
        seconds += t1 - t0
        steps += 1
        if ends_round:
            if begin >= lo and t1 <= hi and seconds > 0:
                out.append([seconds, steps * tokens_per_step / seconds])
            seconds, steps, begin = 0.0, 0, None
    return out


def loss_at_budget(worker: Dict[str, Any], token_budget: int) -> float:
    per_step = worker["tokens_per_step"]
    last = token_budget // per_step - 1
    rows = {row[0]: row for row in worker["step_rows"]}
    if last < 3 or last not in rows:
        raise RunFailed(
            f"token budget {token_budget} not reached: the worker ended "
            f"after {len(rows)} micro-steps of {per_step} tokens")
    return statistics.fmean(rows[k][3] for k in range(last - 3, last + 1))


def first_loss(worker: Dict[str, Any]) -> float:
    """The loss of step 0: the seeded weights on the first batch."""
    return next(row[3] for row in worker["step_rows"] if row[0] == 0)


def stalls(in_window: Dict[int, List[List[Any]]]) -> List[List[Any]]:
    """``[rank, step, seconds]`` of the window's micro-steps that took
    over three times the median of their kind (a micro-step that ends a
    sync round holds the exchange, the others do not): a stalled host
    shows here and nowhere else."""
    out: List[List[Any]] = []
    for rank, rows in in_window.items():
        for ends_round in (True, False):
            spans = [(row[0], row[2] - row[1]) for row in rows
                     if bool(row[4]) == ends_round]
            if spans:
                limit = 3 * statistics.median(s for _k, s in spans)
                out += [[rank, k, round(s, 4)] for k, s in spans if s > limit]
    return sorted(out)[:20]


def summarise(results: Dict[int, Dict[str, Any]], t_command: float,
              token_budget: int, min_learning_nats: float) -> Dict[str, Any]:
    """The numbers every later step reads: metrics, counts, the window."""
    ranks = worker_ranks(results)
    if not ranks:
        raise RunFailed("no worker rank reported a result")
    workers = {r: results[r]["chipbench_worker"] for r in ranks}
    lo, hi = common_window(results)
    if hi <= lo:
        raise RunFailed(f"the workers' windows do not overlap ({lo}, {hi})")
    tokens = sum(tokens_in(w["step_rows"], w["tokens_per_step"], lo, hi)
                 for w in workers.values())
    in_window = {r: [row for row in w["step_rows"]
                     if row[0] >= w["first_window_step"]]
                 for r, w in workers.items()}
    rounds = {r: round_rates(in_window[r], w["tokens_per_step"], lo, hi)
              for r, w in workers.items()}
    if not all(rounds.values()):
        raise RunFailed(f"a worker has no whole sync round inside the "
                        f"common window ({lo}, {hi})")
    median_rate = {r: statistics.median(rate for _s, rate in rs)
                   for r, rs in rounds.items()}
    round_s = sum(s for rs in rounds.values() for s, _rate in rs)
    at_median_s = sum(s * rate / median_rate[r]
                      for r, rs in rounds.items() for s, rate in rs)
    attempted = sum(len(rows) for rows in in_window.values())
    failed = sum(1 for rows in in_window.values() for row in rows
                 if not math.isfinite(row[3]))
    failed += sum(1 for w in workers.values() if w["error"])
    step_s = [row[2] - row[1] for rows in in_window.values() for row in rows]
    return {
        "worker_ranks": ranks,
        "window": [lo, hi],
        "window_s": hi - lo,
        "tokens_per_s": sum(median_rate.values()),
        "tokens_per_s_window_mean": tokens / (hi - lo),
        "round_stall_pct": 100.0 * (1.0 - at_median_s / round_s),
        "loss_at_budget": statistics.fmean(
            loss_at_budget(w, token_budget) for w in workers.values()),
        "first_loss": statistics.fmean(first_loss(w)
                                       for w in workers.values()),
        "min_learning_nats": min_learning_nats,
        "setup_s": lo - t_command,
        "attempted": attempted + sum(1 for w in workers.values() if w["error"]),
        "failed": failed,
        "step_ms_p50": 1e3 * statistics.median(step_s),
        "micro_step_s_max": max(step_s),
        "stalls": stalls(in_window),
        "rounds_in_window": {
            r: sum(1 for row in rows if row[4])
            for r, rows in in_window.items()},
        "memory_peak_bytes": max(w["memory_peak_bytes"]
                                 for w in workers.values()),
    }


def correctness(results: Dict[int, Dict[str, Any]], summary: Dict[str, Any],
                platform: str, least_mosaic_calls: int,
                vector_len: int) -> List[str]:
    """Every reason the run is not ``correct``; empty means it is.
    ``platform`` is what a worker must report: ``tpu``, or ``cpu`` in the
    self-check's rehearsal, which prints no metric.  From the
    configuration's arithmetic: ``least_mosaic_calls``, the fewest
    ``tpu_custom_call``s the lowered step may hold, summed over the
    block's kernel families, and ``vector_len``, the parameters it says
    are exchanged, which the program's flat vector must have exactly."""
    why: List[str] = []
    ranks = summary["worker_ranks"]
    lo, hi = summary["window"]
    nodes = []
    for rank, res in sorted(results.items()):
        bench = res["chipbench"]
        inside = [c for c in bench["compiles"] if lo <= c[1] <= hi]
        if inside:
            why.append(f"rank {rank} compiled inside the window: "
                       f"{[c[0] for c in inside][:4]}")
        if rank in ranks:
            w = res["chipbench_worker"]
            if res.get("platform") != platform or res.get("device_count") != 1:
                why.append(f"worker {rank} on {res.get('platform')!r} with "
                           f"{res.get('device_count')} devices, not one "
                           f"{platform} device")
            if platform == "tpu":
                if len(res.get("chip_nodes", [])) != 1:
                    why.append(f"worker {rank} holds chip nodes "
                               f"{res.get('chip_nodes')}, not one")
                nodes += res.get("chip_nodes", [])
                if (res.get("mosaic_calls") or 0) < least_mosaic_calls:
                    why.append(
                        f"worker {rank}: {res.get('mosaic_calls')} "
                        f"tpu_custom_calls in the lowered step, under the "
                        f"{least_mosaic_calls} its kernel families need")
            if w["vector_len"] != vector_len:
                why.append(f"worker {rank}: the program's vector has "
                           f"{w['vector_len']} elements, the "
                           f"configuration's arithmetic says {vector_len}")
            if not w["stream_ok"]:
                why.append(f"worker {rank}: the copied stream differs from "
                           "the program's")
            if not w["reference"]["ok"]:
                why.append(f"worker {rank}: reference check failed: "
                           f"{w['reference']}")
            if w["error"]:
                why.append(f"worker {rank}: a round raised: {w['error']}")
            if not all(math.isfinite(row[3]) for row in w["step_rows"]):
                why.append(f"worker {rank}: a loss is not finite")
        elif res.get("role") == "server":
            if res.get("platform") != "cpu" or res.get("chip_nodes"):
                why.append(f"server {rank} is not a host role: "
                           f"{res.get('platform')!r} {res.get('chip_nodes')}")
            pushes = sum(results[r]["chipbench_worker"]["pushes"]
                         for r in ranks)
            rounds = sum(len(results[r]["chipbench_worker"]["rounds"])
                         for r in ranks)
            if res.get("grads_applied") != pushes:
                why.append(f"server {rank} applied {res.get('grads_applied')}"
                           f" gradients, its workers had {pushes} "
                           "acknowledged")
            if (res.get("params_served") or 0) < rounds:
                why.append(f"server {rank} served {res.get('params_served')}"
                           f" parameter pulls, under the {rounds} rounds")
    if len(set(nodes)) != len(nodes):
        why.append(f"workers share a chip node: {nodes}")
    learnt = summary["first_loss"] - summary["loss_at_budget"]
    if not learnt >= summary["min_learning_nats"]:
        why.append(f"loss_at_budget {summary['loss_at_budget']:.4f} is "
                   f"{learnt:.4f} nats under the first loss "
                   f"{summary['first_loss']:.4f}; the mix wants "
                   f"{summary['min_learning_nats']:.4f}: too little was "
                   "learnt")
    if summary["failed"]:
        why.append(f"{summary['failed']} micro-steps failed")
    return why
