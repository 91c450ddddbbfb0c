"""PS soak benchmark under skewed per-client compute — BiCNN/ptest2.lua.

The reference's ptest2 adds deliberately unequal fake compute per rank
(quadratic in rank index, BiCNN/ptest2.lua:66-70) to exercise the
asynchronous PS under stragglers: fast clients must keep pushing/pulling
at full rate while slow ones lag — the "workers never wait for each
other" property (SURVEY.md §5 race-tolerance).

This analog runs N clients with per-client compute delays over the
native shm transport and reports aggregate bandwidth plus the
fast/slow per-client round rates; the asynchrony check is that the
fastest client's rate is within a factor of its solo rate rather than
being dragged to the slowest client's pace.

Env knobs: MPIT_BENCH_MB (default 16), MPIT_BENCH_ROUNDS (default 20),
MPIT_BENCH_CLIENTS (default 3), MPIT_BENCH_SKEW (seconds of compute per
round for the slowest client, default 0.02; client i sleeps
skew * (i / (n-1))**2).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _common import join_checked, log as _log, shm_gang  # noqa: E402

MB = float(os.environ.get("MPIT_BENCH_MB", "16"))
ROUNDS = int(os.environ.get("MPIT_BENCH_ROUNDS", "20"))
NCLIENTS = int(os.environ.get("MPIT_BENCH_CLIENTS", "3"))
SKEW = float(os.environ.get("MPIT_BENCH_SKEW", "0.02"))


def main():
    size = int(MB * (1 << 20) / 4)
    nservers = 2
    _log(f"{nservers} servers + {NCLIENTS} skewed clients, "
         f"payload {size * 4 / 2**20:.1f} MB, skew {SKEW}s")

    # Per-client compute skew: client i burns skew*(i/(n-1))^2 seconds per
    # round (the quadratic shape of ptest2.lua:66-70).
    denom = max(NCLIENTS - 1, 1)
    delays = [SKEW * (i / denom) ** 2 for i in range(NCLIENTS)]
    elapsed = [0.0] * NCLIENTS

    with shm_gang(f"ptest2_{os.getpid()}", nservers, NCLIENTS, size) as (
        clients, _params, _grads
    ):
        def run_client(i):
            c = clients[i]
            t0 = time.perf_counter()
            for _ in range(ROUNDS):
                if delays[i]:
                    time.sleep(delays[i])  # fake compute
                c.async_recv_param()
                c.async_send_grad()
                c.wait()
            elapsed[i] = time.perf_counter() - t0

        workers = [
            threading.Thread(target=run_client, args=(i,), daemon=True)
            for i in range(NCLIENTS)
        ]
        t0 = time.perf_counter()
        for t in workers:
            t.start()
        join_checked(workers, 600, "skewed client rounds")
        wall = time.perf_counter() - t0

    rates = [ROUNDS / e if e else 0.0 for e in elapsed]
    mbs = 2 * ROUNDS * NCLIENTS * size * 4 / wall / 2**20
    _log(f"per-client rounds/s: {[f'{r:.2f}' for r in rates]}; "
         f"aggregate {mbs:.1f} MB/s")
    # Asynchrony: fastest client should not be dragged to slowest's pace.
    # join_checked above guarantees every client finished, so rates are
    # all positive and the ratio is finite (valid JSON).
    ratio = max(rates) / min(rates)
    print(json.dumps({
        "metric": "ps_soak_bandwidth_skewed",
        "value": round(mbs, 1),
        "unit": "MB/s",
        "clients": NCLIENTS,
        "fast_slow_ratio": round(ratio, 2),
    }))


if __name__ == "__main__":
    main()
