"""Shared helpers for the benchmark scripts."""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def emit_json(rec: dict, out_path: str = "") -> None:
    """One JSON line to stdout (the bench contract) + optional append to
    ``out_path`` — the single copy of the emit-and-record pattern."""
    import json

    line = json.dumps(rec)
    print(line)
    if out_path:
        with open(out_path, "a") as fh:
            fh.write(line + "\n")


def join_checked(threads, timeout: float, what: str) -> None:
    """Join every thread and fail loudly on a hang — a stalled rank must
    produce an error, not a bogus bandwidth number."""
    for t in threads:
        t.join(timeout)
        if t.is_alive():
            raise RuntimeError(f"{what} thread did not finish within {timeout}s")


import contextlib  # noqa: E402
import threading  # noqa: E402


@contextlib.contextmanager
def shm_gang(ns: str, nservers: int, nclients: int, size: int,
             ring_bytes: int = 1 << 24):
    """A started PS gang over the native shm transport: servers on their
    own threads, clients started concurrently (the reference's per-rank
    processes).  Yields ``(clients, params, grads)``; teardown runs the
    stop protocol in the load-bearing order — client stop, server join,
    transport close."""
    import numpy as np

    from mpit_tpu.comm.shm import ShmTransport
    from mpit_tpu.ps import ParamClient, ParamServer

    nranks = nservers + nclients
    sranks = list(range(nservers))
    cranks = list(range(nservers, nranks))
    transports = [
        ShmTransport(ns, r, nranks, ring_bytes=ring_bytes)
        for r in range(nranks)
    ]
    servers = [
        ParamServer(r, cranks, transports[r], rule="add") for r in sranks
    ]
    sthreads = [threading.Thread(target=s.start, daemon=True) for s in servers]
    for t in sthreads:
        t.start()

    clients = [
        ParamClient(r, sranks, transports[r], seed_servers=(r == cranks[0]))
        for r in cranks
    ]
    params = [np.zeros(size, np.float32) for _ in cranks]
    grads = [np.full(size, 1e-6, np.float32) for _ in cranks]
    starts = [
        threading.Thread(
            target=clients[i].start, args=(params[i], grads[i]), daemon=True
        )
        for i in range(nclients)
    ]
    for t in starts:
        t.start()
    join_checked(starts, 60, "client start")
    try:
        yield clients, params, grads
    finally:
        for c in clients:
            c.stop()
        join_checked(sthreads, 10, "server stop")
        for tr in transports:
            tr.close()
