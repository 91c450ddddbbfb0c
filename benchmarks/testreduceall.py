"""Allreduce timing + correctness spot-check — the test/testreduceall.lua
and test/testireduceall.lua analog.

The reference times a blocking Allreduce of MEGS*2^20 floats (env-sized,
test/testreduceall.lua:8-9,31-33) and a nonblocking Iallreduce with
Test-before/after-Wait (test/testireduceall.lua:32-39), plus a seeded
correctness print (asyncsgd/testreduceall.lua:72-77).  TPU-native:

- device analog — jitted ``psum`` over every device (shard_map), timed
  with the latency-cancelled fetch-fenced recipe of
  :mod:`mpit_tpu.utils.timing` (XLA's async dispatch already gives the
  Iallreduce overlap the reference tests separately: the host thread
  runs free while collectives execute);
- correctness — the psum of seeded per-device uniforms must equal the
  numpy sum of the same stacked array.

Env knobs: MEGS (payload in MB, default 8 — same env var name as the
reference), MPIT_BENCH_ROUNDS (default 20).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _common import join_checked, log as _log  # noqa: E402

import numpy as np  # noqa: E402


MEGS = float(os.environ.get("MEGS", "8"))
ROUNDS = int(os.environ.get("MPIT_BENCH_ROUNDS", "20"))
# ici (default): XLA psum over the device mesh.  shm: ring allreduce
# between real host processes over the shared-memory transport — the
# host-collective twin (MPIT_BENCH_RANKS processes, default 4).
MODE = os.environ.get("MPIT_BENCH_MODE", "ici")
NRANKS = int(os.environ.get("MPIT_BENCH_RANKS", "4"))


def _shm_child() -> None:
    """One rank of the host-transport leg: timed ring allreduce over the
    shm transport — the literal test/testreduceall.lua:31-33 shape (MPI
    Allreduce between host processes, no device in the loop)."""
    rank = int(os.environ["MPIT_RANK"])
    size_ranks = int(os.environ["MPIT_SIZE"])
    ns = os.environ["MPIT_NAMESPACE"]

    from mpit_tpu.comm.collectives import HostCollectives
    from mpit_tpu.comm.shm import ShmTransport

    n_elems = int(MEGS * (1 << 20) / 4)
    ring_bytes = max(64 << 20, (n_elems * 4 // size_ranks) * 4)
    t = ShmTransport(ns, rank, size_ranks, ring_bytes=ring_bytes)
    coll = HostCollectives(t)
    rng = np.random.default_rng(rank)
    arr = rng.uniform(size=n_elems).astype(np.float32)
    base = arr.copy()

    coll.barrier()
    t0 = time.perf_counter()
    for _ in range(ROUNDS):
        coll.allreduce(arr)
    dt = time.perf_counter() - t0

    # Iallreduce leg: Test-before/after-Wait (testireduceall.lua:32-39).
    h = coll.allreduce_async(arr)
    h.test()
    h.wait(600)
    assert h.test() is True

    # Correctness: check one clean allreduce of the seeded uniforms on a
    # fresh buffer (after k timed rounds the main buffer holds
    # size^k-weighted mixes).  Every rank participates; rank 0 asserts.
    fresh = base.copy()
    coll2 = HostCollectives(t, tag_base=1 << 24)
    coll2.allreduce(fresh)
    if rank == 0:
        expect = np.zeros_like(base)
        for r in range(size_ranks):
            expect += np.random.default_rng(r).uniform(
                size=n_elems
            ).astype(np.float32)
        np.testing.assert_allclose(fresh, expect, rtol=1e-4, atol=1e-5)
        mbs = ROUNDS * n_elems * 4 * 2 * (size_ranks - 1) / size_ranks / dt / 2**20
        print(json.dumps({
            "metric": "host_allreduce_bandwidth_shm",
            "value": round(mbs, 1),
            "unit": "MB/s",
            "ms_per_round": round(dt / ROUNDS * 1e3, 3),
            "payload_mb": round(n_elems * 4 / 2**20, 1),
            "ranks": size_ranks,
        }))
    coll.barrier()
    t.close()


def _shm_parent(nranks: int, timeout: float = 300.0) -> None:
    """Gang-monitored spawn: one dead rank would strand its peers in the
    collective's poll loops, so any failure (or the deadline) tears the
    whole gang down — the same policy as train.gang.launch_gang."""
    import subprocess
    import sys as _sys

    ns = f"tra_{os.getpid()}"
    procs = []
    for r in range(nranks):
        env = dict(
            os.environ, MPIT_RANK=str(r), MPIT_SIZE=str(nranks),
            MPIT_NAMESPACE=ns, MPIT_BENCH_MODE="shm-child",
        )
        procs.append(subprocess.Popen(
            [_sys.executable, os.path.abspath(__file__)], env=env,
        ))
    deadline = time.monotonic() + timeout
    failed = None
    while True:
        codes = [p.poll() for p in procs]
        if any(c not in (None, 0) for c in codes):
            failed = codes
            break
        if all(c == 0 for c in codes):
            return
        if time.monotonic() >= deadline:
            break
        time.sleep(0.2)
    for p in procs:  # straggler or failure: kill the gang
        if p.poll() is None:
            p.terminate()
    for p in procs:
        try:
            p.wait(10)
        except subprocess.TimeoutExpired:
            p.kill()
    raise AssertionError(
        f"shm gang {'failed: ' + str(failed) if failed else 'timed out'}"
    )


def main():
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mpit_tpu.utils.platform import default_devices

    devs = default_devices()
    n = len(devs)
    mesh = Mesh(np.array(devs), ("x",))
    size = int(MEGS * (1 << 20) / 4 // n * n)
    _log(f"{n} devices, {size * 4 / 2**20:.1f} MB per-device payload")

    allreduce = jax.jit(
        shard_map(
            lambda x: jax.lax.psum(x, "x"), mesh=mesh,
            in_specs=P("x"), out_specs=P("x"), check_vma=False,
        )
    )

    rng = np.random.default_rng(0)
    stacked = rng.uniform(size=(n, size)).astype(np.float32)
    x = jax.device_put(
        jnp.asarray(stacked.reshape(n * size)),
        NamedSharding(mesh, P("x")),
    )

    # Correctness spot-check (the seeded-uniform print of
    # asyncsgd/testreduceall.lua:72-77, with an actual assertion).
    out = np.asarray(allreduce(x))
    expect = stacked.sum(axis=0)
    np.testing.assert_allclose(out[:size], expect, rtol=1e-4)
    _log("correctness: psum == stacked numpy sum")

    # Latency-cancelled, fetch-fenced timing (mpit_tpu.utils.timing).
    from mpit_tpu.utils.timing import timed_per_call

    # auto_scale: at small MEGS on a loaded host the per-round time can be
    # sub-resolution for the default ROUNDS — iters doubles until the
    # differenced legs clear jitter, and the estimate is floored strictly
    # positive (machine-read JSON must never carry a rounded-to-0 value).
    per_round = timed_per_call(allreduce, x, iters=ROUNDS, auto_scale=True)
    per_round_ms = per_round * 1e3
    _log(f"{per_round_ms:.2f} ms/round")
    print(json.dumps({
        "metric": "allreduce_ms_per_round",
        "value": per_round_ms,
        "unit": "ms",
        "payload_mb": round(size * 4 / 2**20, 1),
        "devices": n,
    }))


if __name__ == "__main__":
    if MODE == "shm-child":
        _shm_child()
    elif MODE == "shm":
        _shm_parent(NRANKS)
    elif MODE == "both":
        main()
        _shm_parent(NRANKS)
    else:
        main()
