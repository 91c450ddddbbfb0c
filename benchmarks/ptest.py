"""PS push/pull bandwidth benchmark — the asyncsgd/ptest.lua analog.

The reference measures bi-directional parameter-server bandwidth: half
the ranks serve shards of a big flat vector, the rest run T rounds of
{pull params, push grads, wait} and print ``2*T*ssize*4/elapsed`` MB/s
(reference asyncsgd/ptest.lua:3,58-67; BASELINE.md config 4).  This
script measures both rebuild transports:

- **ici** — the on-mesh path: one jitted round = reduce-scatter(grad) +
  shard apply + all-gather(param) over the ``shard`` axis
  (:func:`mpit_tpu.parallel.collective.ps_pushpull`), i.e. the traffic
  pattern the reference drives through MPI, riding ICI instead.
- **shm** — the host path: ParamClient/ParamServer over the native C++
  shared-memory transport, **one OS process per rank** (the reference's
  ``mpirun -np N`` shape; train/gang.py is the trainer's analog of the
  same spawner).

Env knobs: MPIT_BENCH_MB (payload size, default 64), MPIT_BENCH_ROUNDS
(default 20), MPIT_BENCH_MODE (ici|shm|both, default both),
MPIT_BENCH_SERVERS / MPIT_BENCH_CLIENTS for the shm topology (default
2/2, the reference's np=4 split).  The shm gang's wire codec is the
package's own switch, MPIT_PS_CODEC (comm/codec.py; default none).

Prints one JSON line per mode: MB/s bi-directional, plus per-chip for
the ici mode.  MB/s counts *logical* payload bytes (2 * size * 4 per
round per client).  These are counts a test asserts on and a quick look
at a host; the repository's benchmark is ``chipbench/`` (PERF.md).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _common import log as _log  # noqa: E402

MB = float(os.environ.get("MPIT_BENCH_MB", "64"))
ROUNDS = int(os.environ.get("MPIT_BENCH_ROUNDS", "20"))
MODE = os.environ.get("MPIT_BENCH_MODE", "both")
NSERVERS = int(os.environ.get("MPIT_BENCH_SERVERS", "2"))
NCLIENTS = int(os.environ.get("MPIT_BENCH_CLIENTS", "2"))
GANG_TIMEOUT_S = 900.0


def bench_ici() -> dict:
    from mpit_tpu.parallel.collective import measure_ps_pushpull

    r = measure_ps_pushpull(MB, rounds=ROUNDS)
    _log(f"[ici] {r['devices']} devices, payload {r['payload_mb']:.1f} MB: "
         f"{r['ms_per_round']:.2f} ms/round -> {r['mbs']:.1f} MB/s "
         f"({r['per_chip']:.1f} MB/s/chip)")
    return {
        "metric": "ps_pushpull_bandwidth_ici",
        "value": round(r["mbs"], 1),
        "unit": "MB/s",
        "per_chip": round(r["per_chip"], 1),
        "devices": r["devices"],
    }


def bench_shm() -> dict:
    """One shm PS push/pull measurement.  Every rank is its own child
    process with JAX_PLATFORMS=cpu: the parent keeps the accelerator for
    the ici leg and never touches jax on this path."""
    from mpit_tpu.comm import codec as codec_mod

    codec_name = codec_mod.get(None).name  # what MPIT_PS_CODEC selects
    size = int(MB * (1 << 20) / 4)
    _log(f"[shm] {NSERVERS} servers + {NCLIENTS} clients, codec "
         f"{codec_name}, payload {size * 4 / 2**20:.1f} MB")
    mbs = _shm_run_procs(size)
    return {
        "metric": "ps_pushpull_bandwidth_shm",
        "value": round(mbs, 1),
        "unit": "MB/s",
        "codec": codec_name,
        "clients": NCLIENTS,
        "servers": NSERVERS,
    }


def _ring_bytes(size: int) -> int:
    # Ring sized for the rank's aggregate inbound traffic: every peer on
    # the other side may have a full shard in flight into this rank's
    # one inbox ring (2 clients -> 1 server ring, and vice versa), so a
    # per-shard ring is perpetually full and each transfer degrades into
    # ring-granularity handoff cycles — each paying a scheduling quantum
    # on a shared core (a whole OS timeslice in the process gang).
    shard_bytes = size * 4 // max(NSERVERS, 1)
    peers = max(NSERVERS, NCLIENTS)
    return max(64 << 20, 2 * peers * shard_bytes + (16 << 20))


def _shm_run_procs(size: int) -> float:
    """One timed gang, one OS process per rank: servers run the PS serve
    loop, clients run T rounds of {pull, push, wait} and report their
    round-loop window; aggregate MB/s uses the union of the client
    windows, so child startup (jax import, seeding) is excluded."""
    import shutil
    import subprocess
    import tempfile

    nranks = NSERVERS + NCLIENTS
    ns = f"ptest_{os.getpid()}"
    spec = {
        "ns": ns, "nservers": NSERVERS, "nclients": NCLIENTS,
        "size": size, "ring": _ring_bytes(size), "rounds": ROUNDS,
    }
    tmpdir = tempfile.mkdtemp(prefix=f"{ns}_")
    procs, result_files = [], []
    for rank in range(nranks):
        result_path = os.path.join(tmpdir, f"rank{rank}.json")
        result_files.append(result_path)
        log_path = os.path.join(tmpdir, f"rank{rank}.log")
        env = dict(
            os.environ, JAX_PLATFORMS="cpu", PTEST_GANG=json.dumps(spec),
            PTEST_RANK=str(rank), PTEST_RESULT=result_path,
        )
        with open(log_path, "w") as fh:
            procs.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--gang-child"],
                env=env, stdout=fh, stderr=subprocess.STDOUT, text=True,
            ))
    deadline = time.monotonic() + GANG_TIMEOUT_S
    try:
        while any(p.poll() is None for p in procs):
            bad = next((r for r, p in enumerate(procs)
                        if p.poll() not in (None, 0)), None)
            if bad is not None or time.monotonic() > deadline:
                for p in procs:
                    if p.poll() is None:
                        p.terminate()
                for path in result_files:
                    with open(path.replace(".json", ".log")) as fh:
                        sys.stderr.write(fh.read())
                raise RuntimeError(
                    f"gang rank {bad} failed (logs: {tmpdir})"
                    if bad is not None else
                    f"gang timed out (logs: {tmpdir})"
                )
            time.sleep(0.1)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    windows = []
    for rank in range(NSERVERS, nranks):
        with open(result_files[rank]) as fh:
            rec = json.load(fh)
        windows.append((rec["t0"], rec["t1"]))
    dt = max(w[1] for w in windows) - min(w[0] for w in windows)
    shutil.rmtree(tmpdir, ignore_errors=True)
    mbs = 2 * ROUNDS * NCLIENTS * size * 4 / dt / 2**20
    _log(f"[shm] {ROUNDS} rounds x {NCLIENTS} client procs in {dt:.3f}s "
         f"-> {mbs:.1f} MB/s aggregate")
    return mbs


def _gang_child() -> None:
    """One rank of the process gang (--gang-child): a server runs the
    serve loop to completion; a client times its round loop and writes
    the window to PTEST_RESULT."""
    import numpy as np

    from mpit_tpu.comm.collectives import HostCollectives
    from mpit_tpu.comm.shm import ShmTransport
    from mpit_tpu.ps import ParamClient, ParamServer

    spec = json.loads(os.environ["PTEST_GANG"])
    rank = int(os.environ["PTEST_RANK"])
    nranks = spec["nservers"] + spec["nclients"]
    sranks = list(range(spec["nservers"]))
    cranks = list(range(spec["nservers"], nranks))
    size = spec["size"]
    transport = ShmTransport(spec["ns"], rank, nranks,
                             ring_bytes=spec["ring"])
    # Startup barrier: no PS traffic until every ring is mapped (the
    # mpirun-gives-you-this guarantee, same as train/gang.py).
    HostCollectives(transport).barrier()
    if rank in sranks:
        server = ParamServer(rank, cranks, transport, rule="add")
        server.start()
        result = {"role": "server", "grads_applied": server.grads_applied}
    else:
        client = ParamClient(rank, sranks, transport,
                             seed_servers=(rank == cranks[0]))
        param = np.zeros(size, np.float32)
        grad = np.full(size, 1e-6, np.float32)
        client.start(param, grad)
        # Align client windows before timing: a non-seeding client's
        # start() returns while the seeder is still pushing the whole
        # vector, and an unaligned window would fold that seeding time
        # into the measured aggregate.  One warmup pull per client (so
        # every server has served once), then a client-only barrier on a
        # tag outside the PS/collectives ranges.
        client.async_recv_param()
        client.wait()
        _SYNC_TAG = 59999
        if rank == cranks[0]:
            for peer in cranks[1:]:
                while not transport.iprobe(peer, _SYNC_TAG):
                    client.ping()
                transport.recv(peer, _SYNC_TAG)
            for peer in cranks[1:]:
                transport.send(b"go", peer, _SYNC_TAG)
        else:
            transport.send(b"rdy", cranks[0], _SYNC_TAG)
            while not transport.iprobe(cranks[0], _SYNC_TAG):
                client.ping()
            transport.recv(cranks[0], _SYNC_TAG)
        t0 = time.time()
        for _ in range(spec["rounds"]):
            client.async_recv_param()
            client.async_send_grad()
            client.wait()
        t1 = time.time()
        client.stop()
        result = {"role": "client", "t0": t0, "t1": t1}
    transport.close()
    with open(os.environ["PTEST_RESULT"], "w") as fh:
        json.dump(result, fh)


def main():
    results = []
    if MODE in ("ici", "both"):
        results.append(bench_ici())
    if MODE in ("shm", "both"):
        results.append(bench_shm())
    for r in results:
        print(json.dumps(r))


if __name__ == "__main__":
    if "--gang-child" in sys.argv:
        _gang_child()
    else:
        main()
