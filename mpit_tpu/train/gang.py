"""Process-gang spawner — the built-in ``mpirun -np N`` analog.

Forks N role processes wired over the native shm transport, monitors them
as a gang (one dead rank starves its peers: servers wait for STOPs that
never arrive — the same failure shape mpirun handles by killing the job),
collects per-rank JSON results from files, and tears everything down on
failure or timeout.  Shared by the MNIST launcher
(:mod:`mpit_tpu.train.launch`) and the BiCNN launcher
(:mod:`mpit_tpu.train.bicnn_launch`).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, Optional, Sequence

from mpit_tpu.utils.config import Config
from mpit_tpu.utils.platform import count_local_chips, cpu_pinned

# Importing this module (and the launchers built on it) initialises no
# jax backend: the gang parent must stay off the chips its workers own.


def assign_devices(
    size: int, chip_owners: Sequence[int],
) -> Dict[int, Dict[str, str]]:
    """Per-rank device environment, applied BEFORE a child imports jax:
    each rank of ``chip_owners`` (workers, a tester) gets a chip of its
    own and sees no other; every other rank (servers, controller,
    readers, spares) is pinned to ``JAX_PLATFORMS=cpu`` and never
    initialises the TPU backend — a chip belongs to one process.  The
    map is a pure function of the roles, so a supervisor restart hands a
    worker the chip its predecessor had.

    Asking for more owners than the host has chips fails here, in the
    parent, before any spawn.  A parent whose own environment pins the
    CPU (``JAX_PLATFORMS=cpu``: tests, CI) assigns nothing — every rank
    inherits it."""
    if cpu_pinned():
        return {}
    chips = count_local_chips()
    if len(chip_owners) > chips:
        raise ValueError(
            f"ranks {list(chip_owners)} each need a TPU chip of their own "
            f"but this host has {chips}; start fewer workers, or set "
            f"JAX_PLATFORMS=cpu to run the whole gang on the host")
    env = {r: {"JAX_PLATFORMS": "cpu"} for r in range(size)}
    for chip, rank in enumerate(chip_owners):
        env[rank] = {
            # tpu first: jax raises at backend start-up when the chip
            # cannot be had, where an unset variable would quietly carry
            # on on the CPU.
            "JAX_PLATFORMS": "tpu,cpu",
            "TPU_VISIBLE_CHIPS": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
        }
    return env


def child_transport(cfg: Config, rank: int, size: int):
    """The gang's wire: shm rings on one host (default), TCP across hosts
    (``transport=tcp`` + ``tcp_addrs=host:port,...`` — one address per
    rank, the hostfile-deployment analog).

    Every gang synchronizes on a startup barrier
    (:class:`mpit_tpu.comm.collectives.HostCollectives`) before any role
    traffic, so a slow-to-spawn rank can't race the PS seeding protocol
    (the mpirun-gives-you-this guarantee; disable with gang_barrier=0).
    """
    if cfg.get("transport", "shm") == "tcp":
        from mpit_tpu.comm.tcp import TcpTransport

        addrs = [a for a in str(cfg.get("tcp_addrs", "")).split(",") if a]
        if len(addrs) != size:
            raise ValueError(
                f"transport=tcp needs {size} comma-separated tcp_addrs, "
                f"got {len(addrs)}"
            )
        dial_peers = None
        reconnect = None
        if bool(cfg.get("elastic", False)):
            # Elastic gangs (PROTOCOL.md §9): the mesh rendezvous must
            # never wait on a spare slot that has not spawned.  Initial
            # members dial only lower *initial* ranks; a
            # controller-spawned joiner dials exactly the live set the
            # controller stamped into its spawn request
            # (MPIT_ELASTIC_DIAL) — a retired or dead rank would burn
            # the whole connect deadline.  Later arrivals (spares, a
            # rejoiner) come through the loop's persistent accept
            # service, so reconnect mode is forced on.
            np0 = int(cfg.get("elastic_np0", 0) or 0) or size
            dial_env = os.environ.get("MPIT_ELASTIC_DIAL", "")
            if dial_env:
                dial_peers = [int(x) for x in dial_env.split(",") if x]
            else:
                dial_peers = list(range(min(rank, np0)))
            reconnect = float(os.environ.get("MPIT_TCP_RECONNECT_S", "60"))
        elif os.environ.get("MPIT_FT_REJOIN", "0") not in ("0", ""):
            # A supervisor-restarted worker joins a mid-run gang: only
            # its servers must be reachable — a sibling worker that
            # already finished and exited is not a failure (PS traffic
            # is client<->server only; the barrier is skipped on rejoin).
            from mpit_tpu.train.launch import assign_roles

            sranks, _cranks, _tester = assign_roles(
                size, int(cfg.get("master_freq", 2)),
                str(cfg.get("tester", "none")),
            )
            if rank not in sranks:
                dial_peers = [r for r in sranks if r < rank]
        transport = TcpTransport(rank, size, addrs, dial_peers=dial_peers,
                                 reconnect=reconnect)
    else:
        from mpit_tpu.comm.shm import ShmTransport

        transport = ShmTransport(
            cfg.namespace, rank, size,
            ring_bytes=int(cfg.get("ring_mb", 64)) << 20,
        )
    if bool(cfg.get("gang_barrier", True)):
        from mpit_tpu.comm.collectives import HostCollectives

        HostCollectives(transport).barrier()
    return transport


def spawn_rank(
    child_module: str, cfg: Config, rank: int, size: int, logdir: str,
    extra_env: Optional[Dict[str, str]] = None,
) -> tuple:
    """Spawn one ``--child`` rank process; returns (proc, logpath,
    resultpath).  The single spawn path shared by :func:`launch_gang`
    and the fault-tolerance supervisor (mpit_tpu.ft.supervisor), which
    re-invokes it to restart a dead rank — logs open in append mode so a
    restarted incarnation continues the same rank log.  ``cfg`` is
    serialized per call, so a restart may carry a modified config
    (barrier off, resume on) without touching its gang-mates."""
    # The parent builds the native library (a no-op once current), so
    # children find it there instead of racing N compilers onto one path.
    from mpit_tpu.comm.native.build import ensure_built

    ensure_built()
    logpath = os.path.join(logdir, f"rank{rank}.log")
    resultpath = os.path.join(logdir, f"rank{rank}.result.json")
    env = {
        **os.environ,
        "MPIT_SIZE": str(size),
        "MPIT_CFG": json.dumps(cfg.to_dict()),
        "MPIT_RANK": str(rank),
        "MPIT_RESULT_FILE": resultpath,
    }
    env.update(extra_env or {})
    with open(logpath, "a") as fh:
        proc = subprocess.Popen(
            [sys.executable, "-m", child_module, "--child"],
            env=env, stdout=fh, stderr=subprocess.STDOUT, text=True,
        )
    return proc, logpath, resultpath


def launch_gang(
    child_module: str, cfg: Config, timeout: float = 3600.0,
    env_overrides: Optional[Dict[int, Dict[str, str]]] = None,
) -> Dict[int, Dict[str, Any]]:
    """Spawn ``python -m <child_module> --child`` per rank; gang-monitor.

    ``env_overrides`` maps rank -> extra env vars for that child — the
    device-assignment hook (the reference's per-rank GPU map,
    mlaunch.lua:56-62, expressed as per-rank platform/visible-device
    env)."""
    size = int(cfg.np)
    namespace = cfg.get("namespace") or f"mpit{os.getpid()}"
    cfg = cfg.merged(namespace=namespace)
    # Children write to per-rank log files, not pipes: nobody needs to
    # drain them while the gang runs, so a log-heavy child can never block
    # on a full pipe buffer mid-run.
    logdir = tempfile.mkdtemp(prefix=f"{namespace}_logs_")
    procs, logfiles, resultfiles = [], [], []
    for rank in range(size):
        proc, logpath, resultpath = spawn_rank(
            child_module, cfg, rank, size, logdir,
            extra_env=(env_overrides or {}).get(rank),
        )
        procs.append(proc)
        logfiles.append(logpath)
        resultfiles.append(resultpath)
    deadline = time.monotonic() + timeout
    failed: Optional[int] = None
    timed_out = False
    states = [None] * size
    while True:
        states = [p.poll() for p in procs]
        if all(s is not None for s in states):
            break
        bad = next((i for i, s in enumerate(states) if s not in (None, 0)), None)
        timed_out = time.monotonic() > deadline
        if bad is not None or timed_out:
            failed = bad
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            break
        time.sleep(0.2)
    for proc in procs:
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
    results: Dict[int, Dict[str, Any]] = {}
    for rank, (logpath, resultpath) in enumerate(zip(logfiles, resultfiles)):
        with open(logpath) as fh:
            for line in fh:
                print(line.rstrip("\n"))
        if os.path.exists(resultpath):
            with open(resultpath) as fh:
                results[rank] = json.load(fh)
    if timed_out and failed is None:
        alive = [r for r, s in enumerate(states) if s is None]
        raise RuntimeError(
            f"gang timed out after {timeout:.0f}s; ranks still running at "
            f"teardown: {alive}; gang torn down (logs: {logdir})"
        )
    if failed is not None:
        raise RuntimeError(
            f"rank {failed} exited with {procs[failed].returncode}; "
            f"gang torn down (logs: {logdir})"
        )
    for rank, proc in enumerate(procs):
        if proc.returncode != 0:
            raise RuntimeError(f"rank {rank} exited with {proc.returncode}")
    missing = [r for r in range(size) if r not in results]
    if missing:
        raise RuntimeError(
            f"ranks {missing} exited 0 but reported no result (logs: {logdir})"
        )
    # Merge the children's per-rank Chrome-trace parts (MPIT_OBS_TRACE)
    # into one timeline — only after a clean gang, so a failure leaves
    # the parts on disk next to the logs for postmortem.
    from mpit_tpu.obs import maybe_merge_rank_traces

    maybe_merge_rank_traces()
    import shutil

    shutil.rmtree(logdir, ignore_errors=True)  # only useful on failure
    return results


def child_env() -> tuple[int, int, Config]:
    """(rank, size, cfg) from the gang environment, for ``--child`` mains
    — the one start-up path of every gang rank, so it also turns on the
    compile cache and holds a chip owner to its chip: a rank whose
    environment names a chip (:func:`assign_devices`) and whose jax did
    not come up on the TPU raises here, before its role starts."""
    from mpit_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    rank = int(os.environ["MPIT_RANK"])
    size = int(os.environ["MPIT_SIZE"])
    cfg = Config(**json.loads(os.environ["MPIT_CFG"]))
    if "TPU_VISIBLE_CHIPS" in os.environ:
        import jax

        if jax.default_backend() != "tpu":
            raise RuntimeError(
                f"rank {rank} was assigned TPU chip "
                f"{os.environ['TPU_VISIBLE_CHIPS']} but jax came up on "
                f"{jax.default_backend()!r}")
    return rank, size, cfg


def write_result(result: Dict[str, Any]) -> None:
    """Report one rank's result, stamped with the device it ran on.
    Results travel over a dedicated file, not stdout: log lines from
    library threads could interleave with (and corrupt) a stdout protocol."""
    from mpit_tpu.utils.platform import device_report

    result = {**result, **device_report()}
    result_file = os.environ.get("MPIT_RESULT_FILE")
    if result_file:
        with open(result_file, "w") as fh:
            json.dump(result, fh)
    else:
        print(f"MPIT_RESULT {os.environ.get('MPIT_RANK')} {json.dumps(result)}", flush=True)
