"""Multi-host process bootstrap — the mpirun/hostfile analog.

The reference scales across nodes with ``mpirun --hostfile`` (6 nodes x 16
slots, reference BiCNN/hostfiles; README.md:57-61), MPI assigning ranks and
wiring the wire protocol.  The TPU-native equivalent is multi-controller
JAX: every host runs the same program, ``jax.distributed.initialize()``
forms the process group, and ``jax.devices()`` then spans every chip on
every host — after which the whole of :mod:`mpit_tpu.parallel` (meshes,
collective PS, ring attention) works unchanged, with XLA routing
cross-host collective hops over DCN.

This module provides the bootstrap glue:

- :func:`read_hostfile` — parse the reference's ``host:slots`` format;
- :func:`bootstrap` — derive (coordinator, num_processes, process_id)
  from explicit args, a hostfile + rank env, cloud TPU metadata (all
  args None), or MPIT_* / standard env vars, then call
  ``jax.distributed.initialize``;
- :class:`ProcessGroup` — the post-init identity handle (process index,
  count, local devices) that launchers hand to role assignment exactly
  like an MPI rank/size pair (reference mlaunch.lua:16-17).
"""

from __future__ import annotations

import dataclasses
import os
import pathlib
from typing import List, Optional, Sequence, Tuple

import jax


@dataclasses.dataclass(frozen=True)
class HostEntry:
    host: str
    slots: int = 1


def read_hostfile(path: str | pathlib.Path) -> List[HostEntry]:
    """Parse ``host:slots`` lines (reference BiCNN/hostfiles; blank lines
    and ``#`` comments ignored; missing ``:slots`` means 1)."""
    entries: List[HostEntry] = []
    for raw in pathlib.Path(path).read_text().splitlines():
        line = raw.split("#", 1)[0].strip()
        if not line:
            continue
        host, _, slots = line.partition(":")
        if not host:
            raise ValueError(f"bad hostfile line: {raw!r}")
        entries.append(HostEntry(host, int(slots) if slots else 1))
    if not entries:
        raise ValueError(f"hostfile {path} is empty")
    return entries


def coordinator_from_hostfile(
    entries: Sequence[HostEntry], port: int = 8476
) -> Tuple[str, int]:
    """(coordinator_address, num_processes): first host coordinates (the
    mpirun convention of rank 0 on the first hostfile line); one JAX
    process per hostfile line — slots describe per-host worker threads
    or local gang size, not extra controllers."""
    return f"{entries[0].host}:{port}", len(entries)


@dataclasses.dataclass(frozen=True)
class ProcessGroup:
    """Identity after bootstrap — the rank/size pair of mlaunch.lua:16-17
    plus device topology."""

    process_id: int
    num_processes: int
    coordinator: Optional[str]

    @property
    def devices(self) -> List[jax.Device]:
        return jax.devices()

    @property
    def local_devices(self) -> List[jax.Device]:
        return jax.local_devices()

    def describe(self) -> str:
        return (
            f"process {self.process_id}/{self.num_processes} "
            f"coordinator={self.coordinator or 'single-host'} "
            f"local={len(self.local_devices)} global={len(self.devices)}"
        )


def bootstrap(
    coordinator: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    hostfile: Optional[str] = None,
    port: int = 8476,
) -> ProcessGroup:
    """Form the multi-host process group and return the identity handle.

    Resolution order for each field: explicit argument > MPIT_* env
    (MPIT_COORDINATOR / MPIT_NUM_PROCESSES / MPIT_PROCESS_ID) > hostfile
    (+ MPIT_PROCESS_ID for our line index) > single-process fallback
    (no initialize call — ``jax.devices()`` is already correct on one
    host, and cloud TPU pods auto-initialize from metadata when
    ``jax.distributed.initialize()`` is called with no args by the
    runtime).
    """
    env = os.environ
    coordinator = coordinator or env.get("MPIT_COORDINATOR") or None
    if num_processes is None:
        num_processes = (
            int(env["MPIT_NUM_PROCESSES"]) if "MPIT_NUM_PROCESSES" in env else None
        )
    if process_id is None:
        process_id = (
            int(env["MPIT_PROCESS_ID"]) if "MPIT_PROCESS_ID" in env else None
        )
    hostfile = hostfile or env.get("MPIT_HOSTFILE") or None

    if hostfile and (coordinator is None or num_processes is None):
        entries = read_hostfile(hostfile)
        hf_coord, hf_n = coordinator_from_hostfile(entries, port)
        coordinator = coordinator or hf_coord
        num_processes = num_processes if num_processes is not None else hf_n

    if coordinator is None and num_processes is None and process_id is None:
        # Single-host run, or a cloud TPU pod whose runtime auto-initialized
        # the group from metadata — report the real identity either way.
        return ProcessGroup(jax.process_index(), jax.process_count(), None)

    num_processes = 1 if num_processes is None else num_processes
    if process_id is None:
        if num_processes > 1:
            # Defaulting to 0 here would make every host claim the
            # coordinator rank and hang the rendezvous — fail with the fix.
            raise ValueError(
                f"process_id required for a {num_processes}-process group: "
                "pass --process_id / MPIT_PROCESS_ID (unique per host)"
            )
        process_id = 0
    if not 0 <= process_id < num_processes:
        raise ValueError(
            f"process_id {process_id} out of range for {num_processes} processes"
        )
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    return ProcessGroup(process_id, num_processes, coordinator)


def shutdown() -> None:
    """Tear down the process group (safe to call when never initialized)."""
    try:
        jax.distributed.shutdown()
    except Exception:
        pass
