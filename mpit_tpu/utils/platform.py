"""Process-level platform helpers: chip discovery without JAX, the
virtual-CPU-mesh headroom convention, the persistent compile cache and
the device pool meshes span.  Nothing here initialises a backend at
import time."""

from __future__ import annotations

import os
import re


# A chip's device node: ``/dev/vfio/<n>`` (v5e and later; <n> is the
# IOMMU group, not a chip index) or ``/dev/accel<n>`` (earlier).
_CHIP_NODE = re.compile(r"/dev/(vfio/\d+|accel\d+)$")


def cpu_pinned() -> bool:
    """Whether this process's environment pins jax to the CPU
    (``JAX_PLATFORMS=cpu`` — tests, CI, a host without chips).  A list
    like ``tpu,cpu`` puts the TPU first and does not."""
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip() == "cpu"


def count_local_chips() -> int:
    """TPU chips on this host, counted WITHOUT touching JAX: a process
    that initialises the TPU backend holds the chips, so the gang parent
    (which must leave them to its workers) counts the device nodes the
    driver exposes instead."""
    import glob

    return sum(1 for p in glob.glob("/dev/vfio/*") + glob.glob("/dev/accel*")
               if _CHIP_NODE.match(p))


def held_chip_nodes() -> list:
    """Chip device nodes THIS process holds open (``/proc/self/fd``) —
    what a rank reports so a gang's results show which process took
    which chip: a process that initialised the TPU backend lists its
    chip's node, a host role lists none.  jax's device ids cannot show
    it (every one-chip process calls its chip id 0)."""
    held = set()
    for fd in os.listdir("/proc/self/fd"):
        try:
            path = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:  # the listing's own fd, closed by now
            continue
        if _CHIP_NODE.match(path):
            held.add(path)
    return sorted(held)


def device_report() -> dict:
    """The device this process ran on, as jax reports it, plus the chip
    nodes it holds — stamped on every rank result and benchmark record
    so no number travels without its device."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "device_ids": [d.id for d in devs],
        "chip_nodes": held_chip_nodes(),
    }


# -- virtual-CPU-mesh headroom ------------------------------------------------
#
# XLA:CPU sizes the PjRt client's execution thread pool to the virtual
# device count (``--xla_force_host_platform_device_count``).  A program
# sharded over *every* virtual device needs one pool thread per partition
# simultaneously; when any pool thread is busy with other client work, one
# partition never starts, every other partition blocks inside the
# cross-device collective rendezvous, and after a 40 s timeout XLA calls
# ``LOG(FATAL)`` -> ``Fatal Python error: Aborted`` (xla rendezvous.cc:127,
# ``InProcessCommunicator::AllReduce``).  Observed ~1 in 500 executions of
# an 8-way-sharded all-reduce program on an 8-device pool; zero in >10^4
# executions once the pool exceeds the mesh.  See
# docs/xla_cpu_rendezvous_abort.md for the full investigation.
#
# Workaround convention: register more virtual devices than any mesh uses,
# and have mesh builders draw from ``default_devices()`` (the first
# ``MPIT_MESH_DEVICES`` devices) rather than ``jax.devices()``.

CPU_POOL_HEADROOM = 4


def ensure_cpu_device_headroom(n_mesh_devices: int, extra: int = CPU_POOL_HEADROOM) -> None:
    """Append a ``--xla_force_host_platform_device_count`` override so the
    host-CPU platform exposes ``n_mesh_devices + extra`` virtual devices
    (the later duplicate flag wins), and pin ``MPIT_MESH_DEVICES`` so mesh
    builders keep using only ``n_mesh_devices``.

    Must run before the jax backend initializes; harmless (ignored by
    XLA) afterwards.  Both knobs only ever affect the host-CPU platform:
    the XLA flag is ignored by accelerator backends, and
    :func:`default_devices` applies the ``MPIT_MESH_DEVICES`` cap only
    when the resolved device pool is CPU — so calling this on a real-TPU
    host cannot shrink the accelerator mesh.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n_mesh_devices + extra}"
    ).strip()
    os.environ["MPIT_MESH_DEVICES"] = str(n_mesh_devices)


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache for this process and
    drop the size/time thresholds so every program is cached; returns
    the directory in use.  Every process entry point that compiles calls
    this before its first compile; idempotent.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    this function sets NO directory in code — the cache can be placed
    from outside (the chip machine says where a cache survives).  Unset,
    the cache is ``.jax_cache/`` at the checkout root, a fixed path (the
    path is part of the cache key, so it must never be derived from a
    temporary name, a pid or the time).
    """
    import pathlib

    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        cache = str(pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache


def default_devices():
    """The device pool meshes should span: the first ``MPIT_MESH_DEVICES``
    of ``jax.devices()`` when that env var is set *and* the pool is the
    host-CPU platform (the headroom convention above only ever registers
    extra CPU devices), else all devices — a stale cap can never shrink a
    real accelerator mesh."""
    import jax

    devs = jax.devices()
    cap = os.environ.get("MPIT_MESH_DEVICES")
    if cap and devs and devs[0].platform == "cpu":
        devs = devs[: int(cap)]
    return devs
