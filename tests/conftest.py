"""Test harness: force an 8-virtual-device CPU JAX platform.

Multi-chip code paths (mesh sharding, collectives, role-split parallelism)
are exercised without TPU hardware by asking XLA for 8 host devices — the
analog of the reference running N MPI ranks on one host over the
shared-memory transport as its "fake backend" (reference README.md:28-31,
SURVEY.md section 4).  Must run before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # the suite never touches a chip
# 8 mesh devices + pool headroom: XLA:CPU sizes the client thread pool to
# the virtual device count, and a program sharded over every device then
# deadlocks its collective rendezvous whenever any pool thread is busy
# with other work (fatal abort after 40 s — docs/xla_cpu_rendezvous_abort.md).
# The extra devices are never meshed (MPIT_MESH_DEVICES caps the pool via
# mpit_tpu.utils.platform.default_devices); they only widen the pool.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=12"
).strip()
os.environ["MPIT_MESH_DEVICES"] = "8"
os.environ.setdefault("JAX_ENABLE_X64", "0")

# Persistent XLA compilation cache for the whole suite: the expensive
# tests are compile-dominated (sharded ring-attention grad graphs), and
# re-running the suite recompiles identical programs.  Same cache as the
# trainers ($JAX_COMPILATION_CACHE_DIR, else the checkout's .jax_cache,
# gitignored) — a fresh clone runs cold once.  Disable with
# MPIT_TEST_COMPILE_CACHE=0.
if os.environ.get("MPIT_TEST_COMPILE_CACHE", "1") != "0":
    from mpit_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()

import contextlib  # noqa: E402
import gc  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402
import warnings  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from mpit_tpu import obs  # noqa: E402
from mpit_tpu.comm import pool as comm_pool  # noqa: E402
from mpit_tpu.obs import flight, metrics, profile, spans  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="also run tests marked slow (gang/integration scale)",
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: integration-scale test (process gangs, long training loops) "
        "skipped by default; enable with --runslow",
    )


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip = pytest.mark.skip(reason="slow: run with --runslow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


# -- a test file leaves the process as it found it ----------------------------
#
# With ``--dist loadfile`` which files share a worker is decided by
# timing, so whatever a file leaves in the process (a thread, a pool, a
# recorder, a switch) changes what its neighbour counts from run to run.
# The guard below makes the leak the leaking file's own error.

#: the obs singletons a file may build and must drop
SINGLETONS = (spans, profile, flight)
FORCED = (metrics, profile)
#: how long a dropped owner's thread may take to see its sentinel
GRACE_S = 2.0


def _package_env():
    return {k: v for k, v in os.environ.items() if k.startswith("MPIT_")}


def process_state():
    """What a file must hand back as it found it."""
    return {
        "threads": set(threading.enumerate()),
        "pool": comm_pool.current_pool(),
        "registry": metrics._GLOBAL,
        "singletons": {mod: mod._GLOBAL for mod in SINGLETONS},
        "forced": {mod: mod._FORCED for mod in FORCED},
        "env": _package_env(),
    }


def _new_threads(before):
    """The threads that were not there before, once dropped owners'
    finalisers have had ``GRACE_S`` to end them.  Any thread, not only
    the package's own (``mpit-*``, ``obs-*``): a server or a cell that
    a test left serving reads clocks and holds cores for its
    neighbours just the same."""
    deadline = time.monotonic() + GRACE_S
    while True:
        left = [t for t in threading.enumerate() if t not in before]
        if not left or time.monotonic() > deadline:
            return left
        time.sleep(0.01)


def left_behind(before):
    """Let finalisers run, name everything that is in the process now
    and was not in ``before``, and put back what can be put back."""
    gc.collect()
    found = [f"thread {t.name!r}"
             for t in _new_threads(before["threads"])]
    # The pool has no owner but the process (any client with a codec
    # builds it on first use): the next file starts without this one's,
    # and the file is not failed for having used a codec.
    if comm_pool.current_pool() is not before["pool"]:
        comm_pool.close()
    for mod in SINGLETONS:
        if mod._GLOBAL not in (None, before["singletons"][mod]):
            found.append(f"{mod.__name__}._GLOBAL, "
                         f"a {type(mod._GLOBAL).__name__}")
    # the registry is never None: one the file made counts if it is in use
    if metrics._GLOBAL is not before["registry"] and metrics._GLOBAL._metrics:
        found.append(f"{metrics.__name__}._GLOBAL, a Registry in use")
    for mod in FORCED:
        if mod._FORCED != before["forced"][mod]:
            found.append(f"{mod.__name__}._FORCED = {mod._FORCED!r}")
    now = _package_env()
    found += [f"{k}={now.get(k)!r} in the environment"
              for k in sorted(now.keys() | before["env"].keys())
              if now.get(k) != before["env"].get(k)]
    if found:  # the neighbours start clean whatever this file did
        for k in now.keys() - before["env"].keys():
            del os.environ[k]
        os.environ.update(before["env"])
        obs.configure(enabled=before["forced"][metrics], reset=True)
        profile.configure(enabled=before["forced"][profile])
    return found


@contextlib.contextmanager
def leaving_nothing_behind(who, failed=lambda: False):
    """``failed``: the file has a failed test since this began.  What
    such a test left (its gang, still retrying) is put back as far as it
    can be and said in a warning: the failure has cost the file once."""
    before = process_state()
    yield
    found = left_behind(before)
    said = f"{who} left behind: " + "; ".join(found)
    if found and failed():
        warnings.warn(said)
    else:
        assert not found, said


@pytest.fixture(scope="module", autouse=True)
def leaves_the_process_as_found(request):
    session, failed_before = request.session, request.session.testsfailed
    with leaving_nothing_behind(
            request.module.__name__,
            failed=lambda: session.testsfailed > failed_before):
        yield
