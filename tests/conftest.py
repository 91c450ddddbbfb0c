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

import numpy as np  # noqa: E402
import pytest  # noqa: E402


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
