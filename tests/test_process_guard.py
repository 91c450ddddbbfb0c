"""The guard every test file runs under (``tests/conftest.py``,
``leaves_the_process_as_found``): a file that leaves a thread, an
obs singleton, a forced flag or an ``MPIT_*`` variable in
the process fails itself and names what it left, and the next file
starts without it.  Each case leaks on purpose inside the guard's own
context manager.
"""

import os
import threading

import pytest

import conftest
from mpit_tpu import obs
from mpit_tpu.comm import pool as comm_pool
from mpit_tpu.obs import metrics, profile, spans


@pytest.fixture(autouse=True)
def short_grace(monkeypatch):
    monkeypatch.setattr(conftest, "GRACE_S", 0.1)


def test_a_file_that_leaves_nothing_passes():
    with conftest.leaving_nothing_behind("tidy"):
        thread = threading.Thread(target=lambda: None, name="mpit-tidy")
        thread.start()
        thread.join()
        obs.configure(enabled=True, reset=True)
        obs.get_recorder()
        obs.configure(enabled=None, reset=True)


def test_a_thread_of_the_packages_is_named():
    release = threading.Event()
    thread = threading.Thread(target=release.wait, name="mpit-round-stream",
                              daemon=True)
    try:
        with pytest.raises(AssertionError, match=(
                "leaky left behind: thread 'mpit-round-stream'")):
            with conftest.leaving_nothing_behind("leaky"):
                thread.start()
    finally:
        release.set()
        thread.join(10)


def test_a_thread_of_a_tests_own_is_named_too():
    """A server a test left serving reads clocks for its neighbours."""
    release = threading.Event()
    thread = threading.Thread(target=release.wait, name="Thread-7 (start)",
                              daemon=True)
    try:
        with pytest.raises(AssertionError, match=r"thread 'Thread-7 \(start\)'"):
            with conftest.leaving_nothing_behind("leaky"):
                thread.start()
    finally:
        release.set()
        thread.join(10)


def test_what_a_failed_test_left_is_said_and_not_charged_twice():
    release = threading.Event()
    thread = threading.Thread(target=release.wait, name="a-failed-tests-gang",
                              daemon=True)
    try:
        with pytest.warns(UserWarning, match="thread 'a-failed-tests-gang'"):
            with conftest.leaving_nothing_behind("failed", lambda: True):
                thread.start()
    finally:
        release.set()
        thread.join(10)


def test_a_recorder_and_a_forced_flag_are_named_and_taken_down():
    with pytest.raises(AssertionError) as failed:
        with conftest.leaving_nothing_behind("leaky"):
            obs.configure(enabled=True)
            obs.get_recorder()
            profile.configure(enabled=True)
            obs.get_registry().counter("mpit_left_behind_total").inc()
    said = str(failed.value)
    assert "obs.spans._GLOBAL, a SpanRecorder" in said
    assert "obs.metrics._FORCED = True" in said
    assert "obs.profile._FORCED = True" in said
    # the next file starts clean whatever this one did
    assert spans._GLOBAL is None
    assert metrics._FORCED is None and profile._FORCED is None
    assert not metrics._GLOBAL._metrics
    assert obs.get_recorder() is obs.NULL_RECORDER


def test_a_variable_of_the_packages_is_named_and_put_back(monkeypatch):
    monkeypatch.setenv("MPIT_WAS_THERE", "before")
    with pytest.raises(AssertionError) as failed:
        with conftest.leaving_nothing_behind("leaky"):
            os.environ["MPIT_LEFT_BEHIND"] = "1"
            os.environ["MPIT_WAS_THERE"] = "changed"
            os.environ["NOT_THE_PACKAGES"] = "1"
    said = str(failed.value)
    assert "MPIT_LEFT_BEHIND='1' in the environment" in said
    assert "MPIT_WAS_THERE='changed' in the environment" in said
    assert "NOT_THE_PACKAGES" not in said
    assert "MPIT_LEFT_BEHIND" not in os.environ
    assert os.environ["MPIT_WAS_THERE"] == "before"
    del os.environ["NOT_THE_PACKAGES"]


def test_the_pool_a_file_built_is_closed_and_the_file_not_failed():
    """The pool has no owner but the process: any client with a codec
    builds it on first use."""
    with conftest.leaving_nothing_behind("codec user"):
        comm_pool.configure(0)
        assert comm_pool.current_pool() is not None
    assert comm_pool.current_pool() is None
