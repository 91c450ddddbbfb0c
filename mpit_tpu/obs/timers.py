"""Per-phase wall-clock timers + jax.profiler hooks (moved here from
``mpit_tpu/utils/timers.py`` when observability unified under
``mpit_tpu.obs``; ``mpit_tpu.utils`` re-exports the names).

The reference tracks phase times in ad-hoc tables — ``tm.feval``/
``tm.sync`` in the MNIST trainer (reference asyncsgd/goot.lua:20-22,
152-157), an 11-bucket table in BiCNN (reference BiCNN/bicnn.lua:17-28),
and optimizers accumulate blocking sync time around every wait
(reference optim-downpour.lua:39-41).  :class:`PhaseTimers` is the same
cheap mechanism with a context manager — the *trainer-loop* timer,
where the registry/spans in :mod:`mpit_tpu.obs.metrics` /
:mod:`mpit_tpu.obs.spans` cover the comm/PS stack.

:func:`trace_annotation` is the jax.profiler bridge: wrap host-side
work in it while capturing a device trace (:func:`profiler_trace`) and
the host phase shows up on the device timeline — the exported obs trace
(``MPIT_OBS_TRACE``) is wall-anchored so the two line up side-by-side
in Perfetto.
"""

from __future__ import annotations

import contextlib
import time
from collections import defaultdict
from typing import Dict, Iterator


class PhaseTimers:
    """Accumulate wall-clock seconds per named phase."""

    def __init__(self) -> None:
        self.total: Dict[str, float] = defaultdict(float)
        self.count: Dict[str, int] = defaultdict(int)
        self._t0 = time.monotonic()

    @contextlib.contextmanager
    def phase(self, name: str) -> Iterator[None]:
        start = time.monotonic()
        try:
            yield
        finally:
            self.total[name] += time.monotonic() - start
            self.count[name] += 1

    def add(self, name: str, seconds: float) -> None:
        self.total[name] += seconds
        self.count[name] += 1

    def elapsed(self) -> float:
        """Seconds since this timer set was created."""
        return time.monotonic() - self._t0

    def summary(self) -> str:
        lines = [f"total elapsed {self.elapsed():.3f}s"]
        for name in sorted(self.total):
            tot, cnt = self.total[name], self.count[name]
            avg = tot / max(cnt, 1)
            lines.append(f"  {name:<16} {tot:9.3f}s  n={cnt:<8d} avg={avg * 1e3:8.3f}ms")
        return "\n".join(lines)


@contextlib.contextmanager
def trace_annotation(name: str) -> Iterator[None]:
    """jax.profiler annotation when available, no-op otherwise."""
    try:
        import jax.profiler as _prof

        annotation = _prof.TraceAnnotation(name)
    except Exception:  # pragma: no cover - profiler unavailable
        annotation = contextlib.nullcontext()
    with annotation:
        yield


@contextlib.contextmanager
def profiler_trace(log_dir: str | None) -> Iterator[None]:
    """Capture a jax.profiler trace into ``log_dir`` (view with
    TensorBoard / xprof) around the enclosed block; no-op when
    ``log_dir`` is falsy.  The deep-trace companion to
    :class:`PhaseTimers` — trainers accept a ``profile_dir`` config knob
    and wrap their hot loop with this (the rebuild's answer to the
    reference's print-only timing, SURVEY.md §5 tracing)."""
    if not log_dir:
        yield
        return
    import jax.profiler as _prof

    _prof.start_trace(str(log_dir))
    try:
        yield
    finally:
        _prof.stop_trace()
