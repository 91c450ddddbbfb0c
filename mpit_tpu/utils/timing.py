"""Device timing under async dispatch: a fetch fence and two-length
differencing.

jax dispatch is async, so a timing must end in a fence.  The plain
recipe is "loop N dispatches, then ``block_until_ready``"; the fence
here is a host fetch of one element of the last result
(:func:`fetch_scalar`) — the value can only be served after the work
that produces it has run, and a device executes its queue in order, so
one fetch fences the whole loop.

What remains in either recipe is the fixed dispatch+fetch latency of a
loop, which matters for short loops of short ops.
:func:`timed_per_call` cancels it by timing two loop lengths and
differencing:

    t(n) = overhead + n * per_call   =>   per_call = (t(b+n) - t(b)) / n

On the v5e machine (jax 0.9.0, libtpu 0.0.34) the two recipes agree: an
8192^3 bf16 matmul, three repetitions each, measured 5.93 ms per call
with a 20-iteration loop ending in ``block_until_ready`` and 5.90 ms
with ``timed_per_call(iters=20)`` (185 and 186 TFLOP/s; chip run of
PR 21, CHANGES.md) — the 0.5% between them is the one loop overhead the
differencing removes.  Which recipe the benchmark keeps is ROADMAP S1's
decision.
"""

from __future__ import annotations

import time
from typing import Any, Callable

import numpy as np


def fetch_scalar(out: Any) -> float:
    """Force completion of everything queued before ``out`` by fetching a
    single element of its first array leaf to the host."""
    import jax

    leaf = jax.tree_util.tree_leaves(out)[0]
    return float(np.asarray(leaf[(0,) * getattr(leaf, "ndim", 0)]))


# Smallest per-call time the estimator will ever report.  A differenced
# estimate at or below zero means the extra iterations were lost in
# timer/scheduler noise; reporting a strictly-positive floor keeps
# machine-read JSON out of the nonsensical "0.0 ms" / negative regime.
MIN_RESOLVABLE_S = 1e-9


def _auto_scaled_estimate(
    measure: Callable[[int], tuple[list, list]],
    iters: int,
    auto_scale: bool,
    max_iters: int,
    min_ratio: float,
) -> float:
    """Shared escalation loop of both timing helpers.  ``measure(iters)``
    returns (small-leg times, big-leg times); the per-call estimate is
    the difference of the per-leg minima, and ``iters`` doubles until
    that difference clears ``min_ratio`` x the observed per-leg jitter
    (or ``max_iters``).  Floored at :data:`MIN_RESOLVABLE_S`."""
    while True:
        smalls, bigs = measure(iters)
        delta = min(bigs) - min(smalls)
        jitter = max(max(smalls) - min(smalls), max(bigs) - min(bigs))
        if (not auto_scale or delta > min_ratio * jitter
                or iters * 2 > max_iters):
            return max(delta, MIN_RESOLVABLE_S * iters) / iters
        iters *= 2


def timed_per_call(
    fn: Callable[..., Any],
    *args: Any,
    iters: int = 10,
    base_iters: int = 1,
    repeats: int = 3,
    auto_scale: bool = False,
    max_iters: int = 2000,
    min_ratio: float = 1.0,
) -> float:
    """Seconds per call of ``fn(*args)`` on device, latency-cancelled.

    ``fn`` is called with the same arguments every iteration; results are
    discarded (the runtime still executes every queued call — the final
    fetch fences them all).  Each leg is measured ``repeats`` times and
    the difference is taken between the per-leg minima: jitter is
    additive-positive, so min() per leg filters it, whereas min over
    *differences* would lock in exactly the repeat whose short leg
    caught a spike (an overestimate of speed).

    With ``auto_scale``, when the big-leg/small-leg difference does not
    exceed the observed per-leg jitter (sub-resolution: the measured op
    is too fast for ``iters`` at the current load), ``iters`` doubles and
    the measurement reruns, up to ``max_iters`` — fast ops on a loaded
    host otherwise difference two minima into a ≤0 estimate.  The result
    is always floored at :data:`MIN_RESOLVABLE_S`.

    ``min_ratio`` sharpens the stop rule: ``delta > min_ratio * jitter``.
    The default (1) only guarantees signal exceeds noise — up to ~100%
    relative error.  Callers that publish the number should pass 5-10:
    the relative error is bounded by roughly ``jitter/delta <
    1/min_ratio`` (on a high-jitter link min_ratio=1 once let one rep
    of a ~2.9 ms op read 1.7x fast; min_ratio=8 held reps within a few
    %).
    """
    fetch_scalar(fn(*args))  # compile + warm

    def run(n: int) -> float:
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            out = fn(*args)
        fetch_scalar(out)
        return time.perf_counter() - t0

    def measure(n: int):
        # the small leg is deliberately re-measured every escalation
        # round: its minimum and spread anchor the jitter estimate, and
        # host load drifts over the seconds an escalated measurement
        # takes — stale smalls would difference against old conditions.
        smalls = [run(base_iters) for _ in range(repeats)]
        bigs = [run(base_iters + n) for _ in range(repeats)]
        return smalls, bigs

    return _auto_scaled_estimate(measure, iters, auto_scale, max_iters,
                                 min_ratio)


def timed_chained(
    fn: Callable[..., Any],
    state: Any,
    *args: Any,
    iters: int = 10,
    base_iters: int = 1,
    repeats: int = 3,
    auto_scale: bool = False,
    max_iters: int = 2000,
    min_ratio: float = 1.0,
) -> float:
    """Like :func:`timed_per_call` for state-threading calls:
    ``state = fn(state, *args)`` each iteration.  This is the honest way
    to time donated/in-place update kernels — calling them repeatedly on
    the *same* buffers would either fault (donated input reuse) or force
    the runtime to insert defensive copies that a real training loop
    never pays.  Per-leg minima and ``auto_scale`` semantics as in
    :func:`timed_per_call` (state keeps threading through escalation
    rounds — fine for update steps, whose cost is state-independent)."""
    state = fn(state, *args)  # compile + warm
    fetch_scalar(state)

    def run(n: int, st: Any) -> tuple[float, Any]:
        t0 = time.perf_counter()
        for _ in range(n):
            st = fn(st, *args)
        fetch_scalar(st)
        return time.perf_counter() - t0, st

    st = [state]  # threaded through every leg across escalation rounds

    def measure(n: int):
        smalls, bigs = [], []
        for _ in range(repeats):
            t_small, st[0] = run(base_iters, st[0])
            smalls.append(t_small)
            t_big, st[0] = run(base_iters + n, st[0])
            bigs.append(t_big)
        return smalls, bigs

    return _auto_scaled_estimate(measure, iters, auto_scale, max_iters,
                                 min_ratio)
