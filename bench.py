"""Headline benchmark: the BASELINE north-star, measured on real training.

Three metrics in one JSON line (reference shapes: asyncsgd/goot.lua:144-157
time-to-test-error loop, asyncsgd/ptest.lua:58-67 push/pull MB/s):

- ``value`` / ``metric`` — steady-state training throughput (samples/s)
  of the flagship MNIST EASGD mesh trainer (mlaunch.lua:39-47 path).
  Each epoch is a fresh shuffle staged to HBM in one transfer (the
  framework's device_stream input pipeline); every step trains a
  different batch; timing is the latency-cancelled fetch-fenced recipe
  of :mod:`mpit_tpu.utils.timing` over whole epoch passes.
- ``time_to_target_s`` — wall-clock from post-compile t0 until test
  error <= ``target_test_err`` (compile is AOT/warmed and reported
  separately as ``compile_s``).  Default mode is ``device_loop``: the
  entire train-to-target runs as one ``lax.while_loop`` device program,
  so the number measures the device rather than per-epoch host round
  trips (the default came from a July 2026 A/B the ledger has not
  reproduced).  ``data_source`` names what was trained on — this
  environment has no real MNIST; the loader uses the committed
  optdigits fixture (data/mnist.py docstring).
- ``ps_pushpull_mbs_per_chip`` — bi-directional PS shard push/pull
  bandwidth per chip over the mesh ``shard`` axis (the ptest.lua
  measurement riding ICI collectives instead of MPI).

``vs_baseline`` compares throughput against a live-measured
reference-equivalent: the same CNN + Nesterov-SGD step in torch on host
CPU with the same staged-epoch input pipeline (one permuted tensor per
epoch, per-step slices) — the reference ran its ranks on CPU torch
(SURVEY.md §6) and publishes no absolute numbers (BASELINE.md), so
CPU-torch throughput of the identical workload is the honest stand-in.
>1.0 means this framework beats the reference-shaped run.

Reproducibility (round-4 discipline): every leg runs
``MPIT_BENCH_REPS`` times (default 3) and the JSON carries the median
plus the per-run values and max-min spread — a single-shot number is
not evidence.  The record names the device it ran on (``platform``,
``device_kind``, ``device_count``); a leg that fails fails the run —
there is no probe, no retry and no CPU re-run.  jit compile is excluded
from the timed region (the trainers precompile with the persistent XLA
cache, utils/platform.enable_compile_cache) and reported separately as
``compile_s``; ``time_to_target_s`` is wall clock from t0 *after*
warmup, as a warm-cache user would experience it.

Env knobs: MPIT_BENCH_EPOCHS (default 30), MPIT_BENCH_MB (PS payload,
default 640 — the reference ptest.lua:3 scale), MPIT_BENCH_ROUNDS
(default 20), MPIT_BENCH_REPS (default 3).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# stdout carries exactly one JSON line (the driver contract); all
# framework logging goes to stderr.
os.environ.setdefault("MPIT_LOG_STREAM", "stderr")

BATCH = 128
SIDE = 32
EPOCHS = int(os.environ.get("MPIT_BENCH_EPOCHS", "30"))
PS_MB = float(os.environ.get("MPIT_BENCH_MB", "640"))  # ptest.lua:3 payload
PS_ROUNDS = int(os.environ.get("MPIT_BENCH_ROUNDS", "20"))
REPS = max(int(os.environ.get("MPIT_BENCH_REPS", "3")), 1)
TORCH_ITERS = 30


def _log(*a):
    print(*a, file=sys.stderr, flush=True)


def _median(xs):
    return float(np.median(np.asarray(xs, np.float64)))


def _spread_pct(xs):
    """max-min spread as % of the median (0 for degenerate medians)."""
    med = _median(xs)
    return abs(max(xs) - min(xs)) / abs(med) * 100.0 if med else 0.0


def _torch_threads() -> int:
    """Cores actually usable by this process (affinity/cgroup aware) —
    os.cpu_count() would oversubscribe a pinned container and slow the
    torch baseline below its honest rate."""
    try:
        return len(os.sched_getaffinity(0)) or 1
    except AttributeError:  # non-linux
        return os.cpu_count() or 1


def bench_train() -> dict:
    """Flagship mesh-EASGD run to target test error on the real stream."""
    from mpit_tpu.train.mesh_launch import (
        FLAGSHIP_BENCH_KWARGS, MESH_LAUNCH_DEFAULTS, run,
    )

    # target_test_err: BASELINE's north star is 1% on real MNIST; this
    # environment has only the sklearn-digits fallback, where the flagship
    # config plateaus at ~2.2% (it memorizes the 1527-example train split)
    # — 2% is the achievable stand-in, and the JSON names both the target
    # and the source.
    target = float(os.environ.get("MPIT_BENCH_TARGET", "0.02"))
    # device_loop=1: the whole train-to-target runs as ONE lax.while_loop
    # device program (on-device shuffle + epoch scan + eval + early
    # exit), so time_to_target measures the device, not per-epoch host
    # round trips — the default came from a July 2026 A/B on a
    # forced-host-device CPU that the ledger has no counterpart of.  The
    # steady-throughput leg is mode-independent (same compiled epoch
    # scan either way).  MPIT_BENCH_DEVICE_LOOP=0 restores the
    # host-loop measurement.
    device_loop = int(os.environ.get("MPIT_BENCH_DEVICE_LOOP", "1"))
    cfg = MESH_LAUNCH_DEFAULTS.merged(
        **FLAGSHIP_BENCH_KWARGS, epochs=EPOCHS,
        target_test_err=target, stop_at_target=1, measure_throughput=1,
        device_loop=device_loop,
    )
    result = run(cfg)
    result["target_test_err"] = target
    result["train_mode"] = "device_loop" if device_loop else "host_loop"
    err = result["final_test_err"]
    _log(
        f"train: {result['samples_trained']} samples in "
        f"{result['train_time']:.2f}s wall train-time "
        f"({result['samples_per_sec']} samples/s wall, "
        f"{result['samples_per_sec_steady']} steady); final test_err "
        f"{'n/a' if err is None else format(err, '.4f')}; time_to_target "
        f"{result['time_to_target']}; source {result['data_source']}"
    )
    return result


def bench_ps_pushpull() -> dict:
    """ptest.lua analog: PS shard push/pull bandwidth over ICI (shared
    implementation: :func:`mpit_tpu.parallel.collective.measure_ps_pushpull`)."""
    from mpit_tpu.parallel.collective import measure_ps_pushpull

    r = measure_ps_pushpull(PS_MB, rounds=PS_ROUNDS)
    _log(f"ps: {r['ms_per_round']:.2f} ms/round of {r['payload_mb']:.1f} MB "
         f"-> {r['mbs']:.1f} MB/s ({r['per_chip']:.1f} MB/s/chip, "
         f"{r['devices']} chips)")
    return r


def bench_torch_cpu() -> float:
    """Reference-equivalent: identical CNN + Nesterov SGD, torch on CPU,
    same staged-epoch pipeline as the jax leg (one permuted tensor per
    epoch, per-step slices of fresh data).  Threads pinned to the host's
    core count (deterministic per host — the round-3 725->1157 samples/s
    drift came from an unpinned, load-dependent thread pool)."""
    import torch
    import torch.nn as tnn

    from mpit_tpu.data.mnist import load_mnist
    from mpit_tpu.train.mesh_launch import FLAGSHIP_BENCH_KWARGS

    # The torch leg must mirror the jax leg's workload shape exactly —
    # raise, not assert: python -O would compile an assert away and the
    # torch leg would silently time a different workload.
    if (FLAGSHIP_BENCH_KWARGS["batch"] != BATCH
            or FLAGSHIP_BENCH_KWARGS["side"] != SIDE):
        raise ValueError(
            "torch baseline shape drifted from FLAGSHIP_BENCH_KWARGS: "
            f"batch {FLAGSHIP_BENCH_KWARGS['batch']} vs {BATCH}, "
            f"side {FLAGSHIP_BENCH_KWARGS['side']} vs {SIDE}")

    (x_train, y_train, _, _), _src = load_mnist(side=SIDE)
    torch.manual_seed(0)
    torch.set_num_threads(_torch_threads())
    width = 32
    model = tnn.Sequential(
        tnn.Conv2d(1, width, 3, padding=1), tnn.ReLU(), tnn.MaxPool2d(2),
        tnn.Conv2d(width, 2 * width, 3, padding=1), tnn.ReLU(), tnn.MaxPool2d(2),
        tnn.Flatten(),
        tnn.Linear((SIDE // 4) ** 2 * 2 * width, 4 * width), tnn.ReLU(),
        tnn.Linear(4 * width, 10), tnn.LogSoftmax(dim=1),
    )
    opt = torch.optim.SGD(model.parameters(), lr=1e-2, momentum=0.99, nesterov=True)
    lossf = tnn.NLLLoss()
    n = len(x_train)
    rng = np.random.default_rng(0)
    steps = max(n // BATCH, 1)
    order = rng.permutation(n)[: steps * BATCH]
    x_ep = torch.from_numpy(
        x_train[order].reshape(steps, BATCH, 1, SIDE, SIDE))
    y_ep = torch.from_numpy(
        y_train[order].astype(np.int64).reshape(steps, BATCH))

    def step(i):
        opt.zero_grad()
        loss = lossf(model(x_ep[i % steps]), y_ep[i % steps])
        loss.backward()
        opt.step()

    for i in range(3):
        step(i)
    t0 = time.perf_counter()
    for i in range(TORCH_ITERS):
        step(i)
    dt = time.perf_counter() - t0
    sps = TORCH_ITERS * BATCH / dt
    _log(f"torch-cpu: {TORCH_ITERS} staged steps of {BATCH} in {dt:.3f}s "
         f"-> {sps:.1f} samples/s")
    return sps


def main():
    trains = []
    for rep in range(REPS):
        _log(f"-- train rep {rep + 1}/{REPS} --")
        trains.append(bench_train())
    sps_runs = [
        t["samples_per_sec_steady"] or t["samples_per_sec"] or 0.0
        for t in trains
    ]
    ttt_runs = [t["time_to_target"] for t in trains
                if t["time_to_target"] is not None]
    compile_runs = [t["compile_s"] for t in trains
                    if t["compile_s"] is not None]
    sps = _median(sps_runs)
    train = trains[0]  # target/data_source/final_err are rep-invariant

    ps_runs = [bench_ps_pushpull() for _ in range(REPS)]
    ps_chip = [r["per_chip"] for r in ps_runs]
    torch_runs = [bench_torch_cpu() for _ in range(REPS)]
    base = _median(torch_runs)
    vs = sps / base

    from mpit_tpu.utils.platform import device_report

    device = device_report()
    print(json.dumps({
        "metric": "mnist_easgd_train_samples_per_sec",
        "platform": device["platform"],
        "device_kind": device["device_kind"],
        "device_count": device["device_count"],
        "value": round(sps, 1),
        "unit": "samples/s",
        "vs_baseline": round(vs, 3),
        "reps": REPS,
        "value_runs": [round(v, 1) for v in sps_runs],
        "value_spread_pct": round(_spread_pct(sps_runs), 1),
        "time_to_target_s": round(_median(ttt_runs), 3) if ttt_runs else None,
        "time_to_target_runs": [round(v, 3) for v in ttt_runs],
        "compile_s": round(_median(compile_runs), 3) if compile_runs else None,
        "target_test_err": train["target_test_err"],
        "train_mode": train["train_mode"],
        "measurement_condition": "BASELINE.md §'Measurement condition in "
        "THIS environment' (optdigits-8x8 fixture, 2% target; no-egress "
        "environment, real MNIST unavailable)",
        "final_test_err": train["final_test_err"],
        "epochs_run": len(train["history"]),
        "data_source": train["data_source"],
        "ps_pushpull_mbs_per_chip": round(_median(ps_chip), 1),
        "ps_pushpull_runs": [round(v, 1) for v in ps_chip],
        "ps_spread_pct": round(_spread_pct(ps_chip), 1),
        "ps_devices": ps_runs[0]["devices"],
        "torch_cpu_sps": round(base, 1),
        "torch_cpu_runs": [round(v, 1) for v in torch_runs],
        "torch_threads": _torch_threads(),
    }))


if __name__ == "__main__":
    main()
