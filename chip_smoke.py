"""The quickest proof that the system still starts on the chip.

Runs the parameter-server training path once through
``mpit_tpu.train.launch`` — the entry point a user calls — at the full
width of the widest model the repo has: ``TinyDecoder`` d_model 1024 x
8 heads (head width 128) x 4 layers, sequence 8192, batch 1, ~59M
parameters, a 237 MB float32 flat vector.  ``--np 3``: servers 0 and 2
on the host CPU backend holding rmsprop slots beside their shards,
worker 1 on the chip with the Pallas flash kernel compiled by Mosaic.
A few steps: pull, forward+backward, d2h, push over shm, jitted
``rule.apply`` on the servers.  Weights are random, from a seed.

Exit 0 and a last stdout line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``
only if every check holds; any failure exits non-zero and prints no
such line.  It refuses to start when the environment pins the CPU
(``JAX_PLATFORMS=cpu``; a list like ``tpu,cpu`` puts the TPU first and
is fine) or the host shows no chip.  The timings it prints are smoke
timings — wall seconds of set-up and of the steps, for telling a cold
compile cache from a warm one.  It prints no rate and no utilisation:
the trainer's meter is not fenced for that (ROADMAP S1).

This process never initialises a jax backend — importing the launchers
does not, and must not: a parent that has touched jax holds the chip
and its worker child then fails.  The device in the result line is the
one the worker rank reports (``jax.devices()`` in that process).
"""

from __future__ import annotations

import json
import math
import sys
import time

STEPS = 4
TIMEOUT_S = 900.0  # well under the gang default of 3600 s


def main() -> int:
    try:
        from mpit_tpu.train.launch import LAUNCH_DEFAULTS, launch_processes
        from mpit_tpu.utils.platform import count_local_chips, cpu_pinned
    except ImportError as exc:
        print(f"chip_smoke: run from the repository root ({exc})",
              file=sys.stderr)
        return 2
    if cpu_pinned():
        print("chip_smoke: JAX_PLATFORMS pins the CPU; this smoke needs a "
              "TPU chip", file=sys.stderr)
        return 2
    if count_local_chips() < 1:
        print("chip_smoke: no TPU chip on this host", file=sys.stderr)
        return 2

    cfg = LAUNCH_DEFAULTS.merged(
        np=3, lm=1, lm_d_model=1024, lm_heads=8, lm_layers=4, lm_seq=8192,
        batch=1, lm_steps=STEPS, lm_eval_every=STEPS,
        # a server-stateful rule, so the shards carry optimizer slots
        opt="rmsprop", lr=1e-3,
        lm_use_flash=1,  # pinned: the Mosaic kernel or an error, never -1
    )
    t0 = time.monotonic()
    results = launch_processes(cfg, timeout=TIMEOUT_S)
    wall = time.monotonic() - t0

    worker = results[1]
    servers = {r: results[r] for r in (0, 2)}
    failures = []

    def check(ok: bool, what: str) -> None:
        if not ok:
            failures.append(what)

    check(worker.get("role") == "worker", f"rank 1 role {worker.get('role')}")
    check(worker.get("platform") == "tpu",
          f"worker platform {worker.get('platform')!r}, not 'tpu'")
    check(bool(worker.get("device_kind")), "worker reports no device_kind")
    check(worker.get("device_count") == 1 and len(worker.get("chip_nodes", [])) == 1,
          f"worker should hold exactly one chip: device_count "
          f"{worker.get('device_count')}, nodes {worker.get('chip_nodes')}")
    check(worker.get("steps") == STEPS, f"worker took {worker.get('steps')} steps")
    for key in ("final_loss", "final_eval_loss"):
        val = worker.get(key)
        check(isinstance(val, float) and math.isfinite(val),
              f"worker {key} {val!r} is not finite")
    check((worker.get("mosaic_calls") or 0) > 0,
          "no Mosaic custom call in the lowered worker step "
          f"(mosaic_calls={worker.get('mosaic_calls')})")
    for rank, res in servers.items():
        check(res.get("role") == "server", f"rank {rank} role {res.get('role')}")
        check(res.get("platform") == "cpu" and not res.get("chip_nodes"),
              f"server {rank} is not a host role: platform "
              f"{res.get('platform')!r}, nodes {res.get('chip_nodes')}")
        check(res.get("grads_applied") == STEPS,
              f"server {rank} grads_applied {res.get('grads_applied')} != {STEPS}")
        check((res.get("params_served") or 0) > 0,
              f"server {rank} served no params")

    print(f"device: platform {worker.get('platform')}, device_kind "
          f"{worker.get('device_kind')!r}, {worker.get('device_count')} device(s), "
          f"worker holds {worker.get('chip_nodes')}")
    for rank, res in sorted(results.items()):
        counts = {k: res[k] for k in
                  ("grads_applied", "params_served", "steps", "mosaic_calls",
                   "final_loss", "final_eval_loss") if k in res}
        print(f"rank {rank}: {res.get('role')} on {res.get('platform')} "
              f"{res.get('chip_nodes')} {counts}")
    first = float(worker.get("first_step_seconds") or 0.0)
    later = float(worker.get("train_seconds") or 0.0) - first
    timers = worker.get("timers", {})
    print("smoke timings (wall seconds; not a benchmark): "
          f"gang {wall:.1f} = set-up {wall - later:.1f} (process start, "
          f"import, native build, INIT+seed {timers.get('start', 0.0):.1f}, "
          f"first step with its compile {first:.1f}, eval with its compile "
          f"{timers.get('eval', 0.0):.1f}, teardown) + steps 2..{STEPS} "
          f"{later:.1f}")
    if failures:
        for what in failures:
            print(f"chip_smoke: FAILED: {what}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": worker["platform"], "kind": worker["device_kind"],
        "count": worker["device_count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
