"""The benchmark's one command:

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from ``BENCHMARK.json`` and the files it names
(``chipbench/spec.py``), starts the gang through the program's own
``train.gang.launch_gang`` with ``chipbench.child`` as the child module,
watches the host's memory and the clock while it runs, and prints one
JSON object as its last line: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, traced, ``breakdown``.  With ``--trace 0``
the metrics are the cell's end-to-end ones, with ``--trace 1`` its
per-layer ones.  Nothing here names a cell, and this process never
initialises a jax backend (importing the program's launcher imports jax,
which is harmless): a parent that has touched a backend holds the chips
its workers need.

It fails without the chip: ``JAX_PLATFORMS=cpu``, fewer chips than the
cell asks for, a worker that is not on ``tpu`` or a ``device_kind``
missing from ``chipbench/peaks.json`` end the run non-zero with no result
line.  The CPU rehearsal is ``python3 -m chipbench.selfcheck``.
"""

from __future__ import annotations

import time

T_COMMAND = time.monotonic()  # the start of the command, for setup_s

import argparse
import glob
import json
import os
import pathlib
import shutil
import sys
import threading
from typing import Any, Dict, List, Optional

from chipbench import measure, spec as spec_mod

SETUP_BUDGET_S = 1000.0  # a cell's first run in a checkout compiles
MARGIN_S = 120.0
MEM_POLL_S = 0.5
RUNS_DIR = ".chipbench_runs"  # inside the checkout; .gitignore lists it


def say(text: str) -> None:
    print(f"chipbench: {text}", flush=True)


def mem_available_bytes() -> int:
    with open("/proc/meminfo") as fh:
        for line in fh:
            if line.startswith("MemAvailable:"):
                return int(line.split()[1]) * 1024
    raise RuntimeError("/proc/meminfo has no MemAvailable row")


class Watchdog(threading.Thread):
    """Polls ``/proc/meminfo`` at 2 Hz.  Kills the gang when MemAvailable
    falls under a quarter of what the machine had at the start, or when
    the gang outlives its deadline: ``terminate``, then ``kill``, then
    the shm namespace.  ``launch_gang`` then sees a dead rank and raises,
    so the run ends as failed, never as a hang.  The same samples give
    ``host_mem_drop_gb``."""

    def __init__(self, namespace: str, deadline_s: float):
        super().__init__(daemon=True)
        self.namespace = namespace
        self.deadline = time.monotonic() + deadline_s
        self.floor = mem_available_bytes() // 4
        self.samples: List[List[float]] = []  # [monotonic, MemAvailable]
        self.reason: Optional[str] = None
        self._stop_event = threading.Event()

    def run(self) -> None:
        while not self._stop_event.wait(MEM_POLL_S):
            avail = mem_available_bytes()
            self.samples.append([time.monotonic(), float(avail)])
            if avail < self.floor:
                self.reason = (f"MemAvailable {avail / 1e9:.1f} GB fell "
                               f"under the floor {self.floor / 1e9:.1f} GB")
            elif time.monotonic() > self.deadline:
                self.reason = "the gang outlived its deadline"
            if self.reason:
                say(f"watchdog: {self.reason}; killing the gang")
                kill_gang(self.namespace)
                return

    def stop(self) -> None:
        self._stop_event.set()
        self.join(timeout=5)


def kill_gang(namespace: str) -> None:
    import psutil

    children = psutil.Process().children(recursive=True)
    for proc in children:
        try:
            proc.terminate()
        except psutil.NoSuchProcess:
            pass
    _gone, alive = psutil.wait_procs(children, timeout=5)
    for proc in alive:
        try:
            proc.kill()
        except psutil.NoSuchProcess:
            pass
    psutil.wait_procs(alive, timeout=5)
    for segment in glob.glob(f"/dev/shm/mt_{namespace}_r*"):
        try:
            os.unlink(segment)
        except OSError:
            pass


def launch_config(cell: spec_mod.Cell, seed: int) -> Any:
    """The program's launch config for this cell, from data alone: the
    configuration's own ``launcher`` switches and ``launcher_from`` (a
    launcher switch for each of its sizes, by the size's key), the mix's
    ``launcher`` switches, batch, rate and period, and the run's seed.  A
    configuration whose block needs another switch names it in its own
    file (the vocabulary is such a size: ``lm_vocab`` from the
    configuration's own key)."""
    from mpit_tpu.train.launch import LAUNCH_DEFAULTS

    config, mix = cell.config, cell.traffic
    try:
        sizes = {switch: config[key]
                 for switch, key in config["launcher_from"].items()}
    except KeyError as exc:
        raise spec_mod.SpecError(
            f"{cell.config_name}: launcher_from names a missing key "
            f"{exc}") from exc
    return LAUNCH_DEFAULTS.merged(
        **{**config.get("launcher", {}), **sizes, **mix["launcher"]},
        batch=mix["batch"], lr=mix["lr"], su=mix["su"], seed=seed,
        lm_eval_every=0, lm_steps=0)


def build_model(cell: spec_mod.Cell, seed: int, **switches: Any) -> Any:
    """The cell's model as the program's own trainer builds it from the
    launch config (``train/launch.py`` ``lm_trainer_cfg``, ``LmTrainer``:
    what a worker rank does), with launcher ``switches`` laid over it.
    For the by-hand tools and the self-check, which need the flat vector
    and the loss without a gang; whatever block the configuration's
    switches select is the block they get."""
    from mpit_tpu.lm import LmTrainer
    from mpit_tpu.train.launch import lm_trainer_cfg

    cfg = launch_config(cell, seed).merged(**switches)
    return LmTrainer(lm_trainer_cfg(cfg)).model


def device_env(cfg: Any) -> Dict[int, Dict[str, str]]:
    from mpit_tpu.train.gang import assign_devices
    from mpit_tpu.train.launch import device_env_overrides

    if int(cfg.np) > 1:
        return device_env_overrides(cfg, int(cfg.np))
    if cfg.get("device_policy") == "cpu":
        return {0: {"JAX_PLATFORMS": "cpu"}}
    return assign_devices(1, [0])


def run_gang(cell: spec_mod.Cell, seed: int, seconds: float, trace: bool,
             run_dir: pathlib.Path) -> Dict[str, Any]:
    """Starts the gang, waits for it under the watchdog, and returns the
    ranks' results with the runner's own records."""
    from mpit_tpu.train.gang import launch_gang
    from mpit_tpu.train.launch import assign_roles

    cfg = launch_config(cell, seed)
    size = int(cfg.np)
    workers = ([0] if size == 1 else
               assign_roles(size, int(cfg.master_freq), "none")[1])
    namespace = f"cb{os.getpid()}"
    cfg = cfg.merged(namespace=namespace)
    shutil.rmtree(run_dir, ignore_errors=True)
    run_dir.mkdir(parents=True)
    mix = cell.traffic
    os.environ["CHIPBENCH_SPEC"] = json.dumps({
        "seconds": seconds, "trace": bool(trace),
        "trace_dir": str(run_dir / "device_trace"),
        "trace_rounds": max(3, -(-4 // int(mix["su"]))),
        "token_budget": int(mix["token_budget"]),
        "warmup_rounds": int(mix["warmup_rounds"]),
        "step_module": mix["step_module"],
        # the configuration as this run has it (the self-check's is
        # shrunk in memory) and where its reference module is found
        "config": cell.config,
        "bench_dir": str(spec_mod.bench_dir(cell.root, cell.bench)),
        "run_dir": str(run_dir), "worker_ranks": workers,
    })
    obs_trace = run_dir / "obs_trace.json"
    if trace:
        os.environ["MPIT_OBS_TRACE"] = str(obs_trace)
    watchdog = Watchdog(namespace, SETUP_BUDGET_S + seconds + MARGIN_S)
    watchdog.start()
    t_spawn = time.monotonic()
    try:
        results = launch_gang("chipbench.child", cfg,
                              timeout=SETUP_BUDGET_S + seconds + 2 * MARGIN_S,
                              env_overrides=device_env(cfg))
    except RuntimeError as exc:
        reason = watchdog.reason or str(exc)
        kill_gang(namespace)
        raise measure.RunFailed(reason) from exc
    finally:
        watchdog.stop()
    return {
        "results": {int(r): res for r, res in results.items()},
        "t_spawn": t_spawn,
        "mem_samples": watchdog.samples,
        "obs_trace": str(obs_trace) if trace else None,
    }


def per_layer(cell: spec_mod.Cell, run: Dict[str, Any]) -> Dict[str, Any]:
    """Each per-layer metric of the cell through its own reader; a
    reader that finds nothing returns None and the metric is left out."""
    out: Dict[str, Any] = {}
    for metric in cell.metrics("per_layer"):
        reader = spec_mod.load_reader(cell.root, cell.bench, metric["name"])
        if reader is None:
            say(f"no reader for {metric['name']}")
            continue
        value = reader(run)
        if value is None:
            say(f"{metric['name']}: nothing to read")
            continue
        out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def run_cell(cell: spec_mod.Cell, seed: int, seconds: float, trace: bool,
             platform: str = "tpu",
             stand_in_reduction: Optional[Dict[str, Any]] = None,
             ) -> Dict[str, Any]:
    """One run of one cell; returns the object of the last line under
    ``line`` with the records it was made from.  ``platform`` is ``tpu``
    except in the self-check's CPU rehearsal, which has no device trace
    and hands the readers the fixture's reduction instead."""
    from chipbench import flops

    run_dir = cell.root / RUNS_DIR / f"{cell.name}-seed{seed}-trace{int(trace)}"
    gang = run_gang(cell, seed, seconds, trace, run_dir)
    results = gang["results"]
    summary = measure.summarise(results, T_COMMAND,
                                int(cell.traffic["token_budget"]),
                                float(cell.traffic["min_learning_nats"]))
    first = results[summary["worker_ranks"][0]]
    kind = first.get("device_kind", "")
    peaks = flops.load_peaks(kind) if platform == "tpu" else None
    arithmetic = cell.arithmetic()
    families = arithmetic.kernels(cell.config, int(cell.traffic["batch"]))
    vector_len = int(arithmetic.param_count(cell.config))
    why_not = measure.correctness(
        results, summary, platform,
        sum(int(k["least_calls"]) for k in families.values()), vector_len)
    for reason in why_not:
        say(f"NOT CORRECT: {reason}")

    reference = first["chipbench_worker"]["reference"]
    say(f"window {summary['window_s']:.3f} s, rounds in it "
        f"{summary['rounds_in_window']}, micro-step median "
        f"{summary['step_ms_p50'] / 1e3:.4f} s, max "
        f"{summary['micro_step_s_max']:.4f} s, batch build median "
        f"{first['chipbench_worker']['batch_build_ms_p50']:.2f} ms")
    say(f"tokens_per_s is the median round's {summary['tokens_per_s']:.1f}; "
        f"tokens over the window's seconds "
        f"{summary['tokens_per_s_window_mean']:.1f}, round_stall_pct "
        f"{summary['round_stall_pct']:.3f}")
    if summary["stalls"]:
        say("micro-steps over three times the median [rank, step, s]: "
            + json.dumps(summary["stalls"]))
    say(f"learning: first loss {summary['first_loss']:.4f}, at the budget "
        f"{summary['loss_at_budget']:.4f}, the mix wants "
        f"{summary['min_learning_nats']:.4f} nats between them")
    say(f"reference check ({cell.config['reference']}): loss "
        f"{reference['loss_sys']:.6f} against {reference['loss_ref']:.6f} "
        f"(|d| {reference['loss_abs_err']:.2e}, limit "
        f"{reference['loss_tol']:.2e}), gradient relative error "
        f"{reference['grad_rel_err']:.3e} (limit "
        f"{reference['grad_tol']:.2e})")
    say(f"exchanged vector: {first['chipbench_worker']['vector_len']} "
        f"elements in the program, {vector_len} by the configuration's "
        f"arithmetic ({cell.config['arithmetic']}), "
        f"{vector_len * flops.F32 / 1e6:.1f} MB; tpu_custom_calls in the "
        f"lowered step {first.get('mosaic_calls')}, its kernel families "
        "need " + json.dumps({f: k["least_calls"]
                              for f, k in families.items()}))
    say("set-up parts of the first worker (s): " + json.dumps(
        setup_parts(gang["t_spawn"], first["chipbench"]["marks"])))
    compiled = [c for c in first["chipbench"]["compiles"]
                if c[0] == "backend_compile_duration"]
    say(f"backend compile calls in the first worker, compile-cache reads "
        f"included: {len(compiled)}, {sum(c[2] for c in compiled):.1f} s")
    say("losses by micro-step, first worker: " + json.dumps(
        [round(row[3], 4) for row in first["chipbench_worker"]["step_rows"]]))
    say(f"device memory_stats: {first['chipbench_worker']['memory_stats']}")
    if peaks is not None and not trace:
        mfu = flops.mfu_pct(cell, summary["tokens_per_s"],
                            len(summary["worker_ranks"]), peaks)
        say(f"mfu_pct of this untraced run: {mfu:.3f}")

    device = {
        "platform": first.get("platform"), "kind": kind,
        "count": sum(results[r].get("device_count", 0)
                     for r in summary["worker_ranks"]),
        "memory_peak_bytes": summary["memory_peak_bytes"],
    }
    line: Dict[str, Any] = {
        "correct": not why_not, "attempted": summary["attempted"],
        "failed": summary["failed"], "metrics": {}, "device": device,
    }
    out = {"line": line, "gang": gang, "summary": summary}
    if not trace:
        for metric in cell.metrics("end_to_end"):
            line["metrics"][metric["name"]] = {
                "value": summary[metric["name"]], "unit": metric["unit"]}
        return out
    reduction = first["chipbench_worker"]["reduction"]
    if platform != "tpu" and stand_in_reduction is not None:
        reduction = stand_in_reduction
    if not reduction or not reduction.get("ok"):
        raise measure.RunFailed(f"no usable device trace: {reduction}")
    run = {**gang, "cell": cell, "summary": summary, "reduction": reduction,
           "peaks": peaks, "first_worker": first}
    say("programs in the trace, runs and total ms: "
        + json.dumps(reduction.get("modules")))
    say("Mosaic kernels in the traced window by model scope [calls, s]: "
        + json.dumps(reduction.get("mosaic_by_scope"))
        + f"; all of them {reduction.get('mosaic_calls')} calls, "
        f"{reduction.get('mosaic_s')} s")
    if reduction.get("mosaic_no_family"):
        say("Mosaic calls under no scope of the configuration or under two, "
            "counted for no kernel family [label, calls, s]: "
            + json.dumps(reduction["mosaic_no_family"]))
    line["metrics"] = per_layer(cell, run)
    device["busy_s"] = reduction["busy_s"]
    device["window_s"] = reduction["window_s"]
    line["breakdown"] = {"device_ops": reduction["device_ops"],
                         "idle_gaps": reduction["idle_gaps"]}
    return out


def setup_parts(t_spawn: float, marks: Dict[str, float]) -> Dict[str, float]:
    """The first worker's set-up, split as PERF.md section 5 has it."""
    return {
        "runner_start_to_spawn": round(t_spawn - T_COMMAND, 3),
        "process_start_and_imports":
            round(marks["jax_imported"] - t_spawn, 3),
        "reaching_the_device": round(
            marks["device_ready"] - marks["jax_imported"], 3),
        "barrier": round(marks["past_barrier"] - marks["device_ready"], 3),
        "trainer_build": round(marks["loop_enter"] - marks["past_barrier"], 3),
        "init_and_seeding": round(
            marks["init_seed_done"] - marks["loop_enter"], 3),
        "reference_check_and_kernel_count": round(
            marks["reference_done"] - marks["init_seed_done"], 3),
        "warmup_with_compile": round(
            marks["warmup_done"] - marks["reference_done"], 3),
        "waiting_for_peers": round(
            marks["window_open"] - marks["warmup_done"], 3),
    }


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(prog="chipbench.run")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    try:
        cell = spec_mod.load_cell(args.workload)
        from mpit_tpu.utils.platform import count_local_chips, cpu_pinned

        if cpu_pinned():
            say("JAX_PLATFORMS pins the CPU; the benchmark needs the chip "
                "(the CPU rehearsal is python3 -m chipbench.selfcheck)")
            return 2
        if count_local_chips() < cell.chips:
            say(f"{cell.name} needs {cell.chips} chip(s); this host shows "
                f"{count_local_chips()}")
            return 2
        line = run_cell(cell, args.seed, args.seconds,
                        bool(args.trace))["line"]
    except (spec_mod.SpecError, measure.RunFailed, KeyError,
            ImportError) as exc:
        say(f"FAILED: {exc}")
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
