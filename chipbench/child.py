"""The gang's child module: ``python -m chipbench.child --child`` is one
rank of a gang that ``chipbench/run.py`` starts through the program's own
``train.gang.launch_gang``.

Roles, devices, transport, servers, client and trainer are built by the
program, unchanged: every rank calls ``train.launch.run_rank``.  A server
rank runs it as it is.  A worker rank (or the one rank of a local cell)
lets ``run_rank`` build the ``LmTrainer`` and the client, and replaces
only the loop of ``LmTrainer.run`` with the benchmark's
(:class:`WorkerLoop`): warm-up in whole sync rounds, the reference check,
then fenced rounds until the window's seconds have passed, on batches
from the benchmark's own copy of the stream, with a timing proxy around
the client at the ``ParamClientAPI`` boundary.

What this leans on inside the program is listed in PERF.md section 7 for
the ``tracing`` issue: ``LmTrainer``'s attributes ``cfg``, ``pc``,
``rank``, ``w``, ``model``, ``optimizer`` and ``_vgf``; that the
optimizer reads ``pc`` when it is first built; ``opt.start``,
``opt.step`` and ``opt.stop``; and the last lines of launch's
``_child_main``.
"""

from __future__ import annotations

import glob
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

SPEC_ENV = "CHIPBENCH_SPEC"
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)

now = time.monotonic  # one clock for every process of the host


class CompileLog:
    """When this process traced, lowered or compiled a program: the
    window may hold none of it."""

    def __init__(self) -> None:
        self.events: List[List[Any]] = []

    def install(self) -> None:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw: Any) -> None:
        if event in COMPILE_EVENTS:
            self.events.append([event.rsplit("/", 1)[1], now(), duration])


class TimingProxy:
    """The benchmark's spans at the ``ParamClientAPI`` boundary
    (``optim/client_api.py``): one round is from the first ``async_*``
    call after a ``wait`` to the return of the next ``wait``.  Everything
    else goes straight to the program's client."""

    def __init__(self, inner: Any, annotate: Any):
        self._inner = inner
        self._annotate = annotate
        self._open: Optional[float] = None
        self._span: Any = None
        self._has_push = False
        self.rounds: List[List[float]] = []  # [t_first_async, t_wait_return]
        self.pushes = 0  # GRAD pushes whose wait returned

    def __getattr__(self, name: str) -> Any:
        return getattr(self._inner, name)

    def _begin(self) -> None:
        if self._open is None:
            self._open = now()
            self._span = self._annotate("bench.ps_round")
            self._span.__enter__()

    def async_send_grad(self) -> None:
        self._begin()
        self._has_push = True
        self._inner.async_send_grad()

    def async_recv_param(self) -> None:
        self._begin()
        self._inner.async_recv_param()

    def async_send_param(self) -> None:
        self._begin()
        self._inner.async_send_param()

    def wait(self) -> None:
        try:
            self._inner.wait()
        finally:
            if self._span is not None:
                self._span.__exit__(None, None, None)
                self._span = None
        if self._open is not None:
            self.rounds.append([self._open, now()])
            self.pushes += int(self._has_push)
            self._open = None
            self._has_push = False


class WorkerLoop:
    """The benchmark's loop in place of ``LmTrainer.run``."""

    def __init__(self, spec: Dict[str, Any], marks: Dict[str, float]):
        self.spec = spec
        self.marks = marks

    # -- pieces ---------------------------------------------------------------

    def _check_stream(self, seed: int, batch: int, seq_len: int) -> bool:
        """The copied generator against the program's, three pairs."""
        import numpy as np

        from chipbench.traffic.packed_bytes import packed_batch
        from mpit_tpu.lm.data import packed_batch as program_batch

        return all(
            np.array_equal(packed_batch(s, k, batch, seq_len),
                           program_batch(s, k, batch=batch, seq_len=seq_len))
            for s, k in ((seed, 0), (seed, 7), (seed + 31, 2)))

    def _reference_check(self, tr: Any, tokens: Any) -> Dict[str, Any]:
        """The system's loss and gradient against the configuration's
        plain reference (``reference/<module>.py``, by the key
        ``reference`` of the configuration's file, which the module is
        handed whole) on one seeded sequence at the cell's widths, on
        the seeded initial weights; and the count of Mosaic calls in the
        lowered step.  The
        system takes the sequence repeated over the cell's batch, so one
        trace and one lowering of the very program the window runs serve
        both (each costs seconds at these depths), and the mean over
        equal rows is the one row's loss and gradient.  The reference
        runs first and flat vector to flat vector, and the comparison is
        one fused reduction: beside the weights only the two gradients
        are alive, which is what the widest cell has room for (the
        step's temporaries stay reserved once it has run, PERF.md
        section 6).  In set-up, outside the window."""
        import jax
        import jax.numpy as jnp

        from chipbench import compare, spec as spec_mod

        config = self.spec["config"]
        reference = spec_mod.load_named(self.spec["bench_dir"], "reference",
                                        config)
        w0 = tr.model.flat.w0
        row = tokens[:1]
        tiled = jnp.tile(row, (tokens.shape[0], 1))
        lowered = jax.jit(tr._vgf).lower(w0, tiled)
        mosaic_calls = lowered.as_text().count("tpu_custom_call")
        ref_loss, ref_grad = reference.loss_and_grad_flat(
            w0, tr.model.flat.unravel, row, config)
        sys_loss, sys_grad = lowered.compile()(w0, tiled)
        out = compare.compare(sys_loss, sys_grad, ref_loss, ref_grad,
                              reference)
        out["mosaic_calls"] = mosaic_calls
        return out

    def _wait_for_peers(self, rank: int) -> bool:
        """Whether every worker of the gang has finished its set-up: each
        leaves a file when it has, and goes on training until all have,
        so that the windows open within a round of each other."""
        run_dir = self.spec["run_dir"]
        mine = os.path.join(run_dir, f"ready.{rank}")
        if not os.path.exists(mine):
            with open(mine, "w") as fh:
                fh.write(repr(now()))
        return all(os.path.exists(os.path.join(run_dir, f"ready.{r}"))
                   for r in self.spec["worker_ranks"])

    # -- the loop -------------------------------------------------------------

    def run(self, tr: Any) -> Dict[str, Any]:
        import jax
        import jax.numpy as jnp

        from chipbench.traffic.packed_bytes import packed_batch

        spec, marks = self.spec, self.marks
        annotate = jax.profiler.TraceAnnotation
        cfg = tr.cfg
        batch, seq_len = int(cfg.batch), int(cfg.seq_len)
        seed = int(cfg.seed) + int(tr.rank)  # per-rank stream, as the program's
        tokens_per_step = batch * seq_len
        stream_ok = self._check_stream(seed, batch, seq_len)

        proxy: Optional[TimingProxy] = None
        if tr.pc is not None:
            proxy = TimingProxy(tr.pc, annotate)
            tr.pc = proxy  # before the optimizer is built: it keeps this
        opt = tr.optimizer
        marks["loop_enter"] = now()
        if hasattr(opt, "start"):
            tr.w = opt.start(tr.w)  # INIT and, from the first worker, seeding
        marks["init_seed_done"] = now()

        reference = self._reference_check(
            tr, jnp.asarray(packed_batch(seed, 0, batch, seq_len)))
        marks["reference_done"] = now()

        steps: List[List[Any]] = []  # [k, t_begin, t_end, loss, round_end]
        losses: List[Any] = []
        batch_build_s: List[float] = []
        state = {"k": 0, "rounds": 0, "t_prev": now()}

        def micro_step() -> bool:
            k = state["k"]
            with annotate("bench.batch", step=k):
                t0 = now()
                grid = packed_batch(seed, k, batch, seq_len)
                batch_build_s.append(now() - t0)
                tokens = jnp.asarray(grid)
            pushes = proxy.pushes if proxy is not None else -1
            with annotate("bench.dispatch", step=k):
                tr.w, loss = opt.step(tr.w, tokens)
            round_end = proxy is None or proxy.pushes > pushes
            if round_end:
                with annotate("bench.fence", step=k):
                    jax.block_until_ready((tr.w, loss))
                state["rounds"] += 1
            t_end = now()
            steps.append([k, state["t_prev"], t_end, None, round_end])
            losses.append(loss)
            state["t_prev"] = t_end
            state["k"] = k + 1
            return round_end

        def one_round() -> None:
            while not micro_step():
                pass

        # Set-up: warm-up in whole rounds, so that the first sync, the
        # accumulate path, the servers' apply and the h2d are compiled.
        while state["rounds"] < max(int(spec["warmup_rounds"]), 2):
            one_round()
        marks["warmup_done"] = now()
        while not self._wait_for_peers(int(tr.rank)):
            one_round()

        # The window.  A traced run traces ``trace_rounds`` rounds of the
        # first worker's chip after the window's first round.
        error: Optional[str] = None
        trace_dir = spec["trace_dir"] if (
            spec["trace"] and int(tr.rank) == spec["worker_ranks"][0]) else None
        trace_from = state["rounds"] + 1
        trace_until = trace_from + int(spec["trace_rounds"])
        tracing = False
        state["t_prev"] = t_open = marks["window_open"] = now()
        first_step = state["k"]
        try:
            while now() - t_open < float(spec["seconds"]):
                if trace_dir and not tracing and state["rounds"] == trace_from:
                    options = jax.profiler.ProfileOptions()
                    options.python_tracer_level = 0
                    options.host_tracer_level = 2
                    jax.profiler.start_trace(trace_dir,
                                             profiler_options=options)
                    tracing = True
                    state["t_prev"] = now()
                one_round()
                if tracing and state["rounds"] >= trace_until:
                    jax.profiler.stop_trace()
                    tracing, trace_from = False, -1
                    state["t_prev"] = now()
        except Exception as exc:  # a round raised: the run has failed steps
            error = repr(exc)
            import traceback

            traceback.print_exc()
        marks["window_close"] = now()
        if tracing:
            jax.profiler.stop_trace()
        for row, loss in zip(steps, losses):
            try:
                row[3] = float(loss)
            except Exception:  # the array of a failed step
                row[3] = float("nan")
        if hasattr(opt, "stop") and error is None:
            opt.stop()

        stats = jax.devices()[0].memory_stats() or {}
        reduction = None
        if trace_dir:
            from chipbench.reduce import reduce_trace

            found = glob.glob(os.path.join(
                trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
            reduction = (reduce_trace(found[0], spec["step_module"],
                                      spec["config"]["scopes"]) if found
                         else {"ok": False, "why": "no .xplane.pb written"})
        return {
            "steps": state["k"],
            "mosaic_calls": reference["mosaic_calls"],
            "chipbench_worker": {
                "tokens_per_step": tokens_per_step,
                "first_window_step": first_step,
                "step_rows": steps,
                "rounds": proxy.rounds if proxy is not None else [],
                "pushes": proxy.pushes if proxy is not None else 0,
                "batch_build_ms_p50": 1e3 * sorted(batch_build_s)[
                    len(batch_build_s) // 2],
                "stream_ok": stream_ok,
                "reference": reference,
                "error": error,
                "reduction": reduction,
                # the program's temporaries are "reserved" on a TPU and
                # are not in bytes_in_use; the two peaks need not fall
                # together, so their sum is held to the chip's limit
                # (PERF.md section 6, PR 22)
                "memory_peak_bytes": min(
                    int(stats.get("peak_bytes_in_use", 0))
                    + int(stats.get("peak_bytes_reserved", 0)),
                    int(stats.get("bytes_limit", 1 << 62))),
                "memory_stats": {k: int(v) for k, v in stats.items()},
                "vector_len": int(tr.model.flat.w0.size),
            },
        }


def main() -> None:
    spec = json.loads(os.environ[SPEC_ENV])
    marks: Dict[str, float] = {}
    import jax

    marks["jax_imported"] = now()
    compiles = CompileLog()
    compiles.install()
    from mpit_tpu.train import launch
    from mpit_tpu.train.gang import child_env, child_transport, write_result

    rank, size, cfg = child_env()
    devices = jax.devices()  # a chip owner reaches its chip here
    marks["device_ready"] = now()
    transport = child_transport(cfg, rank, size) if size > 1 else None
    marks["past_barrier"] = now()
    role = launch.expected_role(rank, size, cfg)
    if role in ("worker", "local"):
        from mpit_tpu.lm import LmTrainer

        loop = WorkerLoop(spec, marks)
        LmTrainer.run = lambda trainer: loop.run(trainer)
    result = launch.run_rank(rank, size, cfg, transport)
    if transport is not None:
        transport.close()
    from mpit_tpu.obs import maybe_write_rank_trace

    maybe_write_rank_trace(rank, role=str(result.get("role", "")))
    from mpit_tpu.obs import clock

    marks["epoch_offset"] = clock.epoch_offset()  # monotonic -> wall, op spans
    result["chipbench"] = {
        "marks": marks, "compiles": compiles.events,
        "devices": len(devices),
    }
    write_result(result)


if __name__ == "__main__":
    if "--child" not in sys.argv:
        sys.exit("chipbench.child is a gang rank; start chipbench.run")
    main()
