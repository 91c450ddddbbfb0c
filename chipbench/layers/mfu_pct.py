"""Device: forward+backward FLOPs per token from shapes, nothing
recomputed (the configuration's own arithmetic,
``chipbench/arithmetic/<module>.py``), times this run's ``tokens_per_s``,
over chips times the published bf16 peak (``chipbench/peaks.json``).
This is the traced run's rate, which tracing slows a little; the
untraced run prints its own on an earlier line."""

from chipbench import flops


def read(run):
    if run["peaks"] is None:
        return None
    summary = run["summary"]
    return flops.mfu_pct(run["cell"], summary["tokens_per_s"],
                         len(summary["worker_ranks"]), run["peaks"])
