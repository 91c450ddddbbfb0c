"""L1 kernels: the least time the chip's peaks allow the micro-step's
held experts' products (FLOPs and bytes of the ``experts`` kernel family
from the configuration's arithmetic, ``chipbench/arithmetic/<module>.py``
``kernels``: three grouped products forward, the same again where the
block recomputes its sparse branch, six backward, over the rows routed
to held experts; the held experts' weights read in each pass and their
gradients written; peaks from ``chipbench/peaks.json``) over the device
time the Mosaic calls under that family's scope took
(``flops.kernel_family``): the kernels alone, where
``held_experts_ms_per_step`` is the whole scope.  The arithmetic counts
the rows at their expectation under uniform routing; the FLOPs and the
rows' bytes are scaled here by the share the program counted **in the
traced rounds** (the ``round`` spans whose number an ``mpit.round``
annotation of the device trace carries; their ``moe_held_rows_share``
over the uniform share), where it recorded one, so a step that routes
more tokens here is not read as a faster kernel; the window's other
rounds may route otherwise and are not the kernels' time.  The line printed before the result says which peak binds, the
achieved rate and the scale.  Nothing to read where the configuration
holds no share or has no such family, or the trace no such call."""

import statistics

from chipbench import flops
from chipbench.layers import (
    held_experts_ms_per_step,
    held_rows_share_pct,
    spantree,
)


def read(run):
    if not held_experts_ms_per_step.holds_a_share(run):
        return None
    found = flops.kernel_family(run, "experts")
    if found is None or run["peaks"] is None:
        return None
    kernel, seconds = found
    config = run["cell"].config
    uniform = int(config["num_experts"]) / int(config["router_experts"])
    path = spantree.xplane_path(run)
    traced = {k for k, _prof, _mono in spantree.anchors(path)} if path else ()
    counted = held_rows_share_pct.rounds_mean(run, traced) if traced else None
    scale = statistics.median(counted) / uniform if counted else 1.0
    cost = run["cell"].arithmetic().experts_cost(
        config, int(run["cell"].traffic["batch"]))
    weights = cost["bytes"] - cost["rows_bytes"]
    nflops = kernel["flops"] * scale
    nbytes = weights + cost["rows_bytes"] * scale
    share, bound = flops.roofline(nflops, nbytes, seconds, run["peaks"])
    print(f"chipbench: held experts roofline is bound by {bound}; "
          f"{nflops / seconds / 1e12:.1f} TFLOP/s and "
          f"{nbytes / seconds / 1e9:.1f} GB/s over the kernels' "
          f"{1e3 * seconds:.3f} ms; rows at {scale:.3f} of the uniform "
          "expectation", flush=True)
    return share
