"""L4 trainer step: device time of one micro-step's program, the median
over the traced micro-steps.  The step's program is the module of the
trace's ``XLA Modules`` line that the mix's file names as
``step_module`` (``jit_loss``, the forward and backward under a
parameter server; ``jit__lambda``, the whole msgd step in the local
cell); ``chipbench/reduce.py`` finds it, and a trace without it gives
nothing."""


def read(run):
    return run["reduction"].get("step_module_ms_p50")
