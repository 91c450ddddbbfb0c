"""L1 kernels: device time per traced micro-step of the flash attention
kernels: the Mosaic calls (``tpu_custom_call`` events of the trace's
``XLA Ops`` line) whose name stack holds the scope of the ``attn``
kernel family, as the configuration's arithmetic names it
(``chipbench/arithmetic/<module>.py`` ``kernels``).  Another kernel of
the step (the fused msgd commit under ``update``, a later block's
grouped expert product) is another family's time and is not counted
here: ``chipbench/reduce.py`` books each call, and the runner prints the
calls and seconds of every scope before the result."""

from chipbench import flops


def read(run):
    found = flops.kernel_family(run, "attn")
    return None if found is None else 1e3 * found[1]
