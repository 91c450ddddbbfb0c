"""L1 kernels: the least time the chip's peaks allow the micro-step's
attention (FLOPs and bytes of the ``attn`` kernel family from the
configuration's arithmetic, ``chipbench/arithmetic/<module>.py``
``kernels``; peaks from ``chipbench/peaks.json``) over the device time
the Mosaic calls under that family's scope took
(``layers/flash_ms_per_step.py``).  The line printed before the result
says which peak binds."""

from chipbench import flops


def read(run):
    found = flops.kernel_family(run, "attn")
    if found is None or run["peaks"] is None:
        return None
    kernel, seconds = found
    share, bound = flops.roofline(kernel["flops"], kernel["bytes"], seconds,
                                  run["peaks"])
    print(f"chipbench: flash roofline is bound by {bound}", flush=True)
    return share
