"""L1 kernels: the least time the chip's peaks allow the micro-step's
sliding-window attention (FLOPs and bytes of the ``attn_window`` kernel
family from the configuration's arithmetic: the (query, key) pairs
inside the window only, q, k, v, o and their gradients moved once, k and
v at the KV heads' size; peaks from ``chipbench/peaks.json``) over the
device time the Mosaic calls under that family's scope took
(``layers/attn_window_ms_per_step.py``).  The kernel walks whole blocks,
so the pairs it computes at a window's edges are more than those
counted: the share says what the window costs against what it needs.
The line printed before the result says which peak binds.  Nothing to
read where the configuration has no such family or the trace no such
call."""

from chipbench import flops


def read(run):
    found = flops.kernel_family(run, "attn_window")
    if found is None or run["peaks"] is None:
        return None
    kernel, seconds = found
    share, bound = flops.roofline(kernel["flops"], kernel["bytes"], seconds,
                                  run["peaks"])
    print(f"chipbench: attn_window roofline is bound by {bound}; "
          f"{kernel['flops'] / seconds / 1e12:.1f} TFLOP/s over the "
          f"kernels' {1e3 * seconds:.3f} ms", flush=True)
    return share
