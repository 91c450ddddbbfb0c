"""L1 kernels: the least time the chip's peaks allow the micro-step's
expert products (FLOPs and bytes of the ``experts`` kernel family from
the configuration's arithmetic, ``chipbench/arithmetic/<module>.py``
``kernels``: the three grouped products forward and backward over the
gathered rows, every expert's weights read forward and read and written
backward; peaks from ``chipbench/peaks.json``) over the device time the
Mosaic calls under that family's scope took (``flops.kernel_family``):
the kernels alone, where ``experts_ms_per_step`` is the whole scope.
The line printed before the result says which peak binds, and the
achieved rate.

A collapsed-routing reading so far: without the recipe's load-balancing
loss the seeded router sends nearly every token to the same few experts
(``expert_load_max_over_mean`` near its worst case), so the kernels run
a few full groups and many empty ones, which is faster than even groups
(PERF.md section 6, PR 26).  Nothing to read where the configuration
has no such family or the trace no such call."""

from chipbench import flops


def read(run):
    found = flops.kernel_family(run, "experts")
    if found is None or run["peaks"] is None:
        return None
    kernel, seconds = found
    share, bound = flops.roofline(kernel["flops"], kernel["bytes"], seconds,
                                  run["peaks"])
    print(f"chipbench: experts roofline is bound by {bound}; "
          f"{kernel['flops'] / seconds / 1e12:.1f} TFLOP/s and "
          f"{kernel['bytes'] / seconds / 1e9:.1f} GB/s over the kernels' "
          f"{1e3 * seconds:.3f} ms", flush=True)
    return share
