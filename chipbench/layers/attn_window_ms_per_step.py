"""L1 kernels: device time per traced micro-step of the flash attention
kernels of the sliding-window layers: the Mosaic calls whose name stack
holds the scope of the ``attn_window`` kernel family, as the
configuration's arithmetic names it (``chipbench/arithmetic/<module>.py``
``kernels``), over all such layers.  The full layers' kernels run under
``attn`` and are ``flash_ms_per_step``: with blocks outside the window
skipped, a windowed layer's time is under a full layer's.  Nothing to
read where the configuration has no such family or the trace no such
call, as with a program that predates the window."""

from chipbench import flops


def read(run):
    found = flops.kernel_family(run, "attn_window")
    return None if found is None else 1e3 * found[1]
