"""L1 kernels: the least time the chip's peaks allow the micro-step's
chunked state-space scans (FLOPs and bytes of the configuration's
arithmetic, ``chipbench/arithmetic/<module>.py`` ``ssd_scan_cost``: what
the chunked algorithm needs at the stated chunk size, forward, the
chunks again in the backward rule and the backward pass; x, B, C and the
step read and y written, and their gradients, once a pass; peaks from
``chipbench/peaks.json``) over the device time under the scope
``ssd_scan`` (``layers/ssd_scan_ms_per_step.py``), ``kda_scan_roofline``'s
form.  The operator is XLA's fusions and small batched products today: a
low share is what a fused kernel would win.  The count belongs to the
algorithm, so a later implementation is read on the same yardstick.  The
line printed before the result says which peak binds and the achieved
rates.  Nothing to read where the configuration's arithmetic has no such
cost, the configuration no such scope, or the trace no operation under
it."""

from chipbench import flops
from chipbench.layers import ssd_scan_ms_per_step


def read(run):
    cost_of = getattr(run["cell"].arithmetic(), "ssd_scan_cost", None)
    if cost_of is None or run.get("peaks") is None:
        return None
    ms = ssd_scan_ms_per_step.read(run)
    if not ms:
        return None
    cost = cost_of(run["cell"].config, int(run["cell"].traffic["batch"]))
    seconds = ms / 1e3
    share, bound = flops.roofline(cost["flops"], cost["bytes"], seconds,
                                  run["peaks"])
    print(f"chipbench: ssd_scan roofline is bound by {bound}; "
          f"{cost['flops'] / seconds / 1e12:.2f} TFLOP/s and "
          f"{cost['bytes'] / seconds / 1e9:.1f} GB/s over {ms:.3f} ms in "
          f"{cost['layers']} Mamba layers", flush=True)
    return share
