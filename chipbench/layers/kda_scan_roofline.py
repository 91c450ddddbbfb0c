"""L1 kernels: the least time the chip's peaks allow the micro-step's
chunked delta-rule scans (FLOPs and bytes of the configuration's
arithmetic, ``chipbench/arithmetic/<module>.py`` ``kda_scan_cost``: what
the chunked algorithm needs at the stated chunk size, forward, the
chunks again in the backward rule and the backward pass; q, k, v, g,
beta read and o written, and their gradients, once a pass; peaks from
``chipbench/peaks.json``) over the device time under the scope
``kda_scan`` (``layers/kda_scan_ms_per_step.py``).  The operator is
XLA's fusions and small batched products today: a low share is what a
fused kernel would win.  The count belongs to the algorithm, so a later
implementation is read on the same yardstick.  The line printed before
the result says which peak binds and the achieved rates.  Nothing to
read where the configuration's arithmetic has no such cost, the
configuration no such scope, or the trace no operation under it."""

from chipbench import flops
from chipbench.layers import kda_scan_ms_per_step


def read(run):
    cost_of = getattr(run["cell"].arithmetic(), "kda_scan_cost", None)
    if cost_of is None or run.get("peaks") is None:
        return None
    ms = kda_scan_ms_per_step.read(run)
    if not ms:
        return None
    cost = cost_of(run["cell"].config, int(run["cell"].traffic["batch"]))
    seconds = ms / 1e3
    share, bound = flops.roofline(cost["flops"], cost["bytes"], seconds,
                                  run["peaks"])
    print(f"chipbench: kda_scan roofline is bound by {bound}; "
          f"{cost['flops'] / seconds / 1e12:.2f} TFLOP/s and "
          f"{cost['bytes'] / seconds / 1e9:.1f} GB/s over {ms:.3f} ms in "
          f"{cost['layers']} KDA layers", flush=True)
    return share
