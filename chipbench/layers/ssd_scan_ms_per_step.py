"""L1 kernels: device time per traced micro-step under the model scope
``ssd_scan`` alone: the state-space layers' chunked scan of every Mamba
layer held (``mpit_tpu/ops/ssd_scan.py``: a chunk's pair matrix a group,
its decay matrix a head, the masked product applied to x, the chunk's
contribution to the state, the carry from chunk to chunk and the
read-out; forward, the same again inside the operator's own backward
rule, and the backward pass), with the step's softplus and the skip ``D
x`` around it, as XLA compiles it: no Mosaic kernel.
``ssd_scan_roofline`` holds this time against what the algorithm needs.
Relayouts the compiler makes as ``copy`` operations with no name stack
are booked ``unscoped`` and are not in this time, as with
``kda_scan_ms_per_step``: read ``unscoped`` of the scope table beside
this metric.  Nothing to read where the configuration lists no such
scope or the trace has no operation under it."""

from chipbench.layers import mla_proj_ms_per_step

SCOPE = "ssd_scan"


def read(run):
    return mla_proj_ms_per_step.scope_ms(run, SCOPE)
