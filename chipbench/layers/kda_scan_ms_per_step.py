"""L1 kernels: device time per traced micro-step under the model scope
``kda_scan`` alone: the gated delta rule's chunked scan of every KDA
layer held (``mpit_tpu/ops/delta_rule.py``: the chunks' pair matrices
through sub-blocks, the triangular solve, the scan over the chunk states
and the read-out; forward, the same again inside the operator's own
backward rule, and the backward pass), as XLA compiles it: no Mosaic
kernel.  ``kda_scan_roofline`` holds this time against what the
algorithm needs.  **Not in this time**: the relayouts between the
projections' row-major ``(L, heads, d)`` and the heads-major layout the
chunks are cut from.  The operator's own ``transpose`` asks for them
inside the scope, but the compiler makes them as ``copy`` operations
and as multi-output fusions whose root has no name stack, so the trace
books them ``unscoped`` (43 copies of ``f32[1024,8,32,128]`` a step
17.7 ms, 4 fusions of that shape 3.8, and a share of 20 fusions' 23.4
that also hold the projections' closing multiply, beside a scope of
366.1 ms: it under-reads the operator by 5.9-12.3%; PERF.md section 5,
PR 43).  A kernel that takes row-major inputs would
remove them and be credited here only for what the scope shows: read
``unscoped`` of the scope table beside this metric.  Nothing to read
where the configuration lists no such scope or the trace has no
operation under it."""

from chipbench.layers import mla_proj_ms_per_step

SCOPE = "kda_scan"


def read(run):
    return mla_proj_ms_per_step.scope_ms(run, SCOPE)
