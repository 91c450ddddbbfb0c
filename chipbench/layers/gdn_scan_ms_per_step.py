"""L1 kernels: device time per traced micro-step under the model scope
``gdn_scan`` alone: the gated delta rule's chunked scan of every Gated
DeltaNet layer held (``mpit_tpu/ops/delta_rule.py`` ``gdn_scan``: one
scalar decay a value head, 16 key heads under 32 value heads; forward,
the same again inside the operator's own backward rule, and the walk
back), **whatever runs under the scope**: in the first form the repeat
of the queries and keys for their value heads, the broadcast of the
decay over the keys' channels, the channel-wise Mosaic kernels on them
and the sums that bring the gradients back to the operands' shapes.
``gdn_scan_roofline`` holds this time against what the scalar-decay
algorithm needs.  Nothing to read where the configuration lists no such
scope or the trace has no operation under it."""

from chipbench.layers import mla_proj_ms_per_step

SCOPE = "gdn_scan"


def read(run):
    return mla_proj_ms_per_step.scope_ms(run, SCOPE)
