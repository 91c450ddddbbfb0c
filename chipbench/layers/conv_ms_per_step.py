"""L4 trainer step: device time per traced micro-step of the gated short
convolution operators whole, over all the conv layers held: the
operations under the model scope ``conv`` (the operator's RMSNorm, the
product into the three gates' width, the product back and the residual
add, forward and backward) and under ``conv_mix`` (the two gates and the
depthwise causal convolution between them), among the operations the
first worker's chip ran inside the step's own program in the traced
window (``spantree.scope_ms_per_step``).  It is the token mixer's time
in three layers of four, beside ``flash_ms_per_step`` and the
projections under ``attn`` for the fourth.  A line before the result
gives the two parts.  Nothing to read where the configuration lists
neither scope or the trace has no operation under them."""

from chipbench.layers import spantree

SCOPES = ("conv", "conv_mix")


def read(run):
    if not set(SCOPES) <= set(spantree.model_scopes(run)):
        return None
    table = spantree.scope_ms_per_step(run)
    if not table or not any(scope in table for scope in SCOPES):
        return None
    parts = {scope: table.get(scope, 0.0) for scope in SCOPES}
    print("chipbench: device ms per micro-step, conv (norm and two "
          f"products) {parts['conv']:.3f}, conv_mix (gates and "
          f"convolution) {parts['conv_mix']:.3f}", flush=True)
    return sum(parts.values())
