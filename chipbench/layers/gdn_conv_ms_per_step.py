"""L1 kernels: device time per traced micro-step under the model scope
``gdn_conv``: the causal depthwise convolution of
``linear_conv_kernel_dim`` taps over q, k and v together with its SiLU,
no bias, in front of every Gated DeltaNet layer's scan
(``mpit_tpu/ops/short_conv.py`` ``causal_depthwise_conv``: elementwise
over positions x 8192 channels, bound by memory), forward, forward again
in the mixer's recomputation and backward.  Nothing to read where the
configuration lists no such scope or the trace has no operation under
it."""

from chipbench.layers import mla_proj_ms_per_step

SCOPE = "gdn_conv"


def read(run):
    return mla_proj_ms_per_step.scope_ms(run, SCOPE)
