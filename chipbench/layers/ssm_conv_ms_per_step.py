"""L1 kernels: device time per traced micro-step under the model scope
``ssm_conv``: the causal depthwise convolution of ``conv_kernel`` taps
over x, B and C together with its bias and SiLU in front of every Mamba
layer's scan (``mpit_tpu/ops/short_conv.py`` ``causal_conv_silu``:
elementwise over positions x 6144 channels, bound by memory), forward,
forward again in the mixer's recomputation and backward.  Nothing to
read where the configuration lists no such scope or the trace has no
operation under it."""

from chipbench.layers import mla_proj_ms_per_step

SCOPE = "ssm_conv"


def read(run):
    return mla_proj_ms_per_step.scope_ms(run, SCOPE)
