"""L4 trainer step: device time per traced micro-step of a Mamba-2
mixer, all its layers: the model scopes ``ssm_proj`` (the norm before
the layer, ``W_in`` to the gate, x, B, C and the step, and ``W_out``),
``ssm_conv`` (the depthwise convolution over x, B and C with its bias
and SiLU, ``ssm_conv_ms_per_step``), ``ssd_scan`` (the step's softplus,
the chunked state and the skip, ``ssd_scan_ms_per_step``) and
``ssm_norm`` (the gate and the groups' RMSNorm)
(``models/transformer.py`` ``state_space_mixer``); forward, forward
again (the block keeps the layer's input and the scan's result and makes
z, x, B, C and the step anew in the backward pass) and backward.  A line
before the result gives the four parts.  Nothing to read where the
configuration lists none of the four or the trace has no operation
under them."""

from chipbench.layers import mla_proj_ms_per_step

SCOPES = ("ssm_proj", "ssm_conv", "ssd_scan", "ssm_norm")


def read(run):
    parts = {scope: mla_proj_ms_per_step.scope_ms(run, scope)
             for scope in SCOPES}
    found = {scope: ms for scope, ms in parts.items() if ms is not None}
    if not found:
        return None
    print("chipbench: device ms per micro-step, " + ", ".join(
        f"{scope} {ms:.3f}" for scope, ms in found.items()), flush=True)
    return sum(found.values())
