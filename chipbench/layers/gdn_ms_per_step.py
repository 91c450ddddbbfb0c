"""L4 trainer step: device time per traced micro-step of a Gated DeltaNet
mixer, all its layers: the model scopes ``gdn_proj`` (the norm before
the layer, ``W_qkvz``, ``W_ba``, the heads' L2 norms, the decay and
``beta``), ``gdn_conv`` (the one depthwise convolution over q, k and v
with its SiLU, ``gdn_conv_ms_per_step``), ``gdn_scan`` (the chunked
state, ``gdn_scan_ms_per_step``) and ``gdn_out`` (the heads' RMSNorm,
the gate ``SiLU(z)`` and ``W_out``) (``models/transformer.py``
``gated_delta_mixer``); forward, forward again (the block keeps the
layer's input and the scan's result and makes q, k, v, z, the decay and
``beta`` anew in the backward pass) and backward.  A line before the
result gives the four parts.  Nothing to read where the configuration
lists none of the four or the trace has no operation under them."""

from chipbench.layers import mla_proj_ms_per_step

SCOPES = ("gdn_proj", "gdn_conv", "gdn_scan", "gdn_out")


def read(run):
    parts = {scope: mla_proj_ms_per_step.scope_ms(run, scope)
             for scope in SCOPES}
    found = {scope: ms for scope, ms in parts.items() if ms is not None}
    if not found:
        return None
    print("chipbench: device ms per micro-step, " + ", ".join(
        f"{scope} {ms:.3f}" for scope, ms in found.items()), flush=True)
    return sum(found.values())
