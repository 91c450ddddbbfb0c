"""L4 trainer step: device time per traced micro-step of a Kimi Delta
Attention mixer, all its layers: the model scopes ``kda_proj`` (the norm
before the mixer, the products of q, k, v, the decay's and ``beta``'s,
the three short convolutions, SiLU and the heads' L2 norms), ``kda_scan``
(the chunked state, ``kda_scan_ms_per_step``) and ``kda_out`` (the
heads' RMSNorm, the output gate's two products and ``W_o``)
(``models/transformer.py`` ``delta_attention``); forward, forward again
(the block keeps the layer's input and the scan's result and makes q, k,
v, g and beta anew in the backward pass) and backward.  Nothing to read
where the configuration lists none of the three or the trace has no
operation under them."""

from chipbench.layers import mla_proj_ms_per_step

SCOPES = ("kda_proj", "kda_scan", "kda_out")


def read(run):
    found = [ms for ms in (mla_proj_ms_per_step.scope_ms(run, scope)
                           for scope in SCOPES) if ms is not None]
    return sum(found) if found else None
