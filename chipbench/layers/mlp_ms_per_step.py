"""L4 trainer step: device time per traced micro-step under the model
scope ``mlp``: the gated SiLU MLP beside every mixer of a dense hybrid
(``models/transformer.py`` ``gated_mlp``: the norm before it, ``[W_a |
W_b]`` as one product, ``SiLU(a) * b`` and ``W_o``, with the branch's
residual multiplier and its join), forward, forward again (the branch
keeps its input alone and makes ``h W_a`` and ``h W_b`` anew in the
backward pass) and backward, all layers.  Nothing to read where the
configuration lists no such scope or the trace has no operation under
it."""

from chipbench.layers import mla_proj_ms_per_step

SCOPE = "mlp"


def read(run):
    return mla_proj_ms_per_step.scope_ms(run, SCOPE)
