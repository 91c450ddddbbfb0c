"""L4 trainer step: device time per traced micro-step under the model
scope ``mla_proj`` of a latent-attention block (``models/transformer.py``
``latent_attention``): the norm before the attention, the two low-rank
products of the queries with the RMSNorm between them, the two of the
keys and values with theirs, the rotary embedding of the interleaved
pairs, the repeat of the one rotary key head into every head's key and
the joins; forward, forward again (the block keeps the layer's input and
the flash kernel's results and makes q, k and v anew in the backward
pass) and backward, in every layer, the MTP module's too.  The flash
kernels and the output product are the scope ``attn``
(``flash_ms_per_step``).  Nothing to read where the configuration lists
no such scope or the trace has no operation under it."""

from chipbench.layers import spantree

SCOPE = "mla_proj"


def scope_ms(run, scope):
    """``scope``'s device ms per traced micro-step, or None."""
    if scope not in spantree.model_scopes(run):
        return None
    table = spantree.scope_ms_per_step(run)
    if not table or scope not in table:
        return None
    return table[scope]


def read(run):
    return scope_ms(run, SCOPE)
