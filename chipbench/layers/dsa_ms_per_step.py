"""L4 trainer step: device time per traced micro-step of the learned
sparse attention, all its layers: the model scopes ``index`` (the
indexer's three products at full float32 precision, its key's LayerNorm,
the rotations, the scores of every causal pair a block of rows at a time
and the exact top-k a row: ``dsa_index_ms_per_step``) and ``attn`` (the
norm before the layer, the four projections, the per-head query/key
norms, the rotations, the flash kernels that mask by the chosen bits
and ``W_o``) (``models/transformer.py`` ``selected_attention``); the
indexer forward only (its bits are kept for the backward pass), the
attention forward, its projections again (the block keeps the layer's
input, the flash kernel's results and the bits) and backward.  Nothing
to read where the configuration lists neither scope or the trace has no
operation under them."""

from chipbench.layers import mla_proj_ms_per_step

SCOPES = ("index", "attn")


def read(run):
    # both or nothing: ``attn`` alone is another block's attention
    found = [mla_proj_ms_per_step.scope_ms(run, scope) for scope in SCOPES]
    return None if None in found else sum(found)
