"""L4 trainer step: device time per traced micro-step under the model
scope ``noise`` alone: what making the noised copy of the batch costs on
the chip (``models/transformer.py`` ``block_noise``: the rows' checksums,
a 32-bit mix a block and a position, a sort of the blocks' keys for
their counts, ``B x B`` compares a block for the positions' places, the
select of the mask id; integers, forward only, once a step).  XLA's
fusions: no Mosaic kernel, no product.  Nothing to read where the
configuration lists no such scope or the trace has no operation under
it."""

from chipbench.layers import mla_proj_ms_per_step

SCOPE = "noise"


def read(run):
    return mla_proj_ms_per_step.scope_ms(run, SCOPE)
