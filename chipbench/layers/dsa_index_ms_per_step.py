"""L1 kernels: device time per traced micro-step under the model scope
``index`` alone: the lightning indexer of every layer held
(``mpit_tpu/ops/index_select.py``: the heads' products against the one
key head at full float32 precision, the ReLU and the weighted sum, a
block of 256 rows at a time inside a ``lax.map``; the 2048th largest of
a row by 32 passes of compare-and-count over the block; the bits
packed), with the three projections, the key's LayerNorm and the
rotations before it; forward only, once a step.  XLA's products and
fusions: no Mosaic kernel.  ``dsa_index_roofline`` holds this time
against what the scores need.  Nothing to read where the configuration
lists no such scope or the trace has no operation under it."""

from chipbench.layers import mla_proj_ms_per_step

SCOPE = "index"


def read(run):
    return mla_proj_ms_per_step.scope_ms(run, SCOPE)
