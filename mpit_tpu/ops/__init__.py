"""TPU pallas kernels for the framework's hot ops.

The reference's hot loops are in-place torch tensor math — the server's
``p:add(g)`` and per-rule optimizer updates (reference
asyncsgd/pserver.lua:83, BiCNN/pserver.lua:123-197) and the client's
Nesterov/elastic updates (reference asyncsgd/optim-msgd.lua:36-39,
optim-eamsgd.lua:58-66).  On TPU those are HBM-bandwidth-bound elementwise
passes; the kernels here fuse each multi-array update into a single
HBM read/write sweep with buffer donation (no param-sized temporaries).
:mod:`mpit_tpu.ops.flash_attention` adds the blockwise-attention kernel
that backs sequence-parallel ring attention
(:mod:`mpit_tpu.parallel.ring_attention`).
:mod:`mpit_tpu.ops.delta_rule` is the gated delta rule's chunked scan
(Kimi Delta Attention): three Mosaic kernels at head widths of whole
lanes (forward, and the backward rule's two), XLA's fusions and products
at every other width, and its entry for one scalar decay a head with
fewer key heads than value heads (Gated DeltaNet, ``gdn_scan``);
:mod:`mpit_tpu.ops.ssd_scan` is the scalar-decay
state-space scan (Mamba-2) in chunks with a backward rule of its own:
three Mosaic kernels at widths of whole lanes (forward, and the rule's
walk that makes the chunk-start states again and its walk back), the
state a VMEM scratch along the chunk axis, XLA's products and fusions
at every other shape; :mod:`mpit_tpu.ops.short_conv` is XLA's
fusions.
:mod:`mpit_tpu.ops.index_select` is a learned selection of keys (an
indexer's scores and an exact top-k a query): one Mosaic kernel a call,
a block of rows' scores made over the causal columns alone, kept as
integer keys in VMEM, bisected and packed there;
:mod:`mpit_tpu.ops.select_bits` is the format its set travels in, the
bits the flash kernels mask by.

Every op has a jnp reference implementation (``*_reference``) used for
testing and as a CPU fallback; kernels run in pallas interpret mode off-TPU
so the whole package is exercised by the CPU test suite.
"""

from mpit_tpu.ops.fused_update import (
    fused_adam,
    fused_adam_reference,
    fused_elastic,
    fused_elastic_reference,
    fused_nesterov_commit,
    fused_nesterov_commit_reference,
)
from mpit_tpu.ops.flash_attention import (
    attention_reference,
    block_attention_partial,
    finalize_partials,
    flash_attention,
    flash_attention_bwd_pair,
    flash_attention_partial,
    merge_partials,
)
from mpit_tpu.ops.tiles import as_rows, from_rows

__all__ = [
    "fused_nesterov_commit", "fused_nesterov_commit_reference",
    "fused_adam", "fused_adam_reference",
    "fused_elastic", "fused_elastic_reference",
    "flash_attention", "flash_attention_partial", "flash_attention_bwd_pair",
    "attention_reference",
    "block_attention_partial", "merge_partials", "finalize_partials",
    "as_rows", "from_rows",
]
