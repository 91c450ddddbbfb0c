"""L4 trainer step: device time per traced micro-step under the model
scope ``attn_gate`` alone (``models/transformer.py``
``grouped_attention`` ``gate=``): the sigmoid gate on the attention
output, a fifth product of the layer's normed input (``hidden_size`` by
heads times head width) and an elementwise pass over ``(rows, heads x
head_dim)`` before ``wo``; forward, forward again (the attention branch
keeps its input and the flash kernel's results and makes the gate anew
in the backward pass) and backward, in every layer.  XLA's product and
fusions: no Mosaic kernel.  The flash kernels and the other four
products are the scopes ``attn`` and ``attn_window``.  Nothing to read
where the configuration lists no such scope or the trace has no
operation under it."""

from chipbench.layers import mla_proj_ms_per_step

SCOPE = "attn_gate"


def read(run):
    return mla_proj_ms_per_step.scope_ms(run, SCOPE)
