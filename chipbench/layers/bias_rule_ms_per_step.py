"""L4 trainer step: device time per traced micro-step under the model
scope ``bias_rule`` alone: what the balancing rule of a router's
selection bias costs on the chip (``parallel/moe.py``): counting a sparse
layer's ``T k`` choices into one bin an expert of the router, forward
and again in the recomputed branch, the rule's step from the counts
(``balance_step``, in the backward pass: signs in integers, a mean, a
scaling; the experts' count of floats a layer), and the optimizer's
reads and writes of the vector's plain ranges round the commit kernel
(``optim/msgd.py`` ``plain_commit``: a slice read before it and two
written over its results, a sparse layer).  XLA's
fusions: no Mosaic kernel, no product.  The aim is under 1% of the step.
Nothing to read where the configuration lists no such scope or the trace
has no operation under it."""

from chipbench.layers import mla_proj_ms_per_step

SCOPE = "bias_rule"


def read(run):
    return mla_proj_ms_per_step.scope_ms(run, SCOPE)
