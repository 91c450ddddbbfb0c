"""L1 kernels: device time per traced micro-step under the model scope
``conv_mix`` alone: the two gates (``B * z`` before, ``C *`` after) and
the depthwise causal convolution of every conv layer held, forward and
backward, as XLA fuses them (``mpit_tpu/ops/short_conv.py``; no Mosaic
kernel).  They are elementwise over ``T x 3 hidden_size`` floats and
bound by memory; ``conv_mix_roofline`` holds this time against the
bytes they cannot avoid.  Nothing to read where the configuration lists
no such scope or the trace has no operation under it."""

from chipbench.layers import spantree

SCOPE = "conv_mix"


def read(run):
    if SCOPE not in spantree.model_scopes(run):
        return None
    table = spantree.scope_ms_per_step(run)
    if not table or SCOPE not in table:
        return None
    return table[SCOPE]
