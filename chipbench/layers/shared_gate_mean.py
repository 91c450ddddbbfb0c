"""L4 trainer step: the mean of the shared expert's own gate
``sigmoid(h w_s)``, a scalar a token, over the tokens, the mean over the
layers, median over the first worker's rounds in the window.  At 0 the
shared expert is off and every token has its routed experts alone; at 1
the gate does nothing and the shared expert is the ungated one of the
other sparse blocks; the seed puts it at a half (``w_s`` at std 0.02
under a normed input) and training moves it with ``w_s``.  Both ends
are a mechanism lost, and the benchmark's entry has to name one
direction: ``lower``, as ``gdn_decay_mean``; what the cell holds it to
is the open interval (0.02, 0.98).  The program reduces it on the
device, an auxiliary output of the step fetched only while obs records,
noted on the ``round`` span as ``lm_shared_gate_mean`` (one entry a
layer; gauge ``mpit_lm_shared_gate_mean``: ``optim/sync.py``
``note_stats``, ``models/transformer.py`` ``shared_sparse_experts``).
Nothing to read from a program or a block that records none."""

from chipbench.layers import gdn_decay_mean

ARG = "lm_shared_gate_mean"


def read(run):
    return gdn_decay_mean.layers_mean_median(run, ARG)
