"""L4 trainer step: the mean of ``|b|`` over a router's experts, ``b``
the selection bias as a forward pass found it, the mean over the sparse
layers, of the window's last round of the first worker.  The balancing
rule moves every entry by at most twice its rate a step and by about the
rate where the loads are uneven, from a seed of std 0.02 (a mean of
0.016): 0 says the bias is not there, the seed's value at the window's
end that the rule does not run, far more than the seed's plus steps
times the rate that something else moves the bias (a learning rate, a
momentum, a decay: the leaf is no optimizer's).  ``lower`` names the
direction away from that fault.  The program reduces it on the device,
an auxiliary output of the step fetched only while obs records, noted on
the ``round`` span as ``moe_bias_abs_mean`` (one entry a sparse layer;
gauge ``mpit_moe_bias_abs_mean``: ``optim/sync.py`` ``note_stats``,
``models/transformer.py`` ``shared_sparse_experts``).  Nothing to read
from a program or a block that records none."""

import statistics

from chipbench.layers import spantree

ARG = "moe_bias_abs_mean"


def read(run):
    tree = spantree.load(run)
    if tree is None:
        return None
    values = [statistics.fmean(r.args[ARG]) for r in tree.rounds()
              if r.args.get(ARG)]
    return float(values[-1]) if values else None
