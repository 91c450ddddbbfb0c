"""L4 trainer step: routing imbalance, the busiest expert's token count
over the mean count (1 is even; experts over experts per token is every
token on the same few), the largest over the layers, median over the
first worker's sync rounds in the window.  The program reduces it on the
device, an auxiliary output of the step that it fetches only while obs
records, and notes it on the ``round`` span as ``moe_load_max_over_mean``
(one entry a layer; the gauge ``mpit_moe_load_max_over_mean`` carries
the same: ``optim/sync.py``, ``lm/model.py`` ``value_grad_stats``).
Nothing to read from a program or a block that records none."""

import statistics

from chipbench.layers import spantree

ARG = "moe_load_max_over_mean"


def read(run):
    tree = spantree.load(run)
    if tree is None:
        return None
    values = [max(r.args[ARG]) for r in tree.rounds() if r.args.get(ARG)]
    return float(statistics.median(values)) if values else None
