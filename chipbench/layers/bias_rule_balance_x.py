"""L4 trainer step: what the balancing rule bought inside one window:
the routing imbalance (the busiest expert's count over the mean count,
the largest over the sparse layers: ``expert_load_max_over_mean``'s
quantity) as the mean over the window's first four rounds of the first
worker, divided by the same over its last four.  1.0: the loads are as
uneven at the end as at the start, which is what a run without the rule
reads at a fixed routing; over 1.0: the window ends more even than it
began.  The weights move the routing too, so the reading is the rule's
only beside the same mix run with the rule's rate at 0 (PERF.md section
6, PR 53).  Read from the ``round`` spans' ``moe_load_max_over_mean``,
and only where the program also records the rule's own counter
``moe_bias_abs_mean`` (a block without the rule has no such reading).
Nothing to read from a program or a block that records none, or from a
window of fewer than eight rounds."""

import statistics

from chipbench.layers import spantree

ARG = "moe_load_max_over_mean"
RULE = "moe_bias_abs_mean"
ROUNDS = 4


def read(run):
    tree = spantree.load(run)
    if tree is None:
        return None
    values = [max(r.args[ARG]) for r in tree.rounds()
              if r.args.get(ARG) and r.args.get(RULE)]
    if len(values) < 2 * ROUNDS:
        return None
    return float(statistics.fmean(values[:ROUNDS])
                 / statistics.fmean(values[-ROUNDS:]))
