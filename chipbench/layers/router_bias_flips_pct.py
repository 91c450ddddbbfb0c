"""L4 trainer step: what the router's selection bias changes: the share
of the ``k T`` (token, expert) choices that are not among the ``k``
largest scores without the bias, in percent, the mean over the sparse
layers, median over the first worker's rounds in the window.  0 says
the mechanism is idle (a bias of zero, or one too small to move a
choice); it stays small where the bias is small beside the spread of
the scores.  The program reduces it on the device, an auxiliary output
of the step that it fetches only while obs records, and notes it on the
``round`` span as ``moe_bias_flips_share`` (one entry a sparse layer;
the gauge ``mpit_moe_bias_flips_share`` carries the same:
``optim/sync.py`` ``note_stats``, ``lm/model.py`` ``value_grad_stats``,
``parallel/moe.py`` ``bias_flips_share``).  Nothing to read from a
program or a block that records none."""

import statistics

from chipbench.layers import spantree

ARG = "moe_bias_flips_share"


def read(run):
    tree = spantree.load(run)
    if tree is None:
        return None
    values = [statistics.fmean(r.args[ARG]) for r in tree.rounds()
              if r.args.get(ARG)]
    return 100.0 * float(statistics.median(values)) if values else None
