"""L4 trainer step: the chosen (query, key) pairs over the causal pairs,
in percent, the mean over the layers, median over the first worker's
rounds in the window.  The selection keeps ``min(t + 1, topk)`` keys a
query whatever the weights, so the reading is the formula's (43.75 at
8192 positions and ``topk`` 2048) in every run: 100 says the selection
is not applied (plain causal attention), anything else that the sets are
not the size the model states.  ``lower`` names the direction away from
that fault.  The program counts it on the device where the sets are
made, an auxiliary output of the step fetched only while obs records,
noted on the ``round`` span as ``lm_dsa_kept_share`` (one entry a layer;
gauge ``mpit_lm_dsa_kept_share``: ``optim/sync.py`` ``note_stats``,
``models/transformer.py`` ``KeyeDecoder``).  Nothing to read from a
program or a block that records none."""

import statistics

from chipbench.layers import spantree

ARG = "lm_dsa_kept_share"


def rounds_median(run, arg):
    """The median over the window's rounds of the layers' mean of the
    ``round`` spans' ``arg``, or None."""
    tree = spantree.load(run)
    if tree is None:
        return None
    values = [statistics.fmean(r.args[arg]) for r in tree.rounds()
              if r.args.get(arg)]
    return float(statistics.median(values)) if values else None


def read(run):
    share = rounds_median(run, ARG)
    return None if share is None else 100.0 * share
