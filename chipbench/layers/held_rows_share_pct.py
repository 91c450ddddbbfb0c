"""L4 trainer step: the share of the ``k T`` (token, expert) assignments
that land on the experts this chip holds, in percent, the mean over the
layers, median over the first worker's rounds in the window (uniform
routing gives held over all experts: 12.5 at 8 of 64).  It is what the
held experts' work is proportional to, so a seed that routes more or
fewer tokens here moves ``tokens_per_s`` with it.  The program reduces
it on the device, an auxiliary output of the step that it fetches only
while obs records, and notes it on the ``round`` span as
``moe_held_rows_share`` (one entry a layer; the gauge
``mpit_moe_held_rows_share`` carries the same: ``optim/sync.py``
``note_stats``, ``lm/model.py`` ``value_grad_stats``), under the shells
and in the single-process path alike (``optim/msgd.py``).  Nothing to
read from a program or a block that records none."""

import statistics

from chipbench.layers import spantree

ARG = "moe_held_rows_share"


def rounds_mean(run, only=None):
    """The mean over layers of each round's shares (of the rounds whose
    number is in ``only``, if given), or None."""
    tree = spantree.load(run)
    if tree is None:
        return None
    values = [statistics.fmean(r.args[ARG]) for r in tree.rounds()
              if r.args.get(ARG)
              and (only is None or r.args.get("round") in only)]
    return values or None


def read(run):
    values = rounds_mean(run)
    return None if values is None else 100.0 * float(
        statistics.median(values))
