"""L4 trainer step: at which pass a position leaves a looped block on
average, by the exit distribution the gates give: ``sum_t t p_t``, the
mean over positions, median over the first worker's rounds in the window.
Between 1 and the number of passes: the number of passes when every
position runs them all (3.89 of four at the seed with the gate's bias
at -4, as ``ouro-l6-local`` seeds it; 1.875 with every gate at a half),
and 1 when the gates have shut the loop out, after which only the first
pass is trained.  It falls as the gates learn that an early pass's head is good
enough, which is what would let generation leave early.  The program
reduces it on the device, an auxiliary output of the step that it
fetches only while obs records, and notes it on the ``round`` span as
``loop_exit_step_mean`` (one entry; the gauge
``mpit_loop_exit_step_mean`` carries the same: ``optim/sync.py``
``note_stats``, ``lm/model.py`` ``value_grad_stats``,
``models/transformer.py`` ``OuroDecoder``), under the shells and in the
single-process path alike.  Nothing to read from a program or a block
that records none."""

import statistics

from chipbench.layers import spantree

ARG = "loop_exit_step_mean"


def rounds_median(run, arg):
    """The median over the window's rounds of the ``round`` span's
    ``arg`` (the mean of its entries), or None."""
    tree = spantree.load(run)
    if tree is None:
        return None
    values = [statistics.fmean(r.args[arg]) for r in tree.rounds()
              if r.args.get(arg)]
    return float(statistics.median(values)) if values else None


def read(run):
    return rounds_median(run, ARG)
