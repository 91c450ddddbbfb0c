"""L4 trainer step: the share of a sequence's positions that the
block-diffusion noise masks, in percent, median over the first worker's
rounds in the window.  The counts of the blocks are a permutation, not
draws, so the reading is ``(B + 1) / (2 B)`` whatever the seed and the
row: 62.5 at blocks of 4.  It is the guard on "every seed does the same
work": another number says the noise is not the recipe's (a count
drawn, a set of the wrong size), 0 that nothing is noised and the loss
is empty.  ``lower`` names the direction away from 100, everything
masked.  The program counts it on the device where the noise is made,
an auxiliary output of the step fetched only while obs records, noted
on the ``round`` span as ``diff_masked_share`` (gauge
``mpit_diff_masked_share``: ``optim/sync.py`` ``note_stats``,
``models/transformer.py`` ``SdarDecoder``).  Nothing to read from a
program or a block that records none."""

from chipbench.layers import dsa_kept_pct

ARG = "diff_masked_share"


def read(run):
    share = dsa_kept_pct.rounds_median(run, ARG)
    return None if share is None else 100.0 * share
