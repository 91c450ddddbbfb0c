"""L4 trainer step: the mean of the delta attention's decay ``alpha =
exp(g)`` over positions, heads and key channels, the mean over the KDA
layers, median over the first worker's rounds in the window.  At 0 a
layer's state forgets everything at every position and the mixer has no
memory; at 1 it is an undecayed delta rule; the seeds put it near 0.85
and training moves it with ``A_log``, ``dt_bias`` and the decay's
low-rank map.  Both ends are a layer lost, and the benchmark's entry
has to name one direction: ``lower``, away from the end the seeded
reading lies nearer to, so that a drift towards 1 never reads as a
gain; what the cell holds it to is the open interval (0.05, 0.999).
The program reduces it on the device, an auxiliary output
of the step fetched only while obs records, noted on the ``round`` span
as ``lm_kda_decay_mean`` (one entry a KDA layer; gauge
``mpit_lm_kda_decay_mean``: ``optim/sync.py`` ``note_stats``,
``models/transformer.py`` ``KimiDecoder``).  Nothing to read from a
program or a block that records none."""

import statistics

from chipbench.layers import spantree

ARG = "lm_kda_decay_mean"


def read(run):
    tree = spantree.load(run)
    if tree is None:
        return None
    values = [statistics.fmean(r.args[ARG]) for r in tree.rounds()
              if r.args.get(ARG)]
    return float(statistics.median(values)) if values else None
