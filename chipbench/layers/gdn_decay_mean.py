"""L4 trainer step: the mean of the gated delta rule's decay ``alpha =
exp(g)``, one scalar a value head, over positions and heads, the mean
over the Gated DeltaNet layers, median over the first worker's rounds in
the window.  At 0 a layer's state forgets everything at every position
and the mixer has no memory; at 1 it is an undecayed delta rule; the
seeds put it near 0.85 (``A_log = log U(1, 16)``, steps drawn
log-uniformly in [0.001, 0.1]) and training moves it with ``A_log``,
``dt_bias`` and ``W_ba``'s step columns.  Both ends are a layer lost,
and the benchmark's entry has to name one direction: ``lower``, away
from the end the seeded reading lies nearer to, as ``kda_decay_mean``
argues; what the cell holds it to is the open interval (0.05, 0.999).
The program reduces it on the device, an auxiliary output of the step
fetched only while obs records, noted on the ``round`` span as
``lm_gdn_decay_mean`` (one entry a Gated DeltaNet layer; gauge
``mpit_lm_gdn_decay_mean``: ``optim/sync.py`` ``note_stats``,
``models/transformer.py`` ``Qwen3NextDecoder``).  Nothing to read from a
program or a block that records none."""

import statistics

from chipbench.layers import spantree

ARG = "lm_gdn_decay_mean"


def layers_mean_median(run, arg):
    """The median over the first worker's rounds of the mean over the
    layers of the ``round`` span's ``arg``; None where no round carries
    it."""
    tree = spantree.load(run)
    if tree is None:
        return None
    values = [statistics.fmean(r.args[arg]) for r in tree.rounds()
              if r.args.get(arg)]
    return float(statistics.median(values)) if values else None


def read(run):
    return layers_mean_median(run, ARG)
