"""L4 trainer step: the mean of the state-space layers' decay ``a_t =
exp(softplus(dt_t + dt_bias) A)`` over positions and heads, the mean
over the Mamba layers, median over the first worker's rounds in the
window.  At 0 a layer's state forgets everything at every position and
the mixer has no memory; at 1 nothing is ever forgotten and the state
only grows; the seeds put it near 0.9 (``A`` from 1 to the head count,
steps drawn log-uniformly in [0.001, 0.1]) and training moves it with
``A_log``, ``dt_bias`` and ``W_in``'s step columns.  Both ends are a
layer lost, and the benchmark's entry has to name one direction:
``lower``, away from the end the seeded reading lies nearer to, as
``kda_decay_mean`` argues; what the cell holds it to is the open
interval (0.05, 0.999).  The program reduces it on the device, an
auxiliary output of the step fetched only while obs records, noted on
the ``round`` span as ``lm_ssm_decay_mean`` (one entry a Mamba layer;
gauge ``mpit_lm_ssm_decay_mean``: ``optim/sync.py`` ``note_stats``,
``models/transformer.py`` ``NemotronDecoder``).  Nothing to read from a
program or a block that records none."""

import statistics

from chipbench.layers import spantree

ARG = "lm_ssm_decay_mean"


def read(run):
    tree = spantree.load(run)
    if tree is None:
        return None
    values = [statistics.fmean(r.args[ARG]) for r in tree.rounds()
              if r.args.get(ARG)]
    return float(statistics.median(values)) if values else None
