"""L4 trainer step: how much worse the multi-token-prediction head
predicts the token after next than the main head predicts the next one:
the MTP head's mean NLL less the main head's, in nats, median over the
first worker's rounds in the window.  Near 0 at the seed (both read ln of
the vocabulary); it opens as the main head learns what the byte stream's
next byte is, and how far the MTP module follows is what its term of the
loss buys.  A gap that grows without bound says the module is not
trained (its weight is zero, or its target is wrong).  The program
reduces both on the device, auxiliary outputs of the step fetched only
while obs records, noted on the ``round`` span as ``lm_mtp_nll`` and
``lm_main_nll`` (gauges ``mpit_lm_mtp_nll``, ``mpit_lm_main_nll``:
``optim/sync.py`` ``note_stats``, ``models/transformer.py``
``JoyaiDecoder``).  Nothing to read from a program or a block that
records neither."""

import statistics

from chipbench.layers import spantree

MTP, MAIN = "lm_mtp_nll", "lm_main_nll"


def read(run):
    tree = spantree.load(run)
    if tree is None:
        return None
    gaps = [statistics.fmean(r.args[MTP]) - statistics.fmean(r.args[MAIN])
            for r in tree.rounds() if r.args.get(MTP) and r.args.get(MAIN)]
    return float(statistics.median(gaps)) if gaps else None
