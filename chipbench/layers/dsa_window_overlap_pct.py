"""L4 trainer step: over the rows that have a choice (``t >= topk``),
the share of chosen positions that lie among the row's ``topk`` most
recent, in percent, the mean over the layers, median over the first
worker's rounds in the window.  At 100 the indexer has degenerated to a
sliding window (it scores by recency alone and the mechanism is Mellum's
window at four times the cost); a choice that ignores order reads ``topk
/ (t + 1)`` on a row, 46 over the rows of 8192 at ``topk`` 2048; a
reading near 0 would be an indexer that avoids the recent past.  Both
ends are a mechanism lost, and the benchmark's entry has to name one
direction: ``lower``, away from the window, the end a trained indexer
drifts to; what the cell holds it to is the open interval (30, 95).
The program counts it on the device where the sets are made, an
auxiliary output of the step fetched only while obs records, noted on
the ``round`` span as ``lm_dsa_window_overlap`` (one entry a layer;
gauge ``mpit_lm_dsa_window_overlap``).  Nothing to read from a program
or a block that records none."""

from chipbench.layers import dsa_kept_pct

ARG = "lm_dsa_window_overlap"


def read(run):
    share = dsa_kept_pct.rounds_median(run, ARG)
    return None if share is None else 100.0 * share
