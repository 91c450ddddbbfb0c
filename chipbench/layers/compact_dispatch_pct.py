"""L4 trainer step: the share of the sparse layers' steps whose dispatch
was done in one window of the sorted rows, in percent: the mean over
the layers, then over the first worker's rounds in the window (100:
every layer of every step; less: a held run outgrew the window and
that layer looped over two windows or more, each at a window's cost,
``parallel/moe.py`` *A window over the held run*).  The program works
it out on the device from the routing's count, an auxiliary output of
the step that it fetches only while obs records, and notes it on the
``round`` span as ``moe_compact_share`` (one entry a layer, 1.0 or 0.0;
the gauge ``mpit_moe_compact_share`` carries the same), by the path
``moe_held_rows_share`` takes.  Nothing to read from a program or a
block that records none."""

import statistics

from chipbench.layers import spantree

ARG = "moe_compact_share"


def read(run):
    tree = spantree.load(run)
    if tree is None:
        return None
    values = [statistics.fmean(r.args[ARG]) for r in tree.rounds()
              if r.args.get(ARG)]
    return 100.0 * statistics.fmean(values) if values else None
