"""L3 shell + client: the ``exchange`` phase of the program's own
``round`` span (``mpit_tpu/optim/sync.py``): first ``async_*`` call to
the return of ``wait``, the median over the first worker's rounds in the
window.  The twin from inside of ``ps_round_ms_p50``, which times the
same boundary from the benchmark's proxy; the two should agree."""

from chipbench.layers import spantree


def read(run):
    tree = spantree.load(run)
    if tree is None:
        return None
    return spantree.median_ms([spantree.phase_ms(r, "exchange")
                               for r in tree.rounds()])
