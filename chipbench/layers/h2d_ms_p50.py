"""L3 shell + client: the ``h2d`` phase of the program's ``round`` span:
``jnp.asarray`` of the pulled parameters to the transfer's completion
(the shell fences it while recording; the benchmark fences right after
in every run); the median over the first worker's rounds in the
window."""

from chipbench.layers import spantree


def read(run):
    tree = spantree.load(run)
    if tree is None:
        return None
    return spantree.median_ms([spantree.phase_ms(r, "h2d")
                               for r in tree.rounds()])
