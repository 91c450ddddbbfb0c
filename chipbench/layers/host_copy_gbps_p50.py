"""L2 servers + wire: what the host's memory gave all copiers together
while any of them ran: per round the memory traffic of every host pass
of all ranks in the round (``host_passes_per_byte``'s numerator) over
the length of the union of their intervals, GB/s; the median over the
first worker's rounds that lie whole in the window."""

from chipbench.layers import copytree


def read(run):
    copies = copytree.load(run)
    if copies is None:
        return None
    return copytree.median(copies.per_round(
        lambda _r, mine, _p: sum(c.moved for c in mine)
        / max(copies.prog.union_seconds(mine), 1e-12) / copytree.GB))
