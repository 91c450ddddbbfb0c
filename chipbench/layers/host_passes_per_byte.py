"""L2 servers + wire: how many times a byte of the vector moves over the
host's memory a round: per round the memory traffic of every host pass
of all ranks whose middle lies in the round (``copytree``: the DMA's
landing writes a byte once, a ring copy reads and writes it, the
servers' sweep moves its ``bytes_moved``, the upload reads it once) over
the bytes the round's DMA landed; the median over the first worker's
rounds that lie whole in the window.  The count the builders kept by
hand from the code (17 with Adam since PR 45); a pass that a later PR
takes out shows here as a whole number less."""

from chipbench.layers import copytree


def read(run):
    copies = copytree.load(run)
    if copies is None:
        return None
    worker = copies.tree.first_worker
    return copytree.median(copies.per_round(
        lambda _r, mine, _p: sum(c.moved for c in mine)
        / copies.prog.vector_bytes(worker, mine)))
