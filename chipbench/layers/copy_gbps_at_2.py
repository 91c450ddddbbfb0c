"""L2 servers + wire: the host memory's rate while exactly two copiers
were at work at once (``copy_gbps_at_1`` has the definition and prints
the table)."""

from chipbench.layers import copytree


def read(run):
    copies = copytree.load(run)
    return None if copies is None else copytree.class_gbps(copies, "2")
