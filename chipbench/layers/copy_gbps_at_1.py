"""L2 servers + wire: the host memory's rate while exactly one copier
was at work: the windowed rounds' host passes of all ranks swept on the
shared clock, each pass's memory traffic spread evenly over its length,
aggregate GB over aggregate seconds of the stretches with one pass
running.  With ``copy_gbps_at_2`` and ``copy_gbps_at_3plus`` it says
what a further copying thread would get of the memory: if the rate with
three and more at work is no higher than with two, it divides the memory
and gains nothing.  The lines before the result are the whole table
(class, seconds, GB, GB/s and which passes met; then a pass's own rate
alone and in company), the traffic a byte by pass, and the count of the
records (``copytree.print_classes``)."""

from chipbench.layers import copytree


def read(run):
    copies = copytree.load(run)
    if copies is None:
        return None
    copytree.print_classes(copies)
    return copytree.class_gbps(copies, "1")
