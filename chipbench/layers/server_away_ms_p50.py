"""L2 servers + wire: chunks of the push lay published in a server's
ring while it was not copying them out (asleep in the scheduler's
back-off, sweeping its Adam slots, in its other work, off the core): per
round the longest ``away_ms`` of the servers' GRAD ``rx`` spans, the
median over the rounds that lie whole in the window."""

from chipbench.layers import wiretree


def read(run):
    wire = wiretree.load(run)
    if wire is None:
        return None
    return wiretree.median(wire.longest("GRAD", "rx", "away_ms"))
