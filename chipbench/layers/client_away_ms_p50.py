"""L2 servers + wire: a shard's chunks lay published in the client's
ring while its one thread was elsewhere (copying the other shard's,
pushing, in Python, asleep): per round the longest ``away_ms`` of the
client's PARAM ``rx`` spans, the median over the rounds that lie whole
in the window."""

from chipbench.layers import wiretree


def read(run):
    wire = wiretree.load(run)
    if wire is None:
        return None
    return wiretree.median(wire.longest("PARAM", "rx", "away_ms"))
