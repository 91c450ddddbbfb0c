"""L2 servers + wire: the servers waited for the client's drain: per
round the longest ``blocked_ms`` of the servers' PARAM ``tx`` spans (the
ring at the client refused a chunk, until it took the next one), the
median over the rounds that lie whole in the window."""

from chipbench.layers import wiretree


def read(run):
    wire = wiretree.load(run)
    if wire is None:
        return None
    return wiretree.median(wire.longest("PARAM", "tx", "blocked_ms"))
