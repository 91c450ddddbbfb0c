"""L2 servers + wire: the push waited for a server's drain: per round
the longest ``blocked_ms`` of the client's GRAD ``tx`` spans (a full
ring refused a chunk, until that ring took the next one), the median
over the rounds that lie whole in the window.  ``tx_ring_full`` in time
and not in polls."""

from chipbench.layers import wiretree


def read(run):
    wire = wiretree.load(run)
    if wire is None:
        return None
    return wiretree.median(wire.longest("GRAD", "tx", "blocked_ms"))
