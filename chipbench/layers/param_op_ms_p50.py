"""L2 servers + wire: the client's PARAM op span per shard (``send`` of
the request to the snapshot received): what a pull costs, which waits
for that server's pending apply; the median over the window, all
workers."""

from chipbench.layers import spantree


def read(run):
    tree = spantree.load(run)
    if tree is None:
        return None
    return spantree.median_ms([(s.t1 - s.t0) / 1e3
                               for s in tree.named("PARAM", "client")])
