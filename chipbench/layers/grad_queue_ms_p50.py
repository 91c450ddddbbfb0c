"""L2 servers + wire: from the client's ``GRAD.send`` mark to the begin
of the server's GRAD span the program's joiner paired with it (by the
per-channel ordinal; ``mpit_tpu/obs/causal.py``), both on the host's
monotonic clock: the wire plus the time the frame waited for the server
to get to it.  The median over the window, all workers."""

from chipbench.layers import spantree


def read(run):
    tree = spantree.load(run)
    if tree is None:
        return None
    waits = []
    for client, server in spantree.joined(tree, "GRAD"):
        sent = client.mark_ts("send", last=False)
        if sent is not None:
            waits.append(1e3 * (tree.mono(server, server.t0)
                                - tree.mono(client, sent)))
    return spantree.median_ms(waits)
