"""L2 servers + wire: how much of ``exchange`` the client's one thread
spends inside the ring copies: ``wire_tx_copy_ms + wire_rx_copy_ms`` of
the first worker's ``round`` spans (the shm endpoint's own totals over
the phase, ``mpit_tpu/optim/sync.py`` ``_exchange``), the median over the
rounds that lie whole in the window.  It is what a second copying thread
in the client could at most halve.  The lines before the result are the
table all five wire metrics are cut from (``wiretree.table``)."""

from chipbench.layers import wiretree


def read(run):
    wire = wiretree.load(run)
    if wire is None:
        return None
    for line in wiretree.table(wire):
        print(f"chipbench: wire: {line}", flush=True)
    return wiretree.median(
        [float(r.args["wire_tx_copy_ms"]) + float(r.args["wire_rx_copy_ms"])
         for r in wire.rounds if "wire_tx_copy_ms" in r.args])
