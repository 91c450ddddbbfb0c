"""L2 servers + wire: how much of the push left before its shard was
whole in the mirror: per round the ``early_bytes`` of the client's GRAD
``tx`` spans (payload bytes placed in a server's ring while their send's
ready mark was short of its length: ``comm/native/transport.cpp``
``SendOp.ready``, moved by the client as the shell stages the shard
piece by piece) over those spans' ``bytes``, in percent, the median over
the rounds that lie whole in the window.  0 says the sends waited for
their whole shards, as they did before PR 40; all but a shard's last
piece or two says the push rode under the staging.  None where the
program's ``tx`` spans carry no ``early_bytes`` (a program from before
PR 40) or no transport ran."""

from chipbench.layers import wiretree


def read(run):
    wire = wiretree.load(run)
    if wire is None:
        return None
    early, total = {}, {}
    for op, k, tx, _rx in wire.messages:
        if op == "GRAD" and "early_bytes" in tx.args:
            early[k] = early.get(k, 0.0) + float(tx.args["early_bytes"])
            total[k] = total.get(k, 0.0) + float(tx.args["bytes"])
    return wiretree.median(
        [100.0 * early[k] / total[k] for k in early if total[k] > 0])
