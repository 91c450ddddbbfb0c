"""L3 shell + client: how much of the push was read where the d2h left
it: per round the ``direct_bytes`` of the program's ``round`` span
(payload bytes the stream's thread handed to the client's GRAD sends as
pieces, which the client copied from there into the servers' rings:
``mpit_tpu/optim/sync.py``, PR 45) over the ``bytes`` of that round's
client GRAD op spans, in percent, the median over the first worker's
rounds in the window.  100 says no byte of the gradient went by the
host mirror ``grad_host`` (two passes over every byte fewer, and the
thread that paces the push does not copy); 0 says every shard was staged
into the mirror first, as a codec, the framed or the chunked wire, a
transport that cannot send pieces and a client that takes no gate need
it.  None where the ``round`` spans carry no ``direct_bytes`` (a program
from before PR 45) or no round lies in the window."""

from chipbench.layers import spantree


def read(run):
    tree = spantree.load(run)
    if tree is None:
        return None
    pushed = {}
    for span in tree.named("GRAD", "client"):
        if span.pid == tree.first_worker and span.args.get("bytes"):
            k = span.args.get("round")
            pushed[k] = pushed.get(k, 0.0) + float(span.args["bytes"])
    return spantree.median_ms(
        [100.0 * float(r.args["direct_bytes"]) / pushed[r.args.get("round")]
         for r in tree.rounds()
         if "direct_bytes" in r.args and pushed.get(r.args.get("round"))])
