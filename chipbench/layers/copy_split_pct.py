"""L2 servers + wire: how much of the rings' traffic was copied in parts
by more than one thread: per round the ``split_bytes`` of the first
worker's and its servers' ``wire`` spans, both ends of every GRAD and
PARAM message (payload bytes of ring copies that the endpoint's calling
thread and its helper threads copied at once:
``comm/native/transport.cpp`` ``Crew``, ``copy_bytes``; the helpers'
count is ``comm/shm.py`` ``copy_helpers``) over those spans' ``bytes``,
in percent, the median over the rounds that lie whole in the window.  0
says every ring copy was one ``memcpy`` on its caller's thread, as
before PR 66 and on a host with no core to spare; near 100 says every
chunk of the vector was split (a message's last chunk and a piece's
short end may fall under the threshold).  None where the program's
``wire`` spans carry no ``split_bytes`` (a program from before PR 66)
or no transport ran."""

from chipbench.layers import wiretree


def read(run):
    wire = wiretree.load(run)
    if wire is None:
        return None
    split, total = {}, {}
    for _op, k, tx, rx in wire.messages:
        for span in (tx, rx):
            if "split_bytes" in span.args:
                split[k] = split.get(k, 0.0) + float(span.args["split_bytes"])
                total[k] = total.get(k, 0.0) + float(span.args["bytes"])
    return wiretree.median(
        [100.0 * split[k] / total[k] for k in split if total[k] > 0])
