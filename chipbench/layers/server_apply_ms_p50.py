"""L2 servers + wire: the ``exec`` phase of the servers' ``apply_exec``
spans: from the moment the jitted apply could run (dispatched, and the
apply before it finished) to its result ready, stamped by the
recorder's waiter thread; per GRAD op, all servers, the median over the
window.  The ``queued`` phase before it is left out.  A line before
the result gives the exchange's parts in ms per MB of the bytes their
own spans carry, and the median ``queued`` (``spantree.per_mb``)."""

from chipbench.layers import spantree


def read(run):
    tree = spantree.load(run)
    if tree is None:
        return None
    rates = spantree.per_mb(tree)
    if rates:
        print("chipbench: ms per MB of the spans' own bytes: "
              + ", ".join(f"{key} {v:.3f}" for key, v in rates.items()),
              flush=True)
    return spantree.median_ms([spantree.phase_ms(s, "exec")
                               for s in tree.named("apply_exec", "server")])
