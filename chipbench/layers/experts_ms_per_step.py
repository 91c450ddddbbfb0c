"""L4 trainer step: device time per traced micro-step of the operations
under the model scope ``experts`` (the three grouped products over the
rows sorted by expert, the SiLU gate between them, and their backward
products: ``parallel/moe.py`` ``swiglu_experts`` as
``dispatch_top_k`` runs it), among the operations the first
worker's chip ran inside the step's own program in the traced window
(``spantree.scope_ms_per_step``).  A collapsed-routing reading so far:
on seeded weights and without a load-balancing loss nearly every token
takes the same few experts, so the products run a few full groups
(PERF.md section 6, PR 26).  Nothing to read where the
configuration has no such scope or the trace no operation under it, as
with a program that predates the block."""

from chipbench.layers import spantree

SCOPE = "experts"


def read(run):
    if SCOPE not in spantree.model_scopes(run):
        return None
    table = spantree.scope_ms_per_step(run)
    if not table or SCOPE not in table:
        return None
    return table[SCOPE]
