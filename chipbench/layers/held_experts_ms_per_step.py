"""L4 trainer step: device time per traced micro-step of the operations
under the model scope ``experts`` in a block that holds a share of its
experts (the three grouped products over the held experts' rows, the
SiLU gate between them, the zeroing of the rows no held expert owns,
the forward pass again where the block recomputes it, and the backward
products: ``parallel/moe.py`` ``swiglu_experts`` from a group offset),
among the operations the first worker's chip ran inside the step's own
program in the traced window (``spantree.scope_ms_per_step``).  A block
that holds every expert reads ``experts_ms_per_step``.  Nothing to read
where the configuration holds no share (no ``router_experts`` beside
``num_experts``), has no such scope or the trace no operation under
it."""

from chipbench.layers import spantree

SCOPE = "experts"


def holds_a_share(run) -> bool:
    config = run["cell"].config
    return "router_experts" in config and \
        int(config["num_experts"]) < int(config["router_experts"])


def read(run):
    if not holds_a_share(run) or SCOPE not in spantree.model_scopes(run):
        return None
    table = spantree.scope_ms_per_step(run)
    if not table or SCOPE not in table:
        return None
    return table[SCOPE]
