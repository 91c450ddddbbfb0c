"""L4 trainer step: device time per traced micro-step under the model
scope ``exit_gate`` of a looped block: after the passes, over their
kept outputs, the gates' products (2048 multiply-adds a position and
pass, elementwise) and sigmoids, the exit distribution, its entropy and
the weighted sum of the passes' losses, forward and backward (``models/transformer.py``
``OuroDecoder``).  It is what leaving early costs to train: small beside
the heads it weighs (``head_loss_ms_per_step``), and a reading that is
not says the gate has grown a ``(positions, vocabulary)`` operand.
Nothing to read where the configuration lists no such scope or the trace
has no operation under it."""

from chipbench.layers import spantree

SCOPE = "exit_gate"


def read(run):
    if SCOPE not in spantree.model_scopes(run):
        return None
    table = spantree.scope_ms_per_step(run)
    if not table or SCOPE not in table:
        return None
    return table[SCOPE]
