"""L4 trainer step: device time per traced micro-step of the operations
whose name stack holds the scope ``head_loss`` innermost (the final
LayerNorm, the head's product, the softmax and the loss, forward and
backward: ``jax.named_scope`` in ``models/transformer.py`` and
``lm/model.py``), among the operations the first worker's chip ran
inside the step's own program in the traced window.  A fusion counts
under the scope of its root operation.  A line before the result gives
the same for every scope of the model (``scopes`` in the
configuration's file), what no
scope names, and what could not be told apart."""

from chipbench.layers import spantree

SCOPE = "head_loss"


def read(run):
    table = spantree.scope_ms_per_step(run)
    if not table or SCOPE not in table:
        return None
    print("chipbench: device ms per micro-step by scope: "
          + ", ".join(f"{key} {ms:.3f}" for key, ms in table.items()),
          flush=True)
    return table[SCOPE]
