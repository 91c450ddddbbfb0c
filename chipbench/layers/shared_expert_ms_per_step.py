"""L4 trainer step: device time per traced micro-step under the model
scope ``shared_expert`` (``models/transformer.py`` ``JoyaiBlock``): the
SiLU-gated MLP of ``moe_intermediate_size`` that every token takes
beside its routed experts, three dense products a sparse layer, whole on
every share; forward, forward again (it lies in the recomputed sparse
branch) and backward, the MTP module's layer too.  What the shared
expert costs beside ``held_experts_ms_per_step`` and
``dispatch_ms_per_step``: no sort, no gather, rows that do not depend on
the routing.  Nothing to read where the configuration lists no such
scope or the trace has no operation under it."""

from chipbench.layers import mla_proj_ms_per_step

SCOPE = "shared_expert"


def read(run):
    return mla_proj_ms_per_step.scope_ms(run, SCOPE)
