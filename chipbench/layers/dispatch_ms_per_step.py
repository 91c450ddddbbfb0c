"""L4 trainer step: device time per traced micro-step under the model
scopes ``router`` (the norm before the experts, the router's product,
its softmax and the top-k) and ``dispatch`` (the sort by expert, the
gather of the rows, and the weighted sum back to the tokens; forward and
backward): what sparsity costs beyond its products
(``spantree.scope_ms_per_step``).  A line before the result gives the
two parts.  Nothing to read where the configuration lists neither scope
or the trace has no operation under them."""

from chipbench.layers import spantree

SCOPES = ("router", "dispatch")


def read(run):
    if not set(SCOPES) <= set(spantree.model_scopes(run)):
        return None
    table = spantree.scope_ms_per_step(run)
    if not table or not any(scope in table for scope in SCOPES):
        return None
    parts = {scope: table.get(scope, 0.0) for scope in SCOPES}
    print("chipbench: device ms per micro-step, router "
          f"{parts['router']:.3f}, dispatch {parts['dispatch']:.3f}",
          flush=True)
    return sum(parts.values())
