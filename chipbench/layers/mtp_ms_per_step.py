"""L4 trainer step: device time per traced micro-step of the
multi-token-prediction module **whole** (``models/transformer.py``
``JoyaiDecoder``, everything inside ``jax.named_scope("mtp")``): the two
norms and the projection of the pair, its sparse layer (latent
attention, router, dispatch, held and shared experts), its final norm,
the second product of the head and its loss, forward and backward.

The module's layer runs under the same scopes as the stack's layers
(``mtp/attn``, ``mtp/experts``, ...), and the configuration's ``scopes``
do not list ``mtp``: a Mosaic call under two listed scopes is booked to
neither (``chipbench/reduce.py``), and the module's flash and grouped
calls belong to the ``attn`` and ``experts`` kernel families like any
layer's.  So this reader does not go through
``spantree.scope_ms_per_step``, which books an operation to the
innermost listed scope: it sums the operations of the step's program
whose name stack holds ``mtp`` **anywhere**.  What it reads is therefore
also inside ``mla_proj_ms_per_step``, ``flash_ms_per_step``,
``dispatch_ms_per_step``, ``held_experts_ms_per_step``,
``shared_expert_ms_per_step`` and ``head_loss_ms_per_step``: a cut
across them, one sixth of the attention and one fifth of the sparse work
by count, not a part beside them.  Nothing to read where the trace has
no operation under the scope (a program without the module)."""

import bisect
import re

from chipbench import reduce as reduce_mod
from chipbench.layers import spantree

PATTERN = re.compile(r"\bmtp\b")


def read(run):
    chip = spantree.traced_chip(run)
    module = run["reduction"].get("step_module")
    if chip is None or not module:
        return None
    steps = sorted((s, s + d) for name, s, d in chip["modules"]
                   if reduce_mod.module_short_name(name) == module
                   and chip["lo"] <= s and s + d <= chip["hi"])
    if not steps:
        return None
    starts = [s for s, _e in steps]
    stacks = spantree.op_scopes(spantree.xplane_path(run), chip["plane"])
    total = 0.0
    for name, start, dur in chip["ops"]:
        at = bisect.bisect_right(starts, start) - 1
        if at < 0 or start >= steps[at][1]:
            continue
        if PATTERN.search(stacks.get(name, "")):
            total += dur
    return total / 1e6 / len(steps) if total else None
