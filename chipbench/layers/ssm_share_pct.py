"""L4 trainer step: the Mamba-2 mixers' share of the traced micro-step's
device time: ``ssm_ms_per_step`` (the scopes ``ssm_proj``, ``ssm_conv``,
``ssd_scan`` and ``ssm_norm``) over the device time of the step's
program itself (``layers/spantree.py`` ``scope_ms_per_step``'s ``step``:
every scope of the configuration, what no scope names and what could
not be told apart lie inside it), in percent.  It says whether a cell
named for its mixers is the mixers': the projections, the convolution,
the scan and the norm against the MLPs, the attention, the head and the
update.  Nothing to read where the configuration lists none of the four
scopes or the trace has no operation under them."""

from chipbench.layers import spantree, ssm_ms_per_step


def read(run):
    mixers = ssm_ms_per_step.read(run)
    table = spantree.scope_ms_per_step(run)
    if not mixers or not table or not table.get("step"):
        return None
    return 100.0 * mixers / table["step"]
