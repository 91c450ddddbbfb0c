"""L1 kernels: the least time the chip's peaks allow the micro-step's
indexer (FLOPs and bytes of the configuration's arithmetic,
``chipbench/arithmetic/<module>.py`` ``index_cost``: 2 a parameter of
the indexer's three matrices a token and 2 x heads x head_dim FLOPs a
causal pair for the scores; the layer's input and the matrices read,
the heads' queries, the key head and the weights written and read, the
selection's bits written, once a layer, forward only; peaks from
``chipbench/peaks.json``) over the device time under the scope
``index`` (``layers/dsa_index_ms_per_step.py``).  Every product the
scope times is in the count; it also holds the LayerNorm, the rotations
and the exact top-k a row: compares and counts, no FLOPs of a product,
so the share says how far the whole indexer is from what its products
alone would take at the bf16 peak.  The products run at full float32
precision (six bf16 passes), the scores over every column, not the
causal half, and the selection makes 32 passes over each block: a low
share is what a fused kernel would win.  The count belongs to the
algorithm, so a later implementation is read on the same yardstick.
The line printed before the result says which peak binds and the
achieved rates.  Nothing to read where the configuration's arithmetic
has no such cost, the configuration no such scope, or the trace no
operation under it."""

from chipbench import flops
from chipbench.layers import dsa_index_ms_per_step


def read(run):
    cost_of = getattr(run["cell"].arithmetic(), "index_cost", None)
    if cost_of is None or run.get("peaks") is None:
        return None
    ms = dsa_index_ms_per_step.read(run)
    if not ms:
        return None
    cost = cost_of(run["cell"].config, int(run["cell"].traffic["batch"]))
    seconds = ms / 1e3
    share, bound = flops.roofline(cost["flops"], cost["bytes"], seconds,
                                  run["peaks"])
    print(f"chipbench: index roofline is bound by {bound}; "
          f"{cost['flops'] / seconds / 1e12:.2f} TFLOP/s and "
          f"{cost['bytes'] / seconds / 1e9:.1f} GB/s over {ms:.3f} ms in "
          f"{cost['layers']} layers", flush=True)
    return share
