"""L1 kernels: the least time the chip's peaks allow the micro-step's
gated-delta scans (FLOPs and bytes of the configuration's arithmetic,
``chipbench/arithmetic/<module>.py`` ``gdn_scan_cost``: what the chunked
**scalar-decay** algorithm needs at the stated chunk size, forward, the
chunks again in the backward rule and the backward pass; q and k read at
the key heads, v read and o written at the value heads, the log-decay
and ``beta`` a float a value head and position, and their gradients,
once a pass; a chunk's pair matrices one product each under a ``C x C``
decay matrix; peaks from ``chipbench/peaks.json``) over the device time
under the scope ``gdn_scan`` (``layers/gdn_scan_ms_per_step.py``),
``kda_scan_roofline``'s form.  The count belongs to the algorithm, not
to what runs under the scope: a form that repeats the keys and
broadcasts the decay through channel-wise kernels moves and multiplies
more than this and reads a lower share, and a kernel of the scalar
rule's own is read on the same yardstick.  The line printed before the
result says which peak binds and the achieved rates.  Nothing to read
where the configuration's arithmetic has no such cost, the configuration
no such scope, or the trace no operation under it."""

from chipbench import flops
from chipbench.layers import gdn_scan_ms_per_step


def read(run):
    cost_of = getattr(run["cell"].arithmetic(), "gdn_scan_cost", None)
    if cost_of is None or run.get("peaks") is None:
        return None
    ms = gdn_scan_ms_per_step.read(run)
    if not ms:
        return None
    cost = cost_of(run["cell"].config, int(run["cell"].traffic["batch"]))
    seconds = ms / 1e3
    share, bound = flops.roofline(cost["flops"], cost["bytes"], seconds,
                                  run["peaks"])
    print(f"chipbench: gdn_scan roofline is bound by {bound}; "
          f"{cost['flops'] / seconds / 1e12:.2f} TFLOP/s and "
          f"{cost['bytes'] / seconds / 1e9:.1f} GB/s over {ms:.3f} ms in "
          f"{cost['layers']} Gated DeltaNet layers", flush=True)
    return share
