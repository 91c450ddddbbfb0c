"""L1 kernels: the least time the chip's memory bandwidth allows the
micro-step's gates and depthwise convolutions (the bytes of the
configuration's arithmetic, ``chipbench/arithmetic/<module>.py``
``conv_mix_cost``: the three gates' width read and one width written
forward, read again with the incoming gradient and written backward,
each pass one fused sweep, nothing recomputed; the peak from
``chipbench/peaks.json``) over the device time under the scope
``conv_mix`` (``layers/conv_mix_ms_per_step.py``).  The operator is
XLA's fusions today: a low share says a fused kernel is worth writing,
a high one that it is not.  The FLOPs are a dozen a channel and
position and never bind.  The line printed before the result gives the
achieved rate.  Nothing to read where the configuration's arithmetic
has no such cost, the configuration no such scope, or the trace no
operation under it."""

from chipbench.layers import conv_mix_ms_per_step


def read(run):
    cost_of = getattr(run["cell"].arithmetic(), "conv_mix_cost", None)
    if cost_of is None or run.get("peaks") is None:
        return None
    ms = conv_mix_ms_per_step.read(run)
    if not ms:
        return None
    cost = cost_of(run["cell"].config, int(run["cell"].traffic["batch"]))
    seconds = ms / 1e3
    least = cost["bytes"] / (run["peaks"]["hbm_gbps"] * 1e9)
    print(f"chipbench: conv_mix roofline is bound by memory; "
          f"{cost['bytes'] / seconds / 1e9:.1f} GB/s over {ms:.3f} ms in "
          f"{cost['layers']} conv layers", flush=True)
    return 100.0 * least / seconds
