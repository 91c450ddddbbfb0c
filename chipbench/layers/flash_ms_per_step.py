"""L1 kernels: device time of the Mosaic calls (``tpu_custom_call``
events of the trace's ``XLA Ops`` line) per traced micro-step."""


def read(run):
    red = run["reduction"]
    if not red.get("step_module_runs") or not red.get("mosaic_calls"):
        return None
    return 1e3 * red["mosaic_s"] / red["step_module_runs"]
