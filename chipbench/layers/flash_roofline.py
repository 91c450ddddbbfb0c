"""L1 kernels: the least time the chip's peaks allow the micro-step's
attention (``chipbench/flops.py`` from shapes, ``chipbench/peaks.json``)
over the device time its Mosaic calls took.  The line the runner prints
before the result says which peak binds."""

from chipbench import flops


def read(run):
    red = run["reduction"]
    if not red.get("step_module_runs") or not red.get("mosaic_calls") \
            or run["peaks"] is None:
        return None
    cell = run["cell"]
    need_flops, need_bytes = flops.flash_step_cost(
        cell.config, int(cell.traffic["batch"]))
    seconds = red["mosaic_s"] / red["step_module_runs"]
    share, bound = flops.roofline(need_flops, need_bytes, seconds,
                                  run["peaks"])
    print(f"chipbench: flash roofline is bound by {bound}", flush=True)
    return share
