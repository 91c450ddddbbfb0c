"""Device: 1 minus the union of the device-op intervals over the traced
window (``chipbench/reduce.py``), of the traced worker's chip."""


def read(run):
    return run["reduction"].get("idle_pct")
