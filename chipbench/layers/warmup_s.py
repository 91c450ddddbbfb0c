"""L5 launch: the warm-up's wall in the first worker, the compile (or
the read from the compile cache) inside it; the worker's host clock."""


def read(run):
    first = run["first_worker"]
    marks = first["chipbench"]["marks"]
    return marks["warmup_done"] - marks["reference_done"]
