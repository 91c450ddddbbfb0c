"""A new per-layer metric from a new file: the sync rounds in the window."""


def read(run):
    return float(sum(run["summary"]["rounds_in_window"].values()))
