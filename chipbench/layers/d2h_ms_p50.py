"""L3 shell + client: the ``d2h`` and ``stage`` phases of the program's
``round`` span: the payload's copy from the device to the host (the
wait for the backward is a phase of its own and not in here) and its
``np.copyto`` into the client's host mirror; the median over the first
worker's rounds in the window."""

from chipbench.layers import spantree


def read(run):
    tree = spantree.load(run)
    if tree is None:
        return None
    return spantree.median_ms([spantree.phase_ms(r, "d2h", "stage")
                               for r in tree.rounds()])
