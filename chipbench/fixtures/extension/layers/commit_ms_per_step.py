"""The reader of the throw-away configuration's own kernel family: device
ms per micro-step of the Mosaic calls under the family's scope."""

from chipbench import flops


def read(run):
    found = flops.kernel_family(run, "commit")
    return None if found is None else 1e3 * found[1]
