"""L2 servers + wire: what the copy helpers' spin costs the cores: of the
time the native helper threads of every rank's shm endpoint spent on a
core in the windowed rounds, by their own readings of the clock
(``crew_copy_ms`` and ``crew_spin_ms`` on the metered spans:
``mt_wire_ns`` 3 and 4), the share they spun with no part to take: the
wait for a chunk's next half and ``comm/shm.py`` ``_HELPER_SPIN_NS`` after
the last (``coretree``).  None where no rank has a helper."""

from chipbench.layers import coretree


def read(run):
    cores = coretree.checked(run)
    if cores is None:
        return None
    return coretree.crew_spin(cores)
