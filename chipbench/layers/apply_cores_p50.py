"""L2 servers + wire: how many cores a server's sweep asked for: the
``cpu_ms`` that the recorder's waiter notes on an ``apply_exec`` span (the
process's ``time.process_time()`` from the ``exec`` mark to the stamp
the span ends at, every thread of the server) over the ``exec`` phase's
length; the median over the applies of both servers in the first
worker's windowed rounds, those a role thread ended on time
(``end_from`` ``wait_apply``) where a round has one (``coretree``).  The
line before the result says how many it used.  On a kernel that counts
a runnable thread as running (gVisor, the chip's host) this is the
sweep's demand, not what it got.  None in a program whose spans carry
no ``cpu_ms``, and where the table's check fails."""

from chipbench.layers import coretree


def read(run):
    cores = coretree.checked(run)
    if cores is None:
        return None
    return coretree.apply_cores(cores)
