"""L2 servers + wire: what the gang asks of the host's cores while the
chip waits: the first worker's process CPU inside its ``exchange`` phase
(``cpu_ms`` on the ``round`` span) and each server's over its metered
stretches that end with that phase (``cpu_ms`` on its GRAD and PARAM
spans that close inside the round: a push's ack and a reply's last
byte; from its last reply of the round before), over the phase's
length; the median over the rounds that lie whole in the window
(``coretree``).  Exact stamps of ``time.process_time()``, every thread
of a process; the line before the result also gives the reading with
only the stretches that lie whole inside the exchange.  The lines before
that are the table all three cores metrics are cut from
(``coretree.print_table``): each rank's threads by name, rank by stretch
over the exchange and the servers' ``exec`` inside it, a line a round,
the applies, and last the check.  None where that check fails."""

from chipbench.layers import coretree


def read(run):
    cores = coretree.load(run)
    if cores is None:
        return None
    if not coretree.print_table(cores):
        return None
    return coretree.exchange_cores(cores)
