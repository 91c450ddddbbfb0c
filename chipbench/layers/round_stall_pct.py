"""L4 trainer step: the share of the window's round time that
``tokens_per_s`` leaves out.  That metric is a median over sync rounds;
this is 1 minus the time the window's rounds would have taken at the
median round's rate over the time they took, over all workers: near 0 in
a steady run, and the whole seconds a stalled host or a periodic slow
round costs otherwise (``chipbench/measure.py``).  This is the traced
run's share; tracing adds a little to the rounds it covers."""


def read(run):
    return run["summary"].get("round_stall_pct")
