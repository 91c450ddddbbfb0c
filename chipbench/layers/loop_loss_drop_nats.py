"""L4 trainer step: what the later passes of a looped block buy: the
mean next-token NLL of the first pass's head less that of the last
pass's, in nats, median over the first worker's rounds in the window.  0
says looping is idle (the last pass predicts no better than the first),
and below 0 that it hurts.  The program reduces it on the device, an
auxiliary output of the step fetched only while obs records, noted on
the ``round`` span as ``loop_loss_drop`` (gauge ``mpit_loop_loss_drop``),
by the path ``loop_exit_step_mean`` takes (``layers/exit_step_mean.py``).
Nothing to read from a program or a block that records none."""

from chipbench.layers import exit_step_mean

ARG = "loop_loss_drop"


def read(run):
    return exit_step_mean.rounds_median(run, ARG)
