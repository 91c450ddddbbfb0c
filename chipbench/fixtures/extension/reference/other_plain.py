"""The plain reference of the self-check's throw-away configuration
(``fixtures/extension/configs/other.json``).  The program has one block,
so this wraps GPT-2's reference; what it proves is that the harness
finds a reference by the configuration's key and hands it the
configuration's own keys, which here are not GPT-2's."""

from chipbench.reference import gpt_plain

# the wrapped reference's, for its reasons
LOSS_TOL_NATS = gpt_plain.LOSS_TOL_NATS
GRAD_REL_TOL = gpt_plain.GRAD_REL_TOL


def loss_and_grad_flat(w0, unravel, row, config):
    return gpt_plain.loss_and_grad_flat(
        w0, unravel, row,
        {"n_head": config["heads"], "n_layer": config["depth"]})
