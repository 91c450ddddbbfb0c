"""L4 trainer step: what a diffusion block's own visible neighbours are
worth: the mean NLL of the masked positions whose block is masked whole
(count ``B``: they see the clean past alone) less that of the positions
masked alone in their block (count 1: their block's other ``B - 1``
positions too, both ways), in nats, the mean over the four micro-steps
that end at the mix's ``token_budget`` (``loss_at_budget``'s four).
Near 0 says the noised block's attention to itself has taught nothing
yet; it opens as the layers learn to read a masked position's
neighbours.  The program reduces each count's NLL on the device,
auxiliary outputs of the step fetched only while obs records, noted on
the ``round`` span as ``diff_nll_c1`` .. ``diff_nll_c<B>`` (gauges
``mpit_diff_nll_c<c>``: ``optim/sync.py`` ``note_stats``,
``models/transformer.py`` ``SdarDecoder``).  Nothing to read from a
program or a block that records none, or where the four steps lie
outside the window."""

import statistics

from chipbench.layers import spantree


def read(run):
    tree = spantree.load(run)
    config, mix = run["cell"].config, run["cell"].traffic
    if tree is None or "block_length" not in config:
        return None
    whole, alone = f"diff_nll_c{int(config['block_length'])}", "diff_nll_c1"
    last = int(mix["token_budget"]) // (
        int(mix["batch"]) * int(config["train_seq"])) - 1
    gaps = [r.args[whole][0] - r.args[alone][0] for r in tree.rounds()
            if r.args.get("round") in range(last - 3, last + 1)
            and r.args.get(whole) and r.args.get(alone)]
    return float(statistics.fmean(gaps)) if gaps else None
