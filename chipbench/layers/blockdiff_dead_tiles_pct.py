"""L1 kernels: of the tiles the attention kernels of a block-diffusion
step run a product on, forward and backward, all layers, the share in
which the mask has no live pair, in percent; median over the first
worker's rounds in the window (a constant of the lowered step, so every
round reads alike).  The mask of the pass over a noised and a clean copy
of a sequence is not inside the causal triangle: walked whole the ``2 L
x 2 L`` square would read 68.75 at ``L`` 4096 on tiles of 512 (80 of 256
live), under a plain causal walk with the clean copy laid first 41 (136
visited for the 80); 0 says the walk visits live tiles alone.  The
program records both counts from the very calls it lowers when the step
is traced (``ops/flash_attention.py`` ``flash_call_counts``: the walk's
own tiles, and the tiles in which the mask's rule has a true entry),
auxiliary outputs of the step fetched only while obs records, noted on
the ``round`` span as ``attn_tiles_visited`` and ``attn_tiles_live``
(one entry a layer; gauges ``mpit_attn_tiles_visited``,
``mpit_attn_tiles_live``: ``optim/sync.py`` ``note_stats``,
``models/transformer.py`` ``SdarDecoder``): not the arithmetic's, so a
later change of the walk shows.  Nothing to read from a program or a
block that records neither."""

import statistics

from chipbench.layers import spantree

VISITED, LIVE = "attn_tiles_visited", "attn_tiles_live"


def read(run):
    tree = spantree.load(run)
    if tree is None:
        return None
    dead = [100.0 * (1.0 - sum(r.args[LIVE]) / sum(r.args[VISITED]))
            for r in tree.rounds()
            if r.args.get(VISITED) and r.args.get(LIVE)]
    return float(statistics.median(dead)) if dead else None
