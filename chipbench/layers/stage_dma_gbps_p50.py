"""L3 shell + client: the rate of the chip's DMA engine while it had a
piece of the payload to land: per round the bytes of the stream thread's
``d2h`` piece spans over the engine's time, every piece counted from the
later of its cut's dispatch and the landing of the piece before it to
its own landing (``mpit_tpu/obs/copies.py`` ``stage``), GB/s; the median
over the first worker's rounds that lie whole in the window.  The lines
before the result are the stream's piece table: the round of the median
rate with the thread's phases, the cuts in flight when a piece was
popped (at 4 the engine is the pace, at 1-2 the cuts' dispatch starves
it) and the uploads by shard (``copytree.print_stage``)."""

from chipbench.layers import copytree


def read(run):
    copies = copytree.load(run)
    if copies is None:
        return None
    copytree.print_stage(copies)
    return copytree.median(
        [row["bytes"] / row["dma_s"] / copytree.GB
         for row in copytree.stage_rows(copies) if row["dma_s"] > 0])
