"""L3 shell + client: how much of the stream thread's time while it
staged a round's payload went into its own serial work between the
pieces (freeing a piece, dispatching the next cut): the ``issue`` phases
of its ``d2h`` piece spans over ``wait`` + ``hand`` + ``issue`` (its
time not held back at ``HELD_BYTES``), in percent; the median over the
first worker's rounds that lie whole in the window.
``stage_dma_gbps_p50`` prints the table."""

from chipbench.layers import copytree


def read(run):
    copies = copytree.load(run)
    if copies is None:
        return None
    shares = []
    for row in copytree.stage_rows(copies):
        busy = row["wait_ms"] + row["hand_ms"] + row["issue_ms"]
        if busy > 0:
            shares.append(100.0 * row["issue_ms"] / busy)
    return copytree.median(shares)
