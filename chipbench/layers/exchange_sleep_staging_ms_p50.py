"""L3 shell + client: how long the client's thread slept in the
scheduler's back-off inside ``exchange`` with a GRAD op at the gate or
its send out of staged pieces (the stream's thread had staged no more):
the ``round`` span's ``sleep_staging_ms``, the median over the first
worker's rounds that lie whole in the window
(``exchange_sleep_apply_ms_p50`` prints all four)."""

from chipbench.layers import copytree


def read(run):
    copies = copytree.load(run)
    if copies is None:
        return None
    return copytree.median(copytree.sleeps(copies).get("staging", []))
