"""L3 shell + client: how long the client's thread slept in the
scheduler's back-off inside ``exchange`` with a PARAM's receive posted
and its server not yet sending (the server's sweep and snapshot stood
before it): the ``round`` span's ``sleep_apply_ms``
(``mpit_tpu/ps/client.py`` ``_why_asleep``), the median over the first
worker's rounds that lie whole in the window.  The line before the
result gives all four named sleeps (``staging``, ``apply``, ``drain``,
``pull``), which sum to ``sched_sleep_ms``."""

from chipbench.layers import copytree


def read(run):
    copies = copytree.load(run)
    if copies is None:
        return None
    copytree.print_sleeps(copies)
    return copytree.median(copytree.sleeps(copies).get("apply", []))
