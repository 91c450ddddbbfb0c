"""Device: the share of the first worker's device idle time in the
traced rounds that falls while at least one host pass of any rank is
running (``copytree``: the DMA's landing, a ring copy, a server's sweep,
an upload), with the passes mapped onto the device trace's clock by the
``mpit.round`` anchors, as ``idle_unnamed_pct`` maps the span tree.  The
lines before the result give the idle seconds by the set of passes
running meanwhile, and what of the rest a leaf of the span tree covers:
what the host was doing in each idle gap."""

from chipbench.layers import copytree


def read(run):
    copies = copytree.load(run)
    if copies is None:
        return None
    got = copytree.idle_by_pass(run, copies)
    if got is None or not got["idle"]:
        return None
    idle, leaf = got.pop("idle"), got.pop("leaf")
    none = got.get("none", 0.0)
    for met, ns in sorted(got.items(), key=lambda kv: -kv[1]):
        copytree.say(f"idle while {met}: {ns / 1e9:.4f} s "
                     f"({100.0 * ns / idle:.1f}%)")
    copytree.say(
        f"idle {idle / 1e9:.4f} s: {100.0 * (idle - none) / idle:.2f}% under "
        f"a host pass, {100.0 * leaf / idle:.2f}% under none but a leaf of "
        f"the span tree, {100.0 * max(none - leaf, 0.0) / idle:.2f}% unnamed")
    return 100.0 * (idle - none) / idle
