"""Device: the share of the first worker's device idle time in the
traced rounds that no leaf of the program's span tree covers (the
worker's ``round`` phases and client op phases, the server spans joined
to them), with the spans mapped onto the device trace's clock by the
``mpit.round`` anchors.  It measures the tracing itself: idle time the
program cannot name.  A line before the result gives the drift of the
clock offset over the traced rounds."""

from chipbench import reduce as reduce_mod
from chipbench.layers import spantree


def read(run):
    tree = spantree.load(run)
    path = spantree.xplane_path(run)
    if tree is None or path is None:
        return None
    rows = spantree.anchors(path)
    gaps = spantree.device_idle(run)
    if not rows or not gaps:
        return None
    named = reduce_mod.union(
        [(spantree.to_profiler_ns(rows, a), spantree.to_profiler_ns(rows, b))
         for a, b in spantree.leaf_intervals(tree)])
    idle_ns = sum(end - start for start, end in gaps)
    covered = sum(e - s for start, end in gaps
                  for s, e in reduce_mod.clip(named, start, end))
    print(f"chipbench: {len(rows)} mpit.round anchors, clock offset drift "
          f"{spantree.drift_us(rows):.1f} us over them", flush=True)
    return 100.0 * (1.0 - covered / idle_ns)
