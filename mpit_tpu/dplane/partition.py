"""Regex -> PartitionSpec rule engine over parameter pytrees.

The fmengine-style ``match_partition_rules`` shape (SNIPPETS [3]): a
rule table is an ordered sequence of ``(pattern, PartitionSpec)`` pairs;
each leaf's ``/``-joined tree path is matched with ``re.search`` and the
**first** matching rule wins, so every leaf resolves to exactly one
spec.  Two hard invariants, property-tested in tests/test_dplane.py:

- scalar leaves (0-d, or single-element) are never partitioned — they
  resolve to ``PartitionSpec()`` without consuming a rule;
- a non-scalar leaf no rule matches is a loud ``ValueError`` naming the
  leaf (or, opt-in, replicates) — silence here would place a tensor
  wrong and surface as a shape error three layers away.

On top of the per-leaf specs sits the **flat-vector layer** that
subsumes shardctl's weighted cuts as the intra-host story: trainers ship
a single raveled vector (``ravel_pytree``), and the PS cut of that
vector should fall on *parameter boundaries*, not arbitrary offsets —
a shard that splits a weight matrix splits its quantization blocks and
its optimizer-state locality with it.  :func:`flat_segments` renders the
pytree as an ordered segment table, :func:`aligned_cut` cuts the vector
at segment boundaries as close to balanced as the boundaries allow (a
*stacked* leaf, one matrix per expert on its leading axis, may also be
cut between two experts: an expert's weights and their optimizer slots
still move as a unit), and
:func:`plan_shard_map` lifts that cut into a versioned
:class:`~mpit_tpu.shardctl.shardmap.ShardMap` — the layout source for
shardctl gangs (``ParamClient(shard_map=...)``).
"""

from __future__ import annotations

import re
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


def _key_str(key: Any) -> str:
    """Render one tree-path key the way rule authors write them."""
    for attr in ("key", "idx", "name"):
        if hasattr(key, attr):
            return str(getattr(key, attr))
    return str(key)


def tree_path_names(tree: Any, sep: str = "/") -> List[str]:
    """The ``sep``-joined path name of every leaf, in tree-leaves order
    (= the ravel_pytree order the flat PS vector uses)."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [sep.join(_key_str(k) for k in path) for path, _ in leaves]


def named_tree_map(fn: Callable[[str, Any], Any], tree: Any,
                   sep: str = "/") -> Any:
    """``tree_map`` whose function also receives the leaf's path name."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = [fn(sep.join(_key_str(k) for k in path), leaf)
           for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, out)


def _is_scalar(leaf: Any) -> bool:
    shape = np.shape(leaf)
    return len(shape) == 0 or int(np.prod(shape)) == 1


def match_partition_rules(rules: Sequence[Tuple[str, PartitionSpec]],
                          tree: Any, *, sep: str = "/",
                          on_unmatched: str = "raise") -> Any:
    """A pytree of ``PartitionSpec``, one per leaf of ``tree``.

    ``rules`` is ordered; the first pattern ``re.search``-matching the
    leaf's path name wins.  Scalars always resolve to ``P()``.
    ``on_unmatched``: ``"raise"`` (default) or ``"replicate"``.
    """
    if on_unmatched not in ("raise", "replicate"):
        raise ValueError(
            f"on_unmatched must be 'raise' or 'replicate', got "
            f"{on_unmatched!r}")

    def pick(name: str, leaf: Any) -> PartitionSpec:
        if _is_scalar(leaf):
            return PartitionSpec()
        for pattern, spec in rules:
            if re.search(pattern, name) is not None:
                return spec
        if on_unmatched == "replicate":
            return PartitionSpec()
        raise ValueError(
            f"no partition rule matches leaf {name!r} "
            f"(shape {np.shape(leaf)}); add a rule or a catch-all "
            "('.*', P()) tail")

    return named_tree_map(pick, tree, sep=sep)


def match_report(rules: Sequence[Tuple[str, PartitionSpec]], tree: Any,
                 *, sep: str = "/") -> Dict[str, int]:
    """Which rule index claimed each leaf: ``{leaf name: rule index}``,
    with ``-1`` for scalar leaves (never partitioned) and ``-2`` for
    unmatched ones.  The audit surface behind the engine: a leaf appears
    exactly once (tree paths are unique), and tests assert every
    non-scalar leaf resolved to exactly one live rule."""
    report: Dict[str, int] = {}

    def pick(name: str, leaf: Any) -> int:
        if _is_scalar(leaf):
            idx = -1
        else:
            idx = -2
            for i, (pattern, _spec) in enumerate(rules):
                if re.search(pattern, name) is not None:
                    idx = i
                    break
        report[name] = idx
        return idx

    named_tree_map(pick, tree, sep=sep)
    return report


def _spec_axes(spec: PartitionSpec):
    """Per-dimension tuples of mesh axis names (PartitionSpec entries
    may be a name, a tuple of names, or None)."""
    out = []
    for entry in spec:
        if entry is None:
            out.append(())
        elif isinstance(entry, (tuple, list)):
            out.append(tuple(entry))
        else:
            out.append((entry,))
    return out


def validate_spec(mesh: Mesh, spec: PartitionSpec, shape: Tuple[int, ...],
                  name: str = "<leaf>") -> None:
    """Loudly reject a spec the mesh cannot realize for ``shape``: an
    unknown axis name, more partitioned dims than the leaf has, or a dim
    not divisible by its axis-size product."""
    axes = _spec_axes(spec)
    if len(axes) > len(shape):
        raise ValueError(
            f"spec {spec} for {name!r} names {len(axes)} dims but the "
            f"leaf has shape {shape}")
    seen: set = set()
    for dim, dim_axes in enumerate(axes):
        factor = 1
        for ax in dim_axes:
            if ax not in mesh.shape:
                raise ValueError(
                    f"spec {spec} for {name!r} uses axis {ax!r} not in "
                    f"mesh axes {tuple(mesh.shape)}")
            if ax in seen:
                raise ValueError(
                    f"spec {spec} for {name!r} repeats mesh axis {ax!r}")
            seen.add(ax)
            factor *= mesh.shape[ax]
        if factor > 1 and shape[dim] % factor:
            raise ValueError(
                f"dim {dim} of {name!r} (shape {shape}) is not divisible "
                f"by mesh factor {factor} for spec {spec}")


def tree_shardings(mesh: Mesh, specs: Any, tree: Optional[Any] = None,
                   *, sep: str = "/", naive_fallback: bool = False) -> Any:
    """Lift a spec pytree into ``NamedSharding``s on ``mesh``.

    With ``tree`` given, every spec is validated against its leaf's
    shape; ``naive_fallback=True`` degrades an indivisible dim to
    unpartitioned (the SNIPPETS [2] naive-sharding behavior) instead of
    raising — axis-name errors always raise."""
    if tree is None:
        return jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, spec), specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec))

    names = iter(tree_path_names(tree, sep=sep))

    def lift(spec: PartitionSpec, leaf: Any) -> NamedSharding:
        name = next(names)
        shape = np.shape(leaf)
        if naive_fallback:
            entries = []
            for dim, dim_axes in enumerate(_spec_axes(spec)):
                factor = 1
                for ax in dim_axes:
                    if ax not in mesh.shape:
                        raise ValueError(
                            f"spec {spec} for {name!r} uses axis {ax!r} "
                            f"not in mesh axes {tuple(mesh.shape)}")
                    factor *= mesh.shape[ax]
                ok = factor == 1 or (dim < len(shape)
                                     and shape[dim] % factor == 0)
                entries.append(spec[dim] if ok else None)
            spec = PartitionSpec(*entries)
        validate_spec(mesh, spec, shape, name)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map(
        lift, specs, tree, is_leaf=lambda x: isinstance(x, PartitionSpec))


def shard_tree(tree: Any, shardings: Any) -> Any:
    """``device_put`` every leaf with its sharding (host -> HBM)."""
    return jax.tree_util.tree_map(jax.device_put, tree, shardings)


# ---------------------------------------------------------------------------
# flat-vector layer: segment tables + boundary-aligned cuts
# ---------------------------------------------------------------------------


class Segment(NamedTuple):
    """One leaf's extent inside the raveled flat vector.  ``unit`` > 0
    marks a stacked leaf that may be cut at every multiple of ``unit``
    elements from its offset (one expert's matrix); 0 is a leaf that is
    never split."""

    name: str
    offset: int
    size: int
    unit: int = 0

    @property
    def end(self) -> int:
        return self.offset + self.size

    def boundaries(self) -> List[int]:
        """Where a cut may fall from this segment's begin up to, not
        including, its end."""
        return list(range(self.offset, self.end, self.unit or self.size))


def flat_segments(tree: Any, sep: str = "/",
                  stacked: Optional[str] = None) -> List[Segment]:
    """The ordered segment table of ``ravel_pytree(tree)``: one entry
    per leaf, contiguous, in tree-leaves order (the order ravel uses).
    ``stacked`` is a pattern (``re.search`` on the leaf's path name) of
    the leaves that hold one matrix per expert on their leading axis:
    such a segment gets ``unit`` = the elements of one expert's matrix
    and may be cut between experts."""
    segments: List[Segment] = []
    offset = 0
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat:
        shape = np.shape(leaf)
        size = int(np.prod(shape)) if shape else 1
        name = sep.join(_key_str(k) for k in path)
        unit = 0
        if stacked and len(shape) > 1 and re.search(stacked, name):
            unit = size // shape[0]
        segments.append(Segment(name, offset, size, unit))
        offset += size
    return segments


def aligned_cut(plong: int, segments: Sequence[Segment], n: int,
                weights: Optional[Sequence[float]] = None):
    """Cut ``[0, plong)`` into ``n`` contiguous shards whose interior
    boundaries fall on segment boundaries (or, inside a stacked segment,
    on a multiple of its ``unit``: between two experts), each as close
    to the equal cut ``i*plong/n`` as the boundaries allow.

    ``weights`` (optional, one positive number per shard) replaces the
    equal targets with cumulative-fraction targets
    ``sum(weights[:i]) / sum(weights) * plong`` — the aligned-cut
    counterpart of :func:`mpit_tpu.ps.sharding.weighted_layout`.  Shard
    ``i`` lands as close to ``weights[i] / sum(weights)`` of the vector
    as the parameter boundaries allow; the :mod:`mpit_tpu.lm` plan uses
    this to equalize *bytes held per server* (params + optimizer slots)
    when server budgets differ.

    Invariants (property-tested): shards tile ``[0, plong)``, every
    shard is nonempty, every interior cut is some segment's offset (or
    an expert boundary of a stacked one), and the result is a pure
    function of its arguments.  Raises when fewer boundaries than cuts
    exist — an element-level cut would split a
    parameter, which is exactly what alignment is for (fall back to
    :func:`mpit_tpu.ps.sharding.shard_layout` deliberately instead).
    """
    from mpit_tpu.ps.sharding import Shard

    if n < 1:
        raise ValueError("need at least one shard")
    if weights is not None:
        w = [float(x) for x in weights]
        if len(w) != n:
            raise ValueError(f"weights has {len(w)} entries for {n} shards")
        if any(x <= 0 for x in w):
            raise ValueError("weights must be positive")
        total = sum(w)
        targets = []
        acc = 0.0
        for x in w[:-1]:
            acc += x
            targets.append(acc / total * plong)
    else:
        targets = [i * plong / n for i in range(1, n)]
    segs = sorted(segments, key=lambda s: s.offset)
    pos = 0
    for s in segs:
        if s.offset != pos or s.size <= 0:
            raise ValueError(
                f"segments must tile [0, plong) contiguously; {s.name!r} "
                f"covers [{s.offset}, {s.end}) but {pos} elements are "
                "assigned so far")
        pos = s.end
    if pos != plong:
        raise ValueError(f"segments cover {pos} of {plong} elements")
    # interior candidates: every segment's begin, and inside a stacked
    # segment every expert's begin
    boundaries = [b for s in segs for b in s.boundaries()][1:]
    if len(boundaries) < n - 1:
        raise ValueError(
            f"cannot align {n} shards on {len(segs)} segments — an "
            "aligned cut never splits a parameter (use shard_layout for "
            "element-level cuts)")
    cuts: List[int] = []
    lo = 0
    for i in range(1, n):
        target = targets[i - 1]
        # Leave enough boundaries for the remaining n-1-i cuts.
        hi = len(boundaries) - (n - 1 - i)
        window = boundaries[lo:hi]
        best = min(range(len(window)),
                   key=lambda j: (abs(window[j] - target), window[j]))
        cuts.append(window[best])
        lo += best + 1
    edges = [0] + cuts + [plong]
    return [Shard(edges[i], edges[i + 1] - edges[i]) for i in range(n)]


def plan_shard_map(tree: Any, server_ranks: Sequence[int], *,
                   sep: str = "/", shards_per_server: int = 1,
                   weights: Optional[Sequence[float]] = None,
                   stacked: Optional[str] = None):
    """A version-0 :class:`~mpit_tpu.shardctl.shardmap.ShardMap` whose
    cut is segment-aligned — the partition engine acting as shardctl's
    layout source.  ``shards_per_server`` over-partitions (the §9.1
    elasticity units) while keeping every cut on a parameter boundary.
    ``weights`` (one per server) skews the cut targets; a server's
    weight is spread evenly over its ``shards_per_server`` shards.
    Pass the result to ``ParamClient(shard_map=...)``."""
    from mpit_tpu.shardctl.shardmap import ShardMap

    ranks = list(server_ranks)
    if not ranks:
        raise ValueError("need at least one server rank")
    k = max(int(shards_per_server), 1)
    segments = flat_segments(tree, sep=sep, stacked=stacked)
    plong = segments[-1].end
    cut_weights = None
    if weights is not None:
        if len(weights) != len(ranks):
            raise ValueError(
                f"weights has {len(list(weights))} entries for "
                f"{len(ranks)} servers")
        cut_weights = [float(w) / k for w in weights for _ in range(k)]
    shards = aligned_cut(plong, segments, len(ranks) * k,
                         weights=cut_weights)
    owners = [r for r in ranks for _ in range(k)]
    return ShardMap.from_shards(shards, owners)
