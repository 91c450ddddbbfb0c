"""Shard planning for the LM TrainState — partition rules in, layout out.

Drives the :mod:`mpit_tpu.dplane.partition` engine over the LM's
params+optimizer pytree and lowers the result to the two placement
artifacts the PS stack consumes:

- :meth:`LmPlan.layout` — a **static weighted aligned cut**: one
  contiguous :class:`~mpit_tpu.ps.sharding.Shard` per server, every
  interior boundary on a parameter boundary (or, inside a stacked
  expert leaf, between two experts: :data:`STACKED_LEAVES`), targets
  skewed by per-server weights.  Passed to ``ParamClient(layout=...)`` /
  ``ReaderClient(layout=...)`` it replaces the equal split while
  keeping the whole static feature lattice (chunked streaming, int8
  EF, staleness, agg tree) negotiable — the flagship composition.
- :meth:`LmPlan.shard_map` — the same cut lifted into a versioned
  shardctl ShardMap (via :func:`~mpit_tpu.dplane.partition.plan_shard_map`)
  when placement should migrate; per-shard optimizer slots move with
  their shard because the cut never splits a parameter.

Footprint model: a server holding ``S`` f32 elements under rule ``R``
allocates ``(1 + STATE_SLOTS[R]) * 4 * S`` bytes (params + per-element
optimizer slots; scalar step counters are free) — the accounting that
sizes the gang so params+optimizer state exceed one server's
comfortable footprint (docs/WORKLOADS.md).
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Sequence

from jax.sharding import PartitionSpec as P

from mpit_tpu.dplane.partition import (
    Segment,
    aligned_cut,
    flat_segments,
    match_report,
    plan_shard_map,
)
from mpit_tpu.optim.rules import state_slots

#: Ordered partition rules for the TinyDecoder TrainState (params AND
#: the mirrored optimizer slots: an opt_state path like
#: ``opt_state/DecoderBlock_0/Dense_0/kernel/m`` contains the same
#: component names, so one table covers both).  First match wins; no
#: catch-all tail — an unmatched non-scalar leaf is a loud error, which
#: is the audit surface tests/test_dplane.py exercises.
PARTITION_RULES = [
    # token + position embeddings: shard the vocab/position axis
    (r"Embed_\d+/embedding", P("mdl", None)),
    # attention qkv/out + MLP kernels: shard the output features
    (r"Dense_\d+/kernel", P(None, "mdl")),
    # biases, norms (and the per-leaf scalar step counters of the
    # optimizer slots resolve as scalars before any rule is consulted)
    (r"Dense_\d+/bias", P()),
    (r"LayerNorm_\d+/(scale|bias)", P()),
    # the OLMoE block (models/transformer.py OlmoeDecoder): token table
    # and head by rows / output features, the stacked experts by expert,
    # attention and router matrices by output features, norms whole
    # (an optimizer slot's path ends in the slot's name: /m, /v)
    (r"(^|/)embed(/|$)", P("mdl", None)),
    (r"(^|/)head(/|$)", P(None, "mdl")),
    (r"experts_(gate|up|down)(/|$)", P("mdl", None, None)),
    (r"OlmoeBlock_\d+/(w[qkvo]|router)(/|$)", P(None, "mdl")),
    (r"(attn|mlp|q|k|final)_norm(/|$)", P()),
]

#: Leaves that hold one matrix per expert on their leading axis: the
#: cut may fall between two experts inside them (an expert's weights
#: and its optimizer slots still move as a unit).  One OLMoE layer has
#: three of 134M elements beside two of 103M (table and head): on leaf
#: boundaries alone a two-server cut is lopsided 1.2 to 1.6 times.
STACKED_LEAVES = r"experts_(gate|up|down)$"


def audit_rules(tree: Any, rules=None, *, sep: str = "/") -> Dict[str, int]:
    """:func:`match_report` over ``tree`` with a loud failure if any
    non-scalar leaf is unmatched (report value -2).  Returns the report
    so callers can also assert exactly-once coverage."""
    report = match_report(rules if rules is not None else PARTITION_RULES,
                          tree, sep=sep)
    missing = sorted(name for name, idx in report.items() if idx == -2)
    if missing:
        raise ValueError(
            f"{len(missing)} TrainState leaves match no partition rule: "
            f"{missing[:5]}{' ...' if len(missing) > 5 else ''}")
    return report


class LmPlan(NamedTuple):
    """A computed shard plan over one LM param vector."""

    segments: List[Segment]       # ordered leaf extents of the flat vector
    layout: List[Any]             # one Shard per server (weighted cut)
    plong: int                    # flat vector length
    rule: str                     # server-side optimizer rule
    slots: int                    # vector-shaped state arrays per element
    weights: Optional[List[float]]

    def footprint_bytes(self, i: int) -> int:
        """Bytes server ``i`` holds: its f32 shard + optimizer slots."""
        return self.layout[i].size * 4 * (1 + self.slots)

    def shard_map(self, server_ranks: Sequence[int]):
        """The same cut as a version-0 shardctl ShardMap (placement can
        then migrate; slots move with their shard)."""
        from mpit_tpu.shardctl.shardmap import ShardMap

        return ShardMap.from_shards(self.layout, list(server_ranks))

    def summary(self) -> Dict[str, Any]:
        sizes = [s.size for s in self.layout]
        foot = [self.footprint_bytes(i) for i in range(len(self.layout))]
        return {
            "plong": self.plong,
            "segments": len(self.segments),
            "servers": len(self.layout),
            "rule": self.rule,
            "slots": self.slots,
            "shard_elems": sizes,
            "footprint_mb": [round(b / 2**20, 3) for b in foot],
            "total_footprint_mb": round(sum(foot) / 2**20, 3),
            "weights": self.weights,
        }


def plan(params: Any, n_servers: int, *, rule: str = "add",
         server_weights: Optional[Sequence[float]] = None,
         sep: str = "/") -> LmPlan:
    """Cut the raveled ``params`` into ``n_servers`` aligned shards.

    ``server_weights`` (optional) skews the cut targets — a server with
    twice the weight aims at twice the elements, to the nearest
    parameter boundary.  ``rule`` names the server-side optimizer whose
    per-element slot count prices the footprint; it does not change the
    cut (every element of one vector carries the same rule, so equal
    weights already equalize params+slots — weights exist for
    *heterogeneous server budgets*; the one exception, the vector's
    plain ranges of ``models/flat.py`` ``plain_ranges``, is a few
    hundred elements whose slots the servers allocate and never use)."""
    if n_servers < 1:
        raise ValueError("need at least one server")
    segments = flat_segments(params, sep=sep, stacked=STACKED_LEAVES)
    plong = segments[-1].end
    weights = ([float(w) for w in server_weights]
               if server_weights is not None else None)
    layout = aligned_cut(plong, segments, n_servers, weights=weights)
    return LmPlan(segments=segments, layout=layout, plong=plong,
                  rule=rule, slots=state_slots(rule), weights=weights)


__all__ = [
    "PARTITION_RULES", "STACKED_LEAVES", "LmPlan", "audit_rules", "plan",
    "plan_shard_map",
]
