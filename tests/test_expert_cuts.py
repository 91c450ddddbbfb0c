"""Cuts on expert boundaries (``dplane/partition.py`` ``aligned_cut``,
``lm/plan.py``): a stacked expert leaf may be cut between two experts,
so that the two servers of the one-layer OLMoE model hold shards within
5% of each other; the committed cells' cuts, whose leaves have no expert
axis, are what they were (pinned on the 111m and 1.3b-d4 trees)."""

import hashlib
import json

import jax
import jax.numpy as jnp
import pytest

from mpit_tpu.dplane.partition import Segment, aligned_cut, flat_segments
from mpit_tpu.lm.plan import STACKED_LEAVES, audit_rules, plan
from mpit_tpu.lm.model import train_state_tree
from mpit_tpu.models.transformer import (
    OlmoeDecoder,
    TinyDecoder,
    default_attn,
)

EXPERT = 2048 * 1024  # one expert matrix of OLMoE-1B-7B


def shapes_of(module, seq):
    return jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32)))["params"]


@pytest.fixture(scope="module")
def olmoe_l1():
    return shapes_of(OlmoeDecoder(
        vocab=50304, d_model=2048, n_heads=16, n_layers=1, n_experts=64,
        experts_per_tok=8, expert_width=1024,
        attn_fn=default_attn(use_flash=False)), 16)


def test_two_servers_hold_shards_within_5_percent_cut_between_experts(olmoe_l1):
    result = plan(olmoe_l1, 2, rule="adam")
    a, b = result.summary()["shard_elems"]
    assert a + b == 625_616_896
    assert max(a, b) / min(a, b) < 1.05
    cut = result.layout[1].offset
    inside = next(s for s in result.segments if s.offset < cut < s.end)
    assert inside.unit == EXPERT and inside.name.endswith("experts_up")
    assert (cut - inside.offset) % EXPERT == 0  # an expert's matrix whole
    # on leaf boundaries alone the same tree is lopsided by a third
    leaves = flat_segments(olmoe_l1)
    sizes = [s.size for s in aligned_cut(leaves[-1].end, leaves, 2)]
    assert max(sizes) / min(sizes) > 1.3


@pytest.mark.parametrize("n", [2, 3, 4, 7])
def test_every_cut_is_a_leaf_or_an_expert_boundary(olmoe_l1, n):
    result = plan(olmoe_l1, n)
    sizes = [s.size for s in result.layout]
    assert sum(sizes) == result.plong and min(sizes) > 0
    allowed = {b for s in result.segments for b in s.boundaries()}
    assert all(shard.offset in allowed for shard in result.layout)
    if n <= 3:  # beyond, the table and the head (103M each, whole) bind
        assert max(sizes) / min(sizes) < 1.05


# The committed configurations' trees: (leaves, elements, the two- and
# three-server cuts, a digest of the segment list), recorded on the
# parent of PR 26.
COMMITTED = {
    "cerebras-gpt-111m": (
        dict(vocab=50257, d_model=768, n_heads=12, n_layers=10, max_len=2048),
        105, 149_617_152, [70_848_000, 78_769_152],
        [49_593_600, 59_851_776, 40_171_776], "cb8cc99913b25091"),
    "cerebras-gpt-1.3b-d4": (
        dict(vocab=50257, d_model=2048, n_heads=16, n_layers=4, max_len=2048),
        45, 411_451_392, [201_400_320, 210_051_072],
        [134_264_832, 170_061_824, 107_124_736], "c2a4ceb972b10106"),
}


@pytest.mark.parametrize("name", sorted(COMMITTED))
def test_the_committed_cells_cuts_are_unchanged(name):
    kw, leaves, total, two, three, digest = COMMITTED[name]
    shapes = shapes_of(TinyDecoder(attn_fn=default_attn(use_flash=False),
                                   **kw), kw["max_len"])
    result = plan(shapes, 2, rule="adam")
    assert len(result.segments) == leaves and result.plong == total
    assert all(s.unit == 0 for s in result.segments)  # no expert axis
    listing = json.dumps([(s.name, s.offset, s.size)
                          for s in result.segments]).encode()
    assert hashlib.sha256(listing).hexdigest()[:16] == digest
    assert result.summary()["shard_elems"] == two
    assert plan(shapes, 3).summary()["shard_elems"] == three


def test_a_stacked_segment_offers_its_expert_boundaries():
    segments = [Segment("a", 0, 10), Segment("experts", 10, 40, unit=10),
                Segment("b", 50, 6)]
    assert segments[1].boundaries() == [10, 20, 30, 40]
    assert segments[0].boundaries() == [0]
    cuts = aligned_cut(56, segments, 2)
    assert [(s.offset, s.size) for s in cuts] == [(0, 30), (30, 26)]
    # without the unit the only boundaries are 10 and 50
    plain = [Segment(s.name, s.offset, s.size) for s in segments]
    assert [s.size for s in aligned_cut(56, plain, 2)] == [10, 46]
    # more shards than leaves: allowed where expert boundaries make up
    assert len(aligned_cut(56, segments, 5)) == 5
    with pytest.raises(ValueError, match="cannot align"):
        aligned_cut(56, plain, 5)


def test_flat_segments_marks_stacked_leaves_by_name_only():
    tree = {"block": {"experts_gate": jnp.zeros((4, 3, 2)),
                      "wq": jnp.zeros((4, 3))},
            "experts_norm": jnp.zeros((4,))}
    by_name = {s.name: s for s in flat_segments(tree, stacked=STACKED_LEAVES)}
    assert by_name["block/experts_gate"].unit == 6
    assert by_name["block/wq"].unit == 0
    assert by_name["experts_norm"].unit == 0
    assert all(s.unit == 0 for s in flat_segments(tree))


def test_the_partition_rules_cover_every_leaf_of_the_olmoe_train_state():
    module = OlmoeDecoder(vocab=320, d_model=64, n_heads=4, n_layers=2,
                          n_experts=8, experts_per_tok=2, expert_width=32,
                          attn_fn=default_attn(use_flash=False))
    params = module.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 16), jnp.int32))["params"]
    report = audit_rules(train_state_tree(params, "adam"))
    assert -2 not in report.values()
    assert any(name.endswith("experts_down") for name in report)
