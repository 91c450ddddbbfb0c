"""The arithmetic of the Keye block as the program builds it
(``mpit_tpu/models/transformer.py`` ``KeyeDecoder``): what a
configuration with ``"arithmetic": "keye"`` needs, from its shapes
alone.

What the algorithm requires of **this chip's share**, never what a
kernel happens to execute.  Every function takes the configuration's
file as a dict and reads the model's own published keys
(``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``num_hidden_layers``, ``sa_config``,
``num_experts_per_tok``, ``moe_intermediate_size``, ``vocab_size``) and
the share's (``num_experts``: the experts held here; ``router_experts``:
the router's width, the published count; ``train_seq``: the sequence the
cell trains at).  The contract of such a module is in
``chipbench/spec.py``.

Two Mosaic kernel families: the selected attention's flash kernels under
the scope ``attn`` and the held experts' grouped products under
``experts``.  The attention is counted **over the selected pairs**
(query ``t`` sees ``min(t + 1, topk)`` keys): what the algorithm needs,
not what a masked kernel does when it visits every causal tile, so that
a later implementation that skips or gathers is read on the same
yardstick.  The indexer (the scores of every causal pair and the exact
top-k a row) is XLA's products and fusions under the scope ``index``,
no Mosaic kernel, so it is no family of ``kernels``;
:func:`index_cost` counts it for ``layers/dsa_index_roofline.py``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

F32 = 4  # bytes; parameters, gradients and activations are float32


def _attention_params(c: Dict[str, Any]) -> int:
    """wq and wo over all query heads, wk and wv over the KV heads."""
    d, head = c["hidden_size"], c["head_dim"]
    return (2 * d * c["num_attention_heads"] * head
            + 2 * d * c["num_key_value_heads"] * head)


def _index_products(c: Dict[str, Any]) -> int:
    """The indexer's three matrices: the heads' queries, the one key
    head, a weight a head."""
    sa = c["sa_config"]
    heads, head = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return c["hidden_size"] * (heads * head + head + heads)


def _expert_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def param_count(c: Dict[str, Any]) -> int:
    """Parameters of the share as the program builds it, all of them
    exchanged: a token table (no position table: rotary), per layer four
    bias-free attention matrices and the two head norms, the indexer's
    three matrices and its LayerNorm's weight and bias, a router over
    all ``router_experts``, three stacked matrices of the ``num_experts``
    held experts and two RMSNorm weights; a final RMSNorm and an untied
    head."""
    d, v = c["hidden_size"], c["vocab_size"]
    layer = (_attention_params(c) + 2 * c["head_dim"]
             + _index_products(c) + 2 * c["sa_config"]["indexer_head_dim"]
             + d * c["router_experts"] + 2 * d
             + c["num_experts"] * _expert_params(c))
    return v * d + c["num_hidden_layers"] * layer + d + d * v


def held_per_token(c: Dict[str, Any]) -> float:
    """Assignments a token sends to held experts under uniform routing."""
    return c["num_experts_per_tok"] * c["num_experts"] / c["router_experts"]


def active_param_count(c: Dict[str, Any]) -> float:
    """Parameters in one token's **trained** products on this chip:
    attention, the router, the held experts it is expected to use, the
    head (the table is a look-up, the norms are not products, the
    indexer's matrices have no backward pass: :func:`train_flops_per_token`
    counts them apart)."""
    d = c["hidden_size"]
    layer = (_attention_params(c) + d * c["router_experts"]
             + held_per_token(c) * _expert_params(c))
    return c["num_hidden_layers"] * layer + d * c["vocab_size"]


def selected_pairs(seq: int, topk: int) -> int:
    """(query, key) pairs of one sequence's selection: ``sum_t min(t +
    1, topk)``."""
    dense = min(seq, topk)
    return dense * (dense + 1) // 2 + (seq - dense) * topk


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def train_flops_per_token(c: Dict[str, Any]) -> float:
    """Forward plus backward FLOPs one trained token needs of this
    share, nothing recomputed: 6 a parameter in a trained product (the
    held experts at their expectation under uniform routing); the
    attention's two products over the **selected** pairs a query sees,
    3 x 4 x (heads x head_dim) x pairs a layer; and the indexer, which
    has a forward pass only: 2 a parameter of its three matrices and 2 x
    heads x head_dim a **causal** pair for its scores.  Look-ups, norms,
    rotary, SiLU, softmax, the selection's compares, sort and gathers
    are left out."""
    sa, seq, layers = c["sa_config"], c["train_seq"], c["num_hidden_layers"]
    width = c["num_attention_heads"] * c["head_dim"]
    attention = 12.0 * width * selected_pairs(seq, sa["topk"]) / seq
    scores = (2.0 * sa["indexer_num_heads"] * sa["indexer_head_dim"]
              * causal_pairs(seq) / seq)
    return (6 * active_param_count(c)
            + layers * (attention + 2 * _index_products(c) + scores))


def select_words(keys: int) -> int:
    """int32 words a row's selection of ``keys`` keys takes as bits: 128
    a 4096 keys (``mpit_tpu/ops/select_bits.py`` ``words_of``, which a
    test holds this to)."""
    return -(-keys // 4096) * 128


def _select_bytes(c: Dict[str, Any], batch: int) -> float:
    """One layer's selection as bits."""
    seq = c["train_seq"]
    return batch * seq * select_words(seq) * 4.0


def flash_call_cost(c: Dict[str, Any], batch: int
                    ) -> Dict[str, Tuple[float, float]]:
    """(FLOPs, HBM bytes) of one layer's attention over a batch of whole
    sequences, forward and backward, as the flash algorithm needs them
    over the selected pairs with grouped KV heads: 4 x head_dim FLOPs a
    selected (query, key) pair forward, 10 backward, over all query
    heads; q in and o out at the query heads' size, k and v in at the KV
    heads' (read once: no repeat), the row sums and the selection's bits;
    backward q, o, do in and dq out at the query heads' size, k, v in
    and dk, dv out at the KV heads', the row sums and the bits."""
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    head, seq = c["head_dim"], c["train_seq"]
    pairs = batch * heads * selected_pairs(seq, c["sa_config"]["topk"])
    q_size = batch * heads * seq * head * F32
    kv_size = batch * kv * seq * head * F32
    rows = batch * heads * seq * F32 + _select_bytes(c, batch)
    return {
        "fwd": (4.0 * head * pairs, 2.0 * q_size + 2.0 * kv_size + rows),
        "bwd": (10.0 * head * pairs, 4.0 * q_size + 4.0 * kv_size + rows),
    }


def index_cost(c: Dict[str, Any], batch: int) -> Dict[str, float]:
    """FLOPs and HBM bytes of the indexer of all layers in one
    micro-step, **everything the scope ``index`` times that is a
    product**, forward only (it has no backward pass and is not computed
    again): 2 a parameter of its three matrices a token and 2 x heads x
    head_dim a causal pair for its scores
    (:func:`train_flops_per_token`'s two indexer terms); the layer's
    normed input and the three matrices read, the heads' queries, the
    one key head and the weights written and read back by the scores,
    and the selection's bits written, once.  The LayerNorm, the
    rotations and the selection's own compares are not FLOPs of a
    product and are left out: a share of this count is what the
    indexer's products would take at the chip's peak."""
    sa, seq, layers = c["sa_config"], c["train_seq"], c["num_hidden_layers"]
    heads, head = sa["indexer_num_heads"], sa["indexer_head_dim"]
    tokens = batch * seq
    made = tokens * (heads * head + head + heads) * F32
    read = tokens * c["hidden_size"] * F32 + _index_products(c) * F32
    return {
        "flops": layers * (2.0 * tokens * _index_products(c)
                           + 2.0 * heads * head * batch * causal_pairs(seq)),
        "bytes": layers * (read + 2.0 * made + _select_bytes(c, batch)),
        "layers": layers,
    }


# As ``arithmetic/mellum.py``: the grouped product is a jitted kernel,
# one body a distinct shape however often it is called.
EXPERT_KERNEL_BODIES = 6


def experts_cost(c: Dict[str, Any], batch: int) -> Dict[str, float]:
    """FLOPs and HBM bytes of the held experts of all layers in one
    micro-step **as the block runs them** (``arithmetic/mellum.py``
    ``experts_cost``, whose block this one's sparse branch is): forward,
    the forward again in the backward pass and backward, over the rows
    expected on held experts under uniform routing."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    rows = batch * c["train_seq"] * held_per_token(c)
    weights = c["num_experts"] * _expert_params(c) * F32
    rows_bytes = c["num_hidden_layers"] * 6.0 * rows * d * F32
    return {
        "flops": c["num_hidden_layers"] * 24.0 * rows * d * f,
        "bytes": c["num_hidden_layers"] * 4.0 * weights + rows_bytes,
        # the part of the bytes that scales with the routing
        # (layers/held_experts_roofline.py)
        "rows_bytes": rows_bytes,
    }


def kernels(c: Dict[str, Any], batch: int) -> Dict[str, Dict[str, Any]]:
    """The block's Mosaic kernel families by model scope.  ``attn``: the
    selected attention, FLOPs and bytes of the selected pairs;
    ``least_calls`` three a layer: a forward call and the two-kernel
    backward, the only schedule under a selection
    (``ops/flash_attention.py`` ``_use_fused_bwd``).  ``experts``: the
    grouped products, FLOPs and bytes of :func:`experts_cost`;
    ``least_calls`` the six kernel bodies."""
    layers = c["num_hidden_layers"]
    cost = flash_call_cost(c, batch)
    experts = experts_cost(c, batch)
    return {
        "attn": {
            "scope": "attn",
            "flops": layers * (cost["fwd"][0] + cost["bwd"][0]),
            "bytes": layers * (cost["fwd"][1] + cost["bwd"][1]),
            "least_calls": 3 * layers,
        },
        "experts": {
            "scope": "experts",
            "flops": experts["flops"],
            "bytes": experts["bytes"],
            "least_calls": EXPERT_KERNEL_BODIES,
        },
    }


# Keye-VL-2.0-30B-A3B's published sizes at the cut of the committed
# configuration (6 layers, 8 of 128 experts, an eighth of the
# vocabulary), for the hand-worked cases only.
KEYE_L6E8 = {
    "hidden_size": 2048, "num_attention_heads": 32,
    "num_key_value_heads": 4, "head_dim": 128, "num_hidden_layers": 6,
    "sa_config": {"indexer_num_heads": 16, "indexer_head_dim": 64,
                  "indexer_num_kv_heads": 1, "topk": 2048},
    "num_experts": 8, "router_experts": 128, "num_experts_per_tok": 8,
    "moe_intermediate_size": 768, "vocab_size": 18992, "train_seq": 8192}


def _committed() -> Dict[str, Any]:
    import json
    import pathlib

    path = (pathlib.Path(__file__).resolve().parent.parent / "configs"
            / "keye-vl2-30b-l6e8.json")
    with open(path) as fh:
        return json.load(fh)


def hand_worked() -> List[Tuple[str, Any, Any]]:
    """``(what, got, want)``: each function on sizes worked by hand, and
    the committed file's flattened indexer keys against its nested
    group."""
    c = KEYE_L6E8
    families = kernels(c, 1)
    experts = experts_cost(c, 1)
    index = index_cost(c, 1)
    committed = _committed()
    sa = committed["sa_config"]
    # Selected pairs a sequence: 2048 x 2049 / 2 = 2,098,176 for the
    # first 2048 queries, then 6144 x 2048 = 12,582,912: 14,681,088.
    # Causal: 8192 x 8193 / 2 = 33,558,528.
    chosen, causal = 14_681_088, 33_558_528
    return [
        # A layer: wq, wo 2 x 2048 x 4096 = 16,777,216; wk, wv 2 x 2048
        # x 512 = 2,097,152; head norms 256; the indexer 2048 x (1024 +
        # 64 + 16) = 2,260,992 and its LayerNorm 128; router 2048 x 128
        # = 262,144; norms 4,096; 8 experts x 3 x 2048 x 768 =
        # 37,748,736: 59,150,720.  Six layers 354,904,320; table and
        # head 2 x 18992 x 2048 = 77,791,232; final norm 2,048.
        ("parameters of keye-vl2-30b at 6 layers, 8 experts held, an "
         "eighth of the vocabulary", param_count(c), 432_697_600),
        ("assignments a token sends to held experts, uniform routing",
         held_per_token(c), 0.5),
        # A layer: attention 18,874,368 + router 262,144 + half an
        # expert 2,359,296 = 21,495,808; six 128,974,848; head
        # 38,895,616.
        ("parameters in one token's trained products on this chip",
         active_param_count(c), 167_870_464.0),
        ("selected pairs of a sequence of 8192 at topk 2048",
         selected_pairs(8192, 2048), chosen),
        ("under topk every causal pair is selected",
         selected_pairs(2048, 2048), causal_pairs(2048)),
        ("the share of causal pairs kept at 8192 (dsa_kept_pct), to two "
         "places", round(100.0 * chosen / causal, 2), 43.75),
        # 6 x 167,870,464 = 1,007,222,784; a layer: attention 12 x 4096
        # x 1792.125 = 88,086,528, the indexer's products 2 x 2,260,992
        # = 4,521,984, its scores 2048 x 4096.5 = 8,389,632: 100,998,144;
        # six layers 605,988,864.
        ("flops per token of the share at sequence 8192",
         train_flops_per_token(c), 1_007_222_784 + 605_988_864.0),
        ("the attn family (six layers) at batch 1: FLOPs, 14 x 128 a "
         "selected pair over 32 heads",
         families["attn"]["flops"], 6 * 1792.0 * 32 * chosen),
        # q-sized 32 x 8192 x 128 x 4 B = 134,217,728; kv-sized an
        # eighth of it, 16,777,216; row sums 32 x 8192 x 4 B = 1,048,576
        # and the bits 8192 x 256 x 4 B = 8,388,608.  Forward 2 q + 2 kv
        # + rows + bits, backward 4 q + 4 kv + rows + bits; six layers.
        ("the attn family: bytes", families["attn"]["bytes"],
         6 * (6.0 * 134_217_728 + 6.0 * 16_777_216
              + 2.0 * (1_048_576 + 8_388_608))),
        ("calls: attn 18 (forward, dq, dk/dv a layer), experts 6 bodies",
         [families[f]["least_calls"] for f in ("attn", "experts")], [18, 6]),
        ("the indexer is no Mosaic family", sorted(families),
         ["attn", "experts"]),
        # The three products 2 x 2,260,992 a token over 8192 tokens =
        # 37,044,092,928; the scores 2 x 16 x 64 = 2048 a causal pair =
        # 68,727,865,344; six layers.
        ("the indexer's products and scores: FLOPs a micro-step at batch "
         "1", index["flops"], 6 * (37_044_092_928.0 + 2048.0 * causal)),
        ("a token's indexer FLOPs are train_flops_per_token's two terms",
         index["flops"] / 8192, 6 * (4_521_984 + 8_389_632.0)),
        # The input 8192 x 2048 x 4 B = 67,108,864 and the matrices
        # 2,260,992 x 4 B = 9,043,968 read; qI 8192 x 1024 x 4 B =
        # 33,554,432, kI 8192 x 64 x 4 B = 2,097,152, w 8192 x 16 x 4 B
        # = 524,288 written and read; the bits 8,388,608 written.
        ("the indexer's bytes a micro-step at batch 1", index["bytes"],
         6 * (67_108_864.0 + 9_043_968
              + 2 * (33_554_432 + 2_097_152 + 524_288) + 8_388_608)),
        ("a row of 8192 keys is 256 words of bits, one of 4097 too",
         [select_words(8192), select_words(4097), select_words(4096)],
         [256, 256, 128]),
        # Rows 8192 x 0.5; 24 x 4096 x 2048 x 768 a layer, six layers.
        ("the held experts' FLOPs a micro-step at batch 1, the forward "
         "pass counted twice", experts["flops"],
         6 * 24.0 * 4096 * 1_572_864),
        # Weights 8 x 4,718,592 x 4 B = 150,994,944 B, four times; rows
        # 4096 x 2048 x 4 B = 33,554,432 B, six times; six layers.
        ("the held experts' bytes a micro-step at batch 1",
         experts["bytes"], 6 * (4.0 * 150_994_944 + 6.0 * 33_554_432)),
        ("the experts family's FLOPs are the experts' cost",
         families["experts"]["flops"], experts["flops"]),
        ("the committed file's sizes give the hand-worked count",
         param_count(committed), 432_697_600),
        ("the committed file's flattened indexer keys are its nested "
         "group's",
         [committed["index_heads"], committed["index_head_dim"],
          committed["index_topk"]],
         [sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]]),
        ("the tiny size's flattened indexer keys are its nested group's",
         [committed["tiny"][key] for key in (
             "index_heads", "index_head_dim", "index_topk")],
         [committed["tiny"]["sa_config"][key] for key in (
             "indexer_num_heads", "indexer_head_dim", "topk")]),
        ("one key head", sa["indexer_num_kv_heads"], 1),
    ]
