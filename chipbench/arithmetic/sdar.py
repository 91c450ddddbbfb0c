"""The arithmetic of the SDAR block as the program builds it
(``mpit_tpu/models/transformer.py`` ``SdarDecoder``): what a
configuration with ``"arithmetic": "sdar"`` needs, from its shapes
alone.

What the algorithm requires of **this chip's share**, never what a
kernel happens to execute.  Every function takes the configuration's
file as a dict and reads the model's own published keys
(``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``num_hidden_layers``, ``num_experts_per_tok``,
``moe_intermediate_size``, ``vocab_size``), the share's (``num_experts``:
the experts held here; ``router_experts``: the router's width, the
published count) and the pass's (``train_seq``: the tokens of a
sequence, ``L``; ``block_length``: ``B``).  The contract of such a
module is in ``chipbench/spec.py``.

**A token is a clean token.**  The block-diffusion pass runs a noised
and a clean copy of every sequence through the layers, ``2 L`` rows for
``L`` tokens counted (``tokens_per_s``, ``mfu_pct`` and
:func:`train_flops_per_token` count those): every product of a layer
is needed twice a token, the head's once (it reads the noised half
alone), and the attention over the ``L (L + B)`` pairs a head that the
mask has (:func:`live_pairs`), not over the ``2 L (2 L + 1) / 2`` of a
causal call on as many rows.

Two Mosaic kernel families: the flash kernels under the scope ``attn``,
counted over the live pairs, and the held experts' grouped products
under ``experts``.  The noise is a few integer passes of XLA's under the
scope ``noise``: no family, no FLOPs of a product.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

F32 = 4  # bytes; parameters, gradients and activations are float32
COPIES = 2  # the rows of a sequence: its noised copy and its clean one


def _attention_params(c: Dict[str, Any]) -> int:
    """wq and wo over all query heads, wk and wv over the KV heads."""
    d, head = c["hidden_size"], c["head_dim"]
    return (2 * d * c["num_attention_heads"] * head
            + 2 * d * c["num_key_value_heads"] * head)


def _expert_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def param_count(c: Dict[str, Any]) -> int:
    """Parameters of the share as the program builds it, all of them
    exchanged: a token table (no position table: rotary), per layer four
    bias-free attention matrices and the two head norms, a router over
    all ``router_experts``, three stacked matrices of the ``num_experts``
    held experts and two RMSNorm weights; a final RMSNorm and an untied
    head."""
    d, v = c["hidden_size"], c["vocab_size"]
    layer = (_attention_params(c) + 2 * c["head_dim"]
             + d * c["router_experts"] + 2 * d
             + c["num_experts"] * _expert_params(c))
    return v * d + c["num_hidden_layers"] * layer + d + d * v


def held_per_token(c: Dict[str, Any]) -> float:
    """Assignments a row sends to held experts under uniform routing."""
    return c["num_experts_per_tok"] * c["num_experts"] / c["router_experts"]


def layer_params_a_row(c: Dict[str, Any]) -> float:
    """Parameters in one row's trained products of one layer: attention,
    the router, the held experts it is expected to use."""
    return (_attention_params(c) + c["hidden_size"] * c["router_experts"]
            + held_per_token(c) * _expert_params(c))


def live_pairs(seq: int, block: int) -> int:
    """(query, key) pairs a head of one sequence's pass: a noised row
    sees its block's ``B`` noised keys and the ``blk B`` clean keys
    before its block, a clean row ``(blk + 1) B`` clean keys; over the
    ``n = L / B`` blocks ``L B + B^2 n (n - 1) / 2 + B^2 n (n + 1) / 2 =
    L (L + B)``."""
    return seq * (seq + block)


def live_tiles(seq: int, block: int, tile: int) -> Tuple[int, int]:
    """``(live, all)`` tiles of ``tile x tile`` of the ``2 L x 2 L``
    square, ``tile`` dividing ``L`` and larger than ``B``: a noised row
    block has its own diagonal tile and the clean tiles up to its own
    (the last one the strict edge), a clean one the clean tiles up to
    its own; the clean-to-noised quadrant has none."""
    n = seq // tile
    return n + n * (n + 1), (COPIES * n) ** 2


def train_flops_per_token(c: Dict[str, Any]) -> float:
    """Forward plus backward FLOPs one **clean** token needs of this
    share, nothing recomputed: 6 a parameter in a trained product, a
    layer's twice (the token's noised and clean row) with the held
    experts at their expectation under uniform routing, the head's once;
    the attention's two products over the live pairs, 3 x 4 x (heads x
    head_dim) x ``L (L + B)`` a sequence a layer.  Look-ups, norms,
    rotary, SiLU, softmax and the noise's integer passes are left out."""
    seq, layers = c["train_seq"], c["num_hidden_layers"]
    width = c["num_attention_heads"] * c["head_dim"]
    attention = 12.0 * width * live_pairs(seq, c["block_length"]) / seq
    trained = (layers * COPIES * layer_params_a_row(c)
               + c["hidden_size"] * c["vocab_size"])
    return 6 * trained + layers * attention


def flash_call_cost(c: Dict[str, Any], batch: int
                    ) -> Dict[str, Tuple[float, float]]:
    """(FLOPs, HBM bytes) of one layer's attention over a batch of whole
    sequences, forward and backward, as the flash algorithm needs them
    over the live pairs with grouped KV heads: 4 x head_dim FLOPs a live
    (query, key) pair forward, 10 backward, over all query heads; q in
    and o out at the query heads' size over the ``2 L`` rows, k and v in
    at the KV heads' (read once: no repeat), the row sums; backward q, o,
    do in and dq out at the query heads' size, k, v in and dk, dv out at
    the KV heads', the row sums.  The mask is geometry: no byte."""
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    head, rows = c["head_dim"], COPIES * c["train_seq"]
    pairs = batch * heads * live_pairs(c["train_seq"], c["block_length"])
    q_size = batch * heads * rows * head * F32
    kv_size = batch * kv * rows * head * F32
    sums = batch * heads * rows * F32
    return {
        "fwd": (4.0 * head * pairs, 2.0 * q_size + 2.0 * kv_size + sums),
        "bwd": (10.0 * head * pairs, 4.0 * q_size + 4.0 * kv_size + sums),
    }


# As ``arithmetic/mellum.py``: the grouped product is a jitted kernel,
# one body a distinct shape however often it is called.
EXPERT_KERNEL_BODIES = 6


def experts_cost(c: Dict[str, Any], batch: int) -> Dict[str, float]:
    """FLOPs and HBM bytes of the held experts of all layers in one
    micro-step **as the block runs them** (``arithmetic/mellum.py``
    ``experts_cost``, whose block this one's sparse branch is): forward,
    the forward again in the backward pass and backward, over the rows
    expected on held experts under uniform routing, of the ``2 L`` a
    sequence."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    rows = batch * COPIES * c["train_seq"] * held_per_token(c)
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
    flash kernels under the block-diffusion mask, FLOPs and bytes of the
    live pairs; ``least_calls`` three a layer: a forward call and the
    two-kernel backward, the schedule the mask takes
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


# SDAR-30B-A3B-Chat's published sizes at the cut of the committed
# configuration (6 layers, 8 of 128 experts, an eighth of the
# vocabulary), for the hand-worked cases only.
SDAR_L6E8 = {
    "hidden_size": 2048, "num_attention_heads": 32,
    "num_key_value_heads": 4, "head_dim": 128, "num_hidden_layers": 6,
    "num_experts": 8, "router_experts": 128, "num_experts_per_tok": 8,
    "moe_intermediate_size": 768, "vocab_size": 18992, "train_seq": 4096,
    "block_length": 4}


def _committed() -> Dict[str, Any]:
    import json
    import pathlib

    path = (pathlib.Path(__file__).resolve().parent.parent / "configs"
            / "sdar-30b-l6e8.json")
    with open(path) as fh:
        return json.load(fh)


def hand_worked() -> List[Tuple[str, Any, Any]]:
    """``(what, got, want)``: each function on sizes worked by hand, and
    the committed file against them."""
    c = SDAR_L6E8
    families = kernels(c, 1)
    experts = experts_cost(c, 1)
    committed = _committed()
    # Live pairs a head of a sequence of 4096 in blocks of 4: noised
    # rows 4096 x 4 own + 16 x (1023 x 1024 / 2) past = 16,384 +
    # 8,380,416; clean rows 16 x (1024 x 1025 / 2) = 8,396,800:
    # 16,793,600 = 4096 x 4100.
    pairs = 16_793_600
    return [
        # A layer: wq, wo 2 x 2048 x 4096 = 16,777,216; wk, wv 2 x 2048
        # x 512 = 2,097,152; head norms 256; router 2048 x 128 =
        # 262,144; norms 4,096; 8 experts x 3 x 2048 x 768 = 37,748,736:
        # 56,889,600.  Six layers 341,337,600; table and head 2 x 18992
        # x 2048 = 77,791,232; final norm 2,048.
        ("parameters of sdar-30b at 6 layers, 8 experts held, an eighth "
         "of the vocabulary", param_count(c), 419_130_880),
        ("assignments a row sends to held experts, uniform routing",
         held_per_token(c), 0.5),
        # attention 18,874,368 + router 262,144 + half an expert
        # 2,359,296
        ("parameters in one row's trained products of a layer",
         layer_params_a_row(c), 21_495_808.0),
        ("live pairs a head of a sequence of 4096 in blocks of 4: L (L + "
         "B)", live_pairs(4096, 4), pairs),
        ("by the parts: the noised rows' own blocks, their clean past, "
         "the clean rows'", 16_384 + 16 * (1023 * 1024 // 2)
         + 16 * (1024 * 1025 // 2), pairs),
        ("a causal call over the 8192 rows would compute twice as many",
         8192 * 8193 // 2, 33_558_528),
        # 8 noised row blocks: a diagonal tile each and 1 + 2 + .. + 8 =
        # 36 clean tiles; 8 clean row blocks: 36
        ("live tiles of 512 at L 4096: 80 of 256", live_tiles(4096, 4, 512),
         (80, 256)),
        ("live tiles of 128 at L 256: 2 + 2 x 3 = 8 of 16",
         live_tiles(256, 4, 128), (8, 16)),
        # 6 x (6 layers x 2 rows x 21,495,808 + the head 38,895,616) = 6
        # x 296,845,312 = 1,781,071,872; attention 12 x 4096 x 4100 =
        # 201,523,200 a layer, six 1,209,139,200.
        ("flops per clean token of the share at L 4096",
         train_flops_per_token(c), 1_781_071_872 + 1_209_139_200.0),
        ("the attn family (six layers) at batch 1: FLOPs, 14 x 128 a "
         "live pair over 32 heads",
         families["attn"]["flops"], 6 * 1792.0 * 32 * pairs),
        # q-sized 32 x 8192 x 128 x 4 B = 134,217,728; kv-sized an
        # eighth of it, 16,777,216; row sums 32 x 8192 x 4 B =
        # 1,048,576.  Forward 2 q + 2 kv + sums, backward 4 q + 4 kv +
        # sums; six layers.
        ("the attn family: bytes", families["attn"]["bytes"],
         6 * (6.0 * 134_217_728 + 6.0 * 16_777_216 + 2.0 * 1_048_576)),
        ("calls: attn 18 (forward, dq, dk/dv a layer), experts 6 bodies",
         [families[f]["least_calls"] for f in ("attn", "experts")], [18, 6]),
        ("the noise is no Mosaic family", sorted(families),
         ["attn", "experts"]),
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
         param_count(committed), 419_130_880),
        ("the committed file's pass gives the hand-worked pairs",
         live_pairs(committed["train_seq"], committed["block_length"]),
         pairs),
        ("every row masks (B + 1) / (2 B) of its positions: 62.5% at 4",
         (committed["block_length"] + 1) / (2 * committed["block_length"]),
         0.625),
        ("the mask id is the slice's last row",
         committed["mask_token_id"], committed["vocab_size"] - 1),
        ("the tiny size's mask id is its table's last row",
         committed["tiny"]["mask_token_id"],
         committed["tiny"]["vocab_size"] - 1),
    ]
