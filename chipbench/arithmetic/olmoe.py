"""The arithmetic of the OLMoE block as the program builds it
(``mpit_tpu/models/transformer.py`` ``OlmoeDecoder``): what a
configuration with ``"arithmetic": "olmoe"`` needs, from its shapes
alone.

What the algorithm requires, never what a kernel happens to execute.
Every function takes the configuration's file as a dict and reads
OLMoE's own published keys (``hidden_size``, ``num_attention_heads``,
``num_hidden_layers``, ``num_experts``, ``num_experts_per_tok``,
``intermediate_size`` (the width of one expert), ``vocab_size``,
``max_position_embeddings`` (the sequence the cells train at)).  The
contract of such a module is in ``chipbench/spec.py``.

Two Mosaic kernel families: flash attention under the scope ``attn``
and the experts' grouped products under ``experts`` (the Pallas
megablox kernels of ``parallel/moe.py`` ``pallas_grouped_dot``, 1.5
times ``jax.lax.ragged_dot`` on the v5e: PERF.md section 6, PR 26).  A
step that lost either family's calls, because the program quietly took
XLA's product, is not ``correct``; the experts' cost
(``experts_cost``) is held against the device time of that family's
calls (``chipbench/layers/experts_roofline.py``).
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

F32 = 4  # bytes; parameters, gradients and activations are float32


def _sizes(c: Dict[str, Any]) -> Tuple[int, int, int, int, int, int]:
    return (c["hidden_size"], c["vocab_size"], c["num_experts"],
            c["num_experts_per_tok"], c["intermediate_size"],
            c["num_hidden_layers"])


def param_count(c: Dict[str, Any]) -> int:
    """Parameters of the block as the program builds it, all of them
    exchanged: a token table (no position table: rotary), per layer four
    bias-free attention matrices, a router, three stacked expert
    matrices and four RMSNorm weights (input, post-attention, query,
    key); a final RMSNorm and an untied head."""
    d, v, e, _k, f, layers = _sizes(c)
    layer = 4 * d * d + d * e + 3 * e * d * f + 4 * d
    return v * d + layers * layer + d + d * v


def active_param_count(c: Dict[str, Any]) -> int:
    """Parameters in one token's products: attention, router, its
    ``num_experts_per_tok`` experts, the head (the table is a look-up,
    the norms are not products)."""
    d, v, e, k, f, layers = _sizes(c)
    return layers * (4 * d * d + d * e + 3 * k * d * f) + d * v


def train_flops_per_token(c: Dict[str, Any]) -> float:
    """Forward plus backward FLOPs one trained token needs, nothing
    recomputed: 2 FLOPs a multiply-add, backward twice the forward, so 6
    a parameter in a product: the active ones (``num_experts_per_tok``
    experts of ``num_experts``, the router over all of them).  Causal
    attention at sequence L as in ``gpt2.py``: 3 x 4 x d x (L+1)/2 a
    layer.  Look-ups, norms, rotary, SiLU, softmax, sort and gathers are
    left out."""
    d, seq = c["hidden_size"], c["max_position_embeddings"]
    attention = 12 * d * (seq + 1) / 2
    return 6 * active_param_count(c) + c["num_hidden_layers"] * attention


def flash_call_cost(c: Dict[str, Any], batch: int) -> Dict[str, Tuple[float, float]]:
    """(FLOPs, HBM bytes) of one layer's attention over a batch of whole
    sequences, forward and backward, as the flash algorithm needs them:
    the same counts as ``gpt2.py`` ``flash_call_cost`` (4 x d_head FLOPs
    a causal (query, key) pair forward, 10 backward; q, k, v in, o and
    the row sums out; backward q, k, v, o, do, lse in, dq, dk, dv out)."""
    heads, seq = c["num_attention_heads"], c["max_position_embeddings"]
    d_head = c["hidden_size"] // heads
    pairs = batch * heads * seq * (seq + 1) / 2
    tensor = batch * heads * seq * d_head * F32
    rows = batch * heads * seq * F32
    return {
        "fwd": (4.0 * d_head * pairs, 4.0 * tensor + rows),
        "bwd": (10.0 * d_head * pairs, 9.0 * tensor + rows),
    }


# The grouped product is a jitted kernel, so the lowered step holds one
# body for each distinct shape however often it is called and however
# many layers call it: the product over (d, f) (gate and up share it)
# and over (f, d), the transposed product for the rows' gradient of
# each, and the weights' gradient of each.  A layer *runs* nine of
# them, three forward and six backward.
EXPERT_KERNEL_BODIES = 6


def kernels(c: Dict[str, Any], batch: int) -> Dict[str, Dict[str, Any]]:
    """The block's Mosaic kernel families by model scope.  ``attn``:
    flash attention, a forward and a backward call a layer.
    ``experts``: the grouped products, FLOPs and bytes of
    ``experts_cost``; ``least_calls`` is what ``correct`` counts in the
    lowered step's text, the six kernel bodies (above)."""
    cost = flash_call_cost(c, batch)
    layers = c["num_hidden_layers"]
    experts = experts_cost(c, batch)
    return {
        "attn": {
            "scope": "attn",
            "flops": layers * (cost["fwd"][0] + cost["bwd"][0]),
            "bytes": layers * (cost["fwd"][1] + cost["bwd"][1]),
            "least_calls": 2 * layers,
        },
        "experts": {
            "scope": "experts",
            "flops": experts["flops"],
            "bytes": experts["bytes"],
            "least_calls": EXPERT_KERNEL_BODIES,
        },
    }


def experts_cost(c: Dict[str, Any], batch: int) -> Dict[str, float]:
    """FLOPs and HBM bytes of the experts of all layers in one
    micro-step, forward and backward, over the ``k T`` gathered rows
    (``T`` = batch x sequence).  FLOPs: three grouped products forward
    (gate, up: d x f; down: f x d), each with two products backward (to
    its rows and to its weights): 3 x 3 x 2 x kT x d x f.  Bytes: every
    expert's three matrices read forward, read again and their gradients
    written backward (all ``num_experts``, whatever the routing: an
    expert with no row still gets a zero gradient); the gathered rows in
    and the results out forward, the results' gradients in and the rows'
    out backward.  The activations kept between the passes are left
    out."""
    d, _v, e, k, f, layers = _sizes(c)
    rows = batch * c["max_position_embeddings"] * k
    weights = 3 * e * d * f * F32
    return {
        "flops": layers * 18.0 * rows * d * f,
        "bytes": layers * (3.0 * weights + 4.0 * rows * d * F32),
    }


# OLMoE-1B-7B's published sizes at the one layer the committed
# configuration keeps, for the hand-worked cases only.
OLMOE_L1 = {"hidden_size": 2048, "num_attention_heads": 16,
            "num_hidden_layers": 1, "num_experts": 64,
            "num_experts_per_tok": 8, "intermediate_size": 1024,
            "vocab_size": 50304, "max_position_embeddings": 4096}


def hand_worked() -> List[Tuple[str, Any, Any]]:
    """``(what, got, want)``: each function on sizes worked by hand."""
    experts = experts_cost(OLMOE_L1, 1)
    families = kernels(OLMOE_L1, 1)
    family = families["attn"]
    return [
        # Table and head 2 x 50304 x 2048 = 2 x 103,022,592; attention 4
        # x 2048^2 = 16,777,216; router 2048 x 64 = 131,072; experts 64 x
        # 3 x 2048 x 1024 = 402,653,184; five norm weights 10,240.
        ("parameters of olmoe-1b-7b at one layer",
         param_count(OLMOE_L1), 625_616_896),
        # Attention 16,777,216 + router 131,072 + 8 experts 8 x 3 x
        # 2,097,152 = 50,331,648 + head 103,022,592.
        ("parameters in one token's products",
         active_param_count(OLMOE_L1), 170_262_528),
        # 6 x 170,262,528 = 1,021,575,168; attention 12 x 2048 x 4097 / 2
        # = 50,343,936.
        ("flops per token of olmoe-1b-7b at one layer, sequence 4096",
         train_flops_per_token(OLMOE_L1), 1_071_919_104),
        # Rows 8 x 4096 = 32,768; 18 x 32,768 x 2048 x 1024.
        ("experts' FLOPs a micro-step at batch 1",
         experts["flops"], 18.0 * 32_768 * 2_097_152),
        # Weights 402,653,184 x 4 B = 1,610,612,736 B, three times; rows
        # 32,768 x 2048 x 4 B = 268,435,456 B, four times.
        ("experts' bytes a micro-step at batch 1",
         experts["bytes"], 3.0 * 1_610_612_736 + 4.0 * 268_435_456),
        # Flash: pairs 16 x 4096 x 4097 / 2 = 134,250,496; 14 x 128 =
        # 1,792 FLOPs a pair forward and backward.
        ("the attn family at batch 1: FLOPs",
         family["flops"], 1792.0 * 134_250_496),
        ("the attn family holds a forward and a backward call a layer",
         family["least_calls"], 2),
        # Two products' shapes, each with a rows' and a weights' gradient.
        ("the experts family: kernel bodies in the lowered step",
         families["experts"]["least_calls"], 2 * 3),
        ("the experts family's FLOPs are the experts' cost",
         families["experts"]["flops"], 18.0 * 32_768 * 2_097_152),
    ]
