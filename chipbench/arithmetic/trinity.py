"""The arithmetic of the Trinity block as the program builds it
(``mpit_tpu/models/transformer.py`` ``TrinityDecoder``): what a
configuration with ``"arithmetic": "trinity"`` needs, from its shapes
alone.

What the algorithm requires of **this chip's share**, never what a
kernel happens to execute.  Every function takes the configuration's
file as a dict and reads Trinity's own published keys (``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``num_hidden_layers``, ``layer_types``, ``sliding_window``,
``num_dense_layers``, ``intermediate_size``, ``num_experts_per_tok``,
``moe_intermediate_size``, ``num_shared_experts``, ``vocab_size``) and
the share's (``num_experts``: the experts held here; ``router_experts``:
the router's width, the published count; ``train_seq``: the sequence the
cell trains at).  The contract of such a module is in
``chipbench/spec.py``.

Three Mosaic kernel families, Mellum's: flash attention of the full
layers under the scope ``attn``, of the sliding-window layers under
``attn_window`` (the same kernels with a window: the FLOPs and bytes
counted are those of the pairs inside it, ``L W - W (W - 1) / 2`` a
head), and the held experts' grouped products under ``experts`` (the
family the accepted readers ``held_experts_ms_per_step`` and
``held_experts_roofline`` ask for by that name).  The gate's product,
the shared expert, the dense MLP and the balancing rule are XLA's: no
kernel of their own.

The experts' rows depend on the routing.  Under uniform routing a token
sends ``num_experts_per_tok x num_experts / router_experts`` of its
assignments to held experts (a half, at 8 of 128 and 8 a token): the
counts here are at that expectation, and
``layers/held_experts_roofline.py`` scales them by the share the program
counted.  The rule drives every seed's routing towards that
expectation.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

F32 = 4  # bytes; parameters, gradients and activations are float32


def _layer_kinds(c: Dict[str, Any]) -> List[str]:
    return list(c["layer_types"][: c["num_hidden_layers"]])


def dense_layers(c: Dict[str, Any]) -> int:
    return min(c["num_dense_layers"], c["num_hidden_layers"])


def sparse_layers(c: Dict[str, Any]) -> int:
    return c["num_hidden_layers"] - dense_layers(c)


def attention_products(c: Dict[str, Any]) -> int:
    """wq, wo and the gate over all query heads, wk and wv over the KV
    heads."""
    d, head = c["hidden_size"], c["head_dim"]
    return (3 * d * c["num_attention_heads"] * head
            + 2 * d * c["num_key_value_heads"] * head)


def _dense_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def _expert_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def layer_param_count(c: Dict[str, Any], dense: bool) -> int:
    """A layer: the attention's five matrices and its two per-head norm
    weights, four RMSNorm weights over the stream, and the MLP (dense:
    three matrices; sparse: a router over all ``router_experts``, its
    selection bias, three stacked matrices of the ``num_experts`` held
    experts and the shared expert's three)."""
    d = c["hidden_size"]
    mlp = (_dense_params(c) if dense else
           d * c["router_experts"] + c["router_experts"]
           + (c["num_experts"] + c["num_shared_experts"])
           * _expert_params(c))
    return attention_products(c) + 2 * c["head_dim"] + 4 * d + mlp


def param_count(c: Dict[str, Any]) -> int:
    """Parameters of the share as the program builds it, all of them
    exchanged (the selection biases too: they move by the rule, and the
    rule's step travels in the gradient): a token table, the layers, a
    final RMSNorm and an untied head."""
    d, v = c["hidden_size"], c["vocab_size"]
    return (v * d + d + d * v
            + dense_layers(c) * layer_param_count(c, True)
            + sparse_layers(c) * layer_param_count(c, False))


def held_per_token(c: Dict[str, Any]) -> float:
    """Assignments a token sends to held experts under uniform routing."""
    return c["num_experts_per_tok"] * c["num_experts"] / c["router_experts"]


def active_param_count(c: Dict[str, Any]) -> float:
    """Parameters in one token's products on this chip: the attention's
    five, the dense MLP or the router, the shared expert and the held
    experts it is expected to use, the head (the table is a look-up, the
    norms and the bias are not products)."""
    d = c["hidden_size"]
    sparse = (d * c["router_experts"]
              + (c["num_shared_experts"] + held_per_token(c))
              * _expert_params(c))
    return (c["num_hidden_layers"] * attention_products(c)
            + dense_layers(c) * _dense_params(c)
            + sparse_layers(c) * sparse + d * c["vocab_size"])


def window_pairs(seq: int, window: int) -> float:
    """(query, key) pairs a head's causal attention has over a sequence
    of ``seq``: ``seq (seq + 1) / 2`` without a window; with one,
    ``seq window - window (window - 1) / 2`` (query ``i`` sees ``min(i +
    1, window)`` keys)."""
    if not window or window >= seq:
        return seq * (seq + 1) / 2
    return seq * window - window * (window - 1) / 2


def _window_of(c: Dict[str, Any], kind: str) -> int:
    return c["sliding_window"] if kind == "sliding_attention" else 0


def train_flops_per_token(c: Dict[str, Any]) -> float:
    """Forward plus backward FLOPs one trained token needs of this
    share, nothing recomputed: 6 a parameter in a product (the gate's
    among them; the held experts at their expectation under uniform
    routing), and the attention's two products over the live pairs: 3 x
    4 x (heads x head_dim) x pairs a query and layer, the pairs inside
    the window on a sliding layer and all the causal ones on a full one.
    Look-ups, norms, rotary, sigmoid, SiLU, softmax, the elementwise
    gate, sort, gathers and the rule are left out."""
    width = c["num_attention_heads"] * c["head_dim"]
    seq = c["train_seq"]
    attention = sum(12 * width * window_pairs(seq, _window_of(c, kind)) / seq
                    for kind in _layer_kinds(c))
    return 6 * active_param_count(c) + attention


def flash_call_cost(c: Dict[str, Any], batch: int, window: int
                    ) -> Dict[str, Tuple[float, float]]:
    """(FLOPs, HBM bytes) of one layer's attention over a batch of whole
    sequences, forward and backward, as the flash algorithm needs them
    with grouped KV heads: 4 x head_dim FLOPs a live (query, key) pair
    forward, 10 backward, over all query heads; q in and o out at the
    query heads' size, k and v in at the KV heads' (read once: no
    repeat), and the row sums; backward q, o, do in and dq out at the
    query heads' size, k, v in and dk, dv out at the KV heads'."""
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    head, seq = c["head_dim"], c["train_seq"]
    pairs = batch * heads * window_pairs(seq, window)
    q_size = batch * heads * seq * head * F32
    kv_size = batch * kv * seq * head * F32
    rows = batch * heads * seq * F32
    return {
        "fwd": (4.0 * head * pairs, 2.0 * q_size + 2.0 * kv_size + rows),
        "bwd": (10.0 * head * pairs, 4.0 * q_size + 4.0 * kv_size + rows),
    }


# One kernel body for each distinct shape of the jitted grouped product
# however often it is called (arithmetic/mellum.py has the six).
EXPERT_KERNEL_BODIES = 6


def experts_cost(c: Dict[str, Any], batch: int) -> Dict[str, float]:
    """FLOPs and HBM bytes of the held experts of the sparse layers in
    one micro-step **as the block runs them**: forward, the forward
    again (the block recomputes its sparse branch in the backward pass)
    and backward, over the rows expected on held experts under uniform
    routing (``T x held_per_token``); ``arithmetic/mellum.py``
    ``experts_cost`` has the terms.  The shared expert is not here: its
    products are XLA's, under the scope ``shared_expert``."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    layers = sparse_layers(c)
    rows = batch * c["train_seq"] * held_per_token(c)
    weights = c["num_experts"] * _expert_params(c) * F32
    rows_bytes = layers * 6.0 * rows * d * F32
    return {
        "flops": layers * 24.0 * rows * d * f,
        "bytes": layers * 4.0 * weights + rows_bytes,
        # the part of the bytes that scales with the routing
        # (layers/held_experts_roofline.py)
        "rows_bytes": rows_bytes,
    }


def kernels(c: Dict[str, Any], batch: int) -> Dict[str, Dict[str, Any]]:
    """The block's Mosaic kernel families by model scope.  ``attn``: the
    full layers' flash attention, a forward and a backward call a layer.
    ``attn_window``: the sliding layers', FLOPs and bytes of the pairs
    inside the window; ``least_calls`` two a layer, the fewest a valid
    step holds (the attention branch's checkpoint keeps the forward
    kernel's two results, so the backward pass calls no forward kernel).
    ``experts``: the grouped products, FLOPs and bytes of
    :func:`experts_cost`; ``least_calls`` the six kernel bodies."""
    kinds = _layer_kinds(c)
    out: Dict[str, Dict[str, Any]] = {}
    for family, kind in (("attn", "full_attention"),
                         ("attn_window", "sliding_attention")):
        layers = kinds.count(kind)
        cost = flash_call_cost(c, batch, _window_of(c, kind))
        out[family] = {
            "scope": family,
            "flops": layers * (cost["fwd"][0] + cost["bwd"][0]),
            "bytes": layers * (cost["fwd"][1] + cost["bwd"][1]),
            "least_calls": 2 * layers,
        }
    experts = experts_cost(c, batch)
    out["experts"] = {
        "scope": "experts",
        "flops": experts["flops"],
        "bytes": experts["bytes"],
        "least_calls": EXPERT_KERNEL_BODIES,
    }
    return out


# Trinity-Mini's published sizes at the cut of the committed
# configuration (layers 1-5: one dense and four sparse, 8 of 128
# experts, an eighth of the vocabulary), for the hand-worked cases only.
TRINITY_L5E8 = {
    "hidden_size": 2048, "num_attention_heads": 32,
    "num_key_value_heads": 4, "head_dim": 128, "num_hidden_layers": 5,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "full_attention", "sliding_attention",
                    "sliding_attention"],
    "sliding_window": 2048, "num_dense_layers": 1,
    "intermediate_size": 6144, "moe_intermediate_size": 1024,
    "num_experts": 8, "router_experts": 128, "num_experts_per_tok": 8,
    "num_shared_experts": 1, "vocab_size": 25024, "train_seq": 8192}


def _committed() -> Dict[str, Any]:
    import json
    import pathlib

    path = (pathlib.Path(__file__).resolve().parent.parent / "configs"
            / "trinity-mini-26b-l5e8.json")
    with open(path) as fh:
        return json.load(fh)


def hand_worked() -> List[Tuple[str, Any, Any]]:
    """``(what, got, want)``: each function on sizes worked by hand, and
    the committed file against them."""
    c = TRINITY_L5E8
    families = kernels(c, 1)
    experts = experts_cost(c, 1)
    committed = _committed()
    # Window pairs a head: 8192 x 2048 - 2048 x 2047 / 2 = 16,777,216 -
    # 2,096,128 = 14,681,088.  Full: 8192 x 8193 / 2 = 33,558,528.
    win_pairs, full_pairs = 14_681_088, 33_558_528
    return [
        # Attention: q, o, gate 3 x 2048 x 4096 = 25,165,824; k, v 2 x
        # 2048 x 512 = 2,097,152; q/k norms 256: 27,263,232.  Four norms
        # 8,192.  Dense MLP 3 x 2048 x 6144 = 37,748,736.
        ("parameters of the dense layer", layer_param_count(c, True),
         65_020_160),
        # Router 2048 x 128 = 262,144; bias 128; shared 3 x 2048 x 1024 =
        # 6,291,456; eight held experts 50,331,648.
        ("parameters of a sparse layer, 8 experts held",
         layer_param_count(c, False), 84_156_800),
        # 65,020,160 + 4 x 84,156,800 = 401,647,360; table and head 2 x
        # 25,024 x 2048 = 102,498,304; final norm 2,048.
        ("parameters of trinity-mini at layers 1-5, 8 experts held, an "
         "eighth of the vocabulary", param_count(c), 504_147_712),
        ("assignments a token sends to held experts, uniform routing",
         held_per_token(c), 0.5),
        # Attention's products 27,262,976 a layer, five: 136,314,880;
        # dense MLP 37,748,736; a sparse layer 262,144 + 1.5 x 6,291,456
        # = 9,699,328, four: 38,797,312; head 51,249,152.
        ("parameters in one token's products on this chip",
         active_param_count(c), 264_110_080.0),
        ("pairs of a head, window 2048 over 8192",
         window_pairs(8192, 2048), win_pairs),
        ("pairs of a head, full, 8192", window_pairs(8192, 0), full_pairs),
        # 6 x 264,110,080 = 1,584,660,480; attention 12 x 4096 x (4 x
        # 14,681,088 + 33,558,528) / 8192 = 12 x 4096 x 11,265 =
        # 553,697,280.
        ("flops per token of the share at sequence 8192",
         train_flops_per_token(c), 1_584_660_480 + 553_697_280.0),
        ("the attn family (one full layer) at batch 1: FLOPs, 14 x 128 a "
         "pair over 32 heads",
         families["attn"]["flops"], 1792.0 * 32 * full_pairs),
        ("the attn_window family (four layers): FLOPs of the pairs "
         "inside the window", families["attn_window"]["flops"],
         4 * 1792.0 * 32 * win_pairs),
        # q-sized 32 x 8192 x 128 x 4 B = 134,217,728; kv-sized an eighth
        # of it, 16,777,216; row sums 32 x 8192 x 4 B = 1,048,576.
        ("the attn_window family: bytes", families["attn_window"]["bytes"],
         4 * (6.0 * 134_217_728 + 6.0 * 16_777_216 + 2.0 * 1_048_576)),
        ("calls: attn 2, attn_window 8, experts 6 bodies",
         [families[f]["least_calls"]
          for f in ("attn", "attn_window", "experts")], [2, 8, 6]),
        # Rows 8192 x 0.5 = 4096; 24 x 4096 x 2048 x 1024 a layer, four
        # sparse layers.
        ("the held experts' FLOPs a micro-step at batch 1, the forward "
         "pass counted twice", experts["flops"],
         4 * 24.0 * 4096 * 2_097_152),
        # Weights 8 x 6,291,456 x 4 B = 201,326,592 B, four times; rows
        # 4096 x 2048 x 4 B = 33,554,432 B, six times; four layers.
        ("the held experts' bytes a micro-step at batch 1",
         experts["bytes"], 4 * (4.0 * 201_326_592 + 6.0 * 33_554_432)),
        ("the experts family's FLOPs are the experts' cost",
         families["experts"]["flops"], experts["flops"]),
        ("the committed file's sizes give the hand-worked count",
         param_count(committed), 504_147_712),
        ("the committed file's layers are the published layers 1-5",
         list(committed["layer_types"]),
         list(committed["published"]["layer_types"][1:6])),
        ("the committed file's launcher string is its layer_types",
         committed["layer_types_here"].split(","),
         list(committed["layer_types"])),
        ("the committed file's input scale is the root of its width",
         round(committed["embed_scale"] ** 2, 6),
         float(committed["hidden_size"])),
    ]
