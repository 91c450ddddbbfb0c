"""The arithmetic of the Mellum block as the program builds it
(``mpit_tpu/models/transformer.py`` ``MellumDecoder``): what a
configuration with ``"arithmetic": "mellum"`` needs, from its shapes
alone.

What the algorithm requires of **this chip's share**, never what a
kernel happens to execute.  Every function takes the configuration's
file as a dict and reads Mellum's own published keys (``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``num_hidden_layers``, ``layer_types``, ``sliding_window``,
``num_experts_per_tok``, ``moe_intermediate_size``, ``vocab_size``) and
the share's (``num_experts``: the experts held here; ``router_experts``:
the router's width, the published count; ``train_seq``: the sequence the
cells train at).  The contract of such a module is in
``chipbench/spec.py``.

Three Mosaic kernel families: flash attention of the full layers under
the scope ``attn``, of the sliding-window layers under ``attn_window``
(the same kernels with a window: blocks outside it are skipped, so the
FLOPs and bytes counted are those of the pairs inside it), and the held
experts' grouped products under ``experts`` (the Pallas megablox
kernels of ``parallel/moe.py`` ``pallas_grouped_dot`` from a group
offset).  A step that lost a family's calls, because the program
quietly took XLA's product or a materialised mask, is not ``correct``.

The experts' rows depend on the routing.  Under uniform routing a token
sends ``num_experts_per_tok x num_experts / router_experts`` of its
assignments to held experts (one, at 8 of 64 and 8 a token): the counts
here are at that expectation, and ``layers/held_experts_roofline.py``
scales them by the share the program counted.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

F32 = 4  # bytes; parameters, gradients and activations are float32


def _layer_kinds(c: Dict[str, Any]) -> List[str]:
    return list(c["layer_types"][: c["num_hidden_layers"]])


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
    bias-free attention matrices, a router over all ``router_experts``,
    three stacked matrices of the ``num_experts`` held experts and two
    RMSNorm weights; a final RMSNorm and an untied head."""
    d, v = c["hidden_size"], c["vocab_size"]
    layer = (_attention_params(c) + d * c["router_experts"] + 2 * d
             + c["num_experts"] * _expert_params(c))
    return v * d + c["num_hidden_layers"] * layer + d + d * v


def held_per_token(c: Dict[str, Any]) -> float:
    """Assignments a token sends to held experts under uniform routing."""
    return c["num_experts_per_tok"] * c["num_experts"] / c["router_experts"]


def active_param_count(c: Dict[str, Any]) -> float:
    """Parameters in one token's products on this chip: attention, the
    router, the held experts it is expected to use, the head (the table
    is a look-up, the norms are not products)."""
    d = c["hidden_size"]
    layer = (_attention_params(c) + d * c["router_experts"]
             + held_per_token(c) * _expert_params(c))
    return c["num_hidden_layers"] * layer + d * c["vocab_size"]


def pairs_per_query(seq: int, window: int) -> float:
    """(query, key) pairs a causal query sees on average over a sequence
    of ``seq``: ``(seq + 1) / 2`` without a window; with one, query
    ``t`` sees ``min(t + 1, window)``."""
    if not window or window >= seq:
        return (seq + 1) / 2
    return (window * (window + 1) / 2 + (seq - window) * window) / seq


def train_flops_per_token(c: Dict[str, Any]) -> float:
    """Forward plus backward FLOPs one trained token needs of this
    share, nothing recomputed: 6 a parameter in a product (the held
    experts at their expectation under uniform routing), and the
    attention's two products over the pairs a query sees: 3 x 4 x (heads
    x head_dim) x pairs a layer, the pairs inside the window on a
    sliding layer.  Look-ups, norms, rotary, SiLU, softmax, sort and
    gathers are left out."""
    width = c["num_attention_heads"] * c["head_dim"]
    seq = c["train_seq"]
    attention = sum(
        12 * width * pairs_per_query(
            seq, c["sliding_window"] if kind == "sliding_attention" else 0)
        for kind in _layer_kinds(c))
    return 6 * active_param_count(c) + attention


def flash_call_cost(c: Dict[str, Any], batch: int, window: int
                    ) -> Dict[str, Tuple[float, float]]:
    """(FLOPs, HBM bytes) of one layer's attention over a batch of whole
    sequences, forward and backward, as the flash algorithm needs them
    with grouped KV heads: 4 x head_dim FLOPs a visible (query, key)
    pair forward, 10 backward, over all query heads; q in and o out at
    the query heads' size, k and v in at the KV heads' (read once: no
    repeat), and the row sums; backward q, o, do in and dq out at the
    query heads' size, k, v in and dk, dv out at the KV heads'."""
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    head, seq = c["head_dim"], c["train_seq"]
    pairs = batch * heads * seq * pairs_per_query(seq, window)
    q_size = batch * heads * seq * head * F32
    kv_size = batch * kv * seq * head * F32
    rows = batch * heads * seq * F32
    return {
        "fwd": (4.0 * head * pairs, 2.0 * q_size + 2.0 * kv_size + rows),
        "bwd": (10.0 * head * pairs, 4.0 * q_size + 4.0 * kv_size + rows),
    }


# The grouped product is a jitted kernel, so the lowered step holds one
# body for each distinct shape however often it is called (the forward
# pass, its recomputation in the backward pass, every layer): the
# product over (d, f) (gate and up share it) and over (f, d), the
# transposed product for the rows' gradient of each, and the weights'
# gradient of each.
EXPERT_KERNEL_BODIES = 6


def experts_cost(c: Dict[str, Any], batch: int) -> Dict[str, float]:
    """FLOPs and HBM bytes of the held experts of all layers in one
    micro-step **as the block runs them**: forward, the forward again
    (the block recomputes its sparse branch in the backward pass) and
    backward, over the rows expected on held experts under uniform
    routing (``T x held_per_token``).  FLOPs: three grouped products a
    forward pass (gate, up: d x f; down: f x d), each with two products
    backward: (3 + 3 + 6) x 2 x rows x d x f.  Bytes: the held experts'
    three matrices read in each forward pass, read again backward and
    their gradients written (an expert with no row still gets a zero
    gradient); the held rows in and the results out in each forward
    pass, the results' gradients in and the rows' out backward.  The
    activations kept inside a pass are left out."""
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
    full layers' flash attention, a forward and a backward call a layer.
    ``attn_window``: the sliding layers', FLOPs and bytes of the pairs
    inside the window; ``least_calls`` two a layer, the fewest a valid
    step holds (the fused backward; the schedule ``auto`` takes under a
    window has two backward calls, three a layer).  ``experts``: the
    grouped products, FLOPs and bytes of ``experts_cost``;
    ``least_calls`` the six kernel bodies (above)."""
    kinds = _layer_kinds(c)
    out: Dict[str, Dict[str, Any]] = {}
    for family, kind, window in (
            ("attn", "full_attention", 0),
            ("attn_window", "sliding_attention", c["sliding_window"])):
        layers = kinds.count(kind)
        cost = flash_call_cost(c, batch, window)
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


# Mellum2-12B-A2.5B's published sizes at the cut of the committed
# configuration (4 layers, 8 of 64 experts, an eighth of the
# vocabulary), for the hand-worked cases only.
MELLUM_L4E8 = {
    "hidden_size": 2304, "num_attention_heads": 32,
    "num_key_value_heads": 4, "head_dim": 128, "num_hidden_layers": 4,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "sliding_window": 1024, "num_experts": 8, "router_experts": 64,
    "num_experts_per_tok": 8, "moe_intermediate_size": 896,
    "vocab_size": 12288, "train_seq": 8192}


def _committed() -> Dict[str, Any]:
    import json
    import pathlib

    path = (pathlib.Path(__file__).resolve().parent.parent / "configs"
            / "mellum2-12b-l4e8.json")
    with open(path) as fh:
        return json.load(fh)


def hand_worked() -> List[Tuple[str, Any, Any]]:
    """``(what, got, want)``: each function on sizes worked by hand, and
    the committed file's flattened rotary keys against its nested
    group."""
    c = MELLUM_L4E8
    families = kernels(c, 1)
    experts = experts_cost(c, 1)
    committed = _committed()
    full = committed["rope_parameters"]["full_attention"]
    # Window pairs a head: 1024 x 1025 / 2 = 524,800 for the first 1024
    # queries, then 7168 x 1024 = 7,340,032: 7,864,832.  Full: 8192 x
    # 8193 / 2 = 33,558,528.
    window_pairs, full_pairs = 7_864_832, 33_558_528
    return [
        # A layer: wq, wo 2 x 2304 x 4096 = 18,874,368; wk, wv 2 x 2304 x
        # 512 = 2,359,296; router 2304 x 64 = 147,456; norms 4,608; 8
        # experts x 3 x 2304 x 896 = 49,545,216: 70,930,944.  Four layers
        # 283,723,776; table and head 2 x 12288 x 2304 = 56,623,104;
        # final norm 2,304.
        ("parameters of mellum2-12b at 4 layers, 8 experts held, an "
         "eighth of the vocabulary", param_count(c), 340_349_184),
        ("assignments a token sends to held experts, uniform routing",
         held_per_token(c), 1.0),
        # A layer: attention 21,233,664 + router 147,456 + one expert
        # 6,193,152 = 27,574,272; four 110,297,088; head 28,311,552.
        ("parameters in one token's products on this chip",
         active_param_count(c), 138_608_640.0),
        ("pairs a query sees, window 1024 over 8192",
         pairs_per_query(8192, 1024), window_pairs / 8192),
        # 6 x 138,608,640 = 831,651,840; attention 12 x 4096 x (3 x
        # 960.0625 + 4096.5) = 12 x 4096 x 6976.6875 = 342,918,144.
        ("flops per token of the share at sequence 8192",
         train_flops_per_token(c), 831_651_840 + 342_918_144.0),
        ("the attn family (one full layer) at batch 1: FLOPs, 14 x 128 a "
         "pair over 32 heads",
         families["attn"]["flops"], 1792.0 * 32 * full_pairs),
        ("the attn_window family (three layers): FLOPs of the pairs "
         "inside the window", families["attn_window"]["flops"],
         3 * 1792.0 * 32 * window_pairs),
        # q-sized 32 x 8192 x 128 x 4 B = 134,217,728; kv-sized a eighth
        # of it, 16,777,216; row sums 32 x 8192 x 4 B = 1,048,576.
        # Forward 2 q + 2 kv + rows, backward 4 q + 4 kv + rows.
        ("the attn family: bytes", families["attn"]["bytes"],
         6.0 * 134_217_728 + 6.0 * 16_777_216 + 2.0 * 1_048_576),
        ("calls: attn 2, attn_window 6, experts 6 bodies",
         [families[f]["least_calls"]
          for f in ("attn", "attn_window", "experts")], [2, 6, 6]),
        # Rows 8192 x 1; 24 x 8192 x 2304 x 896 a layer, four layers.
        ("the held experts' FLOPs a micro-step at batch 1, the forward "
         "pass counted twice", experts["flops"],
         4 * 24.0 * 8192 * 2_064_384),
        # Weights 8 x 6,193,152 x 4 B = 198,180,864 B, four times; rows
        # 8192 x 2304 x 4 B = 75,497,472 B, six times; four layers.
        ("the held experts' bytes a micro-step at batch 1",
         experts["bytes"], 4 * (4.0 * 198_180_864 + 6.0 * 75_497_472)),
        ("the experts family's FLOPs are the experts' cost",
         families["experts"]["flops"], experts["flops"]),
        ("the committed file's sizes give the hand-worked count",
         param_count(committed), 340_349_184),
        ("the committed file's flattened rotary keys are its nested group",
         [committed["rope_theta"], committed["yarn_factor"],
          committed["yarn_original_max_position_embeddings"],
          committed["yarn_beta_fast"], committed["yarn_beta_slow"],
          committed["yarn_attention_factor"]],
         [full["rope_theta"], full["factor"],
          full["original_max_position_embeddings"], full["beta_fast"],
          full["beta_slow"], full["attention_factor"]]),
        ("the committed file's period is its layer_types'",
         [kind == "full_attention" for kind in committed["layer_types"]],
         [(i + 1) % committed["full_attention_every"] == 0
          for i in range(len(committed["layer_types"]))]),
        ("the sliding layers' rotary base is the flattened one",
         committed["rope_parameters"]["sliding_attention"]["rope_theta"],
         committed["rope_theta"]),
    ]
