"""The arithmetic of the LFM2 block as the program builds it
(``mpit_tpu/models/transformer.py`` ``Lfm2Decoder``): what a
configuration with ``"arithmetic": "lfm2"`` needs, from its shapes
alone.

What the algorithm requires of **this chip's share**, never what a
kernel happens to execute.  Every function takes the configuration's
file as a dict and reads LFM2's own published keys (``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``intermediate_size``,
``moe_intermediate_size``, ``conv_L_cache``, ``layer_types``,
``num_dense_layers``, ``num_experts_per_tok``, ``vocab_size``), the
share's (``num_experts``: the experts held here; ``router_experts``: the
router's width, the published count) and the cut's
(``num_hidden_layers`` layers from ``first_layer`` on; ``train_seq``:
the sequence the cells train at).  The contract of such a module is in
``chipbench/spec.py``.

Two Mosaic kernel families: flash attention of the full-attention
layers under the scope ``attn`` (grouped KV heads, heads of
``hidden_size / num_attention_heads``) and the held experts' grouped
products under ``experts`` (the Pallas megablox kernels of
``parallel/moe.py`` ``pallas_grouped_dot`` from a group offset).  A step
that lost a family's calls, because the program quietly took XLA's
product or a materialised mask, is not ``correct``.  The gated short
convolution is no kernel of its own: XLA fuses its gates and shifts,
and :func:`conv_mix_cost` gives the bytes that fusion cannot avoid, for
``layers/conv_mix_roofline.py``.

The experts' rows depend on the routing.  Under uniform routing a token
sends ``num_experts_per_tok x num_experts / router_experts`` of its
assignments to held experts (a half, at 8 of 64 and 4 a token): the
counts here are at that expectation, and
``layers/held_experts_roofline.py`` scales them by the share the program
counted.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

F32 = 4  # bytes; parameters, gradients and activations are float32


def layers_here(c: Dict[str, Any]) -> List[Tuple[str, bool]]:
    """``(token mixer, whether the MLP is dense)`` of each layer held:
    the published layers ``first_layer .. first_layer +
    num_hidden_layers - 1``."""
    first = int(c.get("first_layer", 0))
    return [(c["layer_types"][i], i < c["num_dense_layers"])
            for i in range(first, first + c["num_hidden_layers"])]


def _head(c: Dict[str, Any]) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def _conv_products(c: Dict[str, Any]) -> int:
    """W_in (d x 3d) and W_out (d x d)."""
    return 4 * c["hidden_size"] ** 2


def _attention_products(c: Dict[str, Any]) -> int:
    """wq and wo over all query heads, wk and wv over the KV heads."""
    d, head = c["hidden_size"], _head(c)
    return (2 * d * c["num_attention_heads"] * head
            + 2 * d * c["num_key_value_heads"] * head)


def _dense_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def _expert_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def param_count(c: Dict[str, Any]) -> int:
    """Parameters of the share as the program builds it, all of them
    exchanged: a token table (no position table), per layer two RMSNorm
    weights, the mixer (a conv layer's W_in, taps and W_out; an
    attention layer's four bias-free matrices and two per-head norm
    weights) and the MLP (dense: three matrices; sparse: a router over
    all ``router_experts``, its selection bias, three stacked matrices
    of the ``num_experts`` held experts); a final RMSNorm and an untied
    head."""
    d, v = c["hidden_size"], c["vocab_size"]
    total = v * d + d + d * v
    for mixer, dense in layers_here(c):
        total += 2 * d
        total += (_conv_products(c) + c["conv_L_cache"] * d
                  if mixer == "conv"
                  else _attention_products(c) + 2 * _head(c))
        total += (_dense_params(c) if dense else
                  d * c["router_experts"] + c["router_experts"]
                  + c["num_experts"] * _expert_params(c))
    return total


def held_per_token(c: Dict[str, Any]) -> float:
    """Assignments a token sends to held experts under uniform routing."""
    return c["num_experts_per_tok"] * c["num_experts"] / c["router_experts"]


def active_param_count(c: Dict[str, Any]) -> float:
    """Parameters in one token's products on this chip: the mixers'
    matrices, the dense MLP, the routers, the held experts a token is
    expected to use, the head (the table is a look-up; norms, taps and
    the bias are not products)."""
    d = c["hidden_size"]
    total = float(d * c["vocab_size"])
    for mixer, dense in layers_here(c):
        total += (_conv_products(c) if mixer == "conv"
                  else _attention_products(c))
        total += (_dense_params(c) if dense else
                  d * c["router_experts"]
                  + held_per_token(c) * _expert_params(c))
    return total


def pairs_per_query(seq: int) -> float:
    """(query, key) pairs a causal query sees on average."""
    return (seq + 1) / 2


def train_flops_per_token(c: Dict[str, Any]) -> float:
    """Forward plus backward FLOPs one trained token needs of this
    share, nothing recomputed: 6 a parameter in a product (the held
    experts at their expectation under uniform routing), and the
    attention's two products over the pairs a query sees: 3 x 4 x
    ``hidden_size`` x pairs an attention layer.  Look-ups, norms, the
    convolution's taps and gates, rotary, SiLU, sigmoid, softmax, sort
    and gathers are left out."""
    attention_layers = [m for m, _ in layers_here(c)].count("full_attention")
    width = c["num_attention_heads"] * _head(c)
    return (6 * active_param_count(c) + attention_layers * 12 * width
            * pairs_per_query(c["train_seq"]))


def flash_call_cost(c: Dict[str, Any], batch: int
                    ) -> Dict[str, Tuple[float, float]]:
    """(FLOPs, HBM bytes) of one layer's attention over a batch of whole
    sequences, forward and backward, as the flash algorithm needs them
    with grouped KV heads: 4 x head FLOPs a visible (query, key) pair
    forward, 10 backward, over all query heads; q in and o out at the
    query heads' size, k and v in at the KV heads' (read once: no
    repeat), and the row sums; backward q, o, do in and dq out at the
    query heads' size, k, v in and dk, dv out at the KV heads'."""
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    head, seq = _head(c), c["train_seq"]
    pairs = batch * heads * seq * pairs_per_query(seq)
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
    """FLOPs and HBM bytes of the held experts of all sparse layers in
    one micro-step **as the block runs them**: forward, the forward
    again (the block recomputes its sparse branch in the backward pass)
    and backward, over the rows expected on held experts under uniform
    routing (``T x held_per_token``).  FLOPs: three grouped products a
    forward pass (gate, up: d x f; down: f x d), each with two products
    backward: (3 + 3 + 6) x 2 x rows x d x f.  Bytes: the held experts'
    three matrices read in each forward pass, read again backward and
    their gradients written (an expert with no row still gets a zero
    gradient); the held rows in and the results out in each forward
    pass, the results' gradients in and the rows' out backward.  The
    activations kept inside a pass are left out."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    sparse = sum(not dense for _, dense in layers_here(c))
    rows = batch * c["train_seq"] * held_per_token(c)
    weights = c["num_experts"] * _expert_params(c) * F32
    rows_bytes = sparse * 6.0 * rows * d * F32
    return {
        "flops": sparse * 24.0 * rows * d * f,
        "bytes": sparse * 4.0 * weights + rows_bytes,
        # the part of the bytes that scales with the routing
        # (layers/held_experts_roofline.py)
        "rows_bytes": rows_bytes,
    }


def conv_mix_cost(c: Dict[str, Any], batch: int) -> Dict[str, float]:
    """HBM bytes the two gates and the depthwise convolution of all conv
    layers held need in one micro-step, were each pass one fused sweep,
    nothing recomputed.  Forward: ``h W_in``'s ``T x 3d`` read once and
    ``T x d`` written.  Backward: the same ``T x 3d`` read again with the
    incoming ``T x d``, ``T x 3d`` written, and the taps' gradient
    (``conv_L_cache x d``, with the taps read in both passes).  The
    FLOPs (a dozen a channel and position) never bind."""
    d = c["hidden_size"]
    layers = [m for m, _ in layers_here(c)].count("conv")
    t = batch * c["train_seq"]
    taps = c["conv_L_cache"] * d
    return {"bytes": layers * F32 * (t * (3 * d + d)
                                     + t * (3 * d + d + 3 * d) + 3 * taps),
            "layers": layers}


def kernels(c: Dict[str, Any], batch: int) -> Dict[str, Dict[str, Any]]:
    """The block's Mosaic kernel families by model scope.  ``attn``: the
    full-attention layers' flash attention, a forward and a backward
    call a layer.  ``experts``: the grouped products, FLOPs and bytes of
    ``experts_cost``; ``least_calls`` the six kernel bodies (above)."""
    layers = [m for m, _ in layers_here(c)].count("full_attention")
    cost = flash_call_cost(c, batch)
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


# LFM2-24B-A2B's published sizes at the cut of the committed
# configuration (layers 1-5, 8 of 64 experts, an eighth of the
# vocabulary), for the hand-worked cases only.
_PERIOD = ["full_attention", "conv", "conv", "conv"]
LFM2_L5E8 = {
    "hidden_size": 2048, "num_attention_heads": 32,
    "num_key_value_heads": 8, "intermediate_size": 11776,
    "moe_intermediate_size": 1536, "conv_L_cache": 3,
    "layer_types": ["conv", "conv"] + _PERIOD * 9 + ["full_attention",
                                                     "conv"],
    "num_dense_layers": 2, "first_layer": 1, "num_hidden_layers": 5,
    "num_experts": 8, "router_experts": 64, "num_experts_per_tok": 4,
    "vocab_size": 8192, "train_seq": 8192}


def _committed() -> Dict[str, Any]:
    import json
    import pathlib

    path = (pathlib.Path(__file__).resolve().parent.parent / "configs"
            / "lfm2-24b-l5e8.json")
    with open(path) as fh:
        return json.load(fh)


def hand_worked() -> List[Tuple[str, Any, Any]]:
    """``(what, got, want)``: each function on sizes worked by hand, and
    the committed file's flattened keys against what they copy."""
    c = LFM2_L5E8
    families = kernels(c, 1)
    experts = experts_cost(c, 1)
    committed = _committed()
    here = layers_here(committed)
    full_pairs = 8192 * 8193 // 2        # 33,558,528 a head
    return [
        ("the layers held: 1-5 of the 40, the second dense layer and one "
         "whole period", layers_here(c),
         [("conv", True), ("full_attention", False), ("conv", False),
          ("conv", False), ("conv", False)]),
        # A conv operator: W_in 2048 x 6144 = 12,582,912, W_out 4,194,304,
        # taps 6,144: 16,783,360.  Attention: wq, wo 2 x 4,194,304; wk,
        # wv 2 x 1,048,576; q and k norm 128: 10,485,888.  Two norms
        # 4,096.  Dense MLP 3 x 2048 x 11776 = 72,351,744.  Sparse MLP:
        # router 131,072, bias 64, 8 x 9,437,184: 75,628,608.  Layer 1
        # 89,139,200; layer 2 86,118,592; layers 3-5 92,416,064 each;
        # table and head 2 x 16,777,216; final norm 2,048.
        ("parameters of lfm2-24b at layers 1-5, 8 experts held, an eighth "
         "of the vocabulary", param_count(c), 486_062_464),
        ("assignments a token sends to held experts, uniform routing",
         held_per_token(c), 0.5),
        # Conv 4 x 16,777,216 = 67,108,864; attention 10,485,760; dense
        # 72,351,744; sparse 4 x (131,072 + 4,718,592) = 19,398,656; head
        # 16,777,216.
        ("parameters in one token's products on this chip",
         active_param_count(c), 186_122_240.0),
        # 6 x 186,122,240 = 1,116,733,440; attention 12 x 2048 x 4096.5 =
        # 100,675,584.
        ("flops per token of the share at sequence 8192",
         train_flops_per_token(c), 1_116_733_440 + 100_675_584.0),
        ("the attn family (one layer) at batch 1: FLOPs, 14 x 64 a pair "
         "over 32 heads", families["attn"]["flops"],
         896.0 * 32 * full_pairs),
        # q-sized 32 x 8192 x 64 x 4 B = 67,108,864; kv-sized a quarter
        # of it, 16,777,216; row sums 32 x 8192 x 4 B = 1,048,576.
        # Forward 2 q + 2 kv + rows, backward 4 q + 4 kv + rows.
        ("the attn family: bytes", families["attn"]["bytes"],
         6.0 * 67_108_864 + 6.0 * 16_777_216 + 2.0 * 1_048_576),
        ("calls: attn 2, experts 6 bodies",
         [families[f]["least_calls"] for f in ("attn", "experts")], [2, 6]),
        # Rows 8192 x 0.5 = 4096; 24 x 4096 x 2048 x 1536 a layer, four
        # sparse layers.
        ("the held experts' FLOPs a micro-step at batch 1, the forward "
         "pass counted twice", experts["flops"],
         4 * 24.0 * 4096 * 3_145_728),
        # Weights 8 x 9,437,184 x 4 B = 301,989,888 B, four times; rows
        # 4096 x 2048 x 4 B = 33,554,432 B, six times; four layers.
        ("the held experts' bytes a micro-step at batch 1",
         experts["bytes"], 4 * (4.0 * 301_989_888 + 6.0 * 33_554_432)),
        ("the experts family's FLOPs are the experts' cost",
         families["experts"]["flops"], experts["flops"]),
        # A layer: forward 8192 x (6144 + 2048) floats, backward 8192 x
        # (6144 + 2048 + 6144), the taps three times 6,144: 184,567,808
        # floats; four conv layers.
        ("the gates' and the convolution's bytes a micro-step at batch 1",
         conv_mix_cost(c, 1),
         {"bytes": 4 * 4 * (8192 * 22528 + 18_432), "layers": 4}),
        ("the committed file's sizes give the hand-worked count",
         param_count(committed), 486_062_464),
        ("the committed file's published layer_types are the hand-worked "
         "ones", committed["layer_types"], c["layer_types"]),
        ("the committed file's flattened layer types are its layers'",
         committed["layer_types_here"], ",".join(m for m, _ in here)),
        ("the committed file's count of dense layers here is its layers'",
         committed["dense_layers_here"], sum(dense for _, dense in here)),
        ("the committed file's flattened rotary base is its nested group's",
         committed["rope_theta"],
         committed["rope_parameters"]["rope_theta"]),
    ]
