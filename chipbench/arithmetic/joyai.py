"""The arithmetic of the latent-attention block as the program builds it
(``mpit_tpu/models/transformer.py`` ``JoyaiDecoder``): what a
configuration with ``"arithmetic": "joyai"`` needs, from its shapes
alone.

What the algorithm requires of **this chip's share**, never what a
kernel or the program's recomputation happens to execute.  Every
function takes the configuration's file as a dict and reads the model's
own published keys (``hidden_size``, ``num_attention_heads``,
``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``, ``intermediate_size``,
``moe_intermediate_size``, ``n_shared_experts``,
``first_k_dense_replace``, ``num_experts_per_tok``,
``num_nextn_predict_layers``, ``vocab_size``), the share's
(``n_routed_experts``: the experts held here; ``router_experts``: the
router's width, the published count) and the cut's
(``num_hidden_layers`` layers from the first on; ``train_seq``: the
sequence the cells train at).  The contract of such a module is in
``chipbench/spec.py``.

Three things set this block's arithmetic apart.  **Keys wider than
values**: a head's query and key are ``qk_nope_head_dim +
qk_rope_head_dim`` wide (192) and its value ``v_head_dim`` (128), so the
two score-side products of a (query, key) pair cost 2 x 192 FLOPs each
and the two value-side ones 2 x 128; the kernel pads the keys to 256
lanes and the values not at all, and ``flash_roofline``, counted here at
the real widths, shows what the padding costs.  **A shared expert**:
every token's product, whole on every share.  **A second head**: the
multi-token-prediction module is one more sparse layer with its own
attention, a ``2 hidden x hidden`` projection, and a second product of
the one head matrix, so the head is in a token's products twice.

Two Mosaic kernel families, under the scopes the shared readers ask
``flops.kernel_family`` for: flash attention under ``attn`` (every
layer's, the MTP module's too: its layer runs under the same scopes as
the stack's) and the held experts' grouped products under ``experts``
(the Pallas megablox kernels of ``parallel/moe.py``
``pallas_grouped_dot`` from a group offset).  The experts' rows depend
on the routing: the counts here are at the expectation under uniform
routing, ``num_experts_per_tok x n_routed_experts / router_experts`` of
a token's assignments on held experts (a quarter, at 8 of 256 and 8 a
token), and ``layers/held_experts_roofline.py`` scales them by the share
the program counted.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

F32 = 4  # bytes; parameters, gradients and activations are float32


def _qk(c: Dict[str, Any]) -> int:
    return c["qk_nope_head_dim"] + c["qk_rope_head_dim"]


def attention_products(c: Dict[str, Any]) -> int:
    """W_qa, W_qb, W_kva, W_kvb and W_o."""
    d, heads = c["hidden_size"], c["num_attention_heads"]
    return (d * c["q_lora_rank"] + c["q_lora_rank"] * heads * _qk(c)
            + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * heads * (c["qk_nope_head_dim"]
                                           + c["v_head_dim"])
            + heads * c["v_head_dim"] * d)


def _dense_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def _expert_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def sparse_layers(c: Dict[str, Any]) -> int:
    """Sparse layers on this chip: the stack's and the MTP module's."""
    return (c["num_hidden_layers"] - dense_layers(c)
            + c["num_nextn_predict_layers"])


def dense_layers(c: Dict[str, Any]) -> int:
    return min(c["first_k_dense_replace"], c["num_hidden_layers"])


def attention_layers(c: Dict[str, Any]) -> int:
    return c["num_hidden_layers"] + c["num_nextn_predict_layers"]


def layer_param_count(c: Dict[str, Any], dense: bool) -> int:
    """A layer: the attention's five matrices and two inner norm
    weights, two RMSNorm weights over the stream, and the MLP (dense:
    three matrices; sparse: a router over all ``router_experts``, its
    selection bias, three stacked matrices of the ``n_routed_experts``
    held experts and the shared expert's three)."""
    d = c["hidden_size"]
    mlp = (_dense_params(c) if dense else
           d * c["router_experts"] + c["router_experts"]
           + (c["n_routed_experts"] + c["n_shared_experts"])
           * _expert_params(c))
    return (attention_products(c) + c["q_lora_rank"] + c["kv_lora_rank"]
            + 2 * d + mlp)


def mtp_param_count(c: Dict[str, Any]) -> int:
    """The MTP module: a sparse layer, the projection of the pair and
    three norm weights (the embedding's, the hidden state's, its final
    one); the table and the head are the main model's."""
    d = c["hidden_size"]
    return c["num_nextn_predict_layers"] * (
        layer_param_count(c, False) + 2 * d * d + 3 * d)


def param_count(c: Dict[str, Any]) -> int:
    """Parameters of the share as the program builds it, all of them
    exchanged: a token table (no position table), the layers, a final
    RMSNorm, an untied head, the MTP module."""
    d, v = c["hidden_size"], c["vocab_size"]
    n_dense = dense_layers(c)
    return (v * d + d + d * v
            + n_dense * layer_param_count(c, True)
            + (c["num_hidden_layers"] - n_dense) * layer_param_count(c, False)
            + mtp_param_count(c))


def held_per_token(c: Dict[str, Any]) -> float:
    """Assignments a token sends to held experts under uniform routing."""
    return (c["num_experts_per_tok"] * c["n_routed_experts"]
            / c["router_experts"])


def active_param_count(c: Dict[str, Any]) -> float:
    """Parameters in one token's products on this chip: every layer's
    attention matrices (the MTP module's too), the dense MLP, the
    routers, the held experts a token is expected to use, the shared
    expert, the MTP projection and the head **twice** (each head's
    product is over the one matrix); the table is a look-up, norms and
    the bias are not products."""
    d = c["hidden_size"]
    sparse = (d * c["router_experts"]
              + (held_per_token(c) + c["n_shared_experts"])
              * _expert_params(c))
    return (attention_layers(c) * attention_products(c)
            + dense_layers(c) * _dense_params(c)
            + sparse_layers(c) * sparse
            + c["num_nextn_predict_layers"] * 2 * d * d
            + (1 + c["num_nextn_predict_layers"]) * d * c["vocab_size"])


def pairs_per_query(seq: int) -> float:
    """(query, key) pairs a causal query sees on average."""
    return (seq + 1) / 2


def train_flops_per_token(c: Dict[str, Any]) -> float:
    """Forward plus backward FLOPs one trained token needs of this
    share, nothing recomputed: 6 a parameter in a product (the held
    experts at their expectation under uniform routing) and the
    attention's products over the pairs a query sees, three times the
    forward pass's ``2 qk + 2 v`` a pair and head (the backward pass's
    four products; the scores it computes again are recomputation and
    are in :func:`flash_call_cost`, not here).  Look-ups, norms, rotary,
    SiLU, sigmoid, softmax, sort and gathers are left out."""
    pair = 3 * (2 * _qk(c) + 2 * c["v_head_dim"])
    return (6 * active_param_count(c)
            + attention_layers(c) * c["num_attention_heads"] * pair
            * pairs_per_query(c["train_seq"]))


def flash_call_cost(c: Dict[str, Any], batch: int
                    ) -> Dict[str, Tuple[float, float]]:
    """(FLOPs, HBM bytes) of one layer's attention over a batch of whole
    sequences, forward and backward, as the flash algorithm needs them
    **at the heads' real widths**, a visible (query, key) pair a head:
    forward ``2 qk`` for the score and ``2 v`` for PV; backward the
    score again, ``dS K`` and ``dS^T Q`` at ``2 qk`` each, ``dO V^T``
    and ``P^T dO`` at ``2 v`` each.  Bytes: forward q and k in at the
    keys' width (every head has a key of its own: the shared rotary part
    is repeated into it before the kernel), v in and o out at the
    values', the row sums; backward q, k in and dq, dk out at the keys'
    width, v, o, do in and dv out at the values', the row sums."""
    heads, seq = c["num_attention_heads"], c["train_seq"]
    qk, v = _qk(c), c["v_head_dim"]
    pairs = batch * heads * seq * pairs_per_query(seq)
    qk_size = batch * heads * seq * qk * F32
    v_size = batch * heads * seq * v * F32
    rows = batch * heads * seq * F32
    return {
        "fwd": ((2.0 * qk + 2.0 * v) * pairs,
                2.0 * qk_size + 2.0 * v_size + rows),
        "bwd": ((6.0 * qk + 4.0 * v) * pairs,
                4.0 * qk_size + 4.0 * v_size + rows),
    }


# The grouped product is a jitted kernel, so the lowered step holds one
# body for each distinct shape however often it is called (as
# ``arithmetic/lfm2.py`` has it): two products, the transposed product
# for the rows' gradient of each, and the weights' gradient of each.
EXPERT_KERNEL_BODIES = 6


def experts_cost(c: Dict[str, Any], batch: int) -> Dict[str, float]:
    """FLOPs and HBM bytes of the held experts of all sparse layers (the
    MTP module's among them) in one micro-step **as the block runs
    them**: forward, the forward again (the block recomputes its sparse
    branch in the backward pass) and backward, over the rows expected on
    held experts under uniform routing (``T x held_per_token``).  As
    ``arithmetic/lfm2.py`` ``experts_cost`` counts them: (3 + 3 + 6) x 2
    x rows x d x f FLOPs a layer; the held experts' three matrices read
    in each forward pass, read again backward and their gradients
    written; the held rows in and the results out in each forward pass,
    the results' gradients in and the rows' out backward."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    sparse = sparse_layers(c)
    rows = batch * c["train_seq"] * held_per_token(c)
    weights = c["n_routed_experts"] * _expert_params(c) * F32
    rows_bytes = sparse * 6.0 * rows * d * F32
    return {
        "flops": sparse * 24.0 * rows * d * f,
        "bytes": sparse * 4.0 * weights + rows_bytes,
        # the part of the bytes that scales with the routing
        # (layers/held_experts_roofline.py)
        "rows_bytes": rows_bytes,
    }


def kernels(c: Dict[str, Any], batch: int) -> Dict[str, Dict[str, Any]]:
    """The block's Mosaic kernel families by model scope.  ``attn``:
    every layer's flash attention, the MTP module's too; the fewest
    calls a lowered step may hold are a forward and a backward one a
    layer (the program's backward is the two-kernel schedule at these
    sizes, three a layer).  ``experts``: the grouped products, FLOPs and
    bytes of :func:`experts_cost`; ``least_calls`` the six kernel
    bodies."""
    layers = attention_layers(c)
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


# JoyAI-LLM-Flash's published sizes at the cut of the committed
# configuration (layers 0-4, 8 of 256 experts, an eighth of the
# vocabulary, the MTP module), for the hand-worked cases only.
JOYAI_L5E8 = {
    "hidden_size": 2048, "num_attention_heads": 32, "q_lora_rank": 1536,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "intermediate_size": 7168,
    "moe_intermediate_size": 768, "n_shared_experts": 1,
    "first_k_dense_replace": 1, "num_hidden_layers": 5,
    "n_routed_experts": 8, "router_experts": 256, "num_experts_per_tok": 8,
    "num_nextn_predict_layers": 1, "vocab_size": 16160, "train_seq": 8192}


def _committed() -> Dict[str, Any]:
    import json
    import pathlib

    path = (pathlib.Path(__file__).resolve().parent.parent / "configs"
            / "joyai-flash-48b-l5e8.json")
    with open(path) as fh:
        return json.load(fh)


def hand_worked() -> List[Tuple[str, Any, Any]]:
    """``(what, got, want)``: each function on sizes worked by hand, and
    the committed file's copied keys against what they copy."""
    c = JOYAI_L5E8
    families = kernels(c, 1)
    experts = experts_cost(c, 1)
    committed = _committed()
    full_pairs = 8192 * 8193 // 2        # 33,558,528 a head
    return [
        # W_qa 2048 x 1536 = 3,145,728; W_qb 1536 x 6144 = 9,437,184;
        # W_kva 2048 x 576 = 1,179,648; W_kvb 512 x 8192 = 4,194,304;
        # W_o 4096 x 2048 = 8,388,608.
        ("the attention's five matrices", attention_products(c), 26_345_472),
        # + inner norms 1536 + 512 and two stream norms 4,096: 26,351,616
        # outside the MLP.  Dense MLP 3 x 2048 x 7168 = 44,040,192.
        ("the leading dense layer", layer_param_count(c, True), 70_391_808),
        # Router 524,288, bias 256, 8 held experts and the shared one 9 x
        # 4,718,592 = 42,467,328: 42,991,872.
        ("a sparse layer, 8 experts held and the shared one",
         layer_param_count(c, False), 69_343_488),
        # The layer, W_eh 4096 x 2048 = 8,388,608, three norms 6,144.
        ("the MTP module", mtp_param_count(c), 77_738_240),
        # Table and head 2 x 16160 x 2048 = 66,191,360; final norm 2,048;
        # 70,391,808 + 4 x 69,343,488 = 347,765,760; MTP 77,738,240.
        ("parameters of joyai-flash at layers 0-4, 8 experts held, an "
         "eighth of the vocabulary, the MTP module", param_count(c),
         491_697_408),
        ("assignments a token sends to held experts, uniform routing",
         held_per_token(c), 0.25),
        # Attention 6 x 26,345,472 = 158,072,832; dense 44,040,192;
        # sparse 5 x (524,288 + 1.25 x 4,718,592 = 6,422,528) =
        # 32,112,640; W_eh 8,388,608; the head twice 66,191,360.
        ("parameters in one token's products on this chip",
         active_param_count(c), 308_805_632.0),
        # 6 x 308,805,632 = 1,852,833,792; attention 6 layers x 32 heads
        # x 3 x (384 + 256) x 4096.5 = 1,510,133,760.
        ("flops per token of the share at sequence 8192",
         train_flops_per_token(c), 1_852_833_792 + 1_510_133_760.0),
        # forward 384 + 256 = 640 a pair and head, backward 1152 + 512 =
        # 1664: 2304, six layers of 32 heads
        ("the attn family (six layers) at batch 1: FLOPs at the real "
         "widths", families["attn"]["flops"], 6 * 2304.0 * 32 * full_pairs),
        # keys' size 32 x 8192 x 192 x 4 B = 201,326,592; values'
        # 134,217,728; row sums 1,048,576.  Forward 2 qk + 2 v + rows,
        # backward 4 qk + 4 v + rows.
        ("the attn family: bytes", families["attn"]["bytes"],
         6 * (6.0 * 201_326_592 + 6.0 * 134_217_728 + 2.0 * 1_048_576)),
        ("calls: attn 12, experts 6 bodies",
         [families[f]["least_calls"] for f in ("attn", "experts")], [12, 6]),
        # Rows 8192 x 0.25 = 2048; 24 x 2048 x 2048 x 768 a layer, five
        # sparse layers.
        ("the held experts' FLOPs a micro-step at batch 1, the forward "
         "pass counted twice", experts["flops"],
         5 * 24.0 * 2048 * 1_572_864),
        # Weights 8 x 4,718,592 x 4 B = 150,994,944 B, four times; rows
        # 2048 x 2048 x 4 B = 16,777,216 B, six times; five layers.
        ("the held experts' bytes a micro-step at batch 1",
         experts["bytes"], 5 * (4.0 * 150_994_944 + 6.0 * 16_777_216)),
        ("the experts family's FLOPs are the experts' cost",
         families["experts"]["flops"], experts["flops"]),
        ("the committed file's sizes give the hand-worked count",
         param_count(committed), 491_697_408),
        ("the committed file's copy of the held count for the shared "
         "readers is its own key", committed["num_experts"],
         committed["n_routed_experts"]),
        ("the committed file's head width is its two parts'",
         committed["qk_head_dim"],
         committed["qk_nope_head_dim"] + committed["qk_rope_head_dim"]),
    ]
