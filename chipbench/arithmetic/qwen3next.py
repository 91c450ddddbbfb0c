"""The arithmetic of the gated-delta hybrid as the program builds it
(``mpit_tpu/models/transformer.py`` ``Qwen3NextDecoder``): what a
configuration with ``"arithmetic": "qwen3next"`` needs, from its shapes
alone.

What the algorithm requires of **this chip's share**, never what a
kernel or the program's recomputation happens to execute.  Every
function takes the configuration's file as a dict and reads the model's
own published keys (``hidden_size``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``linear_num_key_heads``,
``linear_num_value_heads``, ``linear_key_head_dim``,
``linear_value_head_dim``, ``linear_conv_kernel_dim``,
``full_attention_interval``, ``moe_intermediate_size``,
``shared_expert_intermediate_size``, ``num_experts_per_tok``,
``vocab_size``), the share's (``num_experts``: the experts held here;
``router_experts``: the router's width, the published count) and the
cut's (``num_hidden_layers`` layers from layer 0 on; ``train_seq``: the
sequence the cells train at; ``gdn_chunk``: the chunk the delta rule is
computed in).  The contract of such a module is in ``chipbench/spec.py``.

Three Mosaic kernel families, under the scopes the shared readers ask
``flops.kernel_family`` for: flash attention under ``attn`` (16 query
heads over 2 key/value heads, keys **and values** 256 wide), the held
experts' grouped products under ``experts`` (32 experts 512 wide over
5,120 expected rows a layer) and the delta rule's scan under
``gdn_scan``.  **The scan's cost is the algorithm's, whatever runs under
the scope** (:func:`gdn_scan_cost`): q and k read at the key heads, v
read and o written at the value heads, the log-decay and ``beta`` one
number a value head and position, a chunk's pair matrices one product
each under a ``C x C`` decay matrix.  The first form runs the
channel-wise kernels on the decay broadcast over the keys' channels and
the keys repeated (``ops/delta_rule.py`` ``gdn_scan``): it moves more
and multiplies more than this count and reads a lower share of it; a
kernel of the scalar rule's own is read on the same yardstick.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

F32 = 4  # bytes; parameters, gradients and activations are float32


def mixer_of(layer: int, c: Dict[str, Any]) -> str:
    """The mixer of the published model's layer ``layer``, from 0."""
    return ("full_attention"
            if (layer + 1) % c["full_attention_interval"] == 0
            else "linear_attention")


def layer_kinds(c: Dict[str, Any]) -> List[str]:
    return [mixer_of(layer, c) for layer in range(c["num_hidden_layers"])]


def layer_types(c: Dict[str, Any]) -> str:
    """The launcher's ``layer_types`` for the layers held."""
    return ",".join(layer_kinds(c))


def layers_of(c: Dict[str, Any], kind: str) -> int:
    return layer_kinds(c).count(kind)


def gdn_widths(c: Dict[str, Any]) -> Tuple[int, int]:
    """The keys' and the values' width over all heads."""
    return (c["linear_num_key_heads"] * c["linear_key_head_dim"],
            c["linear_num_value_heads"] * c["linear_value_head_dim"])


def gdn_products(c: Dict[str, Any]) -> int:
    """``W_qkvz`` (q, k, v and the gate z), ``W_ba`` (beta and the
    decay's step, a value head each) and ``W_out``."""
    d = c["hidden_size"]
    keys, values = gdn_widths(c)
    return (d * (2 * keys + 2 * values)
            + d * 2 * c["linear_num_value_heads"] + values * d)


def gdn_param_count(c: Dict[str, Any]) -> int:
    """The products' matrices, the one convolution's taps over q, k and
    v, ``A_log`` and ``dt_bias`` a value head, the heads' norm weight
    and the norm before the layer."""
    keys, values = gdn_widths(c)
    return (gdn_products(c)
            + c["linear_conv_kernel_dim"] * (2 * keys + values)
            + 2 * c["linear_num_value_heads"] + c["linear_value_head_dim"]
            + c["hidden_size"])


def attention_products(c: Dict[str, Any]) -> int:
    """``W_q`` with the gate (twice the heads' width), ``W_k``, ``W_v``
    and ``W_o``."""
    d, head = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * head, c["num_key_value_heads"] * head
    return d * 2 * q + 2 * d * kv + q * d


def attention_param_count(c: Dict[str, Any]) -> int:
    """The matrices, the query's and the key's head norms and the norm
    before the layer."""
    return attention_products(c) + 2 * c["head_dim"] + c["hidden_size"]


def _expert_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def _shared_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["shared_expert_intermediate_size"]


def moe_param_count(c: Dict[str, Any]) -> int:
    """A router over all ``router_experts`` (no bias), three stacked
    matrices of the ``num_experts`` held experts, the shared expert's
    three, its gate ``w_s`` and the norm before the MLP."""
    d = c["hidden_size"]
    return (d * c["router_experts"] + c["num_experts"] * _expert_params(c)
            + _shared_params(c) + d + d)


def param_count(c: Dict[str, Any]) -> int:
    """Parameters of the share as the program builds it, all of them
    exchanged: a token table (no position table), the layers (a mixer
    and a sparse MLP each, their norms counted with them), a final norm
    and an untied head."""
    d, v = c["hidden_size"], c["vocab_size"]
    return (v * d + d + d * v
            + layers_of(c, "linear_attention") * gdn_param_count(c)
            + layers_of(c, "full_attention") * attention_param_count(c)
            + c["num_hidden_layers"] * moe_param_count(c))


def held_per_token(c: Dict[str, Any]) -> float:
    """Assignments a token sends to held experts under uniform routing."""
    return c["num_experts_per_tok"] * c["num_experts"] / c["router_experts"]


def active_param_count(c: Dict[str, Any]) -> float:
    """Parameters in one token's products on this chip: every mixer's
    matrices, the routers, the held experts a token is expected to use,
    the shared expert with its gate and the head; the table is a
    look-up, and norms, taps, ``A_log`` and ``dt_bias`` are not
    products."""
    d = c["hidden_size"]
    sparse = (d * c["router_experts"] + held_per_token(c) * _expert_params(c)
              + _shared_params(c) + d)
    return (layers_of(c, "linear_attention") * gdn_products(c)
            + layers_of(c, "full_attention") * attention_products(c)
            + c["num_hidden_layers"] * sparse + d * c["vocab_size"])


def pairs_per_query(seq: int) -> float:
    """(query, key) pairs a causal query sees on average."""
    return (seq + 1) / 2


# -- the delta rule's chunked scan --------------------------------------------


def gdn_chunk_flops(c: Dict[str, Any]) -> float:
    """FLOPs of one chunk of one **key head** with its ``r`` value
    heads, forward, as the chunked scalar-decay algorithm needs them
    (``C`` positions a chunk, ``d_k x d_v`` a state; two a multiply-add):
    the products ``k k^T`` (``s < t``) and ``q k^T`` (``s <= t``) **once
    a key head**, ``C^2`` pairs between them at ``2 d_k`` each (a value
    head's ``C x C`` decay matrix over them is elementwise); a value
    head, the unit-lower system solved for ``d_v + d_k`` right-hand
    sides by substitution, ``C^2 / 2`` multiply-adds a column; ``W = U -
    W_k S``, ``S' = .. + K^T W`` and ``Q S`` at ``2 C d_k d_v`` each; ``B
    W`` over the lower triangle, ``C^2 d_v``.  Decays, sums and gates
    are elementwise and left out."""
    chunk = c["gdn_chunk"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    r = c["linear_num_value_heads"] // c["linear_num_key_heads"]
    return (2.0 * chunk * chunk * dk
            + r * (chunk * chunk * (dk + dv)      # the solve
                   + 3 * 2.0 * chunk * dk * dv    # W, the next state, Q S
                   + chunk * chunk * dv))         # B W


def gdn_scan_cost(c: Dict[str, Any], batch: int) -> Dict[str, float]:
    """FLOPs and HBM bytes of the chunked scans of all Gated DeltaNet
    layers held in one micro-step **as the block runs them**: forward,
    the chunks again in the operator's own backward rule (it keeps q, k,
    v, g, beta and computes every chunk's matrices and states again),
    and the backward pass proper at twice the forward's products.
    Bytes, **at the operands' own sizes**: forward q and k read at the
    key heads' width, v read and o written at the value heads', g and
    ``beta`` a float a value head and position; backward the same five
    read with o's gradient, and their five gradients written; the
    recomputation is inside the backward pass and reads nothing more.  A
    decay broadcast over the keys' channels or a key repeated for its
    value heads is an implementation's traffic and is not counted.  A
    last chunk that is not whole counts whole."""
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    seq, layers = c["train_seq"], layers_of(c, "linear_attention")
    chunks = -(-seq // c["gdn_chunk"])
    forward = batch * hk * chunks * gdn_chunk_flops(c)
    keys, values = (batch * seq * width * F32 for width in gdn_widths(c))
    small = batch * seq * hv * F32            # g, beta or a gradient of one
    once = 2.0 * keys + 2.0 * values + 2.0 * small   # q, k, v, g, beta; o
    # backward: q, k, v, g, beta and do read; dq, dk, dv, dg, dbeta written
    back = 4.0 * keys + 3.0 * values + 4.0 * small
    return {
        "flops": layers * 4.0 * forward,
        "bytes": layers * (once + back),
        "layers": layers,
        "forward_flops": layers * forward,
    }


def train_flops_per_token(c: Dict[str, Any]) -> float:
    """Forward plus backward FLOPs one trained token needs of this
    share, nothing recomputed: 6 a parameter in a product (the held
    experts at their expectation under uniform routing), the attention's
    products over the pairs a query sees (three times the forward pass's
    ``4 head_dim`` a pair and head), and the chunked scan's forward
    three times over (:func:`gdn_chunk_flops`).  Look-ups, norms,
    convolutions, SiLU, sigmoid, softplus, softmax, decays, rotations,
    sort and gathers are left out."""
    pair = 3 * 4 * c["head_dim"]
    scan = 3.0 * gdn_scan_cost(c, 1)["forward_flops"] / c["train_seq"]
    return (6 * active_param_count(c)
            + layers_of(c, "full_attention") * c["num_attention_heads"] * pair
            * pairs_per_query(c["train_seq"])
            + scan)


# -- the Mosaic kernel families ------------------------------------------------


def flash_call_cost(c: Dict[str, Any], batch: int
                    ) -> Dict[str, Tuple[float, float]]:
    """(FLOPs, HBM bytes) of one attention layer's kernels over a batch
    of whole sequences, forward and backward, over the causal half of
    the pairs: forward ``4 head_dim`` a visible pair and query head
    (keys and values both ``head_dim`` wide), backward ``10 head_dim``;
    q, o and their gradients over the query heads, k, v and theirs over
    the KV heads, a row statistic a query head."""
    heads, kv, seq = (c["num_attention_heads"], c["num_key_value_heads"],
                      c["train_seq"])
    head = c["head_dim"]
    pairs = batch * heads * seq * pairs_per_query(seq)
    q_size = batch * heads * seq * head * F32
    kv_size = batch * kv * seq * head * F32
    rows = batch * heads * seq * F32
    return {
        "fwd": (4.0 * head * pairs, 2.0 * q_size + 2.0 * kv_size + rows),
        "bwd": (10.0 * head * pairs, 4.0 * q_size + 4.0 * kv_size + rows),
    }


# The grouped product is a jitted kernel, so the lowered step holds one
# body for each distinct shape however often it is called (as
# ``arithmetic/lfm2.py`` has it).
EXPERT_KERNEL_BODIES = 6
# The scan's three kernels (the forward, the forward again writing the
# chunks' states and solves, the walk back) are inlined a layer.
SCAN_KERNELS_A_LAYER = 3


def experts_cost(c: Dict[str, Any], batch: int) -> Dict[str, float]:
    """FLOPs and HBM bytes of the held experts of all sparse layers in
    one micro-step **as the block runs them**: three grouped products
    forward, the forward again (the block recomputes its sparse branch
    in the backward pass) and six backward, over the rows expected on
    held experts under uniform routing, as ``arithmetic/kimi.py``
    ``experts_cost`` counts them."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    sparse = c["num_hidden_layers"]
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


def kernels(c: Dict[str, Any], batch: int) -> Dict[str, Dict[str, Any]]:
    """The block's Mosaic kernel families by model scope.  ``attn``: the
    attention layers' flash kernels at 256-wide keys and values, a
    forward and a backward call a layer at the least.  ``experts``: the
    grouped products, :func:`experts_cost`; ``least_calls`` the six
    kernel bodies.  ``gdn_scan``: the delta rule's scan at the
    algorithm's cost (:func:`gdn_scan_cost`), whichever kernels serve
    it: three calls a layer."""
    layers = layers_of(c, "full_attention")
    cost = flash_call_cost(c, batch)
    experts = experts_cost(c, batch)
    scan = gdn_scan_cost(c, batch)
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
        "gdn_scan": {
            "scope": "gdn_scan",
            "flops": scan["flops"],
            "bytes": scan["bytes"],
            "least_calls": SCAN_KERNELS_A_LAYER * scan["layers"],
        },
    }


# Qwen3-Next-80B-A3B's published sizes at the cut of the committed
# configuration (layers 0-3, 32 of 512 experts, an eighth of the
# vocabulary), for the hand-worked cases only.
QWEN3NEXT_L4E32 = {
    "hidden_size": 2048, "num_attention_heads": 16, "num_key_value_heads": 2,
    "head_dim": 256, "linear_num_key_heads": 16,
    "linear_num_value_heads": 32, "linear_key_head_dim": 128,
    "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4,
    "full_attention_interval": 4, "moe_intermediate_size": 512,
    "shared_expert_intermediate_size": 512, "num_experts_per_tok": 10,
    "num_experts": 32, "router_experts": 512, "num_hidden_layers": 4,
    "vocab_size": 18992, "train_seq": 8192, "gdn_chunk": 64}


def _committed() -> Dict[str, Any]:
    import json
    import pathlib

    path = (pathlib.Path(__file__).resolve().parent.parent / "configs"
            / "qwen3-next-80b-l4e32.json")
    with open(path) as fh:
        return json.load(fh)


def hand_worked() -> List[Tuple[str, Any, Any]]:
    """``(what, got, want)``: each function on sizes worked by hand, and
    the committed file's copied keys against what they copy."""
    c = QWEN3NEXT_L4E32
    families = kernels(c, 1)
    experts = experts_cost(c, 1)
    scan = gdn_scan_cost(c, 1)
    committed = _committed()
    full_pairs = 8192 * 8193 // 2        # 33,558,528 a head
    return [
        # W_qkvz 2048 x 12288 = 25,165,824; W_ba 2048 x 64 = 131,072;
        # W_out 4096 x 2048 = 8,388,608.
        ("a Gated DeltaNet layer's matrices", gdn_products(c), 33_685_504),
        # + taps 4 x 8192 = 32,768, A_log and dt_bias 64, the heads'
        # norm 128, the layer's norm 2,048.
        ("a Gated DeltaNet mixer with its layer norm", gdn_param_count(c),
         33_720_512),
        # W_q with the gate 2048 x 8192 = 16,777,216; keys and values 2 x
        # 2048 x 512 = 2,097,152; W_o 4096 x 2048 = 8,388,608.
        ("the attention's matrices", attention_products(c), 27_262_976),
        # + q/k norms 512, the layer's norm 2,048.
        ("the attention with its layer norm", attention_param_count(c),
         27_265_536),
        # Router 1,048,576; 32 held experts 32 x 3,145,728 = 100,663,296;
        # shared 3,145,728; its gate 2,048; the norm 2,048.
        ("a sparse MLP, 32 experts held, the gated shared one",
         moe_param_count(c), 104_861_696),
        ("layers held: three Gated DeltaNet, one full attention",
         layer_kinds(c), ["linear_attention"] * 3 + ["full_attention"]),
        # Four layers 3 x 33,720,512 + 27,265,536 + 4 x 104,861,696 =
        # 547,873,856; table and head 2 x 18,992 x 2048 = 77,791,232;
        # final norm 2,048.
        ("parameters of qwen3-next at layers 0-3, 32 experts held, an "
         "eighth of the vocabulary", param_count(c), 625_667_136),
        ("assignments a token sends to held experts, uniform routing",
         held_per_token(c), 0.625),
        # Mixers 3 x 33,685,504 + 27,262,976 = 128,319,488; sparse 4 x
        # (1,048,576 + 0.625 x 3,145,728 + 3,145,728 + 2,048 = 6,162,432)
        # = 24,649,728; the head 38,895,616.
        ("parameters in one token's products on this chip",
         active_param_count(c), 191_864_832.0),
        # k k^T and q k^T 2 x 4096 x 128 = 1,048,576 a key head; a value
        # head: the solve 4096 x 256 = 1,048,576, three products with the
        # state 3 x 2 x 64 x 16,384 = 6,291,456, B W 4096 x 128 =
        # 524,288: 7,864,320, twice.
        ("FLOPs of a chunk of 64 and a key head with its two value heads, "
         "forward", gdn_chunk_flops(c), 16_777_216.0),
        # 16 key heads x 128 chunks x 16,777,216 = 34,359,738,368 a layer
        # forward; three layers; four times (forward, the chunks again,
        # backward at twice).
        ("the scans' FLOPs a micro-step at batch 1", scan["flops"],
         3 * 4.0 * 34_359_738_368),
        # keys 8192 x 2048 x 4 B = 67,108,864 B; values 8192 x 4096 x 4 B
        # = 134,217,728 B; g or beta 8192 x 32 x 4 B = 1,048,576 B: six
        # keys, five values and six smalls a layer.
        ("the scans' bytes a micro-step at batch 1: q and k at 16 heads, "
         "g and beta a float a head, no broadcast", scan["bytes"],
         3 * (6.0 * 67_108_864 + 5.0 * 134_217_728 + 6.0 * 1_048_576)),
        # 6 x 191,864,832 = 1,151,188,992; attention 16 heads x 3 x 1024 x
        # 4096.5 = 201,351,168; scans 3 x 3 x 34,359,738,368 / 8192 =
        # 37,748,736.
        ("flops per token of the share at sequence 8192",
         train_flops_per_token(c),
         1_151_188_992 + 201_351_168.0 + 37_748_736.0),
        ("the attn family (one layer) at batch 1: 14 x 256 a pair and "
         "head over the causal half", families["attn"]["flops"],
         3584.0 * 16 * full_pairs),
        # q or o 16 x 8192 x 256 x 4 B = 134,217,728 B, six of them; k or
        # v 2 x 8192 x 1024 B = 16,777,216 B, six; rows 524,288 B, two.
        ("the attn family: bytes", families["attn"]["bytes"],
         6.0 * 134_217_728 + 6.0 * 16_777_216 + 2.0 * 524_288),
        ("calls: attn 2, experts 6 bodies, the scan 3 a layer",
         [families[f]["least_calls"]
          for f in ("attn", "experts", "gdn_scan")], [2, 6, 9]),
        # Rows 8192 x 0.625 = 5120; 24 x 5120 x 2048 x 512 a layer, four
        # layers.
        ("the held experts' FLOPs a micro-step at batch 1, the forward "
         "pass counted twice", experts["flops"],
         4 * 24.0 * 5120 * 1_048_576),
        # Weights 32 x 3,145,728 x 4 B = 402,653,184 B, four times; rows
        # 5120 x 2048 x 4 B = 41,943,040 B, six times; four layers.
        ("the held experts' bytes a micro-step at batch 1",
         experts["bytes"], 4 * (4.0 * 402_653_184 + 6.0 * 41_943_040)),
        ("the experts family's FLOPs are the experts' cost",
         families["experts"]["flops"], experts["flops"]),
        ("the scan family's cost is the algorithm's",
         [families["gdn_scan"]["flops"], families["gdn_scan"]["bytes"]],
         [scan["flops"], scan["bytes"]]),
        ("the committed file's sizes give the hand-worked count",
         param_count(committed), 625_667_136),
        ("the committed file's flattened copies for the launcher are "
         "what they copy",
         [committed["layer_types_here"], committed["gdn_chunk"],
          committed["router_experts"]],
         [layer_types(committed), 64,
          committed["published"]["num_experts"]]),
    ]
