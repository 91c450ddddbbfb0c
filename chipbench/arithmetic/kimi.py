"""The arithmetic of the linear-attention hybrid as the program builds it
(``mpit_tpu/models/transformer.py`` ``KimiDecoder``): what a
configuration with ``"arithmetic": "kimi"`` needs, from its shapes
alone.

What the algorithm requires of **this chip's share**, never what a
kernel or the program's recomputation happens to execute.  Every
function takes the configuration's file as a dict and reads the model's
own published keys (``hidden_size``, ``num_attention_heads``,
``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``intermediate_size``, ``moe_intermediate_size``,
``num_shared_experts``, ``first_k_dense_replace``,
``num_experts_per_token``, ``vocab_size``, and ``linear_attn_config``'s
``num_heads``, ``head_dim``, ``short_conv_kernel_size`` and its two
lists of layer numbers, which count from 1), the share's
(``num_experts``: the experts held here; ``router_experts``: the
router's width, the published count) and the cut's
(``num_hidden_layers`` layers from the first on; ``train_seq``: the
sequence the cells train at; ``kda_chunk``: the chunk the delta rule is
computed in).  The contract of such a module is in ``chipbench/spec.py``.

Two Mosaic kernel families, under the scopes the shared readers ask
``flops.kernel_family`` for: flash attention under ``attn`` (the latent
attention's layers, keys 192 wide and values 128, as JoyAI's) and the
held experts' grouped products under ``experts``.  **The delta rule's
chunked scan is XLA's fusions and products under the scope
``kda_scan``, no Mosaic call**: :func:`kda_scan_cost` counts what the
chunked algorithm needs at the stated chunk size and
``layers/kda_scan_roofline.py`` holds the scope's device time to it, as
``conv_mix_roofline`` holds LFM2's gates to ``conv_mix_cost``.  The
count does not change when the implementation does.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

F32 = 4  # bytes; parameters, gradients and activations are float32


def _qk(c: Dict[str, Any]) -> int:
    return c["qk_nope_head_dim"] + c["qk_rope_head_dim"]


def _linear(c: Dict[str, Any]) -> Dict[str, Any]:
    return c["linear_attn_config"]


def held_layers(c: Dict[str, Any]) -> range:
    """The published model's layers held here, by its own count."""
    return range(1, c["num_hidden_layers"] + 1)


def kda_layers(c: Dict[str, Any]) -> int:
    return sum(n in _linear(c)["kda_layers"] for n in held_layers(c))


def attention_layers(c: Dict[str, Any]) -> int:
    return sum(n in _linear(c)["full_attn_layers"] for n in held_layers(c))


def dense_layers(c: Dict[str, Any]) -> int:
    return min(c["first_k_dense_replace"], c["num_hidden_layers"])


def sparse_layers(c: Dict[str, Any]) -> int:
    return c["num_hidden_layers"] - dense_layers(c)


def layer_types(c: Dict[str, Any]) -> str:
    """The launcher's ``layer_types`` for the layers held, from the
    configuration's two lists."""
    linear = _linear(c)
    return ",".join("kda" if n in linear["kda_layers"] else "full_attention"
                    for n in held_layers(c))


def kda_products(c: Dict[str, Any]) -> int:
    """``W_q, W_k, W_v, W_o``, the decay's and the gate's two low-rank
    maps (a head's width inside) and ``w_beta``."""
    d, heads, hd = c["hidden_size"], _linear(c)["num_heads"], \
        _linear(c)["head_dim"]
    wide = heads * hd
    return 4 * d * wide + 2 * (d * hd + hd * wide) + d * heads


def kda_param_count(c: Dict[str, Any]) -> int:
    """The products' matrices, three convolutions' taps, ``A_log`` a
    head, ``dt_bias`` a channel and the heads' norm weight."""
    heads, hd = _linear(c)["num_heads"], _linear(c)["head_dim"]
    return (kda_products(c)
            + 3 * _linear(c)["short_conv_kernel_size"] * heads * hd
            + heads + heads * hd + hd)


def attention_products(c: Dict[str, Any]) -> int:
    """``W_q`` (no query latent), ``W_kva``, ``W_kvb`` and ``W_o``."""
    d, heads = c["hidden_size"], c["num_attention_heads"]
    return (d * heads * _qk(c)
            + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * heads * (c["qk_nope_head_dim"]
                                           + c["v_head_dim"])
            + heads * c["v_head_dim"] * d)


def attention_param_count(c: Dict[str, Any]) -> int:
    return attention_products(c) + c["kv_lora_rank"]   # the latent's norm


def _dense_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def _expert_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def mlp_param_count(c: Dict[str, Any], dense: bool) -> int:
    """Dense: three matrices.  Sparse: a router over all
    ``router_experts``, its selection bias, three stacked matrices of
    the ``num_experts`` held experts and the shared expert's three."""
    if dense:
        return _dense_params(c)
    return (c["hidden_size"] * c["router_experts"] + c["router_experts"]
            + (c["num_experts"] + c["num_shared_experts"])
            * _expert_params(c))


def param_count(c: Dict[str, Any]) -> int:
    """Parameters of the share as the program builds it, all of them
    exchanged: a token table (no position table), the layers (a mixer,
    an MLP and two RMSNorm weights over the stream each), a final
    RMSNorm and an untied head."""
    d, v = c["hidden_size"], c["vocab_size"]
    n_dense = dense_layers(c)
    return (v * d + d + d * v
            + kda_layers(c) * kda_param_count(c)
            + attention_layers(c) * attention_param_count(c)
            + c["num_hidden_layers"] * 2 * d
            + n_dense * mlp_param_count(c, True)
            + sparse_layers(c) * mlp_param_count(c, False))


def held_per_token(c: Dict[str, Any]) -> float:
    """Assignments a token sends to held experts under uniform routing."""
    return (c["num_experts_per_token"] * c["num_experts"]
            / c["router_experts"])


def active_param_count(c: Dict[str, Any]) -> float:
    """Parameters in one token's products on this chip: every mixer's
    matrices, the dense MLP, the routers, the held experts a token is
    expected to use, the shared expert and the head; the table is a
    look-up, and norms, taps, ``A_log``, ``dt_bias`` and the selection
    bias are not products."""
    d = c["hidden_size"]
    sparse = (d * c["router_experts"]
              + (held_per_token(c) + c["num_shared_experts"])
              * _expert_params(c))
    return (kda_layers(c) * kda_products(c)
            + attention_layers(c) * attention_products(c)
            + dense_layers(c) * _dense_params(c)
            + sparse_layers(c) * sparse
            + d * c["vocab_size"])


def pairs_per_query(seq: int) -> float:
    """(query, key) pairs a causal query sees on average."""
    return (seq + 1) / 2


# -- the delta rule's chunked scan --------------------------------------------


def kda_chunk_flops(c: Dict[str, Any]) -> float:
    """FLOPs of one chunk of one head, forward, as the chunked algorithm
    needs them (``C`` positions a chunk, ``d`` a state's side; two a
    multiply-add): the pair matrices ``A`` (``s < t``) and ``B`` (``s <=
    t``), ``C^2`` pairs between them at ``2 d`` each; the unit-lower
    system solved for ``d_v + d_k`` right-hand sides by substitution,
    ``C^2 / 2`` multiply-adds a column; ``W = U - W_k S``, ``S' = .. +
    K^T W`` and ``Q S`` at ``2 C d^2`` each; ``B W`` over the lower
    triangle, ``C^2 d``.  Decays, sums and gates are elementwise and
    left out."""
    chunk, d = c["kda_chunk"], _linear(c)["head_dim"]
    return (2.0 * chunk * chunk * d          # A and B
            + chunk * chunk * 2.0 * d        # the solve
            + 3 * 2.0 * chunk * d * d        # W, the next state, Q S
            + chunk * chunk * d)             # B W


def kda_scan_cost(c: Dict[str, Any], batch: int) -> Dict[str, float]:
    """FLOPs and HBM bytes of the chunked scans of all KDA layers held in
    one micro-step **as the block runs them**: forward, the chunks again
    in the operator's own backward rule (it keeps q, k, v, g, beta and
    computes every chunk's matrices and states again), and the backward
    pass proper at twice the forward's products.  Bytes: forward q, k,
    v, g read and o written, a head's width a position each, and beta;
    backward the same five read with o's gradient and their five
    gradients written; the recomputation is inside the backward pass
    and reads nothing more.  A last chunk that is not whole counts
    whole."""
    linear = _linear(c)
    heads, d, chunk = linear["num_heads"], linear["head_dim"], c["kda_chunk"]
    seq, layers = c["train_seq"], kda_layers(c)
    chunks = -(-seq // chunk)
    forward = batch * heads * chunks * kda_chunk_flops(c)
    wide = batch * seq * heads * d * F32      # q, k, v, g, o or a gradient
    beta = batch * seq * heads * F32
    return {
        "flops": layers * 4.0 * forward,
        "bytes": layers * ((5.0 * wide + beta) + (10.0 * wide + 2.0 * beta)),
        "layers": layers,
        "forward_flops": layers * forward,
    }


def train_flops_per_token(c: Dict[str, Any]) -> float:
    """Forward plus backward FLOPs one trained token needs of this
    share, nothing recomputed: 6 a parameter in a product (the held
    experts at their expectation under uniform routing), the latent
    attention's products over the pairs a query sees (three times the
    forward pass's ``2 qk + 2 v`` a pair and head), and the chunked
    scan's forward three times over (:func:`kda_chunk_flops`).
    Look-ups, norms, convolutions, SiLU, sigmoid, softmax, decays, sort
    and gathers are left out."""
    pair = 3 * (2 * _qk(c) + 2 * c["v_head_dim"])
    scan = 3.0 * kda_scan_cost(c, 1)["forward_flops"] / c["train_seq"]
    return (6 * active_param_count(c)
            + attention_layers(c) * c["num_attention_heads"] * pair
            * pairs_per_query(c["train_seq"])
            + scan)


# -- the Mosaic kernel families ------------------------------------------------


def flash_call_cost(c: Dict[str, Any], batch: int
                    ) -> Dict[str, Tuple[float, float]]:
    """(FLOPs, HBM bytes) of one latent-attention layer's kernels over a
    batch of whole sequences, forward and backward, **at the heads' real
    widths**, as ``arithmetic/joyai.py`` ``flash_call_cost`` counts
    them: forward ``2 qk + 2 v`` a visible pair and head, backward ``6
    qk + 4 v``; q, k at the keys' width and v, o at the values'."""
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
# ``arithmetic/lfm2.py`` has it).
EXPERT_KERNEL_BODIES = 6


def experts_cost(c: Dict[str, Any], batch: int) -> Dict[str, float]:
    """FLOPs and HBM bytes of the held experts of all sparse layers in
    one micro-step **as the block runs them**: forward, the forward
    again (the block recomputes its sparse branch in the backward pass)
    and backward, over the rows expected on held experts under uniform
    routing, as ``arithmetic/joyai.py`` ``experts_cost`` counts them."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    sparse = sparse_layers(c)
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
    latent-attention layers' flash kernels, a forward and a backward
    call a layer at the least.  ``experts``: the grouped products,
    :func:`experts_cost`; ``least_calls`` the six kernel bodies.  The
    delta rule's scan is no Mosaic kernel and is not here
    (:func:`kda_scan_cost`)."""
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


# Kimi-Linear-48B-A3B's published sizes at the cut of the committed
# configuration (layers 1-5, 8 of 256 experts, an eighth of the
# vocabulary), for the hand-worked cases only.
KIMI_L5E8 = {
    "hidden_size": 2304, "num_attention_heads": 32, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "intermediate_size": 9216, "moe_intermediate_size": 1024,
    "num_shared_experts": 1, "first_k_dense_replace": 1,
    "num_hidden_layers": 5, "num_experts": 8, "router_experts": 256,
    "num_experts_per_token": 8, "vocab_size": 20480, "train_seq": 8192,
    "kda_chunk": 64,
    "linear_attn_config": {
        "full_attn_layers": [4], "head_dim": 128, "kda_layers": [1, 2, 3, 5],
        "num_heads": 32, "short_conv_kernel_size": 4}}


def _committed() -> Dict[str, Any]:
    import json
    import pathlib

    path = (pathlib.Path(__file__).resolve().parent.parent / "configs"
            / "kimi-linear-48b-l5e8.json")
    with open(path) as fh:
        return json.load(fh)


def hand_worked() -> List[Tuple[str, Any, Any]]:
    """``(what, got, want)``: each function on sizes worked by hand, and
    the committed file's copied keys against what they copy."""
    c = KIMI_L5E8
    families = kernels(c, 1)
    experts = experts_cost(c, 1)
    scan = kda_scan_cost(c, 1)
    committed = _committed()
    linear = committed["linear_attn_config"]
    full_pairs = 8192 * 8193 // 2        # 33,558,528 a head
    return [
        # W_q, W_k, W_v, W_o 4 x 2304 x 4096 = 37,748,736; the two
        # low-rank maps 2 x (294,912 + 524,288) = 1,638,400; w_beta
        # 2304 x 32 = 73,728.
        ("a KDA layer's matrices", kda_products(c), 39_460_864),
        # + taps 3 x 4 x 4096 = 49,152, A_log 32, dt_bias 4096, the
        # heads' norm 128.
        ("a KDA layer's parameters", kda_param_count(c), 39_514_272),
        # W_q 2304 x 6144 = 14,155,776; W_kva 2304 x 576 = 1,327,104;
        # W_kvb 512 x 8192 = 4,194,304; W_o 4096 x 2304 = 9,437,184.
        ("the latent attention's four matrices", attention_products(c),
         29_114_368),
        ("the latent attention's parameters", attention_param_count(c),
         29_114_880),
        ("the dense MLP", mlp_param_count(c, True), 63_700_992),
        # Router 589,824, bias 256, 8 held experts and the shared one 9 x
        # 7,077,888 = 63,700,992.
        ("a sparse MLP, 8 experts held and the shared one",
         mlp_param_count(c, False), 64_291_072),
        ("layers held: four KDA, one latent attention, one dense MLP",
         [kda_layers(c), attention_layers(c), dense_layers(c),
          sparse_layers(c)], [4, 1, 1, 4]),
        # Table and head 2 x 20480 x 2304 = 94,371,840; final norm 2,304;
        # mixers 4 x 39,514,272 + 29,114,880 = 187,171,968; stream norms
        # 5 x 4,608 = 23,040; MLPs 63,700,992 + 4 x 64,291,072 =
        # 320,865,280.
        ("parameters of kimi-linear at layers 1-5, 8 experts held, an "
         "eighth of the vocabulary", param_count(c), 602_434_432),
        ("assignments a token sends to held experts, uniform routing",
         held_per_token(c), 0.25),
        # KDA 4 x 39,460,864 = 157,843,456; attention 29,114,368; dense
        # 63,700,992; sparse 4 x (589,824 + 1.25 x 7,077,888 = 9,437,184)
        # = 37,748,736; the head 47,185,920.
        ("parameters in one token's products on this chip",
         active_param_count(c), 335_593_472.0),
        # A and B 2 x 4096 x 128 = 1,048,576; the solve the same; three
        # products with the state 3 x 2 x 64 x 16384 = 6,291,456; B W
        # 4096 x 128 = 524,288.
        ("FLOPs of a chunk of 64 and a head, forward", kda_chunk_flops(c),
         8_912_896.0),
        # 32 heads x 128 chunks x 8,912,896 = 36,507,222,016 a layer
        # forward; four layers; four times (forward, the chunks again,
        # backward at twice).
        ("the scans' FLOPs a micro-step at batch 1", scan["flops"],
         4 * 4.0 * 36_507_222_016),
        # wide 8192 x 4096 x 4 B = 134,217,728 B; beta 1,048,576 B;
        # fifteen wides and three betas a layer.
        ("the scans' bytes a micro-step at batch 1", scan["bytes"],
         4 * (15.0 * 134_217_728 + 3.0 * 1_048_576)),
        # 6 x 335,593,472 = 2,013,560,832; attention 32 heads x 3 x (384
        # + 256) x 4096.5 = 251,688,960; scans 3 x 4 x 36,507,222,016 /
        # 8192 = 53,477,376.
        ("flops per token of the share at sequence 8192",
         train_flops_per_token(c),
         2_013_560_832 + 251_688_960.0 + 53_477_376.0),
        ("the attn family (one layer) at batch 1: FLOPs at the real "
         "widths", families["attn"]["flops"], 2304.0 * 32 * full_pairs),
        ("the attn family: bytes", families["attn"]["bytes"],
         6.0 * 201_326_592 + 6.0 * 134_217_728 + 2.0 * 1_048_576),
        ("calls: attn 2, experts 6 bodies",
         [families[f]["least_calls"] for f in ("attn", "experts")], [2, 6]),
        # Rows 8192 x 0.25 = 2048; 24 x 2048 x 2304 x 1024 a layer, four
        # sparse layers.
        ("the held experts' FLOPs a micro-step at batch 1, the forward "
         "pass counted twice", experts["flops"],
         4 * 24.0 * 2048 * 2_359_296),
        # Weights 8 x 7,077,888 x 4 B = 226,492,416 B, four times; rows
        # 2048 x 2304 x 4 B = 18,874,368 B, six times; four layers.
        ("the held experts' bytes a micro-step at batch 1",
         experts["bytes"], 4 * (4.0 * 226_492_416 + 6.0 * 18_874_368)),
        ("the experts family's FLOPs are the experts' cost",
         families["experts"]["flops"], experts["flops"]),
        ("the committed file's sizes give the hand-worked count",
         param_count(committed), 602_434_432),
        ("the committed file's flattened copies for the launcher are "
         "what they copy",
         [committed["layer_types_here"], committed["kda_heads"],
          committed["kda_head_dim"], committed["short_conv_kernel_size"],
          committed["q_rank_here"], committed["rope_theta_here"],
          committed["kda_chunk"]],
         [layer_types(committed), linear["num_heads"], linear["head_dim"],
          linear["short_conv_kernel_size"], committed["q_lora_rank"] or 0,
          0 if committed["mla_use_nope"] else committed["rope_theta"], 64]),
        ("the committed file's lists name each held layer once",
         sorted(linear["kda_layers"] + linear["full_attn_layers"]),
         list(held_layers(committed))),
    ]
