"""The arithmetic of the state-space hybrid as the program builds it
(``mpit_tpu/models/transformer.py`` ``NemotronDecoder``): what a
configuration with ``"arithmetic": "nemotron"`` needs, from its shapes
alone.

What the algorithm requires of **this chip's share**, never what a
kernel or the program's recomputation happens to execute.  Every
function takes the configuration's file as a dict and reads the model's
own published keys (``hidden_size``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``mamba_num_heads``,
``mamba_head_dim``, ``n_groups``, ``ssm_state_size``, ``conv_kernel``,
``chunk_size``, ``moe_intermediate_size``,
``moe_shared_expert_intermediate_size``, ``n_shared_experts``,
``num_experts_per_tok``, ``vocab_size``, and
``hybrid_override_pattern``: ``M`` a Mamba-2 layer, ``E`` a sparse MLP,
``*`` attention, one branch a layer), the share's (``n_routed_experts``:
the experts held here; ``router_experts``: the router's width, the
published count) and the cut's (``num_hidden_layers`` layers from the
first on; ``train_seq``: the sequence the cell trains at).  The contract
of such a module is in ``chipbench/spec.py``.

Two Mosaic kernel families, under the scopes the shared readers ask
``flops.kernel_family`` for: flash attention under ``attn`` (32 query
heads over 2 key/value heads of 128, no positional term) and the held
experts' grouped products under ``experts``, **two a forward pass at the
experts' own inner width** (``relu(h U)^2 D``: no gate; 1856 columns,
whatever tile the kernels round it up to).  **The state-space scan is
XLA's fusions and products under the scope ``ssd_scan``, no Mosaic
call**: :func:`ssd_scan_cost` counts what the chunked algorithm needs at
the stated chunk size and ``layers/ssd_scan_roofline.py`` holds the
scope's device time to it, as ``kda_scan_roofline`` holds the delta
rule's.  The count does not change when the implementation does.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

F32 = 4  # bytes; parameters, gradients and activations are float32
KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


def layer_kinds(c: Dict[str, Any]) -> List[str]:
    """The held layers' kinds, from the pattern cut to them."""
    return [KINDS[mark] for mark in c["hybrid_override_pattern"]]


def layer_types(c: Dict[str, Any]) -> str:
    """The launcher's ``layer_types`` for the layers held."""
    return ",".join(layer_kinds(c))


def layers_of(c: Dict[str, Any], kind: str) -> int:
    return layer_kinds(c).count(kind)


def ssm_inner(c: Dict[str, Any]) -> int:
    """The mixer's inner width: heads times a head's width (``expand``
    is read by nothing)."""
    return c["mamba_num_heads"] * c["mamba_head_dim"]


def ssm_mixed(c: Dict[str, Any]) -> int:
    """The channels the convolution runs over: x, B and C together."""
    return ssm_inner(c) + 2 * c["n_groups"] * c["ssm_state_size"]


def mamba_products(c: Dict[str, Any]) -> int:
    """``W_in`` (to z, xBC and a step a head) and ``W_out``."""
    d = c["hidden_size"]
    return (d * (ssm_inner(c) + ssm_mixed(c) + c["mamba_num_heads"])
            + ssm_inner(c) * d)


def mamba_param_count(c: Dict[str, Any]) -> int:
    """The two matrices, the convolution's taps and bias, ``dt_bias``,
    ``A_log`` and ``D`` a head, the gated norm's weight a channel and
    the layer's norm."""
    return (mamba_products(c) + (c["conv_kernel"] + 1) * ssm_mixed(c)
            + 3 * c["mamba_num_heads"] + ssm_inner(c) + c["hidden_size"])


def attention_products(c: Dict[str, Any]) -> int:
    """wq and wo over all query heads, wk and wv over the KV heads."""
    d, head = c["hidden_size"], c["head_dim"]
    return (2 * d * c["num_attention_heads"] * head
            + 2 * d * c["num_key_value_heads"] * head)


def attention_param_count(c: Dict[str, Any]) -> int:
    return attention_products(c) + c["hidden_size"]


def _expert_params(c: Dict[str, Any]) -> int:
    """One routed expert: two matrices, no gate."""
    return 2 * c["hidden_size"] * c["moe_intermediate_size"]


def _shared_params(c: Dict[str, Any]) -> int:
    return (c["n_shared_experts"] * 2 * c["hidden_size"]
            * c["moe_shared_expert_intermediate_size"])


def moe_param_count(c: Dict[str, Any]) -> int:
    """A router over all ``router_experts``, its selection bias, the two
    stacked matrices of the ``n_routed_experts`` held experts, the
    shared expert's two and the layer's norm."""
    d = c["hidden_size"]
    return (d * c["router_experts"] + c["router_experts"]
            + c["n_routed_experts"] * _expert_params(c) + _shared_params(c)
            + d)


def param_count(c: Dict[str, Any]) -> int:
    """Parameters of the share as the program builds it, all of them
    exchanged: a token table (no position table), the layers (one branch
    and one RMSNorm weight over the stream each), a final RMSNorm and an
    untied head."""
    d, v = c["hidden_size"], c["vocab_size"]
    return (v * d + d + d * v
            + layers_of(c, "mamba") * mamba_param_count(c)
            + layers_of(c, "attention") * attention_param_count(c)
            + layers_of(c, "moe") * moe_param_count(c))


def held_per_token(c: Dict[str, Any]) -> float:
    """Assignments a token sends to held experts under uniform routing."""
    return (c["num_experts_per_tok"] * c["n_routed_experts"]
            / c["router_experts"])


def active_param_count(c: Dict[str, Any]) -> float:
    """Parameters in one token's products on this chip: the mixers' and
    the attention's matrices, the routers, the held experts a token is
    expected to use, the shared expert and the head; the table is a
    look-up, and norms, taps, biases, ``A_log``, ``dt_bias`` and ``D``
    are not products."""
    d = c["hidden_size"]
    sparse = (d * c["router_experts"] + held_per_token(c) * _expert_params(c)
              + _shared_params(c))
    return (layers_of(c, "mamba") * mamba_products(c)
            + layers_of(c, "attention") * attention_products(c)
            + layers_of(c, "moe") * sparse + d * c["vocab_size"])


def pairs_per_query(seq: int) -> float:
    """(query, key) pairs a causal query sees on average."""
    return (seq + 1) / 2


# -- the state-space scan -----------------------------------------------------


def ssd_chunk_flops(c: Dict[str, Any]) -> float:
    """FLOPs of one chunk of one **group** of heads, forward, as the
    chunked algorithm needs them (``Q`` positions a chunk, ``N`` the
    state's columns, ``P`` a head's width, ``H / G`` heads a group; two
    a multiply-add): the pair matrix ``C B^T`` once a group, ``Q^2 / 2``
    pairs (``s <= t``) at ``2 N``; a head, the pairs applied to ``x``,
    ``Q^2 / 2`` at ``2 P``, the chunk's contribution to the state and
    the read-out of the state it starts from, ``2 Q P N`` each.  Decays,
    sums, gates and the carry from chunk to chunk (``P N`` a head and
    chunk) are elementwise and left out."""
    q, n, p = c["chunk_size"], c["ssm_state_size"], c["mamba_head_dim"]
    per = c["mamba_num_heads"] // c["n_groups"]
    return q * q * n + per * (q * q * p + 2 * 2.0 * q * p * n)


def ssd_scan_cost(c: Dict[str, Any], batch: int) -> Dict[str, float]:
    """FLOPs and HBM bytes of the chunked scans of all Mamba layers held
    in one micro-step **as the block runs them**: forward, the chunks
    again in the operator's own backward rule (it keeps x, dt, A, B, C
    and makes every chunk's matrices and the chunk-start states again),
    and the backward pass proper at twice the forward's products.
    Bytes: forward x read and y written (a head's width a position
    each), B and C (a group's state width each) and the step a head;
    backward the same read with y's gradient and the four gradients
    written; the recomputation is inside the backward pass and reads
    nothing more.  A last chunk that is not whole counts whole."""
    heads, p = c["mamba_num_heads"], c["mamba_head_dim"]
    groups, n = c["n_groups"], c["ssm_state_size"]
    seq, layers = c["train_seq"], layers_of(c, "mamba")
    chunks = -(-seq // c["chunk_size"])
    forward = batch * groups * chunks * ssd_chunk_flops(c)
    wide = batch * seq * heads * p * F32        # x, y or a gradient of one
    shared = batch * seq * groups * n * F32     # B or C, or a gradient
    step = batch * seq * heads * F32
    once = 2.0 * wide + 2.0 * shared + step     # x, B, C, dt in; y out
    return {
        "flops": layers * 4.0 * forward,
        # backward: x, B, C, dt and dy read; dx, dB, dC, ddt written
        "bytes": layers * (once + (once + wide + 2.0 * shared + step)),
        "layers": layers,
        "forward_flops": layers * forward,
    }


def train_flops_per_token(c: Dict[str, Any]) -> float:
    """Forward plus backward FLOPs one trained token needs of this
    share, nothing recomputed: 6 a parameter in a product (the held
    experts at their expectation under uniform routing), the attention's
    products over the pairs a query sees (three times the forward pass's
    ``4 head_dim`` a pair and head), and the chunked scan's forward
    three times over (:func:`ssd_chunk_flops`).  Look-ups, norms,
    convolutions, SiLU, sigmoid, softplus, softmax, decays, sort and
    gathers are left out."""
    pair = 3 * 4 * c["head_dim"]
    scan = 3.0 * ssd_scan_cost(c, 1)["forward_flops"] / c["train_seq"]
    return (6 * active_param_count(c)
            + layers_of(c, "attention") * c["num_attention_heads"] * pair
            * pairs_per_query(c["train_seq"])
            + scan)


# -- the Mosaic kernel families ------------------------------------------------


def flash_call_cost(c: Dict[str, Any], batch: int
                    ) -> Dict[str, Tuple[float, float]]:
    """(FLOPs, HBM bytes) of one attention layer's kernels over a batch
    of whole sequences, forward and backward: forward ``4 head_dim`` a
    visible pair and query head, backward ``10 head_dim``; q, o and
    their gradients over the query heads, k, v and theirs over the KV
    heads, a row statistic a query head."""
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
# body for each distinct shape however often it is called: up and down
# forward, each one's rows' gradient and each one's weights' gradient.
EXPERT_KERNEL_BODIES = 6


def experts_cost(c: Dict[str, Any], batch: int) -> Dict[str, float]:
    """FLOPs and HBM bytes of the held experts of all sparse layers in
    one micro-step **as the block runs them**: **two** products forward
    at the experts' own inner width (no gate; whatever pad or masked
    tile the implementation makes is not counted), the forward again
    (the block recomputes its sparse branch in the backward pass) and
    four backward, over the rows expected on held experts under uniform
    routing: ``2 x 2 d f`` a row forward, twice, and ``2 x 4 d f``
    backward, ``16 d f`` in all.  Bytes: the held experts' weights read
    in each of the three passes and their gradients written; a row of
    ``d`` read and one written a pass, and the backward pass's two
    cotangents."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    sparse = layers_of(c, "moe")
    rows = batch * c["train_seq"] * held_per_token(c)
    weights = c["n_routed_experts"] * _expert_params(c) * F32
    rows_bytes = sparse * 6.0 * rows * d * F32
    return {
        "flops": sparse * 16.0 * rows * d * f,
        "bytes": sparse * 4.0 * weights + rows_bytes,
        # the part of the bytes that scales with the routing
        # (layers/held_experts_roofline.py)
        "rows_bytes": rows_bytes,
    }


def kernels(c: Dict[str, Any], batch: int) -> Dict[str, Dict[str, Any]]:
    """The block's Mosaic kernel families by model scope.  ``attn``: the
    attention layers' flash kernels, a forward and a backward call a
    layer at the least.  ``experts``: the grouped products,
    :func:`experts_cost`; ``least_calls`` the six kernel bodies.  The
    state-space scan is no Mosaic kernel and is not here
    (:func:`ssd_scan_cost`)."""
    layers = layers_of(c, "attention")
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


# Nemotron-3-Nano-30B-A3B's published sizes at the cut of the committed
# configuration (layers 0-8, 8 of 128 experts, an eighth of the
# vocabulary), for the hand-worked cases only.
NEMOTRON_L9E8 = {
    "hidden_size": 2688, "num_attention_heads": 32, "num_key_value_heads": 2,
    "head_dim": 128, "mamba_num_heads": 64, "mamba_head_dim": 64,
    "n_groups": 8, "ssm_state_size": 128, "conv_kernel": 4,
    "chunk_size": 128, "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_shared_experts": 1,
    "num_experts_per_tok": 6, "n_routed_experts": 8, "router_experts": 128,
    "num_hidden_layers": 9, "hybrid_override_pattern": "MEMEM*EME",
    "vocab_size": 16384, "train_seq": 8192}


def _committed() -> Dict[str, Any]:
    import json
    import pathlib

    path = (pathlib.Path(__file__).resolve().parent.parent / "configs"
            / "nemotron-3-nano-30b-l9e8.json")
    with open(path) as fh:
        return json.load(fh)


def hand_worked() -> List[Tuple[str, Any, Any]]:
    """``(what, got, want)``: each function on sizes worked by hand, and
    the committed file's copied keys against what they copy."""
    c = NEMOTRON_L9E8
    families = kernels(c, 1)
    experts = experts_cost(c, 1)
    scan = ssd_scan_cost(c, 1)
    committed = _committed()
    full_pairs = 8192 * 8193 // 2        # 33,558,528 a head
    return [
        ("the mixer's inner width and the convolution's channels",
         [ssm_inner(c), ssm_mixed(c)], [4096, 6144]),
        # W_in 2688 x (4096 + 6144 + 64 = 10304) = 27,697,152; W_out
        # 4096 x 2688 = 11,010,048.
        ("a Mamba layer's two matrices", mamba_products(c), 38_707_200),
        # + taps 4 x 6144 = 24,576, the conv's bias 6,144, dt_bias,
        # A_log and D 192, the gated norm 4,096, the layer's norm 2,688.
        ("a Mamba layer's parameters", mamba_param_count(c), 38_744_896),
        # wq and wo 2 x 2688 x 4096 = 22,020,096; wk and wv 2 x 2688 x
        # 256 = 1,376,256.
        ("the attention's four matrices", attention_products(c),
         23_396_352),
        ("an attention layer's parameters", attention_param_count(c),
         23_399_040),
        # Router 344,064, bias 128, 8 held experts 8 x 9,977,856 =
        # 79,822,848, the shared expert 2 x 2688 x 3712 = 19,955,712,
        # the norm 2,688.
        ("a sparse layer, 8 experts held and the shared one",
         moe_param_count(c), 100_125_440),
        ("layers held: four Mamba, four sparse, one attention",
         [layers_of(c, kind) for kind in ("mamba", "moe", "attention")],
         [4, 4, 1]),
        # Nine layers 4 x 38,744,896 + 4 x 100,125,440 + 23,399,040 =
        # 578,880,384; table and head 2 x 16384 x 2688 = 88,080,384; the
        # final norm 2,688.
        ("parameters of nemotron-3-nano at layers 0-8, 8 experts held, "
         "an eighth of the vocabulary", param_count(c), 666_963_456),
        ("assignments a token sends to held experts, uniform routing",
         held_per_token(c), 0.375),
        # Mamba 4 x 38,707,200 = 154,828,800; attention 23,396,352;
        # sparse 4 x (344,064 + 0.375 x 9,977,856 = 3,741,696 +
        # 19,955,712 = 24,041,472) = 96,165,888; the head 44,040,192.
        ("parameters in one token's products on this chip",
         active_param_count(c), 318_431_232.0),
        # C B^T 128 x 128 x 128 = 2,097,152 a group; a head 128 x 128 x
        # 64 = 1,048,576 for the pairs on x and 2 x 2 x 128 x 64 x 128 =
        # 4,194,304 for the state in and out: 8 heads 41,943,040.
        ("FLOPs of a chunk of 128 and a group of 8 heads, forward",
         ssd_chunk_flops(c), 44_040_192.0),
        # 8 groups x 64 chunks x 44,040,192 = 22,548,578,304 a layer
        # forward; four layers; four times (forward, the chunks again,
        # backward at twice).
        ("the scans' FLOPs a micro-step at batch 1", scan["flops"],
         4 * 4.0 * 22_548_578_304),
        # wide 8192 x 4096 x 4 B = 134,217,728 B; B or C 8192 x 1024 x
        # 4 B = 33,554,432 B; the step 8192 x 64 x 4 B = 2,097,152 B.
        # Forward 2 wides, 2 shared, a step; backward 3 wides, 4 shared,
        # 2 steps on top of the forward's.
        ("the scans' bytes a micro-step at batch 1", scan["bytes"],
         4 * (5.0 * 134_217_728 + 6.0 * 33_554_432 + 3.0 * 2_097_152)),
        # 6 x 318,431,232 = 1,910,587,392; attention 32 heads x 3 x 512
        # x 4096.5 = 201,351,168; scans 3 x 4 x 22,548,578,304 / 8192 =
        # 33,030,144.
        ("flops per token of the share at sequence 8192",
         train_flops_per_token(c),
         1_910_587_392 + 201_351_168.0 + 33_030_144.0),
        ("the attn family (one layer) at batch 1: FLOPs",
         families["attn"]["flops"], 14.0 * 128 * 32 * full_pairs),
        # q, o 8192 x 4096 x 4 B = 134,217,728 B; k, v 8192 x 256 x 4 B
        # = 8,388,608 B; rows 32 x 8192 x 4 B = 1,048,576 B.
        ("the attn family: bytes", families["attn"]["bytes"],
         6.0 * 134_217_728 + 6.0 * 8_388_608 + 2.0 * 1_048_576),
        ("calls: attn 2, experts 6 bodies",
         [families[f]["least_calls"] for f in ("attn", "experts")], [2, 6]),
        # Rows 8192 x 0.375 = 3072; 16 x 3072 x 2688 x 1856 a layer,
        # four sparse layers: two products forward at 1856, not three.
        ("the held experts' FLOPs a micro-step at batch 1, the forward "
         "pass counted twice", experts["flops"],
         4 * 16.0 * 3072 * 4_988_928),
        # Weights 8 x 9,977,856 x 4 B = 319,291,392 B, four times; rows
        # 3072 x 2688 x 4 B = 33,030,144 B, six times; four layers.
        ("the held experts' bytes a micro-step at batch 1",
         experts["bytes"], 4 * (4.0 * 319_291_392 + 6.0 * 33_030_144)),
        ("the experts family's FLOPs are the experts' cost",
         families["experts"]["flops"], experts["flops"]),
        ("the committed file's sizes give the hand-worked count",
         param_count(committed), 666_963_456),
        ("the committed file's flattened copies for the launcher and the "
         "shared readers are what they copy",
         [committed["layer_types_here"], committed["num_experts"],
          committed["rescale_depth"]],
         [layer_types(committed), committed["n_routed_experts"],
          committed["published"]["num_hidden_layers"]]),
        ("the committed file's cut is the published pattern's first "
         "layers, and its two epsilons are one (the program has one size "
         "for them)",
         [committed["published"]["hybrid_override_pattern"].startswith(
             committed["hybrid_override_pattern"]),
          len(committed["hybrid_override_pattern"]),
          committed["layer_norm_epsilon"]],
         [True, committed["num_hidden_layers"], committed["norm_eps"]]),
    ]
