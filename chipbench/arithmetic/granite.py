"""The arithmetic of the dense state-space hybrid as the program builds
it (``mpit_tpu/models/transformer.py`` ``GraniteDecoder``): what a
configuration with ``"arithmetic": "granite"`` needs, from its shapes
alone.

What the algorithm requires of **this chip's share**, never what a
kernel or the program's recomputation happens to execute.  Every
function takes the configuration's file as a dict and reads the model's
own published keys (``hidden_size``, ``num_attention_heads``,
``num_key_value_heads``, ``mamba_n_heads``, ``mamba_d_head``,
``mamba_n_groups``, ``mamba_d_state``, ``mamba_d_conv``,
``shared_intermediate_size``, ``vocab_size`` and ``layer_types``: each
layer's mixer, ``mamba`` or ``attention``, before its gated MLP), the
cut's (``num_hidden_layers`` layers from the first on) and the assumed
ones (``scan_chunk``: the chunk the kernels are built at;
``train_seq``: the sequence the cell trains at).  A head's width is
``hidden_size / num_attention_heads`` (the row gives no ``head_dim``).
The contract of such a module is in ``chipbench/spec.py``.

Two Mosaic kernel families, under the scopes the shared readers ask
``flops.kernel_family`` for: flash attention under ``attn`` (32 query
heads over 8 key/value heads of 64, no positional term) and the
state-space scan's three kernels under ``ssd_scan`` (a step holds **a
part of the one group**, ``ops/ssd_scan.py`` ``heads_a_step``).
:func:`ssd_scan_cost` counts what the chunked algorithm needs at one
group: ``C B^T`` **once a group and chunk** (an implementation that
makes it again a head block does work the yardstick does not count), a
head's own products 64 times; x, B, C and the step read and y written
once a pass, B and C 128 wide once.  ``layers/ssd_scan_roofline.py``
holds the scope's device time to it.  The count does not change when the
implementation does.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

F32 = 4  # bytes; parameters, gradients and activations are float32
#: the three calls of ``ops/ssd_scan.py`` are ``jit``s of their own: one
#: body each in the lowered step however many layers call them
SCAN_KERNEL_BODIES = 3


def layer_kinds(c: Dict[str, Any]) -> List[str]:
    """The held layers' mixers."""
    return list(c["layer_types"])


def layer_types(c: Dict[str, Any]) -> str:
    """The launcher's ``layer_types`` for the layers held."""
    return ",".join(layer_kinds(c))


def layers_of(c: Dict[str, Any], kind: str) -> int:
    return layer_kinds(c).count(kind)


def head_dim(c: Dict[str, Any]) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def ssm_inner(c: Dict[str, Any]) -> int:
    """The mixer's inner width: heads times a head's width
    (``mamba_expand`` agrees with it and is read by nothing)."""
    return c["mamba_n_heads"] * c["mamba_d_head"]


def ssm_mixed(c: Dict[str, Any]) -> int:
    """The channels the convolution runs over: x, B and C together."""
    return ssm_inner(c) + 2 * c["mamba_n_groups"] * c["mamba_d_state"]


def mamba_products(c: Dict[str, Any]) -> int:
    """``W_in`` (to z, xBC and a step a head) and ``W_out``."""
    d = c["hidden_size"]
    return (d * (ssm_inner(c) + ssm_mixed(c) + c["mamba_n_heads"])
            + ssm_inner(c) * d)


def mlp_products(c: Dict[str, Any]) -> int:
    """``[W_a | W_b]`` as one matrix and ``W_o``."""
    return 3 * c["hidden_size"] * c["shared_intermediate_size"]


def mamba_param_count(c: Dict[str, Any]) -> int:
    """A ``mamba`` layer: the mixer's two matrices, the convolution's
    taps and bias, ``dt_bias``, ``A_log`` and ``D`` a head, the gated
    norm's weight a channel, the two norms on the stream and the MLP."""
    return (mamba_products(c) + (c["mamba_d_conv"] + 1) * ssm_mixed(c)
            + 3 * c["mamba_n_heads"] + ssm_inner(c) + 2 * c["hidden_size"]
            + mlp_products(c))


def attention_products(c: Dict[str, Any]) -> int:
    """wq and wo over all query heads, wk and wv over the KV heads."""
    d, head = c["hidden_size"], head_dim(c)
    return (2 * d * c["num_attention_heads"] * head
            + 2 * d * c["num_key_value_heads"] * head)


def attention_param_count(c: Dict[str, Any]) -> int:
    """An ``attention`` layer: the four matrices, the two norms on the
    stream and the MLP."""
    return attention_products(c) + 2 * c["hidden_size"] + mlp_products(c)


def param_count(c: Dict[str, Any]) -> int:
    """Parameters of the share as the program builds it, all of them
    exchanged: a token table **that is also the head** (tied: counted
    once; no position table), the layers and a final RMSNorm."""
    d, v = c["hidden_size"], c["vocab_size"]
    return (v * d + d
            + layers_of(c, "mamba") * mamba_param_count(c)
            + layers_of(c, "attention") * attention_param_count(c))


def active_param_count(c: Dict[str, Any]) -> int:
    """Parameters in one token's products on this chip: the mixers' and
    the MLPs' matrices and the head, the table once **as a product**;
    its look-up is none, and norms, taps, biases, ``A_log``, ``dt_bias``
    and ``D`` are not products."""
    return (layers_of(c, "mamba") * (mamba_products(c) + mlp_products(c))
            + layers_of(c, "attention") * (attention_products(c)
                                           + mlp_products(c))
            + c["hidden_size"] * c["vocab_size"])


def pairs_per_query(seq: int) -> float:
    """(query, key) pairs a causal query sees on average."""
    return (seq + 1) / 2


# -- the state-space scan -----------------------------------------------------


def ssd_chunk_flops(c: Dict[str, Any]) -> float:
    """FLOPs of one chunk of one **group** of heads, forward, as the
    chunked algorithm needs them (``Q`` positions a chunk, ``N`` the
    state's columns, ``P`` a head's width, ``H / G`` heads a group; two
    a multiply-add): the pair matrix ``C B^T`` **once a group**, ``Q^2 /
    2`` pairs (``s <= t``) at ``2 N``; a head, the pairs applied to
    ``x``, ``Q^2 / 2`` at ``2 P``, the chunk's contribution to the state
    and the read-out of the state it starts from, ``2 Q P N`` each.
    Decays, sums, gates and the carry from chunk to chunk (``P N`` a
    head and chunk) are elementwise and left out."""
    q, n, p = c["scan_chunk"], c["mamba_d_state"], c["mamba_d_head"]
    per = c["mamba_n_heads"] // c["mamba_n_groups"]
    return q * q * n + per * (q * q * p + 2 * 2.0 * q * p * n)


def ssd_scan_cost(c: Dict[str, Any], batch: int) -> Dict[str, float]:
    """FLOPs and HBM bytes of the chunked scans of all Mamba layers held
    in one micro-step **as the block runs them**: forward, the chunks
    again in the operator's own backward rule (it keeps x, dt, A, B, C
    and makes every chunk's matrices and the chunk-start states again),
    and the backward pass proper at twice the forward's products.
    Bytes: forward x read and y written (a head's width a position
    each), B and C (a group's state width each, **once**, not once a
    head block) and the step a head; backward the same read with y's
    gradient and the four gradients written; the recomputation is inside
    the backward pass and reads nothing more.  A last chunk that is not
    whole counts whole."""
    heads, p = c["mamba_n_heads"], c["mamba_d_head"]
    groups, n = c["mamba_n_groups"], c["mamba_d_state"]
    seq, layers = c["train_seq"], layers_of(c, "mamba")
    chunks = -(-seq // c["scan_chunk"])
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
    share, nothing recomputed: 6 a parameter in a product (the tied
    table once, as the head), the attention's products over the pairs a
    query sees (three times the forward pass's ``4 head`` a pair and
    head), and the chunked scan's forward three times over
    (:func:`ssd_chunk_flops`).  Look-ups, norms, convolutions, SiLU,
    softplus, softmax, decays and the multipliers are left out."""
    pair = 3 * 4 * head_dim(c)
    scan = 3.0 * ssd_scan_cost(c, 1)["forward_flops"] / c["train_seq"]
    return (6 * active_param_count(c)
            + layers_of(c, "attention") * c["num_attention_heads"] * pair
            * pairs_per_query(c["train_seq"])
            + scan)


# -- the Mosaic kernel families ------------------------------------------------


def flash_call_cost(c: Dict[str, Any], batch: int
                    ) -> Dict[str, Tuple[float, float]]:
    """(FLOPs, HBM bytes) of one attention layer's kernels over a batch
    of whole sequences, forward and backward: forward ``4 head`` a
    visible pair and query head, backward ``10 head``; q, o and their
    gradients over the query heads, k, v and theirs over the KV heads, a
    row statistic a query head."""
    heads, kv, seq = (c["num_attention_heads"], c["num_key_value_heads"],
                      c["train_seq"])
    head = head_dim(c)
    pairs = batch * heads * seq * pairs_per_query(seq)
    q_size = batch * heads * seq * head * F32
    kv_size = batch * kv * seq * head * F32
    rows = batch * heads * seq * F32
    return {
        "fwd": (4.0 * head * pairs, 2.0 * q_size + 2.0 * kv_size + rows),
        "bwd": (10.0 * head * pairs, 4.0 * q_size + 4.0 * kv_size + rows),
    }


def kernels(c: Dict[str, Any], batch: int) -> Dict[str, Dict[str, Any]]:
    """The block's Mosaic kernel families by model scope.  ``attn``: the
    attention layers' flash kernels, a forward and a backward call a
    layer at the least.  ``ssd_scan``: the scan's three kernels
    (:func:`ssd_scan_cost`; ``least_calls`` their three bodies: a step
    that fell back to the XLA form has none and is not ``correct``)."""
    layers = layers_of(c, "attention")
    cost = flash_call_cost(c, batch)
    scan = ssd_scan_cost(c, batch)
    return {
        "attn": {
            "scope": "attn",
            "flops": layers * (cost["fwd"][0] + cost["bwd"][0]),
            "bytes": layers * (cost["fwd"][1] + cost["bwd"][1]),
            "least_calls": 2 * layers,
        },
        "ssd_scan": {
            "scope": "ssd_scan",
            "flops": scan["flops"],
            "bytes": scan["bytes"],
            "least_calls": SCAN_KERNEL_BODIES,
        },
    }


# Granite-4.0-H-Micro's published sizes at the cut of the committed
# configuration (layers 0-9, an eighth of the vocabulary), for the
# hand-worked cases only.
GRANITE_L10 = {
    "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 8,
    "mamba_n_heads": 64, "mamba_d_head": 64, "mamba_n_groups": 1,
    "mamba_d_state": 128, "mamba_d_conv": 4, "scan_chunk": 128,
    "shared_intermediate_size": 8192, "num_hidden_layers": 10,
    "layer_types": ["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
    "vocab_size": 12544, "train_seq": 4096}


def _committed() -> Dict[str, Any]:
    import json
    import pathlib

    path = (pathlib.Path(__file__).resolve().parent.parent / "configs"
            / "granite-4.0-h-micro-l10.json")
    with open(path) as fh:
        return json.load(fh)


def hand_worked() -> List[Tuple[str, Any, Any]]:
    """``(what, got, want)``: each function on sizes worked by hand, and
    the committed file's copied keys against what they copy."""
    c = GRANITE_L10
    families = kernels(c, 1)
    scan = ssd_scan_cost(c, 1)
    committed = _committed()
    full_pairs = 4096 * 4097 // 2        # 8,390,656 a head
    return [
        ("the mixer's inner width and the convolution's channels",
         [ssm_inner(c), ssm_mixed(c)], [4096, 4352]),
        # W_in 2048 x (4096 + 4352 + 64 = 8512) = 17,432,576; W_out
        # 4096 x 2048 = 8,388,608.
        ("a Mamba mixer's two matrices", mamba_products(c), 25_821_184),
        # input_linear 2048 x 16384 = 33,554,432; output_linear 8192 x
        # 2048 = 16,777,216.
        ("an MLP's matrices", mlp_products(c), 50_331_648),
        # + taps 4 x 4352 = 17,408 and the conv's bias 4,352 (21,760),
        # dt_bias, A_log and D 192, the gated norm 4,096, the two
        # stream norms 4,096, the MLP.
        ("a mamba layer's parameters", mamba_param_count(c), 76_182_976),
        # wq and wo 2 x 2048 x 2048 = 8,388,608; wk and wv 2 x 2048 x
        # 512 = 2,097,152.
        ("the attention's four matrices", attention_products(c),
         10_485_760),
        ("an attention layer's parameters", attention_param_count(c),
         60_821_504),
        ("layers held: nine mamba, one attention",
         [layers_of(c, kind) for kind in ("mamba", "attention")], [9, 1]),
        # 9 x 76,182,976 = 685,646,784; + 60,821,504; the final norm
        # 2,048; the tied table 12544 x 2048 = 25,690,112, once.
        ("parameters of granite-4.0-h-micro at layers 0-9 and an eighth "
         "of the vocabulary", param_count(c), 772_160_448),
        # 9 x (25,821,184 + 50,331,648) = 685,375,488; 10,485,760 +
        # 50,331,648 = 60,817,408; the head 25,690,112.
        ("parameters in one token's products on this chip",
         active_param_count(c), 771_883_008),
        # C B^T 128 x 128 x 128 = 2,097,152 ONCE; a head 128 x 128 x 64
        # = 1,048,576 for the pairs on x and 2 x 2 x 128 x 64 x 128 =
        # 4,194,304 for the state in and out: 64 heads 335,544,320.
        ("FLOPs of a chunk of 128 and the one group of 64 heads, forward",
         ssd_chunk_flops(c), 337_641_472.0),
        # 32 chunks x 337,641,472 = 10,804,527,104 a layer forward; nine
        # layers; four times (forward, the chunks again, backward at
        # twice).
        ("the scans' FLOPs a micro-step at batch 1", scan["flops"],
         9 * 4.0 * 10_804_527_104),
        # wide 4096 x 4096 x 4 B = 67,108,864 B; B or C 4096 x 128 x
        # 4 B = 2,097,152 B; the step 4096 x 64 x 4 B = 1,048,576 B.
        # Forward 2 wides, 2 shared, a step; backward 3 wides, 4 shared,
        # 2 steps on top of the forward's.
        ("the scans' bytes a micro-step at batch 1", scan["bytes"],
         9 * (5.0 * 67_108_864 + 6.0 * 2_097_152 + 3.0 * 1_048_576)),
        # 6 x 771,883,008 = 4,631,298,048; attention 32 heads x 3 x 256
        # x 2048.5 = 50,343,936; scans 3 x 9 x 10,804,527,104 / 4096 =
        # 71,221,248.
        ("flops per token of the share at sequence 4096",
         train_flops_per_token(c),
         4_631_298_048 + 50_343_936.0 + 71_221_248.0),
        ("the attn family (one layer) at batch 1: FLOPs",
         families["attn"]["flops"], 14.0 * 64 * 32 * full_pairs),
        # q, o 4096 x 2048 x 4 B = 33,554,432 B; k, v 4096 x 512 x 4 B
        # = 8,388,608 B; rows 32 x 4096 x 4 B = 524,288 B.
        ("the attn family: bytes", families["attn"]["bytes"],
         6.0 * 33_554_432 + 6.0 * 8_388_608 + 2.0 * 524_288),
        ("calls: attn 2, the scan's 3 bodies",
         [families[f]["least_calls"] for f in ("attn", "ssd_scan")], [2, 3]),
        ("the ssd_scan family's FLOPs and bytes are the scan's cost",
         [families["ssd_scan"]["flops"], families["ssd_scan"]["bytes"]],
         [scan["flops"], scan["bytes"]]),
        ("the committed file's sizes give the hand-worked count",
         param_count(committed), 772_160_448),
        ("the committed file's flattened copy for the launcher is what it "
         "copies", committed["layer_types_here"], layer_types(committed)),
        ("the committed file's cut is the published first ten layers, one "
         "whole period with the attention layer in its place",
         [committed["layer_types"],
          committed["published"]["layer_types"][:10].index("attention"),
          len(committed["published"]["layer_types"])],
         [committed["published"]["layer_types"][
             :committed["num_hidden_layers"]], 5, 40]),
        ("the committed file's two MLP widths are one (the public module "
         "reads shared_intermediate_size; the row's dense width is "
         "intermediate_size)",
         committed["shared_intermediate_size"],
         committed["intermediate_size"]),
    ]
