"""The arithmetic of the looped block as the program builds it
(``mpit_tpu/models/transformer.py`` ``OuroDecoder``): what a
configuration with ``"arithmetic": "ouro"`` needs, from its shapes
alone.

What the algorithm requires, never what a kernel or the program's
recomputation happens to execute.  Every function takes the
configuration's file as a dict and reads Ouro's own published keys
(``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``intermediate_size``, ``num_hidden_layers``,
``total_ut_steps``, ``vocab_size``) and the cut's (``train_seq``: the
sequence the cell trains at).  The contract of such a module is in
``chipbench/spec.py``.

The one thing that sets this block's arithmetic apart: **a parameter is
applied more than once a step**.  The ``num_hidden_layers`` layers run
``total_ut_steps`` times with the same weights, and the final norm, the
head and the exit gate once at the end of every pass, so a token needs
6 FLOPs times ``total_ut_steps`` of every parameter in a layer's or the
head's product, where every other block of the benchmark needs 6.

One Mosaic kernel family, flash attention under the scope ``attn``: a
forward and a backward call a layer *application*, ``num_hidden_layers
x total_ut_steps`` of each a micro-step.  The program recomputes every
layer in the backward pass, so it runs each forward kernel twice; the
second run is waste by this file's rule and shows as a lower
``flash_roofline``.  The passes are one ``lax.scan`` body, so the
*lowered* step's text holds the calls of ``num_hidden_layers`` layers
and not of ``num_hidden_layers x total_ut_steps``: ``least_calls`` is
the forward and the backward call of each layer once, the count of that
one body (an unrolled loop would hold ``total_ut_steps`` times as many
and pass too; the configuration's file says which the program is).
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

F32 = 4  # bytes; parameters, gradients and activations are float32


def _attention_products(c: Dict[str, Any]) -> int:
    """wq and wo over the query heads, wk and wv over the KV heads."""
    d, head = c["hidden_size"], c["head_dim"]
    return (2 * d * c["num_attention_heads"] * head
            + 2 * d * c["num_key_value_heads"] * head)


def _mlp_products(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def layer_param_count(c: Dict[str, Any]) -> int:
    """A layer: four bias-free attention matrices, three of the gated
    MLP, and the sandwich's four RMSNorm weights."""
    return _attention_products(c) + _mlp_products(c) + 4 * c["hidden_size"]


def param_count(c: Dict[str, Any]) -> int:
    """Parameters as the program builds them, all of them exchanged and
    each counted once however often it is applied: a token table (no
    position table), the layers, one final RMSNorm, an untied head, and
    the exit gate's ``hidden_size`` weights and its one bias."""
    d, v = c["hidden_size"], c["vocab_size"]
    return (v * d + c["num_hidden_layers"] * layer_param_count(c)
            + d + d * v + d + 1)


def applied_param_count(c: Dict[str, Any]) -> int:
    """Parameters in one token's MXU products, each counted as often as
    it is applied: the layers' matrices and the head, ``total_ut_steps``
    times.  The table is a look-up, the norms are no products, and the
    gate's ``hidden_size`` multiply-adds a pass are left out with
    them."""
    per_pass = (c["num_hidden_layers"]
                * (_attention_products(c) + _mlp_products(c))
                + c["hidden_size"] * c["vocab_size"])
    return c["total_ut_steps"] * per_pass


def pairs_per_query(seq: int) -> float:
    """(query, key) pairs a causal query sees on average."""
    return (seq + 1) / 2


def train_flops_per_token(c: Dict[str, Any]) -> float:
    """Forward plus backward FLOPs one trained token needs, nothing
    recomputed: 6 a parameter *application* in a product (four passes of
    the layers and four heads), and the attention's two products over
    the pairs a query sees, 3 x 4 x heads x head_dim x pairs a layer
    application.  Look-ups, norms, rotary, SiLU, softmax, the gate and
    the exit distribution are left out."""
    width = c["num_attention_heads"] * c["head_dim"]
    applications = c["num_hidden_layers"] * c["total_ut_steps"]
    return (6 * applied_param_count(c)
            + applications * 12 * width * pairs_per_query(c["train_seq"]))


def flash_call_cost(c: Dict[str, Any], batch: int
                    ) -> Dict[str, Tuple[float, float]]:
    """(FLOPs, HBM bytes) of one layer application's attention over a
    batch of whole sequences, forward and backward, as the flash
    algorithm needs them: the same counts as ``gpt2.py`` and
    ``olmoe.py`` ``flash_call_cost`` at as many KV as query heads (4 x
    head_dim FLOPs a causal (query, key) pair forward, 10 backward; q,
    k, v in, o and the row sums out; backward q, k, v, o, do, lse in,
    dq, dk, dv out)."""
    heads, head, seq = c["num_attention_heads"], c["head_dim"], c["train_seq"]
    pairs = batch * heads * seq * pairs_per_query(seq)
    tensor = batch * heads * seq * head * F32
    rows = batch * heads * seq * F32
    return {
        "fwd": (4.0 * head * pairs, 4.0 * tensor + rows),
        "bwd": (10.0 * head * pairs, 9.0 * tensor + rows),
    }


def kernels(c: Dict[str, Any], batch: int) -> Dict[str, Dict[str, Any]]:
    """The block's one Mosaic kernel family, flash attention under the
    scope ``attn``: FLOPs and HBM bytes of a forward and a backward call
    for each of the ``num_hidden_layers x total_ut_steps`` layer
    applications of a micro-step (the forward calls the program's
    recomputation repeats are in the time and not here), and the fewest
    ``tpu_custom_call``s the lowered step may hold: a forward and a
    backward call a layer, the one scanned body's count."""
    cost = flash_call_cost(c, batch)
    applications = c["num_hidden_layers"] * c["total_ut_steps"]
    return {"attn": {
        "scope": "attn",
        "flops": applications * (cost["fwd"][0] + cost["bwd"][0]),
        "bytes": applications * (cost["fwd"][1] + cost["bwd"][1]),
        "least_calls": 2 * c["num_hidden_layers"],
    }}


# Ouro-2.6B's published sizes at the cut of the committed configuration
# (six of 48 layers), for the hand-worked cases only.
OURO_L6 = {"hidden_size": 2048, "num_attention_heads": 16,
           "num_key_value_heads": 16, "head_dim": 128,
           "intermediate_size": 5632, "num_hidden_layers": 6,
           "total_ut_steps": 4, "vocab_size": 49152, "train_seq": 4096}


def _committed() -> Dict[str, Any]:
    import json
    import pathlib

    path = (pathlib.Path(__file__).resolve().parent.parent / "configs"
            / "ouro-2.6b-l6.json")
    with open(path) as fh:
        return json.load(fh)


def hand_worked() -> List[Tuple[str, Any, Any]]:
    """``(what, got, want)``: each function on sizes worked by hand."""
    c = OURO_L6
    family = kernels(c, 1)["attn"]
    cost = flash_call_cost(c, 1)
    committed = _committed()
    pairs = 16 * 4096 * 4097 // 2          # 134,250,496 a layer application
    return [
        # Attention 4 x 2048 x 2048 = 16,777,216; MLP 3 x 2048 x 5632 =
        # 34,603,008; four norms 8,192.
        ("a layer of ouro-2.6b", layer_param_count(c), 51_388_416),
        # Six layers 308,330,496; table and head 2 x 49152 x 2048 =
        # 201,326,592; final norm 2,048; gate 2,048 and its bias 1.
        ("parameters of ouro-2.6b at six layers", param_count(c),
         509_661_185),
        ("the vector is one element over a whole number of lanes",
         param_count(c) % 128, 1),
        # A pass: six layers' matrices 6 x 51,380,224 = 308,281,344, the
        # head 100,663,296: 408,944,640; four passes.
        ("parameter applications in one token's products, four passes",
         applied_param_count(c), 4 * 408_944_640),
        # 6 x 1,635,778,560 = 9,814,671,360; attention 24 applications x
        # 12 x 2048 x 2048.5 = 24 x 50,343,936 = 1,208,254,464.
        ("flops per token at sequence 4096, four passes and four heads",
         train_flops_per_token(c), 9_814_671_360 + 1_208_254_464.0),
        ("a micro-step of 4096 tokens, TFLOP to two decimals",
         round(train_flops_per_token(c) * 4096 / 1e12, 2), 45.15),
        ("one pass alone (total_ut_steps 1) needs a quarter",
         train_flops_per_token({**c, "total_ut_steps": 1}) * 4,
         train_flops_per_token(c)),
        # 14 x 128 = 1,792 FLOPs a pair, forward and backward, over 24
        # layer applications.
        ("the attn family at batch 1: FLOPs of 24 layer applications",
         family["flops"], 24 * 1792.0 * pairs),
        # q, k, v, o are 16 x 4096 x 128 x 4 B = 33,554,432 B each, a row
        # sum 262,144 B: forward 4 tensors and the rows, backward 9.
        ("flash forward bytes of one layer application", cost["fwd"][1],
         4.0 * 33_554_432 + 262_144),
        ("the attn family: bytes of 24 layer applications", family["bytes"],
         24 * (13.0 * 33_554_432 + 2 * 262_144)),
        ("the scanned body holds a forward and a backward call a layer",
         family["least_calls"], 12),
        ("the committed file's sizes give the hand-worked count",
         param_count(committed), 509_661_185),
        ("the committed file's sizes give the hand-worked FLOPs",
         train_flops_per_token(committed), train_flops_per_token(c)),
        ("the committed file's layer_types are its published 48 layers",
         (len(committed["layer_types"]), set(committed["layer_types"])),
         (48, {"full_attention"})),
    ]
