"""The arithmetic of the GPT-2 block as the program builds it
(``mpit_tpu/models/transformer.py`` ``TinyDecoder``): what a
configuration with ``"arithmetic": "gpt2"`` needs, from its shapes alone.

What the algorithm requires, never what a kernel happens to execute
(padding, recomputation and masked-out blocks are waste and show as a
lower share).  Every function takes the configuration's file as a dict
and reads GPT-2's own published keys (``n_embd``, ``n_head``,
``n_layer``, ``n_inner``, ``n_positions``, ``vocab_size``).  The
contract of such a module is in ``chipbench/spec.py``; the functions of
``chipbench/flops.py`` ask it.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

F32 = 4  # bytes; parameters, gradients and activations are float32


def param_count(c: Dict[str, Any]) -> int:
    """Parameters of the block as the program builds it, all of them
    exchanged: token and position tables, per layer two LayerNorms
    (scale, bias), a fused QKV and an output projection without bias, a
    two-matrix MLP with bias; a final LayerNorm and an untied head
    without bias."""
    d, v, n_in = c["n_embd"], c["vocab_size"], c["n_inner"]
    layer = 2 * 2 * d + 3 * d * d + d * d + (d * n_in + n_in) + (n_in * d + d)
    return v * d + c["n_positions"] * d + c["n_layer"] * layer + 2 * d + d * v


def train_flops_per_token(c: Dict[str, Any]) -> float:
    """Forward plus backward FLOPs one trained token needs, nothing
    recomputed: 2 FLOPs a multiply-add, backward twice the forward.
    Matrices: 3x2x(4 d^2 + 2 d n_inner) a layer and 3x2xdxV for the
    head.  Causal attention at sequence L: a token attends to (L+1)/2
    keys on average, scores and the weighted sum are 2x2xd FLOPs a key,
    so 3x4xdx(L+1)/2 a layer.  Embedding look-ups, LayerNorm, GELU and
    softmax are left out (under 1% at these widths)."""
    d, n_in, seq = c["n_embd"], c["n_inner"], c["n_positions"]
    matrices = 6 * (4 * d * d + 2 * d * n_in)
    attention = 12 * d * (seq + 1) / 2
    return c["n_layer"] * (matrices + attention) + 6 * d * c["vocab_size"]


def flash_call_cost(c: Dict[str, Any], batch: int) -> Dict[str, Tuple[float, float]]:
    """(FLOPs, HBM bytes) of one layer's attention over a batch of whole
    sequences, forward and backward, as the flash algorithm needs them.
    Forward: QK^T and PV over the causal half, 4 x d_head FLOPs a
    (query, key) pair; reads q, k, v and writes o and the row
    log-sum-exp.  Backward: recomputes the scores (counted: the
    algorithm, not the kernel, requires it) and forms dV, dP, dQ, dK,
    five products, 10 x d_head a pair; reads q, k, v, o, do, lse and
    writes dq, dk, dv.  Head width as published, not as padded."""
    heads, seq = c["n_head"], c["n_positions"]
    d_head = c["n_embd"] // heads
    pairs = batch * heads * seq * (seq + 1) / 2
    tensor = batch * heads * seq * d_head * F32
    rows = batch * heads * seq * F32
    return {
        "fwd": (4.0 * d_head * pairs, 4.0 * tensor + rows),
        "bwd": (10.0 * d_head * pairs, 9.0 * tensor + rows),
    }


def kernels(c: Dict[str, Any], batch: int) -> Dict[str, Dict[str, Any]]:
    """The block's one Mosaic kernel family, flash attention
    (``ops/flash_attention.py``), whose calls run under the model scope
    ``attn``: FLOPs and HBM bytes of all its calls in one micro-step,
    and the fewest ``tpu_custom_call``s the lowered forward and backward
    may hold, one of each a layer.  (The fused msgd commit of the local
    cell is the optimizer's kernel, under ``update``, and no part of the
    block: it belongs to no family here.)"""
    cost = flash_call_cost(c, batch)
    return {"attn": {
        "scope": "attn",
        "flops": c["n_layer"] * (cost["fwd"][0] + cost["bwd"][0]),
        "bytes": c["n_layer"] * (cost["fwd"][1] + cost["bwd"][1]),
        "least_calls": 2 * c["n_layer"],
    }}


# Cerebras-GPT's sizes (Dey et al., arXiv:2304.03208, Table 1), for the
# hand-worked cases only; the cells read their configuration's file.
C111M = {"n_embd": 768, "n_head": 12, "n_layer": 10, "n_inner": 3072,
         "n_positions": 2048, "vocab_size": 50257}
C13B_D4 = {"n_embd": 2048, "n_head": 16, "n_layer": 4, "n_inner": 8192,
           "n_positions": 2048, "vocab_size": 50257}


def hand_worked() -> List[Tuple[str, Any, Any]]:
    """``(what, got, want)``: each function on sizes worked by hand."""
    cost = flash_call_cost(C111M, 8)
    family = kernels(C111M, 8)["attn"]
    return [
        # A layer's matrices 4 x 768^2 + 2 x 768 x 3072 = 7,077,888
        # weights, x6 = 42,467,328 FLOPs a token; attention 12 x 768 x
        # 2049 / 2 = 9,441,792; ten layers 519,091,200; the head 6 x 768
        # x 50257 = 231,584,256; 750,675,456 in all.  At 6 x 2048 tokens
        # a micro-step: 9.224 TFLOP.
        ("flops per token of cerebras-gpt-111m",
         train_flops_per_token(C111M), 750_675_456),
        ("a micro-step of cerebras-gpt-111m at batch 6, TFLOP to 4 digits",
         round(train_flops_per_token(C111M) * 12288 / 1e12, 3), 9.224),
        # Tables 50257 x 768 + 2048 x 768 = 40,170,240; a layer 3,072 +
        # 2,359,296 + 2,362,368 + 2,360,064 = 7,084,800, ten of them;
        # final LayerNorm 1,536; head 38,597,376: 149,617,152, a 598.5 MB
        # vector.
        ("parameters of cerebras-gpt-111m", param_count(C111M), 149_617_152),
        # Tables 102,926,336 + 4,194,304; a layer 8,192 + 16,777,216 +
        # 16,785,408 + 16,779,264 = 50,350,080, four of them; final
        # LayerNorm 4,096; head 102,926,336.
        ("parameters of cerebras-gpt-1.3b-d4",
         param_count(C13B_D4), 411_451_392),
        # Flash, one layer of 111m at batch 8: pairs 8 x 12 x 2048 x 2049
        # / 2 = 201,424,896; forward 4 x 64 = 256 FLOPs a pair: 51.56
        # GFLOP; backward 640 a pair: 128.9 GFLOP.  q, k, v, o are 8 x 12
        # x 2048 x 64 x 4 B = 50,331,648 B each, a row sum 786,432 B.
        ("flash forward FLOPs per call of 111m at batch 8",
         cost["fwd"][0], 256 * 201_424_896),
        ("flash backward FLOPs per call of 111m at batch 8",
         cost["bwd"][0], 640 * 201_424_896),
        ("flash forward bytes: q, k, v in, o and the row sums out",
         cost["fwd"][1], 4 * 50_331_648 + 786_432),
        ("the attn family of 111m at batch 8: ten layers' FLOPs",
         family["flops"], 10 * 896 * 201_424_896),
        ("the attn family of 111m at batch 8: ten layers' bytes",
         family["bytes"], 10 * (13 * 50_331_648 + 2 * 786_432)),
        ("the attn family holds a forward and a backward call a layer",
         family["least_calls"], 20),
    ]
