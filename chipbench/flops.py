"""Operations and bytes a cell needs, from its shapes alone.

The yardstick's arithmetic: what the algorithm requires, never what a
kernel happens to execute (padding, recomputation and masked-out blocks
are waste and show as a lower share).  Every function takes the
configuration's own keys (``n_embd``, ``n_head``, ``n_layer``,
``n_inner``, ``n_positions``, ``vocab_size``) as a dict.  Hand-worked
cases are in ``chipbench/selfcheck.py``.
"""

from __future__ import annotations

import json
import pathlib
from typing import Any, Dict, Tuple

F32 = 4  # bytes; parameters, gradients and activations are float32


def param_count(c: Dict[str, Any]) -> int:
    """Parameters of the block as the program builds it: token and
    position tables, per layer two LayerNorms (scale, bias), a fused QKV
    and an output projection without bias, a two-matrix MLP with bias;
    a final LayerNorm and an untied head without bias."""
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


def flash_step_cost(c: Dict[str, Any], batch: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of all Mosaic calls of one micro-step."""
    cost = flash_call_cost(c, batch)
    flops = c["n_layer"] * (cost["fwd"][0] + cost["bwd"][0])
    nbytes = c["n_layer"] * (cost["fwd"][1] + cost["bwd"][1])
    return flops, nbytes


def exchange_bytes_per_round(c: Dict[str, Any]) -> int:
    """Bytes one worker moves in one sync round with codec none: the
    whole gradient vector out and the whole parameter vector back.  Each
    crosses the chip's link once (d2h, h2d) and the wire once."""
    return 2 * param_count(c) * F32


def load_peaks(device_kind: str) -> Dict[str, Any]:
    """The chip's published peaks; an unknown ``device_kind`` is an
    error, never a default."""
    path = pathlib.Path(__file__).resolve().parent / "peaks.json"
    with open(path) as fh:
        table = json.load(fh)["peaks"]
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} is not in {path}; "
                       f"have {sorted(table)}")
    return table[device_kind]


def roofline(flops: float, nbytes: float, seconds: float,
             peaks: Dict[str, Any]) -> Tuple[float, str]:
    """(share in percent, which peak binds): the least time the chip
    could take, the larger of FLOPs over peak FLOP/s and bytes over peak
    bytes/s, over the time taken."""
    t_compute = flops / (peaks["bf16_tflops"] * 1e12)
    t_memory = nbytes / (peaks["hbm_gbps"] * 1e9)
    bound = "compute" if t_compute >= t_memory else "memory"
    return 100.0 * max(t_compute, t_memory) / seconds, bound


def mfu_pct(c: Dict[str, Any], tokens_per_s: float, chips: int,
            peaks: Dict[str, Any]) -> float:
    return (100.0 * train_flops_per_token(c) * tokens_per_s
            / (chips * peaks["bf16_tflops"] * 1e12))
