"""Long-context causal-LM training on one chip — the end-to-end showcase
of the flash-attention path.

benchmarks/kernels.py times the op; this runs the *training loop*: a
TinyDecoder (framework model zoo) with the pallas flash kernel trains at
8k-32k context on a single v5e chip, through the framework's flat-param
convention + fused Nesterov commit.  The reference has no long-context
machinery at all (SURVEY.md §5).

Batches cycle through S pre-staged distinct slices of a byte corpus
inside a scanned step (fresh data every step, no host transfer in the
timed region); timing is the latency-cancelled fetch-fenced recipe of
:mod:`mpit_tpu.utils.timing`.

Env knobs: MPIT_LC_LENS (csv, default "8192,16384,32768"),
MPIT_LC_DMODEL (default 1024), MPIT_LC_LAYERS (default 4),
MPIT_LC_ITERS (default 8).  One JSON line per length; a length that
fails to compile or run fails the benchmark.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _common import log as _log  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

LENS = [int(s) for s in os.environ.get(
    "MPIT_LC_LENS", "8192,16384,32768").split(",") if s.strip()]
D_MODEL = int(os.environ.get("MPIT_LC_DMODEL", "1024"))
N_LAYERS = int(os.environ.get("MPIT_LC_LAYERS", "4"))
ITERS = int(os.environ.get("MPIT_LC_ITERS", "8"))
N_HEADS = 8
STAGED = 4  # distinct batches cycled inside the scanned step


ATTN_DTYPE = os.environ.get("MPIT_LC_ATTN_DTYPE", "bfloat16")


def bench_length(L: int) -> dict:
    from mpit_tpu.models import TinyDecoder, flatten_module
    from mpit_tpu.ops import flash_attention, fused_nesterov_commit
    from mpit_tpu.utils.timing import timed_chained

    # bf16 attention inputs (the standard flash trade): the MXU passes
    # are bf16 under default precision anyway, and the bf16 kernel gets
    # the 1024x1024 tiles (f32 auto-selects 512 — ops/flash_attention
    # _default_blocks).  MPIT_LC_ATTN_DTYPE=float32 opts out.
    cast = jnp.bfloat16 if ATTN_DTYPE == "bfloat16" else None

    def attn_fn(q, k, v):
        qh, kh, vh = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
        if cast is not None:
            qh, kh, vh = (t.astype(cast) for t in (qh, kh, vh))
        out = flash_attention(qh, kh, vh, causal=True)
        return out.astype(q.dtype).transpose(0, 2, 1, 3)

    model = TinyDecoder(
        vocab=256, d_model=D_MODEL, n_heads=N_HEADS, n_layers=N_LAYERS,
        max_len=L, attn_fn=attn_fn,
    )
    sample = jnp.zeros((1, L), jnp.int32)
    flat = flatten_module(model, jax.random.PRNGKey(0), sample)
    _log(f"L={L}: {flat.size / 1e6:.1f}M params")

    # A deterministic byte corpus; STAGED distinct (1, L+1) windows.
    rng = np.random.default_rng(7)
    corpus = rng.integers(0, 256, STAGED * (L + 1), dtype=np.int64)
    toks = jnp.asarray(
        corpus.reshape(STAGED, L + 1), jnp.int32
    )

    def loss_fn(w, batch):
        logp = flat.apply_flat(w, batch[:, :-1])
        tgt = batch[:, 1:]
        return -jnp.mean(jnp.take_along_axis(logp, tgt[..., None], -1))

    clr = jnp.float32(1e-3)

    def one_round(state):
        # One scanned pass over the staged batches: S full train steps
        # (fwd + bwd + fused commit), each on different data.
        def step(carry, batch):
            w, vt = carry
            loss, g = jax.value_and_grad(loss_fn)(w, batch[None, :])
            w, vt = fused_nesterov_commit(w, vt, g, clr)
            return (w, vt), loss

        (w, vt), losses = jax.lax.scan(step, state[:2], toks)
        return (w, vt, losses[-1])

    round_jit = jax.jit(one_round, donate_argnums=0)
    state = (flat.w0, jnp.zeros_like(flat.w0), jnp.float32(0))
    per_round = timed_chained(round_jit, state, iters=ITERS, repeats=2)
    per_step = per_round / STAGED
    tokens_s = L / per_step

    # FLOPs/step: matmul params (non-embedding ~ all of it except the two
    # embeds) x 6 x tokens, + causal attention 2*L^2*d_model per layer
    # forward, x3 for fwd+bwd.
    embed_params = 256 * D_MODEL + L * D_MODEL
    flops = (6 * (flat.size - embed_params) * L
             + 3 * N_LAYERS * 2 * L * L * D_MODEL)
    tfs = flops / per_step / 1e12
    rec = {
        "metric": "longcontext_train_tokens_per_sec",
        "value": round(tokens_s, 1),
        "unit": "tokens/s",
        "L": L, "d_model": D_MODEL, "n_layers": N_LAYERS,
        "params_m": round(flat.size / 1e6, 1),
        "step_ms": round(per_step * 1e3, 2),
        "train_tflops": round(tfs, 1),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()),
    }
    _log(f"[longcontext] {rec}")
    return rec


def main() -> None:
    for L in LENS:
        print(json.dumps(bench_length(L)), flush=True)


if __name__ == "__main__":
    main()
