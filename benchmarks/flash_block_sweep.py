"""Flash forward block-size sweep — the 65-70% MFU push (round-5 task).

Round 4 bisected the forward's remaining gap to the online-softmax
state update (builder run, July 2026): the stripped kernel ran at 92%
of bf16 peak, adding the (m, l) scratch chain dropped it to ~60%.  The
state update runs ONCE PER KV BLOCK, so larger block_k amortizes it —
this sweep walks (block_q, block_k) combos under a raised 64 MB VMEM
budget (``MPIT_FA_VMEM_MB``, set below; the stock 16 MB budget rejects
any combo whose (block_q, block_k) f32 score tile exceeds ~4 MB) and
reports TFLOP/s + MFU per combo, compile failures recorded not fatal.

Usage: `python benchmarks/flash_block_sweep.py` (env: MPIT_KBENCH_ITERS,
MPIT_SWEEP_LENGTHS csv default 8192,32768, MPIT_SWEEP_OUT file).
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _common import emit_json, log as _log  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.kernels import BF16_PEAK_TFLOPS  # noqa: E402

LENGTHS = [int(s) for s in os.environ.get(
    "MPIT_SWEEP_LENGTHS", "8192,32768").split(",")]
ITERS = int(os.environ.get("MPIT_KBENCH_ITERS", "20"))
OUT = os.environ.get("MPIT_SWEEP_OUT", "")
B, H, D = 1, 8, 128

# (block_q, block_k): current default first, then the state-update
# amortization candidates.  Prior data (docs/tpu_compile_notes.md §2,
# 100 MB VMEM budget): BIGGER block_q is slower (2048x1024 = 97 vs
# 1024x1024 = 102 TFLOP/s — less double-buffering overlap), but
# bk-heavy combos (1024x2048, 512x2048) — the serialization lever —
# were never measured.  The whole sweep runs under
# MPIT_FA_VMEM_MB=64 (set below; perf-neutral per the same note), with
# (1024, 1024) re-measured under it as the in-sweep control.
COMBOS = [(1024, 1024), (1024, 2048), (2048, 1024), (1536, 1536),
          (2048, 512), (512, 2048), (512, 4096), (2048, 2048)]

os.environ.setdefault("MPIT_FA_VMEM_MB", "64")


def main() -> None:
    from mpit_tpu.ops import flash_attention
    from mpit_tpu.utils.timing import timed_per_call

    dev = jax.devices()[0]
    peak = BF16_PEAK_TFLOPS.get(dev.device_kind)
    rows = []
    for L in LENGTHS:
        key = jax.random.PRNGKey(L)
        q, k, v = (
            jax.random.normal(kk, (B, H, L, D), jnp.bfloat16)
            for kk in jax.random.split(key, 3)
        )
        flops = 2 * B * H * L * L * D * 2 / 2  # causal: half the tiles
        for bq, bk in COMBOS:
            if bq > L or bk > L:
                continue
            fn = jax.jit(lambda q, k, v, bq=bq, bk=bk: flash_attention(
                q, k, v, causal=True, block_q=bq, block_k=bk))
            rec = {"L": L, "block_q": bq, "block_k": bk}
            try:
                t = timed_per_call(fn, q, k, v, iters=ITERS,
                                   auto_scale=True, min_ratio=3.0,
                                   max_iters=max(4 * ITERS, 64))
                tfs = flops / t / 1e12
                rec.update(ms=round(t * 1e3, 3), tflops=round(tfs, 1),
                           mfu=round(tfs / peak, 3) if peak else None)
            except Exception as e:
                rec["error"] = f"{type(e).__name__}: {str(e)[:200]}"
            rows.append(rec)
            _log(f"[sweep] {rec}")
    emit_json({
        "metric": "flash_fwd_block_sweep", "device": dev.device_kind,
        "shape": {"B": B, "H": H, "D": D, "dtype": "bfloat16",
                  "causal": True},
        "bf16_peak_tflops": peak, "rows": rows,
    }, OUT)


if __name__ == "__main__":
    main()
