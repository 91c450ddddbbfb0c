"""Flash backward schedule A/B: fused single-sweep vs two-kernel, on chip.

Round-4 made `_fa_bwd_fused_kernel` the default on a matmul-count
argument (5 vs 7 per tile pair) without an on-chip measurement; the
round-4 verdict requires the numbers — wall time AND peak HBM, with the
dQ-partials transient accounted across the vmapped B*H axis
(`ops/flash_attention.py` fused branch: an (n_kv_blocks, Lq, D) f32
buffer per (B, H) program — with the round-5 length-aware backward
default, 2048-wide kv blocks at 32k make that 256 MB/head; the
analytic column resolves bk through the same default the kernel uses)
— before any more claims stack on the default.

Each (schedule, L) combo runs in a FRESH SUBPROCESS: jax exposes only a
process-cumulative ``peak_bytes_in_use``, so per-variant peaks must not
share a process.  The parent aggregates one JSON line.

Child mode (internal): ``python flash_bwd_ab.py --child MODE L``.
Parent: ``python flash_bwd_ab.py`` (env: MPIT_KBENCH_ITERS, MPIT_KBENCH_OUT,
MPIT_BWDAB_LENGTHS csv, default 8192,16384,32768).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

LENGTHS = [int(s) for s in os.environ.get(
    "MPIT_BWDAB_LENGTHS", "8192,16384,32768").split(",")]
B, H, D = 1, 8, 128


def child(mode: str, L: int) -> None:
    os.environ["MPIT_FA_FUSED_BWD"] = "1" if mode == "fused" else "0"
    from _common import log as _log

    import jax
    import jax.numpy as jnp

    from mpit_tpu.ops import flash_attention
    from mpit_tpu.utils.timing import timed_per_call

    dev = jax.devices()[0]
    key = jax.random.PRNGKey(L)
    q, k, v = (
        jax.random.normal(kk, (B, H, L, D), jnp.bfloat16)
        for kk in jax.random.split(key, 3)
    )
    grad = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=True).astype(jnp.float32)),
        argnums=(0, 1, 2),
    ))

    def stats():
        try:
            s = dev.memory_stats() or {}
            return s.get("peak_bytes_in_use")
        except Exception:
            return None

    rec = {"mode": mode, "L": L, "peak_before": stats()}
    # Beside memory_stats(), XLA's own compile-time accounting:
    # temp_size covers every transient the schedule allocates,
    # including the fused path's dQ partials.
    try:
        grad = grad.lower(q, k, v).compile()  # AOT: compile exactly once
        ma = grad.memory_analysis()
        rec["xla_temp_mb"] = round(ma.temp_size_in_bytes / 2**20, 1)
        rec["xla_peak_mb"] = round(
            (ma.temp_size_in_bytes + ma.argument_size_in_bytes
             + ma.output_size_in_bytes) / 2**20, 1)
    except Exception as e:
        rec["xla_memory_analysis"] = f"unavailable: {type(e).__name__}"
    try:
        iters = int(os.environ.get("MPIT_KBENCH_ITERS", "10"))
        t = timed_per_call(grad, q, k, v, iters=iters, auto_scale=True,
                           min_ratio=3.0, max_iters=max(4 * iters, 64))
        rec["fwdbwd_ms"] = round(t * 1e3, 3)
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {str(e)[:300]}"
    rec["peak_after"] = stats()
    if rec["peak_after"] is not None and rec["peak_before"] is not None:
        rec["peak_delta_mb"] = round(
            (rec["peak_after"] - rec["peak_before"]) / 2**20, 1)
    print("CHILD_JSON " + json.dumps(rec), flush=True)


def main() -> None:
    from _common import log as _log

    out = os.environ.get("MPIT_KBENCH_OUT", "")
    rows = []
    for L in LENGTHS:
        for mode in ("fused", "two-kernel"):
            _log(f"[bwd-ab] {mode} L={L} ...")
            timeout_s = float(os.environ.get("MPIT_BWDAB_TIMEOUT", "900"))
            rec = None
            try:
                r = subprocess.run(
                    [sys.executable, os.path.abspath(__file__),
                     "--child", mode, str(L)],
                    capture_output=True, text=True, timeout=timeout_s,
                )
            except subprocess.TimeoutExpired:
                # One slow/wedged combo must not erase the rows already
                # measured — record it and keep sweeping.
                rec = {"mode": mode, "L": L,
                       "error": f"child timed out after {timeout_s:.0f}s"}
            else:
                for line in r.stdout.splitlines():
                    if line.startswith("CHILD_JSON "):
                        try:
                            rec = json.loads(line[len("CHILD_JSON "):])
                        except json.JSONDecodeError:
                            pass  # truncated line (child killed mid-print)
                if rec is None:
                    rec = {"mode": mode, "L": L,
                           "error": f"child rc={r.returncode}: "
                                    f"{r.stderr[-300:]}"}
            # The analytic transient the fused path pays: one
            # (n_kv_blocks, Lq, D) f32 partial buffer per (B, H)
            # program, all live at once under vmap.  Resolve bk through
            # the SAME length-aware default the fused kernel uses
            # (bwd_long_bk: 2048 at 32k+) so the analytic row describes
            # the schedule that actually ran.
            if mode == "fused":
                import jax.numpy as _jnp

                from mpit_tpu.ops.flash_attention import _tile_dims

                _, _, bk, lq_p, _, d_p = _tile_dims(
                    L, L, D, None, None, None, _jnp.bfloat16,
                    bwd_long_bk=True)
                nj = -(-L // bk)
                rec["bwd_block_k"] = bk
                rec["dq_partials_mb_analytic"] = round(
                    B * H * nj * lq_p * d_p * 4 / 2**20, 1)
                # What the SHIPPING default (MPIT_FA_FUSED_BWD=auto)
                # chooses at this shape — so the aggregate record shows
                # whether each measured row is the default path.
                from mpit_tpu.ops.flash_attention import _use_fused_bwd

                import jax.numpy as jnp
                prev = os.environ.pop("MPIT_FA_FUSED_BWD", None)
                try:
                    rec["auto_picks_fused"] = _use_fused_bwd(
                        (B, H, L, D), (B, H, L, D), D, jnp.bfloat16,
                        None, None, None)
                finally:
                    if prev is not None:
                        os.environ["MPIT_FA_FUSED_BWD"] = prev
            rows.append(rec)
            _log(f"[bwd-ab] {rec}")
    from _common import emit_json

    emit_json({
        "metric": "flash_bwd_fused_vs_twokernel",
        "shape": {"B": B, "H": H, "D": D, "dtype": "bfloat16",
                  "causal": True},
        "rows": rows,
    }, out)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        child(sys.argv[2], int(sys.argv[3]))
    else:
        main()
