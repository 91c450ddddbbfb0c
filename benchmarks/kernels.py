"""Kernel-level performance evidence — pallas vs XLA on the chip.

Three legs, each printing one JSON line (plus stderr narration):

- **flash** — pallas flash attention (fwd and fwd+bwd) vs the dense
  XLA reference (:func:`mpit_tpu.ops.attention_reference`) at 4k-32k
  sequence lengths, causal, bf16 inputs.  The dense legs OOM past the
  HBM budget for the (L, L) score matrix — reported as null, which is
  itself the point: the flash kernel's O(block) memory is what makes
  the long lengths reachable at all.  Flash fwd additionally reports
  TFLOP/s and MFU against the chip's bf16 peak.
- **fused** — the one-sweep pallas optimizer commits
  (:func:`mpit_tpu.ops.fused_nesterov_commit` / ``fused_elastic``) vs
  their unfused jnp references on a 160 MB flat param vector (the
  reference's ptest payload, asyncsgd/ptest.lua:3), reporting effective
  HBM GB/s for each.
- **ring** — worst-device compute per ring step for contiguous vs
  zigzag causal layouts, emulated on one chip: the schedule of
  flash-partial calls the busiest device executes over a full ring pass
  (n=8, 32k global) is timed directly.  This isolates the compute-
  balance claim of :func:`mpit_tpu.parallel.ring_attention`
  (_ring_chunks_zigzag docstring) from ICI transfer effects.

Env knobs: MPIT_KBENCH_LEGS (csv of flash,fused,ring; default all),
MPIT_KBENCH_ITERS (default 10), MPIT_KBENCH_OUT (also append JSON lines
to this file).
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _common import emit_json, log as _log  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

ITERS = int(os.environ.get("MPIT_KBENCH_ITERS", "10"))
LEGS = os.environ.get("MPIT_KBENCH_LEGS", "flash,fused,ring").split(",")
OUT = os.environ.get("MPIT_KBENCH_OUT", "")

# bf16 peak matmul throughput per chip, by jax device_kind.
BF16_PEAK_TFLOPS = {
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,  # v5e
    "TPU v5": 459.0,       # v5p
    "TPU v5p": 459.0,
    "TPU v6 lite": 918.0,  # Trillium
}


def _emit(rec: dict) -> None:
    emit_json(rec, OUT)


def _time(fn, *args, iters=ITERS):
    """Latency-cancelled per-call device time
    (:mod:`mpit_tpu.utils.timing`).  Bounded auto_scale: sub-ms ops at
    fixed iters once printed an absurd 0.0 ms row, so the legs escalate
    until the delta clears 3x jitter — but the cap stays small (4x the
    requested iters): where jitter grows with the leg length an
    aggressive ratio (8x) escalates every ~ms-scale measurement to the
    global cap, turning one kernel table into a ~45-minute stall
    (observed)."""
    from mpit_tpu.utils.timing import timed_per_call

    return timed_per_call(fn, *args, iters=iters, auto_scale=True,
                          min_ratio=3.0, max_iters=max(4 * iters, 64))


def _try_time(fn, *args, what=""):
    try:
        return _time(fn, *args)
    except Exception as e:  # XLA OOM arrives as RuntimeError/XlaRuntimeError
        _log(f"  {what}: failed ({type(e).__name__}: {str(e)[:120]})")
        return None


def leg_flash() -> None:
    from mpit_tpu.ops import attention_reference, flash_attention

    dev = jax.devices()[0]
    peak = BF16_PEAK_TFLOPS.get(dev.device_kind)
    B, H, D = 1, 8, 128
    rows = []
    for L in (4096, 8192, 16384, 32768):
        key = jax.random.PRNGKey(L)
        q, k, v = (
            jax.random.normal(kk, (B, H, L, D), jnp.bfloat16)
            for kk in jax.random.split(key, 3)
        )

        flash = jax.jit(
            lambda q, k, v: flash_attention(q, k, v, causal=True)
        )
        dense = jax.jit(
            lambda q, k, v: attention_reference(q, k, v, causal=True)
        )

        def loss_of(fn):
            return jax.jit(
                jax.grad(
                    lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)),
                    argnums=(0, 1, 2),
                )
            )

        t_flash_f = _try_time(flash, q, k, v, what=f"flash fwd L={L}")
        t_flash_b = _try_time(
            loss_of(lambda q, k, v: flash_attention(q, k, v, causal=True)),
            q, k, v, what=f"flash fwd+bwd L={L}")
        t_dense_f = _try_time(dense, q, k, v, what=f"dense fwd L={L}")
        t_dense_b = _try_time(
            loss_of(lambda q, k, v: attention_reference(q, k, v, causal=True)),
            q, k, v, what=f"dense fwd+bwd L={L}")

        # Causal flops: 2 block matmuls, half the (q, k) tiles live.
        flops_f = 2 * B * H * L * L * D * 2 / 2
        tfs = flops_f / t_flash_f / 1e12 if t_flash_f else None
        row = {
            "L": L,
            "flash_fwd_ms": round(t_flash_f * 1e3, 3) if t_flash_f else None,
            "flash_fwdbwd_ms": round(t_flash_b * 1e3, 3) if t_flash_b else None,
            "dense_fwd_ms": round(t_dense_f * 1e3, 3) if t_dense_f else None,
            "dense_fwdbwd_ms": round(t_dense_b * 1e3, 3) if t_dense_b else None,
            "flash_fwd_tflops": round(tfs, 1) if tfs else None,
            "flash_fwd_mfu": round(tfs / peak, 3) if tfs and peak else None,
            "fwd_speedup": round(t_dense_f / t_flash_f, 2)
            if t_flash_f and t_dense_f else None,
            "fwdbwd_speedup": round(t_dense_b / t_flash_b, 2)
            if t_flash_b and t_dense_b else None,
        }
        rows.append(row)
        _log(f"[flash] {row}")
    _emit({
        "metric": "flash_attention_vs_dense",
        "device": dev.device_kind, "platform": dev.platform,
        "shape": {"B": B, "H": H, "D": D, "dtype": "bfloat16",
                  "causal": True},
        "bf16_peak_tflops": peak,
        "rows": rows,
    })


def leg_fused() -> None:
    from mpit_tpu.ops import (
        fused_elastic, fused_elastic_reference,
        fused_nesterov_commit, fused_nesterov_commit_reference,
    )
    from mpit_tpu.utils.timing import timed_chained

    n = 40 * (1 << 20)  # 40M f32 = 160 MB, the ptest.lua payload scale
    key = jax.random.PRNGKey(0)
    w, vt, g, c = (
        jax.random.normal(kk, (n,), jnp.float32)
        for kk in jax.random.split(key, 4)
    )
    clr = jnp.float32(1e-2)
    mva = jnp.float32(0.15)
    gb = n * 4 / 2**30

    # State is donated and chained call-to-call — how the trainers drive
    # these updates; timing without donation would charge the pallas
    # path's input/output aliasing a defensive copy it never pays in use.
    def nesterov(impl):
        return jax.jit(
            lambda st, g, clr: impl(st[0], st[1], g, clr), donate_argnums=0
        )

    def elastic(impl):
        # State carries (w, sug) so both outputs stay live — returning
        # only w_new would let XLA dead-code the force computation.
        return jax.jit(
            lambda st, c, mva: impl(st[0], c, mva), donate_argnums=0
        )

    # Each measurement donates (consumes) its state — fresh copies per run.
    # Nesterov commit: reads w, vt, g; writes w, vt -> 5 array passes.
    t_fused = timed_chained(
        nesterov(fused_nesterov_commit), (w.copy(), vt.copy()), g, clr,
        iters=ITERS)
    t_ref = timed_chained(
        nesterov(fused_nesterov_commit_reference), (w.copy(), vt.copy()),
        g, clr, iters=ITERS)
    # Elastic: reads w, center; writes w, sug -> 4 passes.
    t_fused_e = timed_chained(
        elastic(fused_elastic), (w.copy(), jnp.zeros_like(w)), c, mva,
        iters=ITERS)
    t_ref_e = timed_chained(
        elastic(fused_elastic_reference), (w.copy(), jnp.zeros_like(w)),
        c, mva, iters=ITERS)

    rec = {
        "metric": "fused_update_sweeps",
        "device": jax.devices()[0].device_kind,
        "payload_mb": round(n * 4 / 2**20, 1),
        "nesterov": {
            "fused_ms": round(t_fused * 1e3, 3),
            "unfused_ms": round(t_ref * 1e3, 3),
            "fused_gbs": round(5 * gb / t_fused, 1),
            "unfused_gbs": round(5 * gb / t_ref, 1),
            "speedup": round(t_ref / t_fused, 2),
        },
        "elastic": {
            "fused_ms": round(t_fused_e * 1e3, 3),
            "unfused_ms": round(t_ref_e * 1e3, 3),
            "fused_gbs": round(4 * gb / t_fused_e, 1),
            "unfused_gbs": round(4 * gb / t_ref_e, 1),
            "speedup": round(t_ref_e / t_fused_e, 2),
        },
    }
    _log(f"[fused] {rec['nesterov']} | {rec['elastic']}")
    _emit(rec)


def leg_ring() -> None:
    """Worst-device compute over one full causal ring pass, one chip.

    Contiguous layout, ring of n: device n-1's Q chunk attends every KV
    chunk — n live (C, C) partials per pass (devices 0..n-2 idle through
    masked steps; the ring's wall-clock is set by device n-1).  Zigzag:
    every device computes the same schedule — per step one statically
    live (C/2, C/2) pair plus at most one conditionally live pair; worst
    case is 2n half-pairs + 1 per pass.  Both schedules are executed
    as the actual flash-partial call sequence under jit.
    """
    from mpit_tpu.ops import flash_attention_partial, merge_partials

    n = 8
    C = 4096  # per-device chunk -> 32k global
    B, H, D = 1, 8, 128
    key = jax.random.PRNGKey(1)
    q, k, v = (
        jax.random.normal(kk, (B, H, C, D), jnp.bfloat16)
        for kk in jax.random.split(key, 3)
    )

    def partial(qc, kc, vc, qo, ko):
        return flash_attention_partial(qc, kc, vc, causal=True,
                                       q_offset=qo, kv_offset=ko)

    def contiguous_worst(q, k, v):
        # Device n-1: q_off = (n-1)*C; kv owner walks n-1, n-2, ... 0.
        part = partial(q, k, v, (n - 1) * C, (n - 1) * C)
        for s in range(1, n):
            owner = (n - 1 + (n - s)) % n
            part = merge_partials(part, partial(q, k, v, (n - 1) * C,
                                                owner * C))
        return part[0]

    def zigzag_worst(q, k, v):
        # Device n-1 owns half-chunks (n-1, n) of 2n. Per step: the
        # statically live (late_q, early_kv) pair, plus (late, late) when
        # owner >= my and (early, early) when my >= owner — my == n-1
        # makes every (early, early) live: the zigzag worst case.
        c = C // 2
        qe, ql = q[..., :c, :], q[..., c:, :]
        ke, kl = k[..., :c, :], k[..., c:, :]
        ve, vl = v[..., :c, :], v[..., c:, :]
        my = n - 1
        qoffs = (my * c, (2 * n - 1 - my) * c)
        # s=0 (owner == my): all three live pairs.
        pe = partial(qe, ke, ve, qoffs[0], my * c)
        plq = partial(ql, ke, ve, qoffs[1], my * c)
        plq = merge_partials(
            plq, partial(ql, kl, vl, qoffs[1], (2 * n - 1 - my) * c))
        for s in range(1, n):
            owner = (my + (n - s)) % n
            koffs = (owner * c, (2 * n - 1 - owner) * c)
            plq = merge_partials(plq, partial(ql, ke, ve, qoffs[1], koffs[0]))
            pe = merge_partials(pe, partial(qe, ke, ve, qoffs[0], koffs[0]))
            if owner >= my:
                plq = merge_partials(
                    plq, partial(ql, kl, vl, qoffs[1], koffs[1]))
        return pe[0], plq[0]

    t_cont = _time(jax.jit(contiguous_worst), q, k, v)
    t_zig = _time(jax.jit(zigzag_worst), q, k, v)
    rec = {
        "metric": "ring_causal_worst_device_compute",
        "device": jax.devices()[0].device_kind,
        "n_ring": n, "chunk": C, "global_L": n * C,
        "shape": {"B": B, "H": H, "D": D, "dtype": "bfloat16"},
        "contiguous_ms": round(t_cont * 1e3, 3),
        "zigzag_ms": round(t_zig * 1e3, 3),
        "zigzag_speedup": round(t_cont / t_zig, 2),
    }
    _log(f"[ring] {rec}")
    _emit(rec)


def main() -> None:
    known = {"flash": leg_flash, "fused": leg_fused, "ring": leg_ring}
    legs = [s.strip() for s in LEGS if s.strip()]
    bad = [s for s in legs if s not in known]
    if bad or not legs:
        raise SystemExit(
            f"MPIT_KBENCH_LEGS={','.join(LEGS)!r}: unknown leg(s) {bad}; "
            f"valid: {sorted(known)}"
        )
    for leg in legs:
        known[leg]()


if __name__ == "__main__":
    main()
