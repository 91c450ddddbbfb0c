"""Pallas ops vs their jnp references (interpret mode on the CPU suite).

The reference frames its "unit tests" as runnable scripts checked by eye
(SURVEY.md §4); here every kernel is pinned to a pure-jnp reference
implementation with tolerances, the golden-value style the rebuild's test
strategy mandates.
"""

import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu.ops import (
    as_rows,
    attention_reference,
    block_attention_partial,
    flash_attention,
    from_rows,
    fused_adam,
    fused_adam_reference,
    fused_elastic,
    fused_elastic_reference,
    fused_nesterov_commit,
    fused_nesterov_commit_reference,
)
from mpit_tpu.ops.flash_attention import finalize_partials, merge_partials
from mpit_tpu.ops.select_bits import pack as pack_bits
from mpit_tpu.optim.rules import adam_apply, adam_init


@pytest.mark.parametrize("n", [7, 128, 1024, 5000])
def test_tiles_roundtrip(rng, n):
    x = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    tiled, m = as_rows(x)
    assert tiled.ndim == 2 and tiled.shape[1] == 128
    np.testing.assert_array_equal(np.asarray(from_rows(tiled, m)), np.asarray(x))


@pytest.mark.parametrize("n", [100, 33000])
@pytest.mark.parametrize("l2wd", [0.0, 0.01])
def test_fused_nesterov(rng, n, l2wd):
    w, vt, g = (jnp.asarray(rng.normal(size=(n,)), jnp.float32) for _ in range(3))
    clr = jnp.float32(0.05)
    w1, vt1 = fused_nesterov_commit(w, vt, g, clr, l2wd=l2wd)
    w2, vt2 = fused_nesterov_commit_reference(w, vt, g, clr, l2wd=l2wd)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(vt1), np.asarray(vt2), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n", [128 * 300, 128 * 300 + 77, 1000],
                         ids=["whole_lanes", "no_whole_lanes", "under_a_block"])
@pytest.mark.parametrize("l2wd", [0.0, 0.01])
def test_the_commit_writes_the_next_steps_displaced_point(rng, n, l2wd):
    """With ``mom_next`` the kernel does the commit and then the next
    step's lookahead on the block it holds: the reference's arithmetic
    in the reference's order, to the bit, at every length (one view,
    the last block overhanging)."""
    w, vt, g = (jnp.asarray(rng.normal(size=(n,)), jnp.float32) for _ in range(3))
    kernel = jax.jit(lambda w, vt, g, clr, mom: fused_nesterov_commit(
        w, vt, g, clr, l2wd=l2wd, mom_next=mom))
    plain = jax.jit(lambda w, vt, g, clr, mom: fused_nesterov_commit_reference(
        w, vt, g, clr, l2wd=l2wd, mom_next=mom))
    clr, mom = jnp.float32(0.05), jnp.float32(0.9)
    got, want = kernel(w, vt, g, clr, mom), plain(w, vt, g, clr, mom)
    for a, b in zip(got, want):
        assert a.shape == (n,)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and it is the two phases: commit, then ``vt *= mom; w += vt``
    w_c, vt_c = fused_nesterov_commit_reference(w, vt, g, clr, l2wd=l2wd)
    np.testing.assert_allclose(np.asarray(want[1]), 0.9 * np.asarray(vt_c),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(want[0]),
                               np.asarray(w_c) + 0.9 * np.asarray(vt_c),
                               rtol=1e-5, atol=1e-6)


def test_fused_nesterov_jit_traced_lr(rng):
    w, vt, g = (jnp.asarray(rng.normal(size=(500,)), jnp.float32) for _ in range(3))

    @jax.jit
    def step(w, vt, g, clr):
        return fused_nesterov_commit(w, vt, g, clr)

    w1, vt1 = step(w, vt, g, jnp.float32(0.1))
    w2, vt2 = fused_nesterov_commit_reference(w, vt, g, 0.1)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(vt1), np.asarray(vt2), rtol=1e-5, atol=1e-6)


def test_fused_adam_matches_rule(rng):
    """Kernel + external bias-correction == optim.rules adam_apply."""
    n = 2000
    p = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    st = adam_init(p)
    lr, b1, b2, eps = 1e-3, 0.9, 0.999, 1e-8
    p_ref, st_ref = p, st
    p_k, m_k, v_k, t = p, st["m"], st["v"], 0
    for _ in range(3):
        g = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
        p_ref, st_ref = adam_apply(
            p_ref, g, st_ref, lr=lr, beta1=b1, beta2=b2, epsilon=eps
        )
        t += 1
        lr_t = lr * np.sqrt(1.0 - b2**t) / (1.0 - b1**t)
        p_k, m_k, v_k = fused_adam(
            p_k, g, m_k, v_k, lr_t, beta1=b1, beta2=b2, epsilon=eps
        )
    np.testing.assert_allclose(np.asarray(p_k), np.asarray(p_ref), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(m_k), np.asarray(st_ref["m"]), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(v_k), np.asarray(st_ref["v"]), rtol=1e-5, atol=1e-6)
    ref = fused_adam_reference(p, g, st["m"], st["v"], lr)
    assert all(r.shape == p.shape for r in ref)


def test_fused_elastic(rng):
    n = 3000
    w = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    w1, sug1 = fused_elastic(w, c, 0.15)
    w2, sug2 = fused_elastic_reference(w, c, 0.15)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(sug1), np.asarray(sug2), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


# The algorithm's tests hold the kernels to the float32 reference at
# float32's tolerances, so they name a precision: the operands then stay
# float32 (``operand_dtype``; in the interpreter a product is exact at
# either).  What a call at the default precision does, bf16 operands
# and float32 results, has its own cases below (PR 57).
flash_f32 = functools.partial(flash_attention, precision="highest")


def _qkv(rng, shape):
    return tuple(
        jnp.asarray(rng.normal(size=shape) * 0.5, jnp.float32) for _ in range(3)
    )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(64, 16), (2, 3, 40, 24)])
def test_flash_matches_reference(rng, causal, shape):
    q, k, v = _qkv(rng, shape)
    out = flash_f32(q, k, v, causal=causal, block_q=16, block_k=128)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_offsets_match_slicing(rng):
    """Offset-masked chunk attention == the matching slice of global
    causal attention (the ring-attention contract)."""
    L, D, C = 32, 16, 8
    q, k, v = _qkv(rng, (L, D))
    full = attention_reference(q, k, v, causal=True)
    for qi in range(L // C):
        parts = [
            block_attention_partial(
                q[qi * C:(qi + 1) * C], k[kj * C:(kj + 1) * C],
                v[kj * C:(kj + 1) * C], causal=True,
                q_offset=qi * C, kv_offset=kj * C,
            )
            for kj in range(L // C)
        ]
        acc, m, l = parts[0]
        for p in parts[1:]:
            acc, m, l = merge_partials((acc, m, l), p)
        merged = finalize_partials(acc, l)
        np.testing.assert_allclose(
            np.asarray(merged), np.asarray(full[qi * C:(qi + 1) * C]), atol=2e-5
        )


def test_flash_offsets_pallas(rng):
    """The pallas kernel honors traced offsets (chunk vs global slice)."""
    L, D, C = 32, 16, 16
    q, k, v = _qkv(rng, (L, D))
    full = attention_reference(q, k, v, causal=True)
    out = flash_f32(
        q[C:], k, v, causal=True, q_offset=jnp.int32(C), block_q=16, block_k=128
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(full[C:]), atol=2e-5)


def _assert_flash_grads_match(q, k, v, fa=None, atol=3e-5):
    """Shared grad check: squared-sum loss through the pallas path vs the
    dense reference, 3e-5 atol (the ONE place the loss/tolerance live).
    ``fa`` overrides the attention callable (default: tiny blocks)."""
    if fa is None:
        fa = functools.partial(flash_f32, block_q=8, block_k=128)
    fa_loss = lambda q, k, v: jnp.sum(fa(q, k, v, causal=True) ** 2)
    ref = lambda q, k, v: jnp.sum(
        attention_reference(q, k, v, causal=True) ** 2
    )
    for a, b in zip(jax.grad(fa_loss, argnums=(0, 1, 2))(q, k, v),
                    jax.grad(ref, argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=atol)
    return fa_loss, ref


@pytest.fixture
def fa_backward_path(request, monkeypatch):
    """Pin the backward schedule (fused vs two-kernel) for one test.

    The MPIT_FA_FUSED_BWD gate is read at trace time, so a cached trace
    from the other leg would silently shadow the pinned one — clear
    jax's trace/compile caches around the leg (cheap at these shapes)."""
    monkeypatch.setenv("MPIT_FA_FUSED_BWD", request.param)
    jax.clear_caches()
    yield request.param
    jax.clear_caches()


@pytest.mark.parametrize("fa_backward_path", ["1", "0"], indirect=True,
                         ids=["fused-bwd", "two-kernel-bwd"])
def test_flash_grad_matches_reference(rng, fa_backward_path):
    _assert_flash_grads_match(*_qkv(rng, (24, 16)))


def test_the_residuals_names_change_nothing_outside_a_checkpoint(
        rng, monkeypatch):
    """The forward rule names its output and row log-sum-exp for a
    checkpoint's policy to keep (``FLASH_OUT``, ``FLASH_LSE``).  Outside
    any checkpoint they are nothing: the same output, the same gradient
    and the same lowered text as a rule that names neither."""
    import re
    import sys

    fa = sys.modules["mpit_tpu.ops.flash_attention"]
    q, k, v = _qkv(rng, (2, 24, 16))

    def read():
        fa._make_flash.cache_clear()
        jax.clear_caches()
        call = lambda q, k, v: flash_attention(q, k, v, causal=True,
                                               block_q=8, block_k=128)
        grad = jax.jit(jax.grad(lambda *x: jnp.sum(call(*x) ** 2),
                                argnums=(0, 1, 2)))
        # the lowering numbers its private functions (``@_pad_70``) from
        # a counter that is not the program's
        text = re.sub(r"@(\w+?)_\d+\b", r"@\1",
                      grad.lower(q, k, v).as_text())
        return call(q, k, v), grad(q, k, v), text

    named = read()
    assert str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(
        flash_attention(q, k, v, causal=True))))(q)).count(fa.FLASH_LSE) == 1
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    try:
        bare = read()
    finally:
        fa._make_flash.cache_clear()
        jax.clear_caches()
    np.testing.assert_array_equal(np.asarray(named[0]), np.asarray(bare[0]))
    for a, b in zip(named[1], bare[1]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert named[2] == bare[2]


def test_fwd_long_bq_block_routing(monkeypatch):
    """Length-aware forward default (from a July 2026 sweep the ledger
    has not reproduced): block_q
    grows to 2048 at Lq >= 16384 bf16 — forward only, explicit blocks
    and the env kill-switch win, f32 keeps its 512 default."""
    from mpit_tpu.ops.flash_attention import _tile_dims

    def bq_of(lq, dtype=jnp.bfloat16, fwd=True, block_q=None, **env):
        for k, v in env.items():
            monkeypatch.setenv(k, v)
        out = _tile_dims(lq, lq, 128, block_q, None, None, dtype,
                         fwd_long_bq=fwd)
        monkeypatch.delenv("MPIT_FA_LONG_BQ", raising=False)
        return out[1]

    assert bq_of(8192) == 1024          # short: flat default
    assert bq_of(16384) == 2048         # long forward: grown
    assert bq_of(32768) == 2048
    assert bq_of(32768, fwd=False) == 1024          # backward: unchanged
    assert bq_of(32768, block_q=1024) == 1024       # explicit wins
    assert bq_of(32768, MPIT_FA_LONG_BQ="0") == 1024  # env kill-switch
    assert bq_of(32768, dtype=jnp.float32) == 512   # f32 path untouched


def test_bwd_long_bk_block_routing(monkeypatch):
    """Backward default block_k grows to 2048 at Lk >= 32768 bf16 (the
    winner of a July 2026 32k sweep the ledger has not reproduced) —
    and the fused-schedule gate
    resolves the SAME bk, so its dQ-partials transient estimate matches
    the schedule that actually runs (2 GB at 32k on the bench shape,
    admitted by the 2048 MB budget)."""
    from mpit_tpu.ops.flash_attention import _tile_dims, _use_fused_bwd

    def bk_of(lk, dtype=jnp.bfloat16, block_k=None, **env):
        for kk, vv in env.items():
            monkeypatch.setenv(kk, vv)
        out = _tile_dims(lk, lk, 128, None, block_k, None, dtype,
                         bwd_long_bk=True)
        monkeypatch.delenv("MPIT_FA_LONG_BK_BWD", raising=False)
        return out[2]

    assert bk_of(16384) == 1024          # jitter-neutral length: flat
    assert bk_of(32768) == 2048          # measured winner
    assert bk_of(32768, block_k=1024) == 1024        # explicit wins
    assert bk_of(32768, MPIT_FA_LONG_BK_BWD="0") == 1024
    assert bk_of(32768, dtype=jnp.float32) == 512

    # Gate/kernel agreement at 32k: bk=2048 -> 16 kv blocks -> exactly
    # 2048 MB on the (1, 8) x 32k x 128 bench shape -> fused admitted.
    monkeypatch.delenv("MPIT_FA_FUSED_BWD", raising=False)
    monkeypatch.delenv("MPIT_FA_FUSED_BWD_MAX_MB", raising=False)
    args32 = ((1, 8, 32768, 128), (1, 8, 32768, 128), 128, jnp.bfloat16,
              None, None, None)
    assert _use_fused_bwd(*args32) is True
    # The kill-switch restores the flat bk -> 4 GB -> two-kernel.
    monkeypatch.setenv("MPIT_FA_LONG_BK_BWD", "0")
    assert _use_fused_bwd(*args32) is False


def test_vmem_pin_keeps_flat_block_defaults(monkeypatch):
    """ADVICE round-5 regression: MPIT_FA_VMEM_MB=0 (the stock-budget
    A/B control) suppresses the auto VMEM raise, so the length-aware
    2048-block defaults — whose >4 MB score tile cannot compile under
    the stock 16 MB budget — must fall back to the flat 1024 blocks.
    Any explicit budget below the 64 MB floor pins the same fallback; a
    budget at/above it (and the unset default) keeps the grown tiles."""
    from mpit_tpu.ops.flash_attention import _tile_dims

    def blocks_of(**env):
        monkeypatch.delenv("MPIT_FA_VMEM_MB", raising=False)
        for kk, vv in env.items():
            monkeypatch.setenv(kk, vv)
        fwd = _tile_dims(32768, 32768, 128, None, None, None, jnp.bfloat16,
                         fwd_long_bq=True)
        bwd = _tile_dims(32768, 32768, 128, None, None, None, jnp.bfloat16,
                         bwd_long_bk=True)
        return fwd[1], bwd[2]

    assert blocks_of() == (2048, 2048)  # unset: length-aware defaults
    # The documented control combination (ADVICE: flash_attention.py
    # _fa_compiler_params) now resolves a compilable geometry.
    assert blocks_of(MPIT_FA_VMEM_MB="0") == (1024, 1024)
    assert blocks_of(MPIT_FA_VMEM_MB="16") == (1024, 1024)  # below floor
    assert blocks_of(MPIT_FA_VMEM_MB="64") == (2048, 2048)  # at floor
    assert blocks_of(MPIT_FA_VMEM_MB="100") == (2048, 2048)
    # Explicit block sizes are never second-guessed by the pin.
    out = _tile_dims(32768, 32768, 128, 2048, None, None, jnp.bfloat16,
                     fwd_long_bq=True)
    assert out[1] == 2048
    monkeypatch.delenv("MPIT_FA_VMEM_MB", raising=False)


@pytest.mark.parametrize("fa_backward_path", ["1", "0"], indirect=True,
                         ids=["fused-bwd", "two-kernel-bwd"])
@pytest.mark.parametrize("blocks", [(1024, 2048)])
def test_flash_grad_matches_reference_wide_bk(rng, blocks, fa_backward_path):
    """Multi-block bk=2048 geometry (the long-L backward default),
    exercised in interpret mode at a size with >=2 kv blocks per grid —
    small-shape grad tests clamp blocks and never see this shape."""
    bq, bk = blocks
    L = 4096
    q, k, v = _qkv(rng, (L, 64))

    fa = functools.partial(flash_f32, block_q=bq, block_k=bk)
    _assert_flash_grads_match(q, k, v, fa=fa)


def test_fused_bwd_auto_gate(monkeypatch):
    """The auto mode picks the fused sweep only while the dQ-partials
    transient (batch x n_kv_blocks x Lq_p x D_p f32) fits the budget."""
    from mpit_tpu.ops.flash_attention import _use_fused_bwd

    monkeypatch.delenv("MPIT_FA_FUSED_BWD", raising=False)
    # 8k, 8 heads, bf16 1024-blocks: 8 * 8 * 8192 * 128 * 4 = 256 MB.
    args = ((1, 8, 8192, 128), (1, 8, 8192, 128), 128, jnp.bfloat16,
            None, None, None)
    assert _use_fused_bwd(*args) is True  # default budget 2048 MB
    monkeypatch.setenv("MPIT_FA_FUSED_BWD_MAX_MB", "255")
    assert _use_fused_bwd(*args) is False
    monkeypatch.delenv("MPIT_FA_FUSED_BWD_MAX_MB", raising=False)
    # 16k, 8 heads: 16 * 16384 * 128 * 4 x 8 = 1 GB — admitted by the
    # round-5 budget (a July 2026 A/B the ledger has not reproduced
    # measured fused 5.7% faster here).
    args16 = ((1, 8, 16384, 128), (1, 8, 16384, 128), 128, jnp.bfloat16,
              None, None, None)
    assert _use_fused_bwd(*args16) is True
    # 32k: the length-aware backward default bk=2048 (16 kv blocks)
    # puts the transient at exactly 2048 MB -> admitted; pinning the
    # flat bk=1024 (32 blocks, 4 GB) or shaving the budget refuses it.
    args32 = ((1, 8, 32768, 128), (1, 8, 32768, 128), 128, jnp.bfloat16,
              None, None, None)
    monkeypatch.delenv("MPIT_FA_FUSED_BWD_MAX_MB", raising=False)
    assert _use_fused_bwd(*args32) is True
    monkeypatch.setenv("MPIT_FA_FUSED_BWD_MAX_MB", "2047")
    assert _use_fused_bwd(*args32) is False
    monkeypatch.delenv("MPIT_FA_FUSED_BWD_MAX_MB", raising=False)
    args32_flat = args32[:-1]
    assert _use_fused_bwd(*args32_flat, 1024) is False
    # The explicit levers stay unconditional.
    monkeypatch.setenv("MPIT_FA_FUSED_BWD", "1")
    assert _use_fused_bwd(*args32) is True
    monkeypatch.setenv("MPIT_FA_FUSED_BWD", "0")
    assert _use_fused_bwd(*args) is False
    # Unknown values fail loudly (pre-r5 semantics force-fused on any
    # non-"0" string — silent reinterpretation would corrupt A/Bs).
    monkeypatch.setenv("MPIT_FA_FUSED_BWD", "true")
    with pytest.raises(ValueError, match="MPIT_FA_FUSED_BWD"):
        _use_fused_bwd(*args)


def test_fused_bwd_auto_gate_end_to_end(rng, monkeypatch):
    """auto mode over budget must route a REAL vmapped grad through the
    two-kernel schedule and still match the reference — the gate's
    integration path, not just its arithmetic."""
    from mpit_tpu.ops.flash_attention import _use_fused_bwd

    monkeypatch.delenv("MPIT_FA_FUSED_BWD", raising=False)
    monkeypatch.setenv("MPIT_FA_FUSED_BWD_MAX_MB", "0.0001")
    jax.clear_caches()
    try:
        q, k, v = _qkv(rng, (2, 24, 16))
        # Pin the ROUTING first: with this budget the gate must pick the
        # two-kernel schedule for exactly this call's shapes — without
        # this, a gate regression (auto always fused) would still pass
        # the numeric check below, since both schedules are correct.
        assert _use_fused_bwd(q.shape, k.shape, q.shape[-1], q.dtype,
                              None, 8, 128) is False
        _assert_flash_grads_match(q, k, v)
    finally:
        jax.clear_caches()


def test_flash_dimsem_off_smoke(rng, monkeypatch):
    """MPIT_FA_DIMSEM=0 (unannotated grids, the other A/B lever) still
    produces correct forward and gradients."""
    monkeypatch.setenv("MPIT_FA_DIMSEM", "0")
    jax.clear_caches()
    try:
        q, k, v = _qkv(rng, (24, 16))
        fa, ref = _assert_flash_grads_match(q, k, v)
        np.testing.assert_allclose(
            float(fa(q, k, v)), float(ref(q, k, v)), rtol=1e-5
        )
    finally:
        jax.clear_caches()


def test_flash_ragged_lengths(rng):
    """Non-block-multiple Lq/Lk/D are padded and masked correctly."""
    q, k, v = _qkv(rng, (19, 12))
    k2, v2 = k[:13], v[:13]
    out = flash_f32(q, k2, v2, block_q=8, block_k=128)
    ref = attention_reference(q, k2, v2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("fa_backward_path", ["1", "0"], indirect=True,
                         ids=["fused-bwd", "two-kernel-bwd"])
def test_flash_bwd_ragged_offset_pair(rng, fa_backward_path):
    """The pallas backward handles the ring's per-step shape: unequal
    ragged Lq/Lk, global offsets, batched leading axes."""
    q = _qkv(rng, (2, 19, 12))[0]
    k, v = (x[:, :13] for x in _qkv(rng, (2, 29, 12))[:2])
    g = jnp.asarray(rng.normal(size=(2, 19, 12)), jnp.float32)
    fa = lambda q, k, v: flash_f32(
        q, k, v, causal=True, q_offset=26, kv_offset=13,
        block_q=8, block_k=128,
    )
    ref = lambda q, k, v: attention_reference(
        q, k, v, causal=True, q_offset=26, kv_offset=13
    )
    o1, vjp1 = jax.vjp(fa, q, k, v)
    o2, vjp2 = jax.vjp(ref, q, k, v)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)
    for a, b, nm in zip(vjp1(g), vjp2(g), "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-4, err_msg=f"d{nm}"
        )


# -- the live-block walk (PR 33) ----------------------------------------------
#
# One function, ``_Walk.live_range``, says which inner blocks a grid row
# visits; the index maps and the kernels' bodies both read it.  The tests
# hold it to the mask itself, and the kernels to the reference on the
# same grid of cases.

# (causal, window, block_q, block_k, (lq, lk))
WALK_MASKS = {
    "plain": (False, None, 16, 32, (128, 128)),
    "causal": (True, None, 16, 32, (128, 128)),
    "window inside a block": (True, 7, 16, 32, (128, 128)),
    "window of two blocks": (True, 64, 16, 32, (128, 128)),
    "window beyond the sequence": (True, 10_000, 16, 32, (128, 128)),
    "causal, ragged": (True, None, 16, 32, (100, 77)),
    "window, ragged": (True, 40, 16, 32, (77, 100)),
    "plain, ragged": (False, None, 16, 32, (100, 77)),
    "window 1024 on 512-blocks": (True, 1024, 512, 512, (2048, 2048)),
}
# (q_offset, kv_offset): as a ring step has them
WALK_OFFSETS = {
    "no offsets": (0, 0),
    "q ahead": (96, 0),
    "q ahead, off the blocks": (75, 11),
    "equal": (48, 48),
    "q behind": (0, 96),
    "q wholly behind": (0, 4096),   # a ring step above the diagonal
}


def _walk_of(mask, groups, kv_outer):
    from mpit_tpu.ops.flash_attention import _Walk
    from mpit_tpu.ops.tiles import round_up

    causal, window, bq, bk, (lq, lk) = WALK_MASKS[mask]
    lq_p, lk_p = round_up(lq, bq), round_up(lk, bk)
    return _Walk(kv_outer, causal, window, bq, bk, lq_p // bq, lk_p // bk,
                 groups), lq_p, lk_p, lk


@pytest.mark.parametrize("kv_outer", [False, True], ids=["q-outer", "kv-outer"])
@pytest.mark.parametrize("groups", [1, 4, 8])
@pytest.mark.parametrize("offsets", sorted(WALK_OFFSETS))
@pytest.mark.parametrize("mask", sorted(WALK_MASKS))
def test_live_range_is_the_blocks_in_which_the_mask_has_an_entry(
        mask, offsets, groups, kv_outer):
    """Brute force: for every outer block, ``[lo, hi]`` is exactly the
    inner blocks whose tile of ``_mask`` has any true entry, it is never
    longer than the walk's extent, and the index map's block is the
    walk's while live and a block already held after."""
    from mpit_tpu.ops.flash_attention import _mask

    walk, lq_p, lk_p, lk = _walk_of(mask, groups, kv_outer)
    q_off, kv_off = WALK_OFFSETS[offsets]
    valid = np.broadcast_to(
        _mask(lq_p, lk_p, q_off, kv_off, lk, walk.causal, walk.window),
        (lq_p, lk_p))
    tiles = valid.reshape(walk.q_blocks, walk.block_q,
                          walk.kv_blocks, walk.block_k).any(axis=(1, 3))
    n_outer = walk.kv_blocks if kv_outer else groups * walk.q_blocks
    outers = np.arange(n_outer)
    _, lo, hi = np.broadcast_arrays(outers, *walk.live_range(
        outers, q_off, kv_off, lk, xp=np))
    refs = [x.astype(np.int32) for x in (lo, hi)]   # the prefetched ranges
    for outer in range(n_outer):
        row = tiles[:, outer] if kv_outer else tiles[outer % walk.q_blocks]
        live = np.flatnonzero(row)
        if live.size == 0:
            assert hi[outer] < lo[outer], (outer, lo[outer], hi[outer])
            continue
        assert (lo[outer], hi[outer]) == (live[0], live[-1]), outer
        assert live.size == hi[outer] - lo[outer] + 1 <= walk.extent
        for t in range(walk.grid[1]):
            head, u = divmod(t, walk.extent) if kv_outer else (0, t)
            want = head * walk.q_blocks + min(lo[outer] + u, hi[outer])
            assert int(walk.fetch(outer, t, *refs)) == want, (outer, t)
    steps = walk.steps(q_off, kv_off, lk)
    assert steps["live"] == groups * int(tiles.sum())
    assert steps["visited"] == walk.grid[0] * walk.grid[1] <= steps["rect"]


@pytest.mark.parametrize("kv_outer", [False, True], ids=["q-outer", "kv-outer"])
def test_the_walks_arithmetic_traces_as_plain_primitives(kv_outer):
    """An index map is traced once an operand, again a ``vmap`` level
    and again at lowering; with ``jnp``'s operators every operation of
    it was a nested jitted ufunc, which cost a ten-layer model seconds
    of every start-up (PERF.md section 6, PR 33).  So the index map
    only reads its row's prefetched range, and that and the ranges'
    own arithmetic bind plain primitives."""
    walk, _, _, lk = _walk_of("window, ragged", 4, kv_outer)

    class Ref:  # stands in for an SMEM ref: reading it is one load
        def __init__(self, value):
            self.value = value

        def __getitem__(self, _index):
            return self.value

    def index_map(outer, t, lo, hi):
        return walk.fetch(outer, t, Ref(lo), Ref(hi))

    def ranges(outer, q_off, kv_off):
        from mpit_tpu.ops.flash_attention import _Int

        lo, hi = walk.live_range(_Int(outer), _Int(q_off), _Int(kv_off), lk)
        return _Int.of(lo).v, hi.v

    one = jnp.int32(1)
    text = (str(jax.make_jaxpr(index_map)(one, one, one, one))
            + str(jax.make_jaxpr(ranges)(jnp.arange(4), one, one)))
    assert "pjit" not in text and "select_n" in text


# (causal, window) x the offsets above, on 64 x 128 blocks over ragged
# lengths; "q wholly behind" leaves every row's range empty
KERNEL_MASKS = {
    "causal": None,
    "window inside a block": 40,
    "window on a block edge": 128,
    "window beyond the sequence": 1000,
}


@pytest.mark.parametrize("fa_backward_path", ["1", "0"], indirect=True,
                         ids=["fused-bwd", "two-kernel-bwd"])
@pytest.mark.parametrize("groups", [1, 4])
@pytest.mark.parametrize("offsets", sorted(WALK_OFFSETS))
@pytest.mark.parametrize("mask", sorted(KERNEL_MASKS))
def test_walked_kernels_equal_the_reference(mask, offsets, groups,
                                            fa_backward_path):
    """Forward, ``lse`` and all three gradients of the interpreted
    kernels against ``attention_reference`` on the walk's grid of cases,
    under both backward schedules.  The interpreter fills what no step
    writes with NaN, so a summed unvisited dQ slot or a row that never
    reached its finalize would show."""
    from mpit_tpu.ops.flash_attention import (
        _lse_of, _mask, flash_attention_partial)

    window = KERNEL_MASKS[mask]
    q_off, kv_off = WALK_OFFSETS[offsets]
    lq, lk, hkv, d = 150, 200, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(groups * 100 + lq), 4)
    q = jax.random.normal(keys[0], (hkv * groups, lq, d))
    k = jax.random.normal(keys[1], (hkv, lk, d))
    v = jax.random.normal(keys[2], (hkv, lk, d))
    g = jax.random.normal(keys[3], q.shape)
    kw = dict(causal=True, window=window, q_offset=q_off, kv_offset=kv_off)

    def kernel(q, k, v):
        return jnp.sum(g * flash_attention(
            q, k, v, block_q=64, block_k=128, interpret=True,
            precision="highest", **kw))

    def plain(q, k, v):
        return jnp.sum(g * attention_reference(q, k, v, **kw))

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(kernel, (0, 1, 2))(q, k, v)
        want = jax.value_and_grad(plain, (0, 1, 2))(q, k, v)
        acc, m, l = flash_attention_partial(
            q.reshape(hkv, groups, lq, d), k, v, block_q=64, block_k=128,
            interpret=True, precision="highest", **kw)
        s = jnp.einsum("hgqd,hkd->hgqk", q.reshape(hkv, groups, lq, d),
                       k) / np.sqrt(d)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)
    valid = _mask(lq, lk, q_off, kv_off, lk, True, window)
    lse = jax.nn.logsumexp(jnp.where(valid, s, -jnp.inf), axis=-1)
    np.testing.assert_allclose(_lse_of(m, l), lse, atol=2e-4, rtol=2e-4)
    dead = ~np.asarray(valid).any(axis=-1)   # rows that see no key
    assert dead.all() == (offsets == "q wholly behind")
    assert dead.any() or offsets != "q behind"
    # the partials' public contract on such rows: zeros, -inf and 0
    assert np.all(np.asarray(acc)[..., dead, :] == 0.0)
    assert np.all(np.isneginf(np.asarray(m)[..., dead]))
    assert np.all(np.asarray(l)[..., dead] == 0.0)


@pytest.mark.parametrize("fa_backward_path", ["1", "0"], indirect=True,
                         ids=["fused-bwd", "two-kernel-bwd"])
@pytest.mark.parametrize("window", [None, 40], ids=["causal", "window"])
def test_traced_offsets_give_what_concrete_ones_give(window,
                                                     fa_backward_path):
    """A ring step's offsets are traced (``axis_index`` under
    ``shard_map``): the prefetched scalars steer the same index maps,
    and the numbers are the concrete call's, bit for bit."""
    from mpit_tpu.ops.flash_attention import (
        _lse_of, flash_attention_bwd_pair, flash_attention_partial)

    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(keys[0], (2, 4, 150, 16))   # 4 heads over 1
    k = jax.random.normal(keys[1], (2, 200, 16))
    v = jax.random.normal(keys[2], (2, 200, 16))
    do = jax.random.normal(keys[3], q.shape)

    def pair(q_offset, kv_offset):
        kw = dict(causal=True, window=window, q_offset=q_offset,
                  kv_offset=kv_offset, block_q=64, block_k=128,
                  interpret=True)
        acc, m, l = flash_attention_partial(q, k, v, **kw)
        o = finalize_partials(acc, l)
        return (acc, m, l) + flash_attention_bwd_pair(
            q, k, v, do, _lse_of(m, l), o=o, **kw)

    for offsets in [(0, 0), (140, 75), (0, 130)]:
        concrete = jax.jit(functools.partial(pair, *offsets))()
        traced = jax.jit(pair)(*(jnp.int32(x) for x in offsets))
        for a, b in zip(traced, concrete):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- row statistics in every lane (PR 52) --------------------------------------
#
# The kernels keep a row's running maximum and sum, and read ``lse`` and
# ``delta``, as ``(rows, 128)`` blocks that hold the row's value in
# every lane, and use them whole (``_lanes``): a statistic read from
# lane 0 alone is spread over the lanes by the chip's cross-lane unit at
# every use, and in the forward kernel that stood between a row's
# scores and its ``exp``.  What the change leans on is held here: every
# lane of the forward kernel's statistics is lane 0, whatever the mask,
# and the numbers are the reference's at widths of more than one lane
# group.

# the mask; (q_offset, kv_offset); (lq, lk)
LANE_CASES = {
    "plain": (dict(causal=False), (0, 0), (150, 200)),
    "causal": (dict(causal=True), (0, 0), (150, 200)),
    "rows that see no key": (dict(causal=True), (0, 96), (150, 200)),
    "no row sees a key": (dict(causal=True), (0, 4096), (150, 200)),
    "window": (dict(causal=True, window=40), (75, 11), (150, 200)),
    "select": (dict(causal=True, select=0.3), (0, 0), (150, 200)),
    "blockdiff": (dict(blockdiff=(96, 4)), (0, 0), (192, 192)),
}


@pytest.mark.parametrize("heads", [None, 3], ids=["one-head", "grouped"])
@pytest.mark.parametrize("case", sorted(LANE_CASES))
def test_the_forward_kernels_statistics_hold_a_rows_value_in_every_lane(
        monkeypatch, case, heads):
    """The raw ``(rows, 128)`` ``m`` and ``l`` the forward kernel writes
    (``partial=True``: ring attention's, and the custom VJP's ``lse``):
    every lane is lane 0, on live rows, on rows that see no key (``-inf``
    and 0 in every lane) and on the rows a block is padded with; and lane
    0 is the reference's row maximum and sum."""
    import importlib

    from mpit_tpu.ops import select_bits

    fa = importlib.import_module("mpit_tpu.ops.flash_attention")
    mask, (q_off, kv_off), (lq, lk) = LANE_CASES[case]
    mask = dict(mask)
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 4)
    lead = () if heads is None else (heads,)
    q = jax.random.normal(keys[0], (*lead, lq, 16))
    k = jax.random.normal(keys[1], (lk, 16))
    v = jax.random.normal(keys[2], (lk, 16))
    chosen = None
    if "select" in mask:
        chosen = jax.random.uniform(keys[3], (lq, lk)) < mask["select"]
        mask["select"] = select_bits.pack(chosen)
    mask.setdefault("causal", False)
    raw = []
    unfold = fa._unfold_stat

    def spy(x, like, lq_p):
        raw.append(np.asarray(x))
        return unfold(x, like, lq_p)

    monkeypatch.setattr(fa, "_unfold_stat", spy)
    acc, m, l = fa._fa_2d(q, k, v, q_off, kv_off, sm_scale=None, block_q=64,
                          block_k=128, interpret=True, partial=True,
                          precision="highest", **mask)
    assert len(raw) == 2 and raw[0].shape[1] == 128
    for stat in raw:
        assert np.array_equal(stat, np.broadcast_to(stat[:, :1], stat.shape))
    valid = fa._mask(lq, lk, q_off, kv_off, lk, mask["causal"],
                     mask.get("window"), mask.get("blockdiff"))
    if chosen is not None:
        valid = valid & chosen
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("...qd,kd->...qk", q, k) / 4.0
    s = jnp.where(valid, s, -jnp.inf)
    want_m = jnp.max(s, axis=-1)
    np.testing.assert_allclose(m, want_m, atol=2e-5, rtol=2e-5)
    dead = np.isneginf(np.asarray(want_m))
    assert dead.all() == (case == "no row sees a key")
    assert dead.any() or "no " not in case
    want_l = jnp.sum(jnp.where(valid, jnp.exp(s - jnp.where(
        dead, 0.0, want_m)[..., None]), 0.0), axis=-1)
    np.testing.assert_allclose(l, want_l, atol=2e-4, rtol=2e-4)
    assert np.all(np.asarray(acc)[dead] == 0.0)


@pytest.mark.parametrize("widths", [(16, 16), (200, 130), (130, 300),
                                    (300, 16)],
                         ids=lambda w: f"keys{w[0]}_values{w[1]}")
@pytest.mark.parametrize("fa_backward_path", ["1", "0"], indirect=True,
                         ids=["fused-bwd", "two-kernel-bwd"])
def test_statistics_meet_operands_of_several_lane_groups(widths,
                                                          fa_backward_path):
    """``_lanes`` lays a statistic's vregs side by side: against score
    tiles of 128 and 256 keys and accumulators of 128, 256 and 384 lanes
    (a value width of its own), forward and in both backward schedules,
    the kernels give the reference's output and gradients."""
    d, dv = widths
    keys = jax.random.split(jax.random.PRNGKey(d + dv), 4)
    q = jax.random.normal(keys[0], (2, 90, d)) * 0.5
    k = jax.random.normal(keys[1], (2, 300, d)) * 0.5
    v = jax.random.normal(keys[2], (2, 300, dv)) * 0.5
    g = jax.random.normal(keys[3], (2, 90, dv))
    kw = dict(causal=True, q_offset=210)

    def kernel(q, k, v):
        return jnp.sum(g * flash_attention(
            q, k, v, block_q=32, block_k=256, interpret=True,
            precision="highest", **kw))

    def plain(q, k, v):
        return jnp.sum(g * attention_reference(q, k, v, **kw))

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(kernel, (0, 1, 2))(q, k, v)
        want = jax.value_and_grad(plain, (0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


def test_lanes_lays_a_statistic_side_by_side():
    from mpit_tpu.ops.flash_attention import _lanes

    stat = jnp.broadcast_to(jnp.arange(16.0)[:, None], (16, 128))
    assert _lanes(stat, 128) is stat
    wide = _lanes(stat, 384)
    assert wide.shape == (16, 384)
    assert np.array_equal(wide, np.broadcast_to(np.arange(16.0)[:, None],
                                                (16, 384)))


@pytest.mark.parametrize("width", [64, 72, 192, 200])
def test_lanes_lays_a_statistic_over_a_width_that_is_no_whole_tile(width):
    """A head narrower than a tile, or one and a half of them: whole
    copies and then the first lanes of one more."""
    from mpit_tpu.ops.flash_attention import _lanes

    stat = jnp.broadcast_to(jnp.arange(16.0)[:, None], (16, 128))
    wide = _lanes(stat, width)
    assert np.array_equal(wide, np.broadcast_to(np.arange(16.0)[:, None],
                                                (16, width)))


# -- every operand at the width it has (PR 54) --------------------------------

WIDTHS = [(64, 64), (72, 72), (192, 128), (128, 128)]   # keys, values
MASKS = ["causal", "window", "selection", "block_diffusion"]
WIDE_L = 256


def _mask_of(mask, rng):
    """The call's keywords for one of the four masks at ``WIDE_L`` rows."""
    from mpit_tpu.ops import select_bits

    if mask == "causal":
        return dict(causal=True)
    if mask == "window":
        return dict(causal=True, window=100)
    if mask == "block_diffusion":
        return dict(blockdiff=(WIDE_L // 2, 32))
    # a third of the causal pairs, every row its own position among them
    chosen = np.tril(rng.random((1, WIDE_L, WIDE_L)) < 1 / 3)
    chosen |= np.eye(WIDE_L, dtype=bool)
    return dict(causal=True, select=select_bits.pack(jnp.asarray(chosen)))


@pytest.mark.parametrize("fa_backward_path", ["1", "0"], indirect=True,
                         ids=["fused-bwd", "two-kernel-bwd"])
@pytest.mark.parametrize("heads", [(2, 2), (4, 1)],
                         ids=["plain", "group_of_four"])
@pytest.mark.parametrize("mask", MASKS)
@pytest.mark.parametrize("d,dv", WIDTHS,
                         ids=[f"{d}_over_{dv}" for d, dv in WIDTHS])
def test_the_kernels_take_every_operand_at_its_own_width(
        rng, d, dv, mask, heads, fa_backward_path):
    """q, k at ``d`` lanes and v, dO at ``dv``, whole tiles or not, go to
    the kernels as they are: the forward and the three gradients are the
    reference's, and **to the bit** those of the same call on operands
    padded to whole 128-lane tiles by hand (what the wrapper did in XLA
    before PR 54): a zero lane adds an exact zero to every product, so
    the scores, ``P``, ``dS`` and every result are the padded call's.
    (At 72 lanes to a few units in the last place: interpreted, a tile's
    product is the CPU's, which sums a contraction of 72, no whole
    number of its own vectors, in another order than one of 128.)"""
    hq, hkv = heads
    kw = _mask_of(mask, rng)
    q = jnp.asarray(rng.normal(size=(1, hq, WIDE_L, d)) * 0.5, jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, hkv, WIDE_L, d)) * 0.5, jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, hkv, WIDE_L, dv)) * 0.5, jnp.float32)
    g = jnp.asarray(rng.normal(size=(1, hq, WIDE_L, dv)), jnp.float32)
    scale = 1.0 / np.sqrt(d)

    def attend(q, k, v):
        return flash_attention(q, k, v, sm_scale=scale, block_q=64,
                               block_k=128, precision="highest", **kw)

    def kernels(q, k, v, g=g):
        return jnp.sum(g * attend(q, k, v))

    def plain(q, k, v):
        return jnp.sum(g * attention_reference(q, k, v, sm_scale=scale,
                                               **kw))

    def lanes(x):
        pad = -x.shape[-1] % 128
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])

    def same(a, b):
        if d % 64:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
        else:
            assert np.array_equal(a, b)

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(kernels, (0, 1, 2))(q, k, v)
        want = jax.value_and_grad(plain, (0, 1, 2))(q, k, v)
        by_hand = jax.value_and_grad(kernels, (0, 1, 2))(
            lanes(q), lanes(k), lanes(v), lanes(g))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)
    # (not the loss: a sum over an array of another shape is XLA's own)
    out, out_by_hand = attend(q, k, v), attend(lanes(q), lanes(k), lanes(v))
    same(out, out_by_hand[..., :dv])
    for a, b, width in zip(got[1], by_hand[1], (d, d, dv)):
        same(a, b[..., :width])
        assert not np.any(np.asarray(b[..., width:]))


# sha256 of ``str(make_jaxpr(grad(loss)))`` (addresses blanked) of a
# 128-wide call.  With a ``precision`` named, **as the parent commit of
# PR 57 printed it**: the float32 operands, specs, scratch and kernel
# bodies behind ``precision=`` are what they were, to the character
# (OLMoE's path).  At the default precision, **as PR 57 prints it**: the
# rules round q, k, v and dO to bf16, the tiles are that dtype's and
# the results float32 (the digests before it were PR 54's parent's).  A
# PR that changes the kernels on purpose records the new digests here
# and says so.
PARENTS_128_WIDE = {
    ("highest", None, 8, 8): "6403152374f75525",
    ("highest", None, 32, 4): "3d638c4da492255b",
    ("highest", 1024, 8, 8): "7932ac0eb8040ea6",
    ("highest", 1024, 32, 4): "7746f5b61cd391ee",
    (None, None, 8, 8): "edbb1493a4c4c6d8",
    (None, None, 32, 4): "1a196ec84df32818",
    (None, 1024, 8, 8): "dca1f07bcd644e60",
    (None, 1024, 32, 4): "80f9dd923fa4069d",
}


@pytest.mark.parametrize("precision,window,hq,hkv", sorted(
    PARENTS_128_WIDE, key=str), ids=lambda x: str(x))
def test_a_128_wide_call_traces_to_the_parents_program(precision, window,
                                                       hq, hkv):
    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, window=window,
                                       interpret=False,
                                       precision=precision) ** 2)

    shapes = [jax.ShapeDtypeStruct((1, heads, 2048, 128), jnp.float32)
              for heads in (hq, hkv, hkv)]
    text = re.sub(r"0x[0-9a-f]+", "0x", str(
        jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(*shapes)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARENTS_128_WIDE[precision, window, hq, hkv]


# -- bf16 operands at the default precision (PR 57) ---------------------------
#
# A call that names no ``precision`` hands its kernels q, k, v and dO
# rounded to bf16 and gets float32 back.  On inputs that are bf16
# numbers already the float32-operand kernels (``precision=`` named)
# multiply the same numbers, so the two differ only where a body rounds
# ``p`` and ``dS`` to the operands' dtype before their products (on the
# chip the MXU does that to float32 operands itself, and the two agree to
# the bit: PERF.md section 6, PR 57): two bf16 ulps of the largest entry.

OPERAND_MASKS = {
    "causal": lambda rows: dict(causal=True),
    "window": lambda rows: dict(causal=True, window=80),
    "select": lambda rows: dict(causal=True, select=pack_bits(
        (jnp.arange(rows)[:, None] * 7 + jnp.arange(rows)[None, :] * 3)
        % 5 < 3)[None]),
    "blockdiff": lambda rows: dict(blockdiff=(rows // 2, 4)),
}
#: body -> (MPIT_FA_FUSED_BWD, which of (o, dq, dk, dv) it writes)
OPERAND_BODIES = {"forward": ("0", (0,)), "dq": ("0", (1,)),
                  "dkdv": ("0", (2, 3)), "fused": ("1", (1, 2, 3))}


@pytest.mark.parametrize("d,dv", [(64, 64), (128, 128), (192, 128)],
                         ids=["64", "128", "192_over_128"])
@pytest.mark.parametrize("body,mask", [
    (body, mask) for body in sorted(OPERAND_BODIES)
    for mask in sorted(OPERAND_MASKS)
    # a selection's backward is the two-kernel schedule whatever is asked
    if (body, mask) != ("fused", "select")])
def test_bf16_operands_give_what_float32_operands_give_on_rounded_inputs(
        body, mask, d, dv, monkeypatch):
    schedule, written = OPERAND_BODIES[body]
    monkeypatch.setenv("MPIT_FA_FUSED_BWD", schedule)
    rows = 256
    keys = jax.random.split(jax.random.PRNGKey(d + len(mask)), 4)
    rounded = lambda key, shape: jax.random.normal(key, shape).astype(
        jnp.bfloat16).astype(jnp.float32)
    q = rounded(keys[0], (1, 4, rows, d))      # two query heads a KV head
    k = rounded(keys[1], (1, 2, rows, d))
    v = rounded(keys[2], (1, 2, rows, dv))
    g = rounded(keys[3], (1, 4, rows, dv))
    call = functools.partial(flash_attention, block_q=64, block_k=128,
                             **OPERAND_MASKS[mask](rows))

    def results(precision):
        fn = functools.partial(call, precision=precision)
        if body == "forward":
            return (fn(q, k, v),)
        out, vjp = jax.vjp(fn, q, k, v)
        return (out, *vjp(g))

    got, want = results(None), results("highest")
    for i in written:
        assert got[i].dtype == jnp.float32 and got[i].shape == want[i].shape
        scale = max(1.0, float(jnp.max(jnp.abs(want[i]))))
        assert float(jnp.max(jnp.abs(got[i] - want[i]))) < 2 ** -7 * scale, i


def _kernel_operands(fn, *args):
    """``(operand dtypes, result dtypes)`` of every ``pallas_call`` that
    ``fn`` traces to, the floating ones."""
    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    floats = lambda vs: [v.aval.dtype for v in vs
                         if jnp.issubdtype(v.aval.dtype, jnp.floating)]
    return [(floats(eqn.invars), floats(eqn.outvars))
            for eqn in calls(jax.make_jaxpr(fn)(*args).jaxpr)]


@pytest.mark.parametrize("precision,operand", [
    (None, jnp.bfloat16), ("highest", jnp.float32), ("default", jnp.float32)])
def test_a_named_precision_keeps_float32_operands(precision, operand):
    """The rule reads the argument the kernels already have: no
    ``precision``, bf16 operands (q, k, v, and dO in the backward
    kernels; ``lse`` and ``delta`` stay float32); any named one, the
    float32 arrays as they came.  Results are float32 either way, and
    bf16 arrays are passed through with bf16 results, as before."""
    from mpit_tpu.ops.flash_attention import operand_dtype

    q = jnp.zeros((1, 2, 256, 64), jnp.float32)
    loss = lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, precision=precision) ** 2)
    kernels = _kernel_operands(jax.grad(loss, (0, 1, 2)), q, q, q)
    assert len(kernels) >= 2
    assert operand_dtype(jnp.float32, precision) == operand
    for n, (operands, results) in enumerate(kernels):
        wide = 0 if n == 0 else 2   # the backward kernels' lse and delta
        narrow = [d for d in operands if d != jnp.float32]
        assert narrow == ([] if operand == jnp.float32 else
                          [jnp.bfloat16] * (len(operands) - wide))
        assert all(r == jnp.float32 for r in results)
    half = q.astype(jnp.bfloat16)
    assert operand_dtype(jnp.bfloat16, precision) == jnp.bfloat16
    assert flash_attention(half, half, half, causal=True,
                           precision=precision).dtype == jnp.bfloat16


def test_flash_bwd_no_quadratic_intermediate():
    """The backward must never materialize an (Lq, Lk) array — the memory
    property flash attention exists for (VERDICT r2 missing-item #2).
    Audited on the jaxpr: every intermediate stays below Lq*Lk elements."""
    L, D = 4096, 64

    def loss(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, block_q=256, block_k=512)
            ** 2
        )

    spec = jax.ShapeDtypeStruct((L, D), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(spec, spec, spec)

    def walk(jx):
        for eqn in jx.eqns:
            for var in eqn.outvars:
                size = int(np.prod(var.aval.shape)) if var.aval.shape else 1
                assert size < L * L, (
                    f"quadratic intermediate {var.aval.shape} from {eqn.primitive}"
                )
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    walk(sub.jaxpr)

    walk(jaxpr.jaxpr)


class TestFusedRouting:
    """The opt-in wiring: rules/msgd route through the pallas kernels and
    match the plain-XLA path bit-for-bit (interpret mode on CPU)."""

    def test_adam_rule_fused_matches(self, rng):
        from mpit_tpu.optim import rules

        p0 = jnp.asarray(rng.normal(size=(300,)), jnp.float32)
        gs = [jnp.asarray(rng.normal(size=(300,)), jnp.float32) for _ in range(3)]
        outs = []
        for fused in (False, True):
            rule = rules.make("adam", lr=1e-2, use_fused=fused)
            p, st = p0, rule.init(p0)
            for g in gs:
                p, st = rule.apply(p, g, st)
            outs.append(np.asarray(p))
        np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-6)

    def test_msgd_fused_matches(self, rng):
        from mpit_tpu.optim.msgd import (MSGDConfig, msgd_init, msgd_params,
                                         msgd_step)

        w0 = jnp.asarray(rng.normal(size=(257,)), jnp.float32)
        xs = [jnp.asarray(rng.normal(size=(257,)), jnp.float32) for _ in range(4)]

        def vgf(w, target):
            return 0.5 * jnp.sum((w - target) ** 2), w - target

        outs = []
        for fused in (False, True):
            cfg = MSGDConfig(lr=0.05, mom=0.9, l2wd=1e-3, use_fused=fused)
            w, st = w0, msgd_init(w0)
            for t in xs:
                w, st, _ = msgd_step(vgf, w, st, cfg, t)
            # the committed vector: the fused step hands back another point
            outs.append(np.asarray(msgd_params(w, st, cfg)))
        np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-6)

    def test_resolution_order(self, monkeypatch):
        from mpit_tpu.ops.fused_update import fused_enabled

        # Explicit flag is a hard constraint and beats the env (mesh
        # trainers force False inside sharded jits).
        monkeypatch.setenv("MPIT_FUSED", "1")
        assert fused_enabled(False) is False
        monkeypatch.setenv("MPIT_FUSED", "0")
        assert fused_enabled(True) is True
        # Env applies to the unconstrained (None) sites.
        assert fused_enabled(None) is False
        monkeypatch.setenv("MPIT_FUSED", "1")
        assert fused_enabled(None) is True
