"""Compiles for a *described* v5e, no chip attached (the
on-chip-measurement guide, section 2): the Pallas grouped product of
``parallel/moe.py`` at OLMoE's published shapes, forward and backward;
the flash kernels at LFM2's attention shape (32 query over 8 KV heads of
64 at 8192) and at Mellum's sliding layer's (32 over 4 heads of 128
under a window of 1024); the delta rule's three kernels at Kimi's KDA
shape (32 heads of 128 at 8192); and the msgd commit over LFM2's vector, whose length is
whole lanes and no whole number of blocks, and over Ouro's, which is no
whole number of lanes, with ``w`` and ``vt`` donated.  What interpret mode cannot show: that the tiles fit the chip's fast
memory and the kernels lower.  A compile that passes is not a chip run
and says nothing about time.

The topology is described inside a fixture, never at import, and all
such tests live in this one file: only one process at a time may load
the TPU's compiler (the guide says why)."""

import jax
import jax.numpy as jnp
import pytest

ROWS, D, F, E = 8 * 4096, 2048, 1024, 64  # one sequence's assignments


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("k,n", [(D, F), (F, D)], ids=["up", "down"])
def test_the_pallas_grouped_product_compiles_at_published_shapes(one_chip, k, n):
    from mpit_tpu.parallel import moe

    def loss(rows, w, sizes):
        return jnp.sum(moe.pallas_grouped_dot(rows, w, sizes) ** 2)

    args = (jax.ShapeDtypeStruct((ROWS, k), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((E, k, n), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((E,), jnp.int32, sharding=one_chip))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*args).compile()
    text = compiled.as_text()
    # the product, its rows' gradient and its weights' gradient
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    d_rows, d_w = compiled.output_shardings  # both gradients come out
    assert moe.pallas_fits(ROWS, k, n)
    # float32 results: the weights' gradient is never rounded to bf16
    assert "f32[64,%d,%d]" % (k, n) in text


@pytest.mark.parametrize("kv_heads,width,window", [(8, 64, None),
                                                   (4, 128, 1024)],
                         ids=["32_over_8_heads_of_64",
                              "32_over_4_heads_of_128_window_1024"])
def test_flash_attention_compiles_at(one_chip, kv_heads, width, window):
    """LFM2's attention layer (PR 32): a group's four query heads folded
    into the kernel's rows, the head width padded to the lanes; and
    Mellum's sliding layer at its published shape (PR 33): a group of
    eight under a window of 1024, the inner grid axis the window's
    static bound and the index maps reading the prefetched offsets.
    Forward and the backward kernels lower for the chip, k and v at the
    KV heads' size."""
    from mpit_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, window=window,
                                       interpret=False) ** 2)

    q = jax.ShapeDtypeStruct((1, 32, 8192, width), jnp.float32,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, kv_heads, 8192, width), jnp.float32,
                              sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    calls = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    # forward, and the fused (no window) or the two backward kernels
    assert calls >= (2 if window is None else 3)
    dq, dk, dv = compiled.output_shardings


def test_the_delta_rules_kernels_compile_at_kimis_shape(one_chip, monkeypatch):
    """Kimi's KDA layer (PR 44): 32 heads of 128 at 8192, the forward
    kernel and the rule's two (the forward that writes the 128 chunk
    states and solves a head, the walk back), on the row-major inputs: blocks of
    ``(64, 4 x 128)`` and the tables fit the chip's fast memory and every
    product lowers, the solve's at full float32 precision among them.
    The choice of interpret mode asks for the backend, which is the CPU
    here: steered in the test."""
    from mpit_tpu.ops import delta_rule

    monkeypatch.setattr(delta_rule, "use_interpret", lambda flag: False)
    wide = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.float32,
                                sharding=one_chip)
    beta = jax.ShapeDtypeStruct((1, 8192, 32), jnp.float32,
                                sharding=one_chip)

    def loss(q, k, v, g, beta):
        return jnp.sum(delta_rule.kda_scan(q, k, v, g, beta) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        wide, wide, wide, wide, beta).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert "f32[1,32,128,128,128]" in text   # the chunks' starting states
    assert "f32[1,32,128,64,64]" in text     # and their solves
    assert len(compiled.output_shardings) == 5


def test_the_donated_commit_keeps_no_copy_of_lfm2s_vector(one_chip):
    """486,062,464 elements are whole lanes and 14,833.45 blocks: the
    commit sweeps the vector where it lies with an overhanging last
    block, so with ``w`` and ``vt`` donated the program holds no third
    vector (a padded copy of each operand would be three)."""
    from mpit_tpu.ops.fused_update import fused_nesterov_commit

    n = 486_062_464
    vec = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda w, vt, g: fused_nesterov_commit(w, vt, g, 0.03,
                                               interpret=False),
        donate_argnums=(0, 1)).lower(vec, vec, vec).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * 4 * n   # both, to the tile
    assert mem.temp_size_in_bytes < 4 * n // 8


def test_the_donated_commit_keeps_no_copy_of_ouros_vector(one_chip):
    """509,661,185 elements are one over a whole number of lanes: the
    commit sweeps the vector as it is, in 1-D blocks with an overhanging
    last one, so with ``w`` and ``vt`` donated the program holds no
    third vector (a pad, or a slice of the aligned prefix, would copy
    each operand whole)."""
    from mpit_tpu.ops.fused_update import fused_nesterov_commit

    n = 509_661_185
    assert n % 128 == 1
    vec = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda w, vt, g: fused_nesterov_commit(w, vt, g, 0.03,
                                               interpret=False),
        donate_argnums=(0, 1)).lower(vec, vec, vec).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * 4 * n   # both, to the tile
    assert mem.temp_size_in_bytes < 4 * n // 8
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1
