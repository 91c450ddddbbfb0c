"""Compiles for a *described* v5e, no chip attached (the
on-chip-measurement guide, section 2): the Pallas grouped product of
``parallel/moe.py`` at OLMoE's published shapes, forward and backward.
What interpret mode cannot show: that the tiles fit the chip's fast
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
