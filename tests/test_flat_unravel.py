"""``models/flat.py`` ``leaf_unravel``: the one function that cuts the
exchanged vector into a module's leaves, as a ``jax.custom_vjp``.  Its
forward is ``ravel_pytree``'s with every leaf's 1-D piece behind an
``optimization_barrier`` before its reshape; its backward is one
``concatenate`` of the leaves' cotangents.  Held here: the values and the
gradient are ``ravel_pytree``'s bit for bit on the tiny model of every
block the launcher builds, on a GPT-2 block 128 wide and on a hand-made
tree; the gradient's jaxpr writes the vector once and pads nothing; the
barriers are as many as the leaves, whatever a leaf's shape; an unread
leaf gets zeros and one read twice the sum; ``jax.checkpoint``, ``jit``
and donation change nothing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from mpit_tpu.lm.model import build
from mpit_tpu.models import flat as flat_mod

# a cell of each block the launcher builds (chipbench/configs/*.json)
CELLS = {"gpt2": "c111m-local", "olmoe": "olmoe-l1-ps1w-su1",
         "mellum": "mellum2-l4e8-local", "lfm2": "lfm2-l5e8-local",
         "ouro": "ouro-l6-local", "joyai": "joyai-l5e8-local",
         "kimi": "kimi-linear-l5e8-local"}
TREES = tuple(CELLS) + ("hand_made", "gpt2_wide")
_built = {}


def hand_made():
    """A scalar, ``(64,)``, ``(7, 64)`` and ``(5, 1)`` end under the
    chip's 128 lanes; ``(3, 128)`` and ``(2, 3, 256)`` do not."""
    rs = np.random.RandomState(5)
    return {"a": jnp.float32(0.3), "b": jnp.asarray(rs.randn(64), jnp.float32),
            "c": jnp.asarray(rs.randn(7, 64), jnp.float32),
            "d": jnp.asarray(rs.randn(5, 1), jnp.float32),
            "e": jnp.asarray(rs.randn(3, 128), jnp.float32),
            "f": jnp.asarray(rs.randn(2, 3, 256), jnp.float32)}


def tree_of(name):
    """``(params, the FlatModel or None)``: the launcher's own model of
    the block at its configuration's ``tiny`` size, built once."""
    if name not in _built:
        if name == "hand_made":
            _built[name] = (hand_made(), None)
        else:
            if name == "gpt2_wide":
                model = build(arch="gpt2", d_model=128, n_heads=2, n_layers=1,
                              seq_len=32, vocab=256, use_flash=False)
            else:
                from chipbench import run as runner, spec as spec_mod

                cell = spec_mod.load_cell(CELLS[name])
                cell.config.update(cell.config["tiny"])
                model = runner.build_model(cell, seed=1, lm_use_flash=0)
            _built[name] = (model.flat.unravel(model.flat.w0), model.flat)
    return _built[name]


def through(unravel):
    """A loss that reads every leaf, each element with its own weight."""
    def f(w):
        return sum(jnp.sum(jnp.sin(leaf) * (1.0 + 0.5 * i)) for i, leaf in
                   enumerate(jax.tree_util.tree_leaves(unravel(w))))
    return f


def primitives(jaxpr, found=None):
    """``[(primitive's name, its first result's shape)]`` over ``jaxpr``
    and every jaxpr its equations hold."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        found.append((eqn.primitive.name, tuple(eqn.outvars[0].aval.shape)))
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (list, tuple)) else [value]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    primitives(inner, found)
    return found


@pytest.mark.parametrize("name", TREES)
def test_values_and_gradient_are_ravel_pytrees_bit_for_bit(name):
    params, _ = tree_of(name)
    flat, plain = ravel_pytree(params)
    cut = flat_mod.leaf_unravel(params)
    w = flat + 0.05 * jnp.asarray(
        np.random.RandomState(0).randn(flat.size), jnp.float32)
    for a, b in zip(jax.tree_util.tree_leaves(plain(w)),
                    jax.tree_util.tree_leaves(cut(w)), strict=True):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    assert jax.tree_util.tree_structure(plain(w)) == \
        jax.tree_util.tree_structure(cut(w))
    np.testing.assert_array_equal(jax.grad(through(cut))(w),
                                  jax.grad(through(plain))(w))
    np.testing.assert_array_equal(jax.jit(jax.grad(through(cut)))(w),
                                  jax.jit(jax.grad(through(plain)))(w))


@pytest.mark.parametrize("name", TREES)
def test_the_gradient_is_one_concatenate_and_no_pad_of_the_vector(name):
    params, _ = tree_of(name)
    flat = ravel_pytree(params)[0]
    cut = flat_mod.leaf_unravel(params)
    found = primitives(jax.make_jaxpr(jax.grad(through(cut)))(flat).jaxpr)
    whole = [prim for prim, shape in found if shape == flat.shape]
    assert whole.count("concatenate") == 1
    assert "pad" not in whole and "add_any" not in whole
    # what this replaces (PR 26's unravel: a slice a leaf, each behind
    # the barrier) transposes to one pad of the whole vector a leaf
    offsets = np.cumsum([0] + [int(np.size(leaf)) for leaf in
                               jax.tree_util.tree_leaves(params)])

    def sliced(w):
        return [jax.lax.optimization_barrier(w[int(a):int(b)])
                for a, b in zip(offsets[:-1], offsets[1:])]

    pads = primitives(jax.make_jaxpr(jax.grad(through(sliced)))(flat).jaxpr)
    assert [prim for prim, shape in pads if shape == flat.shape
            ].count("pad") == len(offsets) - 1


@pytest.mark.parametrize("name", TREES)
def test_as_many_barriers_as_leaves_each_before_its_reshape(name):
    params, _ = tree_of(name)
    flat = ravel_pytree(params)[0]
    found = primitives(jax.make_jaxpr(flat_mod.leaf_unravel(params))(flat).jaxpr)
    barriers = [shape for prim, shape in found if prim == "optimization_barrier"]
    sizes = [(int(np.size(leaf)),) for leaf in jax.tree_util.tree_leaves(params)]
    assert barriers == sizes  # 1-D pieces, in the vector's order
    if name == "hand_made":
        assert barriers == [(1,), (64,), (448,), (5,), (384,), (1536,)]


@pytest.mark.parametrize("shape", [
    (), (1,), (64,), (127,), (128,), (2048, 64), (2304, 64), (5, 1), (3, 128),
    (96, 769), (64, 576), (2, 3, 256)])
def test_a_leaf_of_any_shape_is_cut_the_same_way(shape):
    """No rule reads a leaf's shape: under the lanes, over them, a
    multiple of them or not, a scalar, an ``(n, 1)`` column."""
    rs = np.random.RandomState(3)
    params = {"before": jnp.asarray(rs.randn(130), jnp.float32),
              "leaf": jnp.asarray(rs.randn(*shape), jnp.float32),
              "z_after": jnp.asarray(rs.randn(3, 5), jnp.float32)}
    flat, plain = ravel_pytree(params)
    cut = flat_mod.leaf_unravel(params)
    found = primitives(jax.make_jaxpr(cut)(flat).jaxpr)
    assert [s for prim, s in found if prim == "optimization_barrier"] == \
        [(130,), (int(np.prod(shape)),), (15,)]
    np.testing.assert_array_equal(cut(flat)["leaf"], params["leaf"])
    assert cut(flat)["leaf"].shape == shape
    np.testing.assert_array_equal(jax.grad(through(cut))(flat),
                                  jax.grad(through(plain))(flat))


def test_an_unread_leaf_gets_zeros_and_one_read_twice_the_sum():
    params = hand_made()
    flat, plain = ravel_pytree(params)
    cut = flat_mod.leaf_unravel(params)

    def loss(unravel):
        def f(w):
            p = unravel(w)   # "a" and "e" are not read; "b" by every pass

            def one_pass(h, _):
                return jnp.tanh(h @ p["c"].T @ p["c"] + p["b"]), None

            h, _ = jax.lax.scan(one_pass, p["f"].reshape(-1)[:64], None,
                                length=3)
            return jnp.sum(h * p["b"]) + jnp.sum(p["d"]) ** 2
        return f

    got, want = jax.grad(loss(cut))(flat), jax.grad(loss(plain))(flat)
    np.testing.assert_array_equal(got, want)
    leaves = cut(got)
    assert float(leaves["a"]) == 0.0 and not np.any(leaves["e"])
    assert leaves["e"].shape == (3, 128)
    assert np.all(np.asarray(leaves["d"]) == 2 * float(jnp.sum(params["d"])))
    assert np.any(leaves["b"]) and np.any(leaves["c"])


@pytest.mark.parametrize("name", [n for n in TREES if n != "hand_made"])
def test_the_blocks_own_step_has_ravel_pytrees_gradient(name):
    """Through the module itself, by the model's own ``unravel``: Ouro's
    scanned passes under their checkpoints (a weight's cotangent is
    summed over the passes before it reaches the rule), the recomputed
    sparse branches, JoyAI's second loss."""
    params, flat = tree_of(name)
    plain = ravel_pytree(params)[1]
    own_loss = name in ("ouro", "joyai", "kimi")  # called with the targets
    tokens = jnp.asarray(np.random.RandomState(2).randint(0, 256, (2, 33)),
                         jnp.int32)

    def loss(unravel):
        def f(w):
            out = flat.module.apply({"params": unravel(w)}, tokens[:, :-1],
                                    *([tokens[:, 1:]] if own_loss else []))
            if own_loss:
                return out[0]
            return -jnp.mean(jnp.take_along_axis(out, tokens[:, 1:, None], -1))
        return f

    got = jax.jit(jax.value_and_grad(loss(flat.unravel)))(flat.w0)
    want = jax.jit(jax.value_and_grad(loss(plain)))(flat.w0)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    assert np.any(got[1])


@pytest.mark.parametrize("how", ["checkpoint", "jit_donated", "both"])
def test_checkpoint_jit_and_donation_change_nothing(how):
    params = hand_made()
    flat = ravel_pytree(params)[0]
    cut = flat_mod.leaf_unravel(params)
    want = jax.grad(through(cut))(flat)
    f = through(cut)
    if how in ("checkpoint", "both"):
        f = jax.checkpoint(f)
    g = jax.grad(f)
    if how in ("jit_donated", "both"):
        g = jax.jit(g, donate_argnums=0)
    np.testing.assert_array_equal(g(flat + 0.0), want)


def test_mixed_dtypes_are_refused():
    with pytest.raises(TypeError, match="one dtype"):
        flat_mod.leaf_unravel({"a": jnp.ones(3), "b": jnp.ones(3, jnp.bfloat16)})
