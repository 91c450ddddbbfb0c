"""Sorted dropless top-k dispatch (``parallel/moe.py``) against the dense
float32 oracle: every token through every expert, masked by its top-k
router weights.  The CPU's products are full float32, so the two agree
to rounding; the tolerances below are a few float32 ulps of sums of a
few dozen terms, and a dropped router weight or a lost assignment is
wrong by tens of percent."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu.parallel import moe

ATOL = 2e-5  # outputs are O(1); float32 sums of <= 64 terms


def weights(seed, d=16, f=8, e=6):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (e, d, f)) * 0.3,
            jax.random.normal(ks[1], (e, d, f)) * 0.3,
            jax.random.normal(ks[2], (e, f, d)) * 0.3)


def system(x, probs, wg, wu, wd, k):
    w, e = moe.route_top_k(probs, k)
    return moe.dispatch_top_k(
        x, w, e, probs.shape[-1],
        lambda rows, sizes: moe.swiglu_experts(rows, sizes, wg, wu, wd))


def random_probs(seed, t, e):
    return jax.nn.softmax(
        3.0 * jax.random.normal(jax.random.PRNGKey(seed), (t, e)), axis=-1)


@pytest.mark.parametrize("k", [1, 2, 3, 6])
def test_dispatch_equals_dense_oracle_on_random_assignments(k):
    t, d, e = 40, 16, 6
    x = jax.random.normal(jax.random.PRNGKey(7), (t, d))
    probs = random_probs(11 + k, t, e)
    wg, wu, wd = weights(k)
    got = system(x, probs, wg, wu, wd, k)
    want = moe.moe_dense_reference(x, probs, wg, wu, wd, k)
    np.testing.assert_allclose(got, want, atol=ATOL)


def skewed_probs(t, e, k, hot, cold):
    """Every token puts ``hot`` first and never chooses ``cold``."""
    logits = np.array(jax.random.normal(jax.random.PRNGKey(3), (t, e)))
    logits[:, hot] = 20.0
    logits[:, cold] = -20.0
    return jax.nn.softmax(jnp.asarray(logits), axis=-1)


def test_no_token_loses_an_expert_when_one_expert_takes_every_token():
    t, d, e, k = 32, 16, 6, 2
    probs = skewed_probs(t, e, k, hot=4, cold=1)
    _w, experts = moe.route_top_k(probs, k)
    _order, _inv, sizes = moe.sort_by_expert(experts, e)
    assert int(sizes[4]) == t and int(sizes[1]) == 0  # all, and none
    assert int(sizes.sum()) == t * k                  # nothing dropped
    x = jax.random.normal(jax.random.PRNGKey(5), (t, d))
    wg, wu, wd = weights(9)
    got = system(x, probs, wg, wu, wd, k)
    want = moe.moe_dense_reference(x, probs, wg, wu, wd, k)
    np.testing.assert_allclose(got, want, atol=ATOL)
    assert float(moe.load_max_over_mean(experts, e)) == pytest.approx(e / k)


@pytest.mark.parametrize("skew", [False, True])
def test_dispatch_gradients_equal_the_dense_oracles(skew):
    t, d, e, k = 24, 16, 6, 3
    x = jax.random.normal(jax.random.PRNGKey(0), (t, d))
    router = jax.random.normal(jax.random.PRNGKey(1), (d, e))
    if skew:  # expert 2 takes every token, expert 0 none
        router = router.at[:, 2].set(0).at[:, 0].set(0)
        bias = jnp.zeros(e).at[2].set(30.0).at[0].set(-30.0)
    else:
        bias = jnp.zeros(e)
    wg, wu, wd = weights(4)

    def loss(fn):
        def f(x, router, wg, wu, wd):
            probs = jax.nn.softmax(x @ router + bias, axis=-1)
            return jnp.sum(fn(x, probs, wg, wu, wd, k) ** 2)
        return jax.grad(f, argnums=(0, 1, 2, 3, 4))(x, router, wg, wu, wd)

    for got, want in zip(loss(system), loss(moe.moe_dense_reference)):
        scale = float(jnp.max(jnp.abs(want))) or 1.0
        np.testing.assert_allclose(got / scale, want / scale, atol=ATOL)


def test_a_dropped_router_weight_is_far_outside_the_tolerance():
    t, d, e, k = 40, 16, 6, 2
    x = jax.random.normal(jax.random.PRNGKey(7), (t, d))
    probs = random_probs(2, t, e)
    wg, wu, wd = weights(1)
    w, ex = moe.route_top_k(probs, k)
    unweighted = moe.dispatch_top_k(
        x, jnp.ones_like(w), ex, e,
        lambda rows, sizes: moe.swiglu_experts(rows, sizes, wg, wu, wd))
    want = moe.moe_dense_reference(x, probs, wg, wu, wd, k)
    assert float(jnp.max(jnp.abs(unweighted - want))) > 1e3 * ATOL


def test_top_k_ties_go_to_the_lower_expert_index():
    probs = jnp.asarray([[0.25, 0.25, 0.25, 0.25], [0.1, 0.4, 0.4, 0.1]])
    w, e = moe.route_top_k(probs, 2)
    assert e.tolist() == [[0, 1], [1, 2]]
    np.testing.assert_allclose(w, [[0.25, 0.25], [0.4, 0.4]])  # not renormalised


def test_sort_by_expert_is_a_stable_permutation_with_the_routers_counts():
    experts = jnp.asarray([[2, 0], [1, 2], [2, 1], [0, 2]])
    order, inverse, sizes = moe.sort_by_expert(experts, 4)
    flat = experts.reshape(-1)
    assert flat[order].tolist() == sorted(flat.tolist())
    assert order[inverse].tolist() == list(range(8))
    assert sizes.tolist() == [2, 2, 4, 0]
    assert order.tolist() == [1, 6, 2, 5, 0, 3, 4, 7]  # stable inside a group


@pytest.mark.parametrize("sizes", [[2, 2, 2, 2], [0, 5, 0, 3], [8, 0, 0, 0],
                                   [0, 0, 0, 8]])
def test_the_grouped_product_is_each_groups_rows_times_its_matrix(sizes):
    """By hand: group ``e``'s run of rows against ``w[e]``, an empty
    group and one that takes every row included."""
    rows = jax.random.normal(jax.random.PRNGKey(3), (8, 16))
    w = jax.random.normal(jax.random.PRNGKey(4), (4, 16, 12)) * 0.3
    got = moe.grouped_dot(rows, w, jnp.asarray(sizes, jnp.int32))
    want, at = np.zeros((8, 12), np.float32), 0
    for e, n in enumerate(sizes):
        want[at:at + n] = np.asarray(rows[at:at + n]) @ np.asarray(w[e])
        at += n
    np.testing.assert_allclose(got, want, atol=ATOL)


@pytest.mark.parametrize("m,k,n,fits", [
    (32768, 2048, 1024, True), (32768, 1024, 2048, True),
    (256, 128, 128, True), (512, 64, 32, False), (100, 2048, 1024, False),
    # since PR 30 a dimension over one tile takes the largest multiple of
    # 128 that divides it (768 for 1536 and for Mellum's 2304)
    (256, 1536, 1024, True), (65536, 2304, 896, True),
    (256, 2300, 896, False)])
def test_pallas_takes_whole_tiles_only(m, k, n, fits):
    assert moe.pallas_fits(m, k, n) is fits
