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
    assert float(moe.load_max_over_mean(
        moe.expert_counts(experts, e), experts.size)) == pytest.approx(e / k)


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


# -- a window over the held run (PR 35) -----------------------------------------
#
# One chip's share of the experts: 2 of 16, top-2 of 32 tokens, so 64
# sorted assignments of which uniform routing sends 8 here and a window
# takes 16: a held run is done in one window to four.  The routing is
# made by hand, so that the run has exactly the length a case names.

W_T, W_K, W_E, W_HELD, W_D = 32, 2, 16, 2, 16
W_ROWS = W_T * W_K
W_C = 16


def forced_probs(first, n_held, seed=0):
    """Router probabilities whose top-2 put exactly ``n_held`` of the 64
    assignments on the experts ``first, first + 1``: the first tokens
    choose both, then one token one of them, the rest neither."""
    rs = np.random.RandomState(seed)
    logits = rs.uniform(-1.0, 1.0, (W_T, W_E))
    others = [e for e in range(W_E) if not first <= e < first + W_HELD]
    for t in range(W_T):
        n = min(2, n_held - 2 * t) if n_held > 2 * t else 0
        want = [first, first + 1][:n] + list(
            rs.permutation(others)[:W_K - n])
        logits[t, want] += 10.0
        logits[t, [first, first + 1][n:]] -= 10.0
    return jax.nn.softmax(jnp.asarray(logits, jnp.float32), axis=-1)


def held_layer(first, seed=5):
    """Inputs and all sixteen experts' matrices; ``cut`` are the held."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (W_T, W_D))
    return x, weights(seed, d=W_D, e=W_E), slice(first, first + W_HELD)


def held_system(x, probs, mats, first, windowed=True):
    """The share's part of the layer: a window at a time where
    ``windowed``, else by the path a caller without windows takes (the
    experts' function bound to its offset, ``held`` not given: all ``k
    T`` rows at once)."""
    w, chosen = moe.route_top_k(probs, W_K)
    if windowed:
        return moe.dispatch_top_k(x, w, chosen, W_E, moe.swiglu_experts,
                                  *mats, held=(first, W_HELD))
    return moe.dispatch_top_k(
        x, w, chosen, W_E,
        lambda rows, sizes: moe.swiglu_experts(rows, sizes, *mats, first))


def held_oracle(x, probs, wg, wu, wd, first):
    """The dense oracle with every absent expert's output zero."""
    keep = (jnp.arange(W_E) >= first) & (jnp.arange(W_E) < first + W_HELD)
    return moe.moe_dense_reference(
        x, probs, wg, wu, jnp.where(keep[:, None, None], wd, 0.0), W_K)


# the held run's length: well under the window, the window exactly, one
# row more (a second window for one row), three windows with the split
# between the two experts inside the second, every assignment (four
# windows, nothing lost), none (no window at all)
RUNS = {"under": 5, "full": W_C, "over": W_C + 1, "three": 2 * W_C + 7,
        "all": W_ROWS, "none": 0}


def test_the_window_is_twice_the_uniform_expectation_in_whole_tiles():
    assert moe.held_window(W_ROWS, W_D, W_HELD, W_E) == W_C
    # the two cells' shapes: whole row tiles of the Pallas kernels
    assert moe.held_window(8 * 8192, 2304, 8, 64) == 16384
    assert moe.held_window(4 * 8192, 2048, 8, 64) == 8192
    assert moe.pallas_fits(16384, 2304, 896)
    # rounded up to a tile where the kernels take the rows, to 8 elsewhere
    assert moe.held_window(4096, 128, 3, 64) == 512
    assert moe.held_window(4096, 100, 3, 64) == 384
    # everything held, or a half and more: no window
    assert moe.held_window(W_ROWS, W_D, W_E, W_E) == W_ROWS
    assert moe.held_window(W_ROWS, W_D, 8, W_E) == W_ROWS


@pytest.mark.parametrize("run", sorted(RUNS))
@pytest.mark.parametrize("first", [0, 7, 14])
def test_windowed_dispatch_equals_the_dense_oracle(first, run):
    """Forward, for a held range first, in the middle and last among
    the experts (the last clamps a window's start), in one window and
    in several; the counter says which."""
    probs = forced_probs(first, RUNS[run], seed=first)
    x, (wg, wu, wd), cut = held_layer(first)
    _w, chosen = moe.route_top_k(probs, W_K)
    _order, _inv, sizes = moe.sort_by_expert(chosen, W_E)
    assert int(sizes[cut].sum()) == RUNS[run]
    assert bool(moe.takes_window(chosen, first, W_HELD, W_E, W_D)) is (
        RUNS[run] <= W_C)
    mats = (wg[cut], wu[cut], wd[cut])
    got = jax.jit(held_system, static_argnums=(3, 4))(x, probs, mats, first)
    np.testing.assert_allclose(
        got, held_oracle(x, probs, wg, wu, wd, first), atol=ATOL)
    np.testing.assert_allclose(
        got, held_system(x, probs, mats, first, windowed=False), atol=ATOL)
    if run == "none":
        assert not np.asarray(got).any()


@pytest.mark.parametrize("run", sorted(RUNS))
@pytest.mark.parametrize("first", [0, 7, 14])
def test_windowed_dispatch_gradients_equal_the_dense_oracles(first, run):
    """Of the tokens, the router's probabilities and the held matrices,
    against the oracle and against the path without windows."""
    probs = forced_probs(first, RUNS[run], seed=first)
    x, (wg, wu, wd), cut = held_layer(first)
    cot = jax.random.normal(jax.random.PRNGKey(8), (W_T, W_D))

    def grads(fn):
        return jax.jit(jax.grad(
            lambda x, probs, mats: jnp.sum(cot * fn(x, probs, mats)),
            argnums=(0, 1, 2)))(x, probs, (wg[cut], wu[cut], wd[cut]))

    def oracle(x, probs, mats):
        whole = [w.at[cut].set(m) for w, m in zip((wg, wu, wd), mats)]
        return held_oracle(x, probs, *whole, first)

    got = jax.tree.leaves(grads(
        lambda x, probs, mats: held_system(x, probs, mats, first)))
    plain = jax.tree.leaves(grads(
        lambda x, probs, mats: held_system(x, probs, mats, first, False)))
    for g, p, want in zip(got, plain, jax.tree.leaves(grads(oracle))):
        scale = float(jnp.max(jnp.abs(want))) or 1.0
        np.testing.assert_allclose(g / scale, want / scale, atol=ATOL)
        np.testing.assert_allclose(g / scale, p / scale, atol=ATOL)


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of what it calls."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def test_a_share_moves_no_array_of_all_the_sorted_rows():
    """Differentiated under the block's ``jax.checkpoint``, the share's
    layer holds no float array of ``k T`` rows anywhere, forward or
    backward (the sort's integers are all that long), and the compiled
    step has one loop over the windows a pass (the recomputation's,
    whose result nothing reads, is gone)."""
    probs = forced_probs(7, 5)
    x, (wg, wu, wd), cut = held_layer(7)
    args = (x, probs, (wg[cut], wu[cut], wd[cut]))

    def loss(x, probs, mats):
        return jnp.sum(held_system(x, probs, mats, 7) ** 2)

    grad = jax.grad(jax.checkpoint(loss), argnums=(0, 2))
    eqns = list(_equations(jax.make_jaxpr(grad)(*args).jaxpr))
    assert sum(e.primitive.name == "while" for e in eqns) == 3
    wide = [v.aval.shape for e in eqns for v in e.outvars
            if getattr(v.aval, "ndim", 0) >= 2 and v.aval.shape[0] == W_ROWS
            and jnp.issubdtype(v.aval.dtype, jnp.floating)]
    assert wide == []
    compiled = jax.jit(grad).lower(*args).compile().as_text()
    assert compiled.count(" while(") == 2


def test_a_share_of_a_half_has_no_window():
    """Where the window would be no smaller than ``k T`` all the rows
    are moved at once, as without a share: no loop."""
    probs = random_probs(1, W_T, W_E)
    x, (wg, wu, wd), _cut = held_layer(0)
    w, chosen = moe.route_top_k(probs, W_K)
    jaxpr = jax.make_jaxpr(lambda x: moe.dispatch_top_k(
        x, w, chosen, W_E, moe.swiglu_experts, wg[:8], wu[:8], wd[:8],
        held=(0, 8)))(x).jaxpr
    assert not any(e.primitive.name == "while" for e in _equations(jaxpr))
    assert not bool(moe.takes_window(chosen, 0, 8, W_E, W_D))


def test_the_windows_scopes_stay_flat_forward_and_backward():
    """No operation of the share's layer is under both ``dispatch`` and
    ``experts``: the benchmark books a kernel under the one model scope
    of its name stack, and one under two counts for no family."""
    probs = forced_probs(7, 5)
    x, (wg, wu, wd), cut = held_layer(7)

    def loss(x, probs, mats):
        return jnp.sum(held_system(x, probs, mats, 7) ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 2)))(
        x, probs, (wg[cut], wu[cut], wd[cut])).jaxpr

    def stacks(jaxpr, outer=""):
        for eqn in jaxpr.eqns:
            stack = f"{outer}/{eqn.source_info.name_stack}"
            yield stack
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from stacks(sub, stack)

    seen = set(stacks(jaxpr))
    assert any("experts" in s for s in seen)
    assert any("dispatch" in s for s in seen)
    assert not [s for s in seen if "experts" in s and "dispatch" in s]
