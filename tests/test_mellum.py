"""The Mellum block on the normal path (``lm/model.py``
``build(arch="mellum")``: ``models/transformer.py`` ``MellumDecoder``
with grouped KV heads, sliding-window and full attention mixed, a
rotary table per layer type, a renormalised top-k and a share of the
experts by ``parallel/moe.py``'s sorted dropless dispatch) against its
plain float32 reference (``chipbench/reference/mellum_plain.py``, the
benchmark's: dense over the held experts, a materialised mask, no code
shared), at the benchmark
configuration's ``tiny`` size on seeded weights; the flash kernel's
grouped heads and window against ``attention_reference``; and the share
of the experts against the uncut layer.

Tolerances.  On the CPU both sides multiply in full float32, so they
differ by the rounding of sums taken in another order: under 1e-6 of the
gradient's norm and of a nat as measured here.  The limits are 1e-5.
What they must refuse, each tried below on the reference itself with
one thing wrong, is wrong by 1e-3 or more."""

import json
import pathlib
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import mellum_plain as ref
from mpit_tpu import obs
from mpit_tpu.lm.model import build, build_kw
from mpit_tpu.ops.flash_attention import attention_reference, flash_attention
from mpit_tpu.parallel import moe

LOSS_TOL_NATS = 1e-5
GRAD_REL_TOL = 1e-5

ROOT = pathlib.Path(__file__).resolve().parent.parent
FILE = json.loads(
    (ROOT / "chipbench/configs/mellum2-12b-l4e8.json").read_text())
CONFIG = {**FILE, **FILE["tiny"]}   # the reference's keys, tiny
YARN = FILE["rope_parameters"]["full_attention"]
TINY = dict(
    vocab=CONFIG["vocab_size"], d_model=CONFIG["hidden_size"],
    n_heads=CONFIG["num_attention_heads"],
    kv_heads=CONFIG["num_key_value_heads"], head_dim=CONFIG["head_dim"],
    n_layers=CONFIG["num_hidden_layers"], seq_len=CONFIG["train_seq"],
    n_experts=CONFIG["router_experts"], experts_held=CONFIG["num_experts"],
    experts_first=CONFIG["experts_first"],
    experts_per_tok=CONFIG["num_experts_per_tok"],
    expert_width=CONFIG["moe_intermediate_size"],
    window=CONFIG["sliding_window"],
    full_every=CONFIG["full_attention_every"],
    rope_theta=float(YARN["rope_theta"]), yarn_factor=YARN["factor"],
    yarn_orig=YARN["original_max_position_embeddings"],
    yarn_beta_fast=YARN["beta_fast"], yarn_beta_slow=YARN["beta_slow"],
    yarn_attn_factor=YARN["attention_factor"],
    norm_eps=CONFIG["rms_norm_eps"])


@pytest.fixture(scope="module")
def case():
    """The tiny model, seeded weights moved off their initial values
    (norm weights off 1, the routers spread, so that top-k margins are
    not ties), one batch, and both sides' loss and flat gradient."""
    model = build(arch="mellum", seed=3, use_flash=False, **TINY)
    rs = np.random.RandomState(0)
    w = model.flat.w0 + 0.05 * jnp.asarray(rs.randn(model.flat.size),
                                           jnp.float32)
    tokens = jnp.asarray(rs.randint(0, 256, (2, TINY["seq_len"] + 1)),
                         jnp.int32)
    sys_loss, sys_grad = jax.jit(model.value_and_grad)(w, tokens)
    ref_loss, ref_grad = ref.loss_and_grad_flat(w, model.flat.unravel,
                                                tokens, CONFIG)
    return dict(model=model, w=w, tokens=tokens, sys=(sys_loss, sys_grad),
                ref=(ref_loss, ref_grad))


def errors(got, want):
    (loss, grad), (ref_loss, ref_grad) = got, want
    return (abs(float(loss) - float(ref_loss)),
            float(jnp.linalg.norm(grad - ref_grad)
                  / jnp.linalg.norm(ref_grad)))


def test_loss_and_flat_gradient_equal_the_plain_references(case):
    loss_err, grad_err = errors(case["sys"], case["ref"])
    assert loss_err <= LOSS_TOL_NATS and grad_err <= GRAD_REL_TOL


def test_every_leaf_of_the_gradient_is_inside_the_tolerance(case):
    """No leaf hides behind the large ones (the table's and the head's
    gradients are most of the norm)."""
    unravel = case["model"].flat.unravel
    got, want = unravel(case["sys"][1]), unravel(case["ref"][1])
    scale = float(jnp.linalg.norm(case["ref"][1]))
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == 4 * 10 + 3   # ten a layer; table, norm, head
    for (path, g), r in zip(flat_got, flat_want):
        err = float(jnp.linalg.norm(g - r))
        assert err <= 10 * GRAD_REL_TOL * max(float(jnp.linalg.norm(r)),
                                              1e-3 * scale), \
            jax.tree_util.keystr(path)


# -- what the tolerance refuses: the reference with one thing wrong ---------------


def _with(config_change=None, **replaced):
    """``loss_and_grad_flat`` of the reference with functions of its
    module replaced, or keys of the configuration changed."""
    def run(case, monkeypatch):
        for name, fn in replaced.items():
            monkeypatch.setattr(ref, name, fn(getattr(ref, name)))
        config = {**CONFIG, **(config_change or {})}
        return ref.loss_and_grad_flat(case["w"], case["model"].flat.unravel,
                                      case["tokens"], config)
    return run


def _one_more_key(_visible):
    def visible(seq, window):
        t, j = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
        mask = j <= t
        return mask & (t - j <= window) if window else mask  # <= for <
    return visible


def _wrong_kv_head(_attention):
    """Query head g on KV head g % kv_heads in place of g // group."""
    def attention(x, p, n_head, n_kv, head, window, rope):
        b, seq, _ = x.shape
        q = (x @ p["wq"]).reshape(b, seq, n_head, head).transpose(0, 2, 1, 3)
        k = (x @ p["wk"]).reshape(b, seq, n_kv, head).transpose(0, 2, 1, 3)
        v = (x @ p["wv"]).reshape(b, seq, n_kv, head).transpose(0, 2, 1, 3)
        cos, sin = ref.rotary_table(seq, head, rope)
        q, k = ref.rotate(q, cos, sin), ref.rotate(k, cos, sin)
        mask = ref.visible(seq, window)
        out = jnp.concatenate(
            [ref._heads(q[:, g:g + 1], k[:, g % n_kv], v[:, g % n_kv], mask)
             for g in range(n_head)], axis=1)
        return out.transpose(0, 2, 1, 3).reshape(b, seq, -1) @ p["wo"]
    return attention


def _interleaved(_rotate):
    def rotate(x, cos, sin):
        half = x.shape[-1] // 2
        c, s = cos[..., :half], sin[..., :half]
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * c - b * s, b * c + a * s],
                         axis=-1).reshape(x.shape)
    return rotate


PLAIN_FULL = {"rope_parameters": {
    **FILE["rope_parameters"],
    "full_attention": FILE["rope_parameters"]["sliding_attention"]}}
NO_FACTOR = {"rope_parameters": {
    **FILE["rope_parameters"],
    "full_attention": {**YARN, "attention_factor": 1.0}}}
WRONG = {
    "a window one key too long": _with(visible=_one_more_key),
    "a top-k not renormalised": _with({"norm_topk_prob": False}),
    "no attention_factor on the full layers": _with(NO_FACTOR),
    "the plain rotary table on the full layers": _with(PLAIN_FULL),
    "the sliding layers' window on the full layer too": _with(
        {"layer_types": ["sliding_attention"] * 4}),
    "query heads on the wrong KV head": _with(attention=_wrong_kv_head),
    "rotary pairs interleaved": _with(rotate=_interleaved),
    "another share of the experts": _with({"experts_first": 4}),
}


@pytest.mark.parametrize("what", sorted(WRONG))
def test_the_tolerance_refuses(case, what, monkeypatch):
    loss_err, grad_err = errors(WRONG[what](case, monkeypatch), case["ref"])
    assert grad_err > 100 * GRAD_REL_TOL, (what, loss_err, grad_err)


def test_the_tolerance_refuses_bf16_parameters_and_activations(case):
    unravel = case["model"].flat.unravel
    low = jax.jit(jax.value_and_grad(lambda flat, tok: ref.loss(
        jax.tree_util.tree_map(lambda p: p.astype(jnp.bfloat16),
                               unravel(flat)), tok, CONFIG)))(
        case["w"], case["tokens"])
    low = (low[0].astype(jnp.float32), low[1].astype(jnp.float32))
    _loss_err, grad_err = errors(low, case["ref"])
    assert grad_err > 100 * GRAD_REL_TOL


# -- the pieces by hand ------------------------------------------------------------


def test_yarn_blends_between_the_plain_table_and_the_divided_one():
    """At the published sizes (head 128, theta 5e5, original 8192): pair
    0 turns 1304 times over the original context, far over beta_fast, and
    keeps its frequency; the last pair turns less than once and takes it
    divided by 16; c(32) = 18.08 and c(1) = 34.99, so the ramp runs over
    pairs 18..35; and the program's table is the reference's."""
    from mpit_tpu.models.transformer import yarn_inv_freq

    got = np.asarray(ref.yarn_frequencies(128, YARN))
    plain = 500000.0 ** (-np.arange(64) / 64.0)
    np.testing.assert_allclose(got[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(got[35:], plain[35:] / 16, rtol=1e-6)
    assert np.all(got[19:35] < plain[19:35])
    assert np.all(got[19:35] > plain[19:35] / 16)
    r = (27 - 18) / (35 - 18)
    np.testing.assert_allclose(got[27], plain[27] * (1 - r + r / 16),
                               rtol=1e-5)
    np.testing.assert_allclose(
        yarn_inv_freq(128, 500000.0, 16, 8192, 32, 1), got, rtol=1e-6)
    assert YARN["attention_factor"] == pytest.approx(0.1 * np.log(16) + 1)


def test_the_window_is_the_query_and_the_keys_before_it():
    mask = np.asarray(ref.visible(6, 3))
    want = np.array([[1, 0, 0, 0, 0, 0], [1, 1, 0, 0, 0, 0],
                     [1, 1, 1, 0, 0, 0], [0, 1, 1, 1, 0, 0],
                     [0, 0, 1, 1, 1, 0], [0, 0, 0, 1, 1, 1]], bool)
    np.testing.assert_array_equal(mask, want)
    np.testing.assert_array_equal(np.asarray(ref.visible(6, 0)),
                                  np.tril(np.ones((6, 6), bool)))


def test_the_vector_at_published_widths_is_340_349_184_elements():
    """The cut of the benchmark's configuration, counted from the
    module's shapes without building it."""
    from mpit_tpu.models.transformer import MellumDecoder

    module = MellumDecoder(
        vocab=FILE["vocab_size"], d_model=FILE["hidden_size"],
        n_heads=FILE["num_attention_heads"],
        kv_heads=FILE["num_key_value_heads"], head_dim=FILE["head_dim"],
        n_layers=FILE["num_hidden_layers"], n_experts=FILE["router_experts"],
        experts_held=FILE["num_experts"],
        experts_per_tok=FILE["num_experts_per_tok"],
        expert_width=FILE["moe_intermediate_size"],
        window=FILE["sliding_window"])
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 16), jnp.int32))
    assert sum(int(np.prod(leaf.shape)) for leaf in
               jax.tree_util.tree_leaves(shapes)) == 340_349_184


def test_the_table_is_at_std_8_and_the_rest_at_002(case):
    params = case["model"].flat.unravel(
        build(arch="mellum", seed=3, use_flash=False, **TINY).flat.w0)
    assert float(jnp.std(params["embed"])) == pytest.approx(8.0, rel=0.05)
    assert float(jnp.std(params["head"])) == pytest.approx(0.02, rel=0.05)
    assert float(jnp.std(params["MellumBlock_0"]["wq"])) == \
        pytest.approx(0.02, rel=0.05)


# -- the flash kernel: grouped KV heads and the window ------------------------------

# (query heads, KV heads, sequence, window[, head width: 32]); blocks
# are 64 x 128
FLASH_CASES = {
    # LFM2's attention layer (PR 32): 32 query over 8 KV heads of 64
    "grouped 32 on 8, heads of 64": (32, 8, 256, None, 64),
    "grouped 4 on 1": (4, 1, 256, None),
    "grouped 8 on 2": (8, 2, 256, None),
    "window ends inside a block": (4, 4, 256, 100),
    "window on a block edge": (4, 4, 256, 128),
    "window of one key": (2, 2, 256, 1),
    "window beyond the sequence": (4, 4, 200, 1000),
    "window x groups": (8, 2, 256, 128),
    "window x groups, ragged sequence": (4, 1, 300, 70),
}


@pytest.mark.parametrize("schedule", ["auto", "1"])
@pytest.mark.parametrize("what", sorted(FLASH_CASES))
def test_flash_kernel_equals_the_reference(what, schedule, monkeypatch):
    """Forward and all three gradients, interpreted, against
    ``attention_reference`` with the same two arguments; under a window
    ``auto`` is the two-kernel backward and ``1`` forces the fused."""
    monkeypatch.setenv("MPIT_FA_FUSED_BWD", schedule)
    hq, hkv, seq, window, *width = FLASH_CASES[what]
    head = width[0] if width else 32
    keys = jax.random.split(jax.random.PRNGKey(hq * 1000 + seq), 4)
    q = jax.random.normal(keys[0], (2, hq, seq, head))
    k = jax.random.normal(keys[1], (2, hkv, seq, head))
    v = jax.random.normal(keys[2], (2, hkv, seq, head))
    g = jax.random.normal(keys[3], (2, hq, seq, head))

    def kernel(q, k, v):
        return jnp.sum(g * flash_attention(
            q, k, v, causal=True, window=window, block_q=64, block_k=128,
            interpret=True, precision="highest"))

    def plain(q, k, v):
        return jnp.sum(g * attention_reference(q, k, v, causal=True,
                                               window=window))

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(kernel, (0, 1, 2))(q, k, v)
        want = jax.value_and_grad(plain, (0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


# -- the live-block walk's own numbers (PR 33) ----------------------------------

# q shape, k shape (float32, so 512 x 512 blocks), window -> visited, live, rect
STEP_SHAPES = {
    # a sliding layer: 16 x 16 block pairs a head, 45 of them inside the
    # window of 1024 (1 + 2 + 14 x 3), 32 heads; the inner axis is the
    # window's static bound, 4, times the 128 x 4 outer blocks
    "mellum, window 1024": ((1, 32, 8192, 128), (1, 4, 8192, 128), 1024,
                            (2048, 1440, 8192)),
    # its full layer: 136 of 256 pairs on or under the diagonal
    "mellum, full": ((1, 32, 8192, 128), (1, 4, 8192, 128), None,
                     (8192, 4352, 8192)),
    "lfm2": ((1, 32, 8192, 64), (1, 8, 8192, 64), None, (8192, 4352, 8192)),
    # 72 (batch, head) programs of 4 x 4 blocks, 10 live
    "cerebras-gpt-111m": ((6, 12, 2048, 64), (6, 12, 2048, 64), None,
                          (1152, 720, 1152)),
}


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkdv", "fused"])
@pytest.mark.parametrize("shape", sorted(STEP_SHAPES))
def test_the_walks_step_counts_at_published_shapes(shape, kernel):
    """The plain function's numbers are the program's own (the walk the
    kernels lower with): under a window the grid visits the window's
    static bound a row, not the row; under plain causal masking it still
    visits the rectangle, and the dead steps are the clamped ones."""
    from mpit_tpu.ops.flash_attention import flash_step_counts

    q_shape, k_shape, window, (visited, live, rect) = STEP_SHAPES[shape]
    counts = flash_step_counts(kernel, q_shape, k_shape, jnp.float32,
                               causal=True, window=window)
    assert counts == {"visited": visited, "live": live, "rect": rect}
    if window is not None:  # the static bound times the outer blocks
        bound = -(-(window + 512 - 2) // 512) + 1
        heads_kv, groups = k_shape[1], q_shape[1] // k_shape[1]
        outer = 16 if kernel in ("dkdv", "fused") else 16 * groups
        per_row = bound * (groups if kernel in ("dkdv", "fused") else 1)
        assert visited == heads_kv * outer * per_row


def test_with_obs_off_a_lowering_touches_no_registry(monkeypatch):
    def no_counter(*_a, **_k):
        raise AssertionError("a counter was asked for with obs off")

    assert not obs.obs_enabled()
    monkeypatch.setattr(obs.NullRegistry, "counter", no_counter)
    q = jnp.ones((1, 2, 128, 32))
    jax.jit(jax.grad(lambda q: jnp.sum(flash_attention(
        q, q, q, causal=True, block_q=64, block_k=128,
        interpret=True)))).lower(q)


def test_a_window_needs_causal():
    q = jnp.zeros((1, 2, 16, 8))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, causal=False, window=4, interpret=True)
    with pytest.raises(ValueError, match="causal"):
        attention_reference(q, q, q, causal=False, window=4)


def test_grouped_heads_repeat_no_key_and_sum_dk_in_the_kernel():
    """The lowered grouped call holds k and v at the KV heads' size only
    (no operand of the kernels has the query heads' count of keys)."""
    q = jnp.zeros((1, 8, 256, 32))
    k = jnp.zeros((1, 2, 256, 32))

    def f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=64,
                                       block_k=128, interpret=True))

    text = str(jax.make_jaxpr(jax.grad(f, (1, 2)))(q, k, k))
    assert "repeat" not in text and "f32[1,8,256,128]" not in text.replace(
        "f32[1,2,4,256,128]", "")   # q is (B, Hkv, G, L, D_p) only
    dk, dv = jax.grad(f, (1, 2))(q + 1.0, k + 1.0, k + 2.0)
    assert dk.shape == k.shape and dv.shape == k.shape


# -- the share of the experts --------------------------------------------------------


def _layer(rs, tokens=48, d=16, f=8, e=8):
    return dict(
        h=jnp.asarray(rs.randn(tokens, d), jnp.float32),
        router=jnp.asarray(rs.randn(d, e), jnp.float32),
        experts_gate=jnp.asarray(rs.randn(e, d, f), jnp.float32) * 0.3,
        experts_up=jnp.asarray(rs.randn(e, d, f), jnp.float32) * 0.3,
        experts_down=jnp.asarray(rs.randn(e, f, d), jnp.float32) * 0.3)


def _program_share(p, first, held, top_k=2):
    """One chip's expert layer as the block runs it: router over all the
    experts, renormalised top-k, the held experts' part."""
    e = p["router"].shape[1]
    probs = jax.nn.softmax(p["h"] @ p["router"], axis=-1)
    weights, experts = moe.route_top_k(probs, top_k, renormalise=True)
    cut = slice(first, first + held)
    return moe.dispatch_top_k(
        p["h"], weights, experts, e,
        lambda rows, sizes: moe.swiglu_experts(
            rows, sizes, p["experts_gate"][cut], p["experts_up"][cut],
            p["experts_down"][cut], first if held < e else None))


@pytest.mark.parametrize("held", [1, 2, 4, 8])
def test_the_shares_partial_outputs_add_up_to_the_uncut_layer(held):
    """Every share's partial layer output adds up to what the uncut
    reference gives for the whole layer (nothing in this layer is
    computed by all chips alike, so nothing is counted twice), and each
    share's gradients of its held experts are the uncut gradients'
    slices."""
    rs = np.random.RandomState(held)
    p = _layer(rs)
    cot = jnp.asarray(rs.randn(*p["h"].shape), jnp.float32)
    names = ("experts_gate", "experts_up", "experts_down")

    def uncut(weights):
        return jnp.sum(cot * ref.experts(
            p["h"], {**p, **dict(zip(names, weights))}, 2, True, 0))

    whole = ref.experts(p["h"], p, 2, True, 0)
    whole_grads = jax.grad(uncut)(tuple(p[n] for n in names))
    total = jnp.zeros_like(whole)
    for first in range(0, 8, held):
        def share(weights, first=first):
            return jnp.sum(cot * _program_share(
                {**p, **dict(zip(names, weights))}, first, held))

        total = total + _program_share(p, first, held)
        grads = jax.grad(share)(tuple(p[n] for n in names))
        for got, want in zip(grads, whole_grads):
            np.testing.assert_allclose(got[first:first + held],
                                       want[first:first + held],
                                       atol=1e-5, rtol=1e-4)
            rest = np.delete(np.asarray(got), np.s_[first:first + held], 0)
            assert not rest.any()   # absent experts: computed by nobody
    np.testing.assert_allclose(total, whole, atol=1e-5, rtol=1e-4)


def test_a_share_equals_the_reference_given_the_same_share():
    p = _layer(np.random.RandomState(9))
    cut = slice(2, 5)
    held = {**p, **{n: p[n][cut] for n in
                    ("experts_gate", "experts_up", "experts_down")}}
    np.testing.assert_allclose(_program_share(p, 2, 3),
                               ref.experts(p["h"], held, 2, True, 2),
                               atol=1e-5, rtol=1e-4)


def test_absent_experts_rows_come_back_zero_and_pass_zero_back():
    rows = jnp.ones((12, 4))
    sizes = jnp.asarray([3, 0, 4, 2, 3], jnp.int32)   # held: groups 2, 3
    w = jnp.ones((2, 4, 6))
    out = moe.grouped_dot(rows, w, sizes, 2)
    np.testing.assert_array_equal(np.asarray(out)[:, 0],
                                  [0, 0, 0, 4, 4, 4, 4, 4, 4, 0, 0, 0])
    d_rows = jax.grad(lambda r: jnp.sum(moe.grouped_dot(r, w, sizes, 2)))(rows)
    np.testing.assert_array_equal(np.asarray(d_rows)[:, 0],
                                  [0, 0, 0, 6, 6, 6, 6, 6, 6, 0, 0, 0])
    held = np.asarray(moe._held_rows(sizes, 2, 2, 12))[:, 0]
    np.testing.assert_array_equal(held, np.asarray(out)[:, 0] != 0)


def test_held_rows_share_counts_assignments_on_held_experts():
    experts = jnp.asarray([[0, 5], [6, 7], [2, 3], [4, 1]])
    assert float(moe.held_rows_share(experts, 4, 4)) == 0.5
    assert float(moe.held_rows_share(experts, 0, 8)) == 1.0
    assert float(moe.held_rows_share(experts, 0, 1)) == 0.125


# -- the router's weights and the kernels' tiling -------------------------------------


def test_route_top_k_renormalises_over_the_chosen():
    probs = jnp.asarray([[0.5, 0.1, 0.3, 0.1], [0.25, 0.25, 0.25, 0.25]])
    weights, experts = moe.route_top_k(probs, 2, renormalise=True)
    np.testing.assert_allclose(weights, [[0.625, 0.375], [0.5, 0.5]])
    np.testing.assert_array_equal(experts, [[0, 2], [0, 1]])
    plain, same = moe.route_top_k(probs, 2)
    np.testing.assert_allclose(plain, [[0.5, 0.3], [0.25, 0.25]])
    np.testing.assert_array_equal(same, experts)


@pytest.mark.parametrize("k, n, tiling", [
    (2048, 1024, (256, 1024, 1024)),    # OLMoE's, as it was
    (1024, 2048, (256, 1024, 1024)),
    (2304, 896, (256, 768, 896)),       # Mellum's: 768 divides 2304
    (896, 2304, (256, 896, 768)),
    (2048, 1536, (256, 1024, 768)),     # LFM2's: 768 divides 1536
    (1536, 2048, (256, 768, 1024)),
    (128, 128, (256, 128, 128)),
])
def test_the_grouped_products_tiling(k, n, tiling):
    assert moe.pallas_fits(65536, k, n)
    assert moe.pallas_fits(32768, k, n)   # LFM2's 4 T rows at 8192
    assert moe._gmm_tiling(k, n) == tiling
    assert k % tiling[1] == 0 and n % tiling[2] == 0


def _tiny_window(batch):
    """The window's share of the ``k T`` rows at the ``tiny`` size."""
    rows = CONFIG["num_experts_per_tok"] * batch * CONFIG["train_seq"]
    return moe.held_window(rows, CONFIG["hidden_size"], CONFIG["num_experts"],
                           CONFIG["router_experts"]) / rows


@pytest.mark.parametrize("batch", [1, 2])
def test_the_tiny_size_has_a_window_smaller_than_its_rows(batch):
    """Twice the uniform quarter: the tests of this file compile and
    differentiate the dispatch's loop over windows through the whole
    block, and a held run can outgrow one."""
    assert _tiny_window(batch) == 0.5


def test_pallas_fits_wants_whole_row_tiles_and_lanes():
    assert not moe.pallas_fits(100, 2304, 896)
    assert not moe.pallas_fits(256, 2300, 896)
    assert not moe.pallas_fits(256, 2304, 900)


# -- through the launcher: a gang of three and a run of one ------------------------


LAUNCH = dict(
    lm=1, lm_arch="mellum", lm_use_flash=0, lm_eval_every=4, seed=5,
    device_policy="cpu",
    **{switch: CONFIG[key] for switch, key in FILE["launcher_from"].items()})


@pytest.fixture
def obs_on():
    obs.configure(enabled=True, reset=True)
    try:
        yield obs.get_recorder()
    finally:
        obs.configure(enabled=None, reset=True)


def _counters_on_round_spans(recorder, layers):
    rounds = [s for s in recorder.spans if s.name == "round"]
    assert rounds
    for span in rounds:
        load = span.args["moe_load_max_over_mean"]
        share = span.args["moe_held_rows_share"]
        compact = span.args["moe_compact_share"]
        assert len(load) == len(share) == len(compact) == layers
        assert all(1.0 <= x <= 4.0 for x in load)     # 8 experts, 2 a token
        assert all(0.0 < x < 1.0 for x in share)
        # in one window exactly where the held run fits in it
        assert compact == [float(x <= _tiny_window(2)) for x in share]
    reg = obs.get_registry()
    assert reg.gauge("mpit_moe_held_rows_share", layer=layers - 1).value == \
        rounds[-1].args["moe_held_rows_share"][-1]
    assert reg.gauge("mpit_moe_load_max_over_mean", layer=0).value == \
        rounds[-1].args["moe_load_max_over_mean"][0]
    return rounds


def test_a_three_rank_gang_learns_and_carries_both_counters(obs_on):
    """``--np 3 --opt adam`` through ``run_rank``: servers 0 and 2,
    worker 1, the same launcher, trainer, shell, client and servers as
    the other blocks, on threads over the in-process router."""
    from mpit_tpu.comm.local import LocalRouter
    from mpit_tpu.train import launch

    steps = 12
    cfg = launch.LAUNCH_DEFAULTS.merged(
        np=3, master_freq=2, opt="adam", lr=3e-3, batch=2, lm_steps=steps,
        **LAUNCH)
    assert build_kw(launch.lm_trainer_cfg(cfg))["experts_held"] == 2
    router = LocalRouter(3)
    results, failed = {}, {}

    def target(rank):
        try:
            results[rank] = launch.run_rank(rank, 3, cfg,
                                            router.endpoint(rank))
        except BaseException as exc:  # noqa: BLE001
            failed[rank] = exc

    threads = [threading.Thread(target=target, args=(r,), daemon=True)
               for r in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(300)
    if failed:
        raise next(iter(failed.values()))
    assert not any(t.is_alive() for t in threads)
    worker = next(r for r in results.values() if r["role"] == "worker")
    history = worker["history"]
    assert history[-1]["avg_loss"] < history[0]["avg_loss"] - 0.1
    rounds = _counters_on_round_spans(obs_on, CONFIG["num_hidden_layers"])
    assert len(rounds) == steps
    assert worker["moe_held_rows_share"] == \
        rounds[-1].args["moe_held_rows_share"]


def test_a_one_rank_local_run_learns_and_carries_both_counters(obs_on):
    """``--np 1 --opt msgd``: the single-process path hands ``MSGD`` the
    step with the block's telemetry, and each step is a ``round`` span
    with both counters while obs records."""
    from mpit_tpu.train import launch

    steps = 12
    cfg = launch.LAUNCH_DEFAULTS.merged(
        np=1, opt="msgd", mom=0.9, lr=0.3, batch=2, lm_steps=steps,
        **LAUNCH)
    result = launch.run_rank(0, 1, cfg, None)
    assert result["role"] == "local"
    history = result["history"]
    assert history[-1]["avg_loss"] < history[0]["avg_loss"] - 0.1
    rounds = _counters_on_round_spans(obs_on, CONFIG["num_hidden_layers"])
    assert len(rounds) == steps
    assert [name for name, _t in rounds[0].marks] == ["step", "telemetry"]
    assert result["moe_load_max_over_mean"] == \
        rounds[-1].args["moe_load_max_over_mean"]


class Unreadable:
    """A statistic that fails the test if anything fetches it."""

    def __array__(self, *a, **k):
        raise AssertionError("fetched with obs off")


def test_the_local_step_fetches_no_statistic_with_obs_off():
    from mpit_tpu.optim.msgd import MSGD, MSGDConfig

    obs.configure(enabled=False, reset=True)
    try:
        def step(w, target):
            return (jnp.sum((w - target) ** 2), {"load": jnp.ones(2)}), \
                2 * (w - target)

        opt = MSGD(MSGDConfig(lr=0.1, mom=0.9), step, has_aux=True)
        opt._step = lambda w, state, t, inner=opt._step: (
            lambda out: (out[0], out[1], (out[2][0], {"load": Unreadable()}))
        )(inner(w, state, t))
        w, loss = opt.step(jnp.zeros(4), jnp.ones(4))
        assert float(loss) == 4.0 and opt.stats_last == {}
        assert not obs.get_recorder().enabled
    finally:
        obs.configure(enabled=None, reset=True)


@pytest.mark.parametrize("mom,fused,want", [
    (0.9, True, {"commit": "kernel", "lookahead": "folded"}),
    (0.9, False, {"commit": "xla", "lookahead": "pass"}),
    (0.0, True, {"commit": "xla", "lookahead": "none"}),
], ids=["kernel_folded", "xla_pass", "no_momentum"])
def test_the_round_span_says_what_the_step_does_with_the_vector(obs_on, mom,
                                                                fused, want):
    """With obs on the first local step's ``round`` span carries, beside
    the block's statistics, whether the commit is the kernel's sweep and
    whether the next step's lookahead rides it: a constant of the run,
    said once."""
    from mpit_tpu.optim.msgd import MSGD, MSGDConfig

    def step(w, target):
        return (jnp.sum((w - target) ** 2), {"load": jnp.ones(2)}), \
            2 * (w - target)

    opt = MSGD(MSGDConfig(lr=0.1, mom=mom, use_fused=fused), step,
               has_aux=True)
    w = jnp.zeros(300)
    for _ in range(2):
        w, _loss = opt.step(w, jnp.ones(300))
    first, second = [s for s in obs_on.spans if s.name == "round"]
    assert {k: first.args[k] for k in want} == want
    assert not set(want) & set(second.args)
    for span in (first, second):
        assert span.args["load"] == [1.0, 1.0]


def test_a_block_without_statistics_takes_the_plain_local_step():
    """gpt2 under ``--opt msgd`` is the program it was: no auxiliary
    output, no span."""
    from mpit_tpu.lm import LmTrainer
    from mpit_tpu.train import launch

    cfg = launch.LAUNCH_DEFAULTS.merged(lm=1, lm_d_model=32, lm_heads=2,
                                        lm_layers=1, lm_seq=16, opt="msgd",
                                        lm_use_flash=0)
    trainer = LmTrainer(launch.lm_trainer_cfg(cfg))
    assert trainer.model.value_grad_stats is None
    assert trainer.optimizer._has_aux is False


def test_the_seeded_weights_do_not_depend_on_the_samples_length():
    """``build`` initialises on a short sample (a host role's forward
    pass at the training sequence with the reference attention would
    take minutes); no parameter's shape or value depends on it."""
    from mpit_tpu.models.transformer import MellumDecoder

    module = MellumDecoder(vocab=320, n_experts=8, experts_held=2,
                           experts_first=2)
    key = jax.random.PRNGKey(3)
    short = module.init(key, jnp.zeros((1, 16), jnp.int32))
    long = module.init(key, jnp.zeros((1, 64), jnp.int32))
    for a, b in zip(jax.tree_util.tree_leaves(short),
                    jax.tree_util.tree_leaves(long)):
        np.testing.assert_array_equal(a, b)


def test_default_attn_takes_the_window_as_a_keyword_of_the_one_callable():
    from mpit_tpu.models.transformer import default_attn

    attn = default_attn(causal=True, use_flash=False)
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(keys[0], (1, 32, 4, 8))
    k = jax.random.normal(keys[1], (1, 32, 2, 8))
    v = jax.random.normal(keys[2], (1, 32, 2, 8))
    heads = lambda x: x.transpose(0, 2, 1, 3)
    np.testing.assert_allclose(
        attn(q, k, v, window=5),
        heads(attention_reference(heads(q), heads(k), heads(v), causal=True,
                                  window=5)), atol=1e-6)
    np.testing.assert_allclose(
        attn(q, k, v),
        heads(attention_reference(heads(q), heads(k), heads(v),
                                  causal=True)), atol=1e-6)
    assert not np.allclose(attn(q, k, v), attn(q, k, v, window=5))
