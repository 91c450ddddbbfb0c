"""The LFM2 block on the normal path (``lm/model.py``
``build(arch="lfm2")``: ``models/transformer.py`` ``Lfm2Decoder``, a
layer's token mixer a gated short convolution or grouped-head attention
with a per-head query/key norm, its MLP dense or a share of the sparse
experts behind a sigmoid router with a selection bias) against its plain
float32 reference, at the benchmark configuration's ``tiny`` size on
seeded weights.  The reference exists once, as the benchmark's
``chipbench/reference/lfm2_plain.py`` (no code shared with the block),
and is imported from there; the short convolution against a double loop;
the router's selection bias, its normalisation and its ties; the share
of the experts against the uncut layer; the layers' kinds from the
configuration; and the local step's donation.

Tolerances.  On the CPU both sides multiply in full float32, so they
differ by the rounding of sums taken in another order: under 1e-6 of the
gradient's norm and of a nat as measured here.  The limits are 1e-5.
What they must refuse, each tried below on the reference itself with
one thing wrong, is wrong by 1e-3 or more (a bias of 0.05 that leaks
into the weights by 3e-4)."""

import json
import pathlib
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.arithmetic import lfm2 as arithmetic
from chipbench.reference import lfm2_plain as ref
from mpit_tpu import obs
from mpit_tpu.lm.model import build, build_kw
from mpit_tpu.ops.short_conv import causal_depthwise_conv
from mpit_tpu.parallel import moe

LOSS_TOL_NATS = 1e-5
GRAD_REL_TOL = 1e-5

ROOT = pathlib.Path(__file__).resolve().parent.parent
FILE = json.loads((ROOT / "chipbench/configs/lfm2-24b-l5e8.json").read_text())
CONFIG = {**FILE, **FILE["tiny"]}   # the reference's keys, tiny
TINY = dict(
    vocab=CONFIG["vocab_size"], d_model=CONFIG["hidden_size"],
    n_heads=CONFIG["num_attention_heads"],
    kv_heads=CONFIG["num_key_value_heads"],
    n_layers=CONFIG["num_hidden_layers"], seq_len=CONFIG["train_seq"],
    layer_types=CONFIG["layer_types_here"],
    dense_layers=CONFIG["dense_layers_here"],
    dense_width=CONFIG["intermediate_size"],
    conv_kernel=CONFIG["conv_L_cache"],
    n_experts=CONFIG["router_experts"], experts_held=CONFIG["num_experts"],
    experts_first=CONFIG["experts_first"],
    experts_per_tok=CONFIG["num_experts_per_tok"],
    expert_width=CONFIG["moe_intermediate_size"],
    route_scale=CONFIG["routed_scaling_factor"],
    rope_theta=float(CONFIG["rope_theta"]), norm_eps=CONFIG["norm_eps"])


@pytest.fixture(scope="module")
def case():
    """The tiny model, seeded weights moved off their initial values
    (norm weights off 1, the routers and their biases spread, so that
    top-k margins are not ties), one batch, and both sides' loss and flat
    gradient."""
    model = build(arch="lfm2", seed=3, use_flash=False, **TINY)
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
    """No leaf hides behind the large ones, and the selection bias gets
    a gradient of exactly zero on both sides."""
    unravel = case["model"].flat.unravel
    got, want = unravel(case["sys"][1]), unravel(case["ref"][1])
    scale = float(jnp.linalg.norm(case["ref"][1]))
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    # conv + dense 8; attention + sparse 13; three conv + sparse 10 each;
    # table, norm, head
    assert len(flat_got) == 8 + 13 + 3 * 10 + 3
    for (path, g), r in zip(flat_got, flat_want):
        err = float(jnp.linalg.norm(g - r))
        assert err <= 10 * GRAD_REL_TOL * max(float(jnp.linalg.norm(r)),
                                              1e-3 * scale), \
            jax.tree_util.keystr(path)
        if "router_bias" in jax.tree_util.keystr(path):
            assert not np.asarray(g).any() and not np.asarray(r).any()


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


def _exchanged(_gated_conv):
    def gated_conv(h, p):
        d = h.shape[-1]
        bcz = h @ p["conv_in"]
        c_gate, b_gate, z = bcz[..., :d], bcz[..., d:2 * d], bcz[..., 2 * d:]
        return (c_gate * ref.short_conv(b_gate * z, p["conv_taps"])) \
            @ p["conv_out"]
    return gated_conv


def _leaking(_router_gates):
    """The bias added to the scores that become the weights."""
    def router_gates(h, router, bias, top_k, normalise, scale):
        scores = jax.nn.sigmoid(h @ router) + bias
        _, chosen = jax.lax.top_k(scores, top_k)
        gates = jnp.zeros_like(scores).at[
            jnp.arange(scores.shape[0])[:, None], chosen].set(
                jnp.take_along_axis(scores, chosen, axis=-1))
        if normalise:
            gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-6)
        return gates * scale
    return router_gates


def _no_bias(router_gates):
    def without(h, router, bias, top_k, normalise, scale):
        return router_gates(h, router, jnp.zeros_like(bias), top_k,
                            normalise, scale)
    return without


def _whole_norm(rms_norm):
    """The query/key norm over all heads at once (OLMoE's way)."""
    def norm(x, weight, eps):
        if x.ndim == 4:
            mean = jnp.mean(x * x, axis=(1, 3), keepdims=True)
            return x / jnp.sqrt(mean + eps) * weight
        return rms_norm(x, weight, eps)
    return norm


def _wrong_kv_head(_attention):
    """Query head g on KV head g % kv_heads in place of g // group."""
    def attention(h, p, n_head, n_kv, eps, rope):
        b, seq, d = h.shape
        head = d // n_head

        def split(x, count):
            return x.reshape(b, seq, count, head).transpose(0, 2, 1, 3)

        q = ref.rms_norm(split(h @ p["wq"], n_head), p["q_norm"], eps)
        k = ref.rms_norm(split(h @ p["wk"], n_kv), p["k_norm"], eps)
        v = split(h @ p["wv"], n_kv)
        cos, sin = ref.rotary_table(seq, head, rope)
        q, k = ref.rotate(q, cos, sin), ref.rotate(k, cos, sin)
        mask = jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None]
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


WRONG = {
    "the taps reversed": _with(
        short_conv=lambda f: lambda u, taps: f(u, taps[::-1])),
    "the convolution one position late": _with(
        short_conv=lambda f: lambda u, taps: ref.shifted(f(u, taps), 1)),
    "the convolution one position early (it sees the future)": _with(
        short_conv=lambda f: lambda u, taps: jnp.concatenate(
            [f(u, taps)[:, 1:], jnp.zeros_like(u[:, :1])], axis=1)),
    "the gates B and C exchanged": _with(gated_conv=_exchanged),
    "the bias leaking into the weights": _with(router_gates=_leaking),
    "no selection bias": _with(router_gates=_no_bias),
    "a top-k not normalised": _with({"norm_topk_prob": False}),
    "another routed scale": _with({"routed_scaling_factor": 2.5}),
    "the q/k norm over the whole projection": _with(rms_norm=_whole_norm),
    "query heads on the wrong KV head": _with(attention=_wrong_kv_head),
    "rotary pairs interleaved": _with(rotate=_interleaved),
    "another share of the experts": _with({"experts_first": 4}),
    "the attention layer a conv layer's place later": _with(
        {"first_layer": 2}),
}


@pytest.mark.parametrize("what", sorted(WRONG))
def test_the_tolerance_refuses(case, what, monkeypatch):
    if what == "the attention layer a conv layer's place later":
        with pytest.raises(KeyError):   # layer 2's parameters are not a conv's
            WRONG[what](case, monkeypatch)
        return
    loss_err, grad_err = errors(WRONG[what](case, monkeypatch), case["ref"])
    least = 20 if "leaking" in what else 100   # a bias of 0.05 is small
    assert grad_err > least * GRAD_REL_TOL, (what, loss_err, grad_err)


def test_the_tolerance_refuses_bf16_parameters_and_activations(case):
    unravel = case["model"].flat.unravel
    low = jax.jit(jax.value_and_grad(lambda flat, tok: ref.loss(
        jax.tree_util.tree_map(lambda p: p.astype(jnp.bfloat16),
                               unravel(flat)), tok, CONFIG)))(
        case["w"], case["tokens"])
    low = (low[0].astype(jnp.float32), low[1].astype(jnp.float32))
    _loss_err, grad_err = errors(low, case["ref"])
    assert grad_err > 100 * GRAD_REL_TOL


# -- the short convolution against a double loop ---------------------------------


def _conv_by_loops(u, taps):
    """``c[b, t, ch] = sum_j taps[j, ch] * u[b, t - (K - 1) + j, ch]``,
    positions before the sequence zero: one term at a time."""
    u, taps = np.asarray(u, np.float64), np.asarray(taps, np.float64)
    k = taps.shape[0]
    out = np.zeros_like(u)
    for t in range(u.shape[1]):
        for j in range(k):
            src = t - (k - 1) + j
            if src >= 0:
                out[:, t] += taps[j] * u[:, src]
    return out


@pytest.mark.parametrize("length", [1, 2, 3, 4, 17])
@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_short_convolution_equals_a_double_loop(side, length):
    """Forward and both gradients, at sequences shorter than, equal to
    and longer than the three taps; the program's operator and the
    reference's three shifted products alike."""
    conv = causal_depthwise_conv if side == "program" else ref.short_conv
    rs = np.random.RandomState(length)
    u = jnp.asarray(rs.randn(2, length, 5), jnp.float32)
    taps = jnp.asarray(rs.randn(3, 5), jnp.float32)
    cot = rs.randn(2, length, 5)
    np.testing.assert_allclose(conv(u, taps), _conv_by_loops(u, taps),
                               atol=1e-5)
    d_u, d_taps = jax.grad(
        lambda u, taps: jnp.sum(jnp.asarray(cot, jnp.float32)
                                * conv(u, taps)), (0, 1))(u, taps)
    # the transpose by hand: u[s] reaches c[s + (K - 1) - j] through tap j
    want_u = np.zeros(u.shape)
    want_taps = np.zeros(taps.shape)
    for t in range(length):
        for j in range(3):
            src = t - 2 + j
            if src >= 0:
                want_u[:, src] += np.asarray(taps)[j] * cot[:, t]
                want_taps[j] += np.sum(np.asarray(u)[:, src] * cot[:, t],
                                       axis=0)
    np.testing.assert_allclose(d_u, want_u, atol=1e-5)
    np.testing.assert_allclose(d_taps, want_taps, atol=1e-4)


def test_the_last_tap_is_on_the_current_position_and_nothing_later_is_seen():
    u = jnp.zeros((1, 6, 1)).at[0, 2, 0].set(1.0)
    taps = jnp.asarray([[100.0], [10.0], [1.0]])
    np.testing.assert_array_equal(
        np.asarray(causal_depthwise_conv(u, taps))[0, :, 0],
        [0, 0, 1, 10, 100, 0])


# -- the router: sigmoid scores, a selection bias, the normalisation ---------------


def test_the_bias_changes_the_selection_and_not_the_weights():
    scores = jnp.asarray([[0.9, 0.5, 0.4, 0.1]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.5])       # lifts expert 3 to 0.6
    plain_w, plain_e = moe.route_top_k(scores, 2, renormalise=True, eps=1e-6)
    w, e = moe.route_top_k(scores, 2, renormalise=True, bias=bias, eps=1e-6)
    np.testing.assert_array_equal(plain_e, [[0, 1]])
    np.testing.assert_array_equal(e, [[0, 3]])
    # the weights are the chosen scores without the bias
    np.testing.assert_allclose(w, [[0.9 / (1.0 + 1e-6), 0.1 / (1.0 + 1e-6)]],
                               rtol=1e-6)
    # and no gradient reaches the bias
    grad = jax.grad(lambda b: jnp.sum(moe.route_top_k(
        scores, 2, renormalise=True, bias=b, eps=1e-6)[0] ** 2))(bias)
    assert not np.asarray(grad).any()
    assert float(moe.bias_flips_share(scores, e)) == 0.5
    assert float(moe.bias_flips_share(scores, plain_e)) == 0.0


def test_the_normalisation_carries_its_1e_6_and_the_scale():
    tiny = jnp.asarray([[1e-6, 1e-6, 0.0, 0.0]])   # a size where it shows
    w, _ = moe.route_top_k(tiny, 2, renormalise=True, eps=1e-6, scale=2.5)
    np.testing.assert_allclose(w, [[2.5 / 3, 2.5 / 3]], rtol=1e-5)
    without, _ = moe.route_top_k(tiny, 2, renormalise=True, scale=2.5)
    np.testing.assert_allclose(without, [[1.25, 1.25]], rtol=1e-5)
    gates = ref.router_gates(jnp.ones((1, 1)), jnp.full((1, 4), -13.8155),
                             jnp.asarray([0.0, 0.0, -1.0, -1.0]), 2, True, 2.5)
    np.testing.assert_allclose(gates[0, :2], [2.5 / 3, 2.5 / 3], rtol=1e-3)


def test_ties_go_to_the_lower_index_in_the_program_and_the_reference():
    scores = jnp.full((3, 6), 0.5)
    bias = jnp.zeros(6)
    _, chosen = moe.route_top_k(scores, 2, renormalise=True, bias=bias,
                                eps=1e-6)
    np.testing.assert_array_equal(chosen, [[0, 1]] * 3)
    gates = ref.router_gates(jnp.zeros((3, 4)), jnp.zeros((4, 6)), bias, 2,
                             True, 1.0)
    np.testing.assert_allclose(gates, [[0.5, 0.5, 0, 0, 0, 0]] * 3,
                               atol=1e-6)
    # a tie made by the bias breaks the same way
    _, chosen = moe.route_top_k(jnp.asarray([[0.2, 0.5, 0.3, 0.5]]), 1,
                                bias=jnp.asarray([0.3, 0.0, 0.2, 0.0]))
    np.testing.assert_array_equal(chosen, [[0]])


def test_without_a_bias_route_top_k_is_the_call_it_was():
    """OLMoE's and Mellum's calls lower as they did: no gather, no
    epsilon, no scale in the jaxpr."""
    probs = jnp.asarray([[0.5, 0.1, 0.3, 0.1]])
    text = str(jax.make_jaxpr(
        lambda p: moe.route_top_k(p, 2, renormalise=True))(probs))
    assert "gather" not in text and "mul" not in text and "add" not in text


# -- the share of the experts --------------------------------------------------------


def _layer(rs, tokens=48, d=16, f=8, e=8):
    return dict(
        h=jnp.asarray(rs.randn(tokens, d), jnp.float32),
        router=jnp.asarray(rs.randn(d, e), jnp.float32),
        router_bias=jnp.asarray(rs.randn(e), jnp.float32) * 0.3,
        experts_gate=jnp.asarray(rs.randn(e, d, f), jnp.float32) * 0.3,
        experts_up=jnp.asarray(rs.randn(e, d, f), jnp.float32) * 0.3,
        experts_down=jnp.asarray(rs.randn(e, f, d), jnp.float32) * 0.3)


NAMES = ("experts_gate", "experts_up", "experts_down")


def _program_share(p, first, held, top_k=2, scale=1.5):
    """One chip's sparse MLP as the block runs it: a sigmoid router over
    all the experts, the biased choice, the normalised and scaled
    weights, the held experts' part."""
    e = p["router"].shape[1]
    scores = jax.nn.sigmoid(p["h"] @ p["router"])
    weights, chosen = moe.route_top_k(scores, top_k, renormalise=True,
                                      bias=p["router_bias"], eps=1e-6,
                                      scale=scale)
    cut = slice(first, first + held)
    return moe.dispatch_top_k(
        p["h"], weights, chosen, e,
        lambda rows, sizes: moe.swiglu_experts(
            rows, sizes, p["experts_gate"][cut], p["experts_up"][cut],
            p["experts_down"][cut], first if held < e else None))


@pytest.mark.parametrize("held", [1, 2, 4, 8])
def test_the_shares_partial_outputs_add_up_to_the_uncut_layer(held):
    """The guide's share test: every share's partial output of a sparse
    layer adds up to what the uncut reference gives for the whole layer
    (the router, which every chip computes alike, enters each share's
    weights and is counted once in the sum; nothing else is replicated
    inside the sparse MLP), and each share's gradients of its held
    experts are the uncut gradients' slices."""
    rs = np.random.RandomState(held)
    p = _layer(rs)
    cot = jnp.asarray(rs.randn(*p["h"].shape), jnp.float32)

    def uncut(weights):
        return jnp.sum(cot * ref.experts(
            p["h"], {**p, **dict(zip(NAMES, weights))}, 2, True, 1.5, 0))

    whole = ref.experts(p["h"], p, 2, True, 1.5, 0)
    whole_grads = jax.grad(uncut)(tuple(p[n] for n in NAMES))
    total = jnp.zeros_like(whole)
    for first in range(0, 8, held):
        def share(weights, first=first):
            return jnp.sum(cot * _program_share(
                {**p, **dict(zip(NAMES, weights))}, first, held))

        total = total + _program_share(p, first, held)
        grads = jax.grad(share)(tuple(p[n] for n in NAMES))
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
    held = {**p, **{n: p[n][cut] for n in NAMES}}
    np.testing.assert_allclose(_program_share(p, 2, 3),
                               ref.experts(p["h"], held, 2, True, 1.5, 2),
                               atol=1e-5, rtol=1e-4)


# -- the layers' kinds, from the configuration ----------------------------------------


def test_layer_kinds_and_the_parameter_count_come_from_the_configuration():
    """``layer_types`` and ``num_dense_layers`` of the file, through the
    launcher's flattened keys, give the mixers and MLPs the file lists:
    at published widths without building (shapes only), and at the tiny
    size in the built model."""
    from mpit_tpu.models.transformer import Lfm2Decoder

    here = arithmetic.layers_here(FILE)
    assert here == [("conv", True), ("full_attention", False),
                    ("conv", False), ("conv", False), ("conv", False)]
    assert FILE["layer_types_here"] == ",".join(m for m, _ in here)
    assert FILE["dense_layers_here"] == 1
    assert len(FILE["layer_types"]) == FILE["published"]["num_hidden_layers"]
    assert FILE["layer_types"].count("full_attention") == 10
    module = Lfm2Decoder(
        vocab=FILE["vocab_size"], d_model=FILE["hidden_size"],
        n_heads=FILE["num_attention_heads"],
        kv_heads=FILE["num_key_value_heads"],
        head_dim=FILE["hidden_size"] // FILE["num_attention_heads"],
        layer_types=tuple(FILE["layer_types_here"].split(",")),
        dense_layers=FILE["dense_layers_here"],
        dense_width=FILE["intermediate_size"],
        n_experts=FILE["router_experts"], experts_held=FILE["num_experts"],
        experts_per_tok=FILE["num_experts_per_tok"],
        expert_width=FILE["moe_intermediate_size"],
        conv_kernel=FILE["conv_L_cache"])
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 16), jnp.int32))["params"]
    assert sum(int(np.prod(leaf.shape)) for leaf in
               jax.tree_util.tree_leaves(shapes)) == 486_062_464 \
        == arithmetic.param_count(FILE)
    for i, (mixer, dense) in enumerate(here):
        leaves = set(shapes[f"Lfm2Block_{i}"])
        assert ("conv_taps" in leaves) == (mixer == "conv")
        assert ("wq" in leaves) == (mixer == "full_attention")
        assert ("w1" in leaves) == dense
        assert ("router_bias" in leaves) == (not dense)
    block = shapes["Lfm2Block_1"]
    assert block["wq"].shape == (2048, 32 * 64)
    assert block["wk"].shape == (2048, 8 * 64)
    assert block["q_norm"].shape == block["k_norm"].shape == (64,)
    assert shapes["Lfm2Block_0"]["w1"].shape == (2048, 11776)
    assert shapes["Lfm2Block_0"]["conv_in"].shape == (2048, 3 * 2048)
    assert shapes["Lfm2Block_2"]["experts_gate"].shape == (8, 2048, 1536)
    assert shapes["Lfm2Block_2"]["router"].shape == (2048, 64)


def test_the_built_models_vector_is_the_arithmetics_at_the_tiny_size(case):
    assert case["model"].flat.size == arithmetic.param_count(CONFIG)
    with pytest.raises(ValueError, match="layer_types"):
        build(arch="lfm2", **{**TINY, "n_layers": 4})
    with pytest.raises(ValueError, match="layer type"):
        build(arch="lfm2", **{**TINY, "layer_types":
                              "conv,attention,conv,conv,conv"})


def test_the_seeding_is_the_tables_std_8_the_taps_a_third_and_the_rest_002():
    params = build(arch="lfm2", seed=3, use_flash=False, **TINY)
    params = params.flat.unravel(params.flat.w0)
    assert float(jnp.std(params["embed"])) == pytest.approx(8.0, rel=0.05)
    assert float(jnp.std(params["head"])) == pytest.approx(0.02, rel=0.05)
    conv, attn = params["Lfm2Block_0"], params["Lfm2Block_1"]
    assert float(jnp.std(conv["conv_taps"])) == pytest.approx(1 / 3, rel=0.15)
    assert float(jnp.std(conv["conv_in"])) == pytest.approx(0.02, rel=0.05)
    assert float(jnp.std(attn["router"])) == pytest.approx(0.02, rel=0.15)
    assert np.asarray(attn["router_bias"]).any()   # seeded away from zero
    assert float(jnp.max(jnp.abs(attn["router_bias"]))) < 0.1
    np.testing.assert_array_equal(attn["q_norm"], np.ones(16))


def test_the_seeded_weights_do_not_depend_on_the_samples_length():
    """``build`` initialises on 16 positions, as Mellum's; no
    parameter's shape or value depends on the sample."""
    from mpit_tpu.models.transformer import Lfm2Decoder

    module = Lfm2Decoder(vocab=320, n_experts=8, experts_held=2,
                         experts_first=2)
    key = jax.random.PRNGKey(3)
    short = module.init(key, jnp.zeros((1, 16), jnp.int32))
    long = module.init(key, jnp.zeros((1, 64), jnp.int32))
    for a, b in zip(jax.tree_util.tree_leaves(short),
                    jax.tree_util.tree_leaves(long)):
        np.testing.assert_array_equal(a, b)


# -- donation: the local step consumes its vectors ------------------------------------


@pytest.mark.parametrize("fused", [False, True], ids=["phases", "kernel"])
def test_two_donated_steps_are_the_undonated_steps_bit_for_bit(fused):
    """``MSGD.step`` donates ``w`` and its state to the jitted step; the
    numbers are ``msgd_step``'s own.  The first call steps on a copy, so
    the model's seeded vector stays readable; what a later call is
    handed is consumed.  Against ``msgd_lookahead`` / ``msgd_commit``
    in turn the losses and ``msgd_params`` are the two phases': their
    program to the bit off the kernel's path, and on it, where the step
    hands back the point its next gradient is taken at, to rounding
    (the CPU's compiler contracts the two programs' multiply-adds
    differently; tests/test_optim_rules.py holds the arithmetic to the
    bit)."""
    from mpit_tpu.optim.msgd import (MSGD, MSGDConfig, msgd_commit,
                                     msgd_init, msgd_lookahead, msgd_params,
                                     msgd_step)

    model = build(arch="lfm2", seed=4, use_flash=False, **TINY)
    flat = model.flat
    rs = np.random.RandomState(1)
    batches = [jnp.asarray(rs.randint(0, 256, (2, TINY["seq_len"] + 1)),
                           jnp.int32) for _ in range(2)]
    cfg = MSGDConfig(lr=0.1, mom=0.9, use_fused=fused)
    plain = jax.jit(lambda w, state, tok: msgd_step(
        model.value_and_grad, w, state, cfg, tok))
    want, state = flat.w0, msgd_init(flat.w0)
    for tokens in batches:
        want, state, want_loss = plain(want, state, tokens)

    @jax.jit
    def phases(w, state, tok):
        w_la, state = msgd_lookahead(w, state, cfg)
        loss, grad = model.value_and_grad(w_la, tok)
        return (*msgd_commit(w_la, grad, state, cfg), loss)

    committed, phase_state = flat.w0, msgd_init(flat.w0)
    for tokens in batches:
        committed, phase_state, phase_loss = phases(committed, phase_state,
                                                    tokens)

    opt = MSGD(cfg, model.value_and_grad)
    w1, _ = opt.step(flat.w0, batches[0])
    w2, loss = opt.step(w1, batches[1])
    np.testing.assert_array_equal(np.asarray(w2), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(opt.state["vt"]),
                                  np.asarray(state["vt"]))
    assert float(loss) == float(want_loss)
    np.testing.assert_array_equal(np.asarray(opt.params(w2)),
                                  np.asarray(msgd_params(want, state, cfg)))
    if fused:
        np.testing.assert_allclose(float(loss), float(phase_loss), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(opt.params(w2)),
                                   np.asarray(committed), rtol=1e-5, atol=1e-6)
        assert not np.allclose(np.asarray(w2), np.asarray(committed), atol=1e-4)
    else:
        assert float(loss) == float(phase_loss)
        np.testing.assert_array_equal(np.asarray(w2), np.asarray(committed))
        assert opt.params(w2) is w2
    assert not flat.w0.is_deleted() and int(flat.w0.size) == flat.size
    assert float(jnp.sum(flat.w0)) == float(jnp.sum(flat.w0))  # readable
    assert w1.is_deleted()           # the old w is not
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(w1)


def test_the_trainers_vector_survives_the_models_own():
    """``LmTrainer.w`` starts as an alias of ``flat.w0``; after local
    steps ``flat.w0`` is still what it was (the benchmark reads it after
    warm-up) and ``tr.w`` has moved."""
    from mpit_tpu.lm import LmTrainer
    from mpit_tpu.train import launch

    cfg = launch.LAUNCH_DEFAULTS.merged(
        lm=1, lm_d_model=32, lm_heads=2, lm_layers=1, lm_seq=16, opt="msgd",
        mom=0.9, lr=0.1, lm_use_flash=0, batch=2)
    tr = LmTrainer(launch.lm_trainer_cfg(cfg))
    seeded = np.asarray(tr.model.flat.w0).copy()
    for k in range(3):
        tr.w, _ = tr.optimizer.step(tr.w, jnp.asarray(tr.stream.batch_at(k)))
    np.testing.assert_array_equal(np.asarray(tr.model.flat.w0), seeded)
    assert not np.array_equal(np.asarray(tr.w), seeded)


def test_the_trainer_evaluates_the_committed_vector(monkeypatch):
    """Between local steps on the kernel's path ``tr.w`` is the point
    the next gradient is taken at; ``eval_loss`` reads the committed
    vector out of it, and a trainer that never stepped builds no
    optimizer to ask."""
    from mpit_tpu.lm import LmTrainer
    from mpit_tpu.optim.msgd import msgd_params
    from mpit_tpu.train import launch

    cfg = launch.LAUNCH_DEFAULTS.merged(
        lm=1, lm_d_model=32, lm_heads=2, lm_layers=1, lm_seq=16, opt="msgd",
        mom=0.9, lr=0.1, lm_use_flash=0, batch=2)
    monkeypatch.setenv("MPIT_FUSED", "1")  # the chip's default, here
    tr = LmTrainer(launch.lm_trainer_cfg(cfg))
    assert tr.eval_loss() == tr.eval_loss(tr.w)
    assert "optimizer" not in tr.__dict__
    for k in range(3):
        tr.w, _ = tr.optimizer.step(tr.w, jnp.asarray(tr.stream.batch_at(k)))
    committed = msgd_params(tr.w, tr.optimizer.state, tr.optimizer.cfg)
    np.testing.assert_array_equal(np.asarray(tr.params), np.asarray(committed))
    assert tr.eval_loss() == tr.eval_loss(committed)
    assert tr.eval_loss() != tr.eval_loss(tr.w)


# -- through the launcher: a gang of three and a run of one ------------------------


LAUNCH = dict(
    lm_use_flash=0, lm_eval_every=4, seed=5, device_policy="cpu",
    **FILE["launcher"],
    **{switch: CONFIG[key] for switch, key in FILE["launcher_from"].items()})
SPARSE_LAYERS = 4


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


@pytest.fixture
def obs_on():
    obs.configure(enabled=True, reset=True)
    try:
        yield obs.get_recorder()
    finally:
        obs.configure(enabled=None, reset=True)


def _counters_on_round_spans(recorder):
    rounds = [s for s in recorder.spans if s.name == "round"]
    assert rounds
    for span in rounds:
        load = span.args["moe_load_max_over_mean"]
        share = span.args["moe_held_rows_share"]
        flips = span.args["moe_bias_flips_share"]
        compact = span.args["moe_compact_share"]
        # one entry a sparse layer: the dense layer has none
        assert len(load) == len(share) == len(flips) == len(compact) \
            == SPARSE_LAYERS
        # in one window exactly where the held run fits in it
        assert compact == [float(x <= _tiny_window(2)) for x in share]
        assert all(1.0 <= x <= 4.0 for x in load)     # 8 experts, 2 a token
        assert all(0.0 < x < 1.0 for x in share)
        assert all(0.0 < x < 1.0 for x in flips)
    reg = obs.get_registry()
    last = rounds[-1].args
    assert reg.gauge("mpit_moe_bias_flips_share",
                     layer=SPARSE_LAYERS - 1).value == \
        last["moe_bias_flips_share"][-1]
    assert reg.gauge("mpit_moe_held_rows_share", layer=0).value == \
        last["moe_held_rows_share"][0]
    assert reg.gauge("mpit_moe_load_max_over_mean", layer=0).value == \
        last["moe_load_max_over_mean"][0]
    return rounds


def test_a_three_rank_gang_learns_and_carries_the_three_counters(obs_on):
    """``--np 3 --opt adam`` through ``run_rank``: servers 0 and 2,
    worker 1, the same launcher, trainer, shell, client and servers as
    the other blocks, on threads over the in-process router."""
    from mpit_tpu.comm.local import LocalRouter
    from mpit_tpu.train import launch

    steps = 12
    cfg = launch.LAUNCH_DEFAULTS.merged(
        np=3, master_freq=2, opt="adam", lr=3e-3, batch=2, lm_steps=steps,
        **LAUNCH)
    kw = build_kw(launch.lm_trainer_cfg(cfg))
    assert kw["arch"] == "lfm2" and kw["experts_held"] == 2
    assert kw["layer_types"] == "conv,full_attention,conv,conv,conv"
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
    rounds = _counters_on_round_spans(obs_on)
    assert len(rounds) == steps
    assert worker["moe_bias_flips_share"] == \
        rounds[-1].args["moe_bias_flips_share"]


def test_a_one_rank_local_run_learns_and_carries_the_three_counters(obs_on):
    """``--np 1 --opt msgd``: the single-process path hands ``MSGD`` the
    step with the block's telemetry, and each donated step is a
    ``round`` span with the three counters while obs records."""
    from mpit_tpu.train import launch

    steps = 12
    cfg = launch.LAUNCH_DEFAULTS.merged(
        np=1, opt="msgd", mom=0.9, lr=0.3, batch=2, lm_steps=steps,
        **LAUNCH)
    result = launch.run_rank(0, 1, cfg, None)
    assert result["role"] == "local"
    history = result["history"]
    assert history[-1]["avg_loss"] < history[0]["avg_loss"] - 0.1
    rounds = _counters_on_round_spans(obs_on)
    assert len(rounds) == steps
    assert [name for name, _t in rounds[0].marks] == ["step", "telemetry"]
    assert result["moe_bias_flips_share"] == \
        rounds[-1].args["moe_bias_flips_share"]
