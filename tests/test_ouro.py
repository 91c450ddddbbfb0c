"""The looped block on the normal path (``lm/model.py``
``build(arch="ouro")``: ``models/transformer.py`` ``OuroDecoder``, a
stack of layers run several times with the same weights, sandwich norms,
a head and an exit gate at every pass, a loss of its own over them)
against its plain float32 reference, at the benchmark configuration's
``tiny`` size on seeded weights.  The reference exists once, as the
benchmark's ``chipbench/reference/ouro_plain.py`` (no code shared with
the block), and is imported from there.  Weight sharing against an
unrolled model with untied copies; one pass as the plain next-token NLL;
the exit distribution and the three counters by hand; the scanned,
recomputed program against the unrolled, unrecomputed one; the counters
on the ``round`` spans under the shells and in the single-process path;
the commit of a vector that is no whole number of lanes.

Tolerances.  On the CPU both sides multiply in full float32, so they
differ by the rounding of sums taken in another order: under 1e-6 of the
gradient's norm and of a nat as measured here.  The limits are 1e-5.
What they must refuse, each tried below on the reference itself with one
thing wrong, is wrong by 1e-3 or more."""

import functools
import json
import math
import pathlib
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.arithmetic import ouro as arithmetic
from chipbench.reference import ouro_plain as ref
from mpit_tpu import obs
from mpit_tpu.lm.model import build, build_kw
from mpit_tpu.models import transformer
from mpit_tpu.models.transformer import exit_distribution

LOSS_TOL_NATS = 1e-5
GRAD_REL_TOL = 1e-5

ROOT = pathlib.Path(__file__).resolve().parent.parent
FILE = json.loads((ROOT / "chipbench/configs/ouro-2.6b-l6.json").read_text())
CONFIG = {**FILE, **FILE["tiny"], "total_ut_steps": 3}  # the reference's keys
TINY = dict(
    vocab=CONFIG["vocab_size"], d_model=CONFIG["hidden_size"],
    n_heads=CONFIG["num_attention_heads"],
    kv_heads=CONFIG["num_key_value_heads"], head_dim=CONFIG["head_dim"],
    n_layers=CONFIG["num_hidden_layers"], seq_len=CONFIG["train_seq"],
    dense_width=CONFIG["intermediate_size"],
    loop_steps=CONFIG["total_ut_steps"],
    exit_beta=CONFIG["exit_entropy_beta"],
    rope_theta=float(CONFIG["rope_theta"]), norm_eps=CONFIG["rms_norm_eps"])
LAYER_LEAVES = 11   # four norms, four attention and three MLP matrices


def moved(model, scale=0.05):
    """The seeded weights moved off their initial values: norm weights
    off 1, the gate's weight and bias spread, so that the exit
    distribution differs by position and no pass weighs nothing."""
    rs = np.random.RandomState(0)
    return model.flat.w0 + scale * jnp.asarray(rs.randn(model.flat.size),
                                               jnp.float32)


@pytest.fixture(scope="module")
def case():
    """The tiny model at three passes, one batch, and both sides' loss
    and flat gradient."""
    model = build(arch="ouro", seed=3, use_flash=False, **TINY)
    w = moved(model)
    tokens = jnp.asarray(np.random.RandomState(1).randint(
        0, 256, (2, TINY["seq_len"] + 1)), jnp.int32)
    (sys_loss, stats), sys_grad = jax.jit(model.value_grad_stats)(w, tokens)
    ref_loss, ref_grad = ref.loss_and_grad_flat(w, model.flat.unravel,
                                                tokens, CONFIG)
    return dict(model=model, w=w, tokens=tokens, stats=stats,
                sys=(sys_loss, sys_grad), ref=(ref_loss, ref_grad))


def errors(got, want):
    (loss, grad), (ref_loss, ref_grad) = got, want
    return (abs(float(loss) - float(ref_loss)),
            float(jnp.linalg.norm(grad - ref_grad)
                  / jnp.linalg.norm(ref_grad)))


def test_loss_and_flat_gradient_equal_the_plain_references(case):
    loss_err, grad_err = errors(case["sys"], case["ref"])
    assert loss_err <= LOSS_TOL_NATS and grad_err <= GRAD_REL_TOL


def test_the_plain_loss_and_the_loss_with_statistics_are_one_number(case):
    loss, grad = jax.jit(case["model"].value_and_grad)(case["w"],
                                                       case["tokens"])
    assert float(loss) == float(case["sys"][0])
    np.testing.assert_array_equal(np.asarray(grad),
                                  np.asarray(case["sys"][1]))
    assert float(jax.jit(case["model"].loss)(case["w"], case["tokens"])) \
        == pytest.approx(float(loss), abs=1e-6)


def test_every_leaf_of_the_gradient_is_inside_the_tolerance(case):
    """No leaf hides behind the large ones: the gate's weight and bias
    and every norm of the sandwich among them."""
    unravel = case["model"].flat.unravel
    got, want = unravel(case["sys"][1]), unravel(case["ref"][1])
    scale = float(jnp.linalg.norm(case["ref"][1]))
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    # the layers; table, final norm, head, the gate's weight and bias
    assert len(flat_got) == CONFIG["num_hidden_layers"] * LAYER_LEAVES + 5
    for (path, g), r in zip(flat_got, flat_want):
        err = float(jnp.linalg.norm(g - r))
        assert float(jnp.linalg.norm(r)) > 0, jax.tree_util.keystr(path)
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


def _no_out_norms(layer):
    """Pre-norm only: the sublayers' outputs are added as they come."""
    def plain(u, p, config):
        eps = float(config["rms_norm_eps"])
        a = ref.rms(u, p["attn_norm"], eps)
        u = u + ref.attention(a, p, int(config["num_attention_heads"]),
                              int(config["head_dim"]),
                              float(config["rope_theta"]))
        b = ref.rms(u, p["mlp_norm"], eps)
        return u + (jax.nn.silu(b @ p["w_gate"]) * (b @ p["w_up"])) \
            @ p["w_down"]
    return plain


def _unnormed_carry(pass_end):
    """The next pass takes the stream before the final norm."""
    def end(u, params, targets, eps):
        _h, nll, lam = pass_end(u, params, targets, eps)
        return u, nll, lam
    return end


def _gate_without_bias(pass_end):
    def end(u, params, targets, eps):
        return pass_end(u, {**params, "loop_gate_bias": jnp.zeros(1)},
                        targets, eps)
    return end


def _interleaved(_rotate):
    def rotate(x, cos, sin):
        half = x.shape[-1] // 2
        c, s = cos[..., :half], sin[..., :half]
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * c - b * s, b * c + a * s],
                         axis=-1).reshape(x.shape)
    return rotate


def _sees_the_future(_rows):
    def rows(q, k, v, first):
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
        at = first + jnp.arange(q.shape[2]) + 1     # one key too many
        seen = jnp.arange(k.shape[2])[None, :] <= at[:, None]
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(
            jnp.where(seen, scores, -jnp.inf), axis=-1), v)
    return rows


WRONG = {
    "no norm on the sublayers' outputs": _with(layer=_no_out_norms),
    "the next pass fed the stream before the final norm": _with(
        pass_end=_unnormed_carry),
    "a gate without its bias": _with(pass_end=_gate_without_bias),
    "one pass fewer": _with({"total_ut_steps": 2}),
    "one pass more": _with({"total_ut_steps": 4}),
    "no entropy term": _with({"exit_entropy_beta": 0.0}),
    "another entropy weight": _with({"exit_entropy_beta": 0.2}),
    "rotary pairs interleaved": _with(rotate=_interleaved),
    "another rotary base": _with({"rope_theta": 10000}),
    "attention one key into the future": _with(_rows=_sees_the_future),
    "another epsilon in the norms": _with({"rms_norm_eps": 1e-2}),
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


# -- the same weights in every pass ------------------------------------------------


def test_a_shared_weights_gradient_is_the_sum_over_its_applications(case):
    """An unrolled model of ``R x L`` layers with untied copies holding
    the same values (the reference given ``R`` times the layers and told
    which copy a pass uses) gives, summed over a layer's ``R`` copies,
    the shared layer's gradient; and the once-held final norm, head and
    gate theirs as they are."""
    model, w, tokens = case["model"], case["w"], case["tokens"]
    params = model.flat.unravel(w)
    layers, steps = CONFIG["num_hidden_layers"], CONFIG["total_ut_steps"]
    untied = dict(params)
    for t in range(steps):
        for i in range(layers):
            untied[f"OuroBlock_{t * layers + i}"] = params[f"OuroBlock_{i}"]

    def unrolled(untied, tokens):
        eps = float(CONFIG["rms_norm_eps"])
        targets = tokens[:, 1:]
        h = untied["embed"][tokens[:, :-1]]
        nll, lam = [], []
        for t in range(steps):
            for i in range(layers):
                h = ref.layer(h, untied[f"OuroBlock_{t * layers + i}"],
                              CONFIG)
            h, nll_t, lam_t = ref.pass_end(h, untied, targets, eps)
            nll.append(nll_t)
            lam.append(lam_t)
        p = exit_distribution(jnp.stack(lam[:-1]))
        entropy = -jnp.sum(p * jnp.log(p), axis=0)
        return jnp.mean(jnp.sum(p * jnp.stack(nll), axis=0)
                        - CONFIG["exit_entropy_beta"] * entropy)

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(unrolled))(untied, tokens)
    assert float(loss) == pytest.approx(float(case["sys"][0]), abs=1e-5)
    got = model.flat.unravel(case["sys"][1])
    for i in range(layers):
        for name, shared in got[f"OuroBlock_{i}"].items():
            copies = [grads[f"OuroBlock_{t * layers + i}"][name]
                      for t in range(steps)]
            # every copy has a gradient of its own: the passes differ
            assert all(float(jnp.linalg.norm(c)) > 0 for c in copies)
            np.testing.assert_allclose(np.asarray(shared),
                                       np.asarray(sum(copies)),
                                       rtol=2e-4, atol=2e-6)
    for name in ("embed", "final_norm", "head", "loop_gate",
                 "loop_gate_bias"):
        np.testing.assert_allclose(np.asarray(got[name]),
                                   np.asarray(grads[name]),
                                   rtol=2e-4, atol=2e-6)


def test_one_pass_is_the_plain_next_token_nll():
    """``total_ut_steps`` 1: ``p`` = (1), no entropy term, no gate in the
    loss (its gradient is zero), and the loss is the mean NLL of one
    head over one pass of the layers."""
    kw = {**TINY, "loop_steps": 1}
    model = build(arch="ouro", seed=3, use_flash=False, **kw)
    w = moved(model)
    tokens = jnp.asarray(np.random.RandomState(2).randint(
        0, 256, (2, kw["seq_len"] + 1)), jnp.int32)
    (loss, stats), grad = jax.jit(model.value_grad_stats)(w, tokens)
    params = model.flat.unravel(w)
    with jax.default_matmul_precision("highest"):
        h = params["embed"][tokens[:, :-1]]
        for i in range(kw["n_layers"]):
            h = ref.layer(h, params[f"OuroBlock_{i}"], CONFIG)
        logp = jax.nn.log_softmax(
            ref.rms(h, params["final_norm"], CONFIG["rms_norm_eps"])
            @ params["head"])
    want = -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))
    assert float(loss) == pytest.approx(float(want), abs=1e-5)
    assert float(stats["loop_exit_step_mean"]) == 1.0
    assert float(stats["loop_exit_entropy"]) == 0.0
    assert float(stats["loop_loss_drop"]) == 0.0
    gate = model.flat.unravel(grad)
    assert not np.asarray(gate["loop_gate"]).any()
    assert not np.asarray(gate["loop_gate_bias"]).any()
    one = ref.loss_and_grad_flat(w, model.flat.unravel, tokens,
                                 {**CONFIG, "total_ut_steps": 1})
    assert errors((loss, grad), one) <= (LOSS_TOL_NATS, GRAD_REL_TOL)


# -- the exit distribution and the counters ---------------------------------------


@pytest.mark.parametrize("gates", [0, 1, 3, 7])
def test_the_exit_distribution_sums_to_one_for_random_gates(gates):
    lam = jnp.asarray(np.random.RandomState(gates).uniform(
        0.0, 1.0, (gates, 5, 9)), jnp.float32)
    p = exit_distribution(lam)
    assert p.shape == (gates + 1, 5, 9)
    assert np.asarray(p).min() >= 0.0
    np.testing.assert_allclose(np.asarray(jnp.sum(p, axis=0)), 1.0,
                               rtol=0, atol=2e-6)
    # by hand: the product of the gates passed, times the one taken
    for t in range(gates + 1):
        want = np.prod(1.0 - np.asarray(lam[:t]), axis=0)
        if t < gates:
            want = want * np.asarray(lam[t])
        np.testing.assert_allclose(np.asarray(p[t]), want, atol=1e-6)


def test_a_gate_shut_or_wide_open_leaves_no_nan():
    """``lam`` of exactly 0 or 1 gives a ``p`` of exactly 0, whose
    entropy term and its gradient are 0 and not NaN."""
    def entropy(lam):
        return jnp.sum(transformer.exit_entropy(exit_distribution(lam)))

    lam = jnp.asarray([[1.0, 0.0], [0.3, 0.0], [0.5, 1.0]])
    value, grad = jax.value_and_grad(entropy)(lam)
    assert np.isfinite(float(value)) and np.isfinite(np.asarray(grad)).all()


def test_the_counters_at_the_seed_are_their_hand_worked_values():
    """Gate weight and bias at zero: every ``lam`` is 0.5 and ``p`` is
    (0.5, 0.25, 0.125, 0.125) at four passes: the exit step 0.5 + 0.5 +
    0.375 + 0.5 = 1.875, the entropy 0.5 ln 2 + 0.25 ln 4 + 2 x 0.125
    ln 8 = 1.75 ln 2 = 1.2130, and the loss the weighted NLLs less a
    tenth of it."""
    kw = {**TINY, "loop_steps": 4}
    model = build(arch="ouro", seed=3, use_flash=False, **kw)
    params = model.flat.unravel(model.flat.w0)
    assert not np.asarray(params["loop_gate_bias"]).any()   # seeded at 0
    params["loop_gate"] = jnp.zeros_like(params["loop_gate"])
    w = jax.flatten_util.ravel_pytree(params)[0]
    tokens = jnp.asarray(np.random.RandomState(4).randint(
        0, 256, (2, kw["seq_len"] + 1)), jnp.int32)
    (loss, stats), _grad = jax.jit(model.value_grad_stats)(w, tokens)
    assert float(stats["loop_exit_step_mean"]) == pytest.approx(1.875,
                                                                abs=1e-6)
    assert float(stats["loop_exit_entropy"]) == pytest.approx(
        1.75 * math.log(2), abs=5e-6)
    assert 1.75 * math.log(2) == pytest.approx(1.2130, abs=5e-5)
    config = {**CONFIG, "total_ut_steps": 4}
    with jax.default_matmul_precision("highest"):
        nll, p = ref.passes(params, tokens[:, :-1], tokens[:, 1:], config)
    np.testing.assert_allclose(np.asarray(p[:, 0, 0]),
                               [0.5, 0.25, 0.125, 0.125], atol=1e-7)
    means = [float(jnp.mean(x)) for x in nll]
    assert float(stats["loop_loss_drop"]) == pytest.approx(
        means[0] - means[3], abs=1e-5)
    assert float(loss) == pytest.approx(
        0.5 * means[0] + 0.25 * means[1] + 0.125 * (means[2] + means[3])
        - 0.1 * 1.75 * math.log(2), abs=1e-5)


def test_the_counters_equal_the_references_distribution(case):
    """Off the seed, where ``p`` differs by position: the three counters
    against the reference's own ``nll`` and ``p``."""
    params = case["model"].flat.unravel(case["w"])
    tokens = case["tokens"]
    with jax.default_matmul_precision("highest"):
        nll, p = ref.passes(params, tokens[:, :-1], tokens[:, 1:], CONFIG)
    at = jnp.arange(1, CONFIG["total_ut_steps"] + 1)[:, None, None]
    stats = case["stats"]
    assert float(jnp.std(p[0])) > 1e-3          # the gates do differ
    assert float(stats["loop_exit_step_mean"]) == pytest.approx(
        float(jnp.mean(jnp.sum(at * p, axis=0))), abs=1e-5)
    assert float(stats["loop_exit_entropy"]) == pytest.approx(
        float(jnp.mean(-jnp.sum(p * jnp.log(p), axis=0))), abs=1e-5)
    assert float(stats["loop_loss_drop"]) == pytest.approx(
        float(jnp.mean(nll[0]) - jnp.mean(nll[-1])), abs=1e-5)
    assert 1.0 <= float(stats["loop_exit_step_mean"]) <= 3.0
    assert float(stats["loop_exit_entropy"]) <= math.log(3) + 1e-6


# -- one scanned, recomputed body -------------------------------------------------


@pytest.mark.parametrize("scan,remat,exact", [
    (False, False, False), (False, True, False), (True, False, False),
    (True, True, True)],
    ids=["unrolled_kept", "unrolled_recomputed", "scanned_kept",
         "policy_against_bare_checkpoint"])
def test_scan_and_recomputation_change_no_number(case, scan, remat, exact,
                                                 monkeypatch):
    """The program's scanned, recomputed passes against the same module
    unrolled and with every activation kept: the loss, the counters and
    the flat gradient to float32 rounding.  And against itself under the
    bare ``jax.checkpoint`` (a policy that saves no name saves nothing):
    a kept value is the one the backward pass computed again, so the
    loss and every leaf of the gradient are equal exactly."""
    model, w, tokens = case["model"], case["w"], case["tokens"]
    other = model.module.clone(scan=scan, remat=remat)
    if exact:
        monkeypatch.setattr(transformer, "OURO_KEPT", ())

    def loss(flat, tokens):
        return other.apply({"params": model.flat.unravel(flat)},
                           tokens[:, :-1], tokens[:, 1:])

    (got, stats), grad = jax.jit(jax.value_and_grad(loss, has_aux=True))(
        w, tokens)
    assert float(got) == pytest.approx(float(case["sys"][0]), abs=1e-6)
    assert errors((got, grad), case["sys"])[1] <= 1e-6
    for name, value in case["stats"].items():
        assert float(stats[name]) == pytest.approx(float(value), abs=1e-6)
    if exact:
        assert float(got) == float(case["sys"][0])
        for leaf, kept in zip(
                jax.tree_util.tree_leaves(model.flat.unravel(grad)),
                jax.tree_util.tree_leaves(
                    model.flat.unravel(case["sys"][1]))):
            np.testing.assert_array_equal(np.asarray(leaf), np.asarray(kept))


def test_the_lowered_step_holds_one_body_for_all_the_passes(case):
    """The layers' products appear once a layer in the scanned program's
    text, whatever the number of passes, and ``loop_steps`` times as
    often unrolled: what keeps the set-up's two compiles from growing
    with the passes."""
    model, w, tokens = case["model"], case["w"], case["tokens"]

    def dots(module, steps):
        module = module.clone(loop_steps=steps)
        text = jax.jit(lambda flat, tok: module.apply(
            {"params": model.flat.unravel(flat)}, tok[:, :-1],
            tok[:, 1:])[0]).lower(w, tokens).as_text()
        return text.count("dot_general")

    scanned = model.module
    assert dots(scanned, 2) == dots(scanned, 4)
    unrolled = scanned.clone(scan=False)
    assert dots(unrolled, 4) > 1.8 * dots(unrolled, 2)
    assert dots(unrolled, 2) > 1.5 * dots(scanned, 2)


def _equations(jaxpr, primitive):
    """Every equation of ``primitive`` in ``jaxpr`` and the jaxprs its
    equations hold (a scan's body, a checkpoint's, a custom rule's)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub, primitive)


@pytest.fixture(scope="module")
def flash_case(case):
    """The tiny decoder with the flash kernels (interpreted) for its
    attention, and one layer's weights."""
    model = case["model"]
    module = model.module.clone(attn_fn=transformer.default_attn(
        causal=True, use_flash=True, interpret=True))
    params = model.flat.unravel(case["w"])
    return module, params, case["tokens"]


@pytest.mark.parametrize("kept,forward_calls", [
    (None, TINY["n_layers"]), ((), 2 * TINY["n_layers"])],
    ids=["policy", "bare_checkpoint"])
def test_the_gradient_holds_one_flash_forward_a_layer(flash_case, kept,
                                                      forward_calls,
                                                      monkeypatch):
    """The gradient's jaxpr holds the forward kernel once a layer, in
    the scanned forward body: the backward body takes the kernel's
    output and row statistics as kept and does not call it again.  Under
    the bare checkpoint it holds it twice a layer."""
    from mpit_tpu.ops.flash_attention import _fa_kernel

    module, params, tokens = flash_case
    if kept is not None:
        monkeypatch.setattr(transformer, "OURO_KEPT", kept)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: module.apply(
        {"params": p}, tokens[:, :-1], tokens[:, 1:])[0]))(params)
    kernels = [eqn.params["jaxpr"].debug_info.func_name
               for eqn in _equations(jaxpr.jaxpr, "pallas_call")]
    assert kernels.count(_fa_kernel.__name__) == forward_calls
    assert len(kernels) - forward_calls >= TINY["n_layers"]   # the backward


def _saved(capsys, fn, *args):
    """What a checkpointed ``fn`` saves beside its arguments and
    constants, as ``(dtype, shape)``.  The public reading is the printed
    one: a line a residual, ``f32[2,64,64] named 'mlp_out' from ...``."""
    jax.ad_checkpoint.print_saved_residuals(fn, *args)
    lines = [line for line in capsys.readouterr().out.splitlines()
             if "from the argument" not in line
             and "from a constant" not in line]
    return [(dtype, tuple(int(n) for n in shape.split(",")))
            for dtype, shape in (re.match(r"(\w+)\[([\d,]*)\]", line).groups()
                                 for line in lines)]


def test_a_layer_and_a_pass_end_keep_the_named_values_and_nothing_wide(
        flash_case, capsys):
    """The saved residuals under the decoder's policy, beside the
    arguments: of one layer application the flash kernel's output and
    row statistics and the MLP's output, of a pass's end the head's row
    log-sum-exp; float32, and no array with ``dense_width`` or ``vocab``
    columns.  Their bytes are what ``kept_residual_bytes`` says."""
    module, params, tokens = flash_case
    block = transformer.OuroBlock(
        d_model=TINY["d_model"], n_heads=TINY["n_heads"],
        kv_heads=TINY["kv_heads"], head_dim=TINY["head_dim"],
        dense_width=TINY["dense_width"], rope_theta=TINY["rope_theta"],
        norm_eps=TINY["norm_eps"], attn_fn=module.attn_fn)
    keep = functools.partial(
        jax.checkpoint, policy=jax.checkpoint_policies
        .save_only_these_names(*transformer.OURO_KEPT))
    b, l, d = 2, TINY["seq_len"], TINY["d_model"]
    heads, hd = TINY["n_heads"], TINY["head_dim"]
    u = jnp.zeros((b, l, d), jnp.float32)
    layer = _saved(capsys, keep(lambda u, p: block.apply(
        {"params": p}, u, p, method="apply_weights")),
        u, params["OuroBlock_0"])
    assert sorted(layer) == sorted([
        ("f32", (b, heads, l, hd)), ("f32", (b, heads, l)),   # o, lse
        ("f32", (b, l, d))])                                  # m
    end = _saved(capsys, keep(lambda h, head: transformer.row_lse(h @ head)),
                 u, params["head"])
    assert end == [("f32", (b, l))]
    assert not any(shape[-1] in (TINY["dense_width"], TINY["vocab"])
                   for _, shape in layer + end)
    # a layer application: o 2 x 64 positions x 4 heads x 16, lse
    # 2 x 64 x 4, m 2 x 64 x 64; 2 layers, and the head's 2 x 64, a
    # pass; 3 passes; float32
    assert 3 * sum(4 * math.prod(shape) for _, shape in 2 * layer + end) \
        == module.kept_residual_bytes(b * l, flash=True) \
        == 4 * 3 * 2 * 64 * (2 * (64 + 4 + 64) + 1)


def test_the_heads_row_lse_is_logsumexp_with_its_gradient():
    """``row_lse``'s own backward rule, ``g exp(z - lse)``, against the
    derivative JAX takes of ``logsumexp``."""
    z = jnp.asarray(np.random.RandomState(5).randn(3, 7, 50) * 4, jnp.float32)
    g = jnp.asarray(np.random.RandomState(6).randn(3, 7), jnp.float32)
    want, pull = jax.vjp(lambda z: jax.nn.logsumexp(z, axis=-1), z)
    got, pull_own = jax.vjp(transformer.row_lse, z)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_allclose(np.asarray(pull_own(g)[0]),
                               np.asarray(pull(g)[0]), rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("arch,want", [
    # on the reference attention: m of 2 layers, 2 x 64 positions x 64,
    # and the head's 2 x 64, a pass; 3 passes; float32
    ("ouro", 4 * 3 * 2 * 64 * (2 * 64 + 1)), ("gpt2", 0)])
def test_the_kept_bytes_gauge_reads_the_hand_worked_bytes(arch, want,
                                                          obs_on):
    """``mpit_lm_kept_residual_bytes``, set where the trainer builds its
    model: the named values' bytes a step at the tiny size (the flash
    rule's two are not among them on the reference attention), 0 for a
    decoder that names none."""
    from mpit_tpu.lm.trainer import LM_DEFAULTS, LmTrainer

    assert (TINY["d_model"], TINY["seq_len"], TINY["n_layers"],
            TINY["loop_steps"]) == (64, 64, 2, 3)
    shared = {"d_model", "n_heads", "n_layers", "seq_len", "vocab"}
    sizes = {key: value for key, value in TINY.items()
             if arch == "ouro" or key in shared}
    LmTrainer(LM_DEFAULTS.merged(arch=arch, batch=2, seed=3, use_flash=0,
                                 **sizes), rank=7)
    assert obs.get_registry().gauge("mpit_lm_kept_residual_bytes",
                                    rank=7).value == want


# -- the configuration, the arithmetic and the switches -----------------------------


def test_the_built_models_vector_is_the_arithmetics_at_the_tiny_size(case):
    assert case["model"].flat.size == arithmetic.param_count(CONFIG)
    # 2 x 2048 x 64 + 2 layers x (4 x 64 x 64 + 3 x 64 x 96 + 4 x 64) + 64
    # + 64 + 1
    assert case["model"].flat.size == 262_144 + 2 * 35_072 + 129


@pytest.mark.parametrize("what,got,want", arithmetic.hand_worked(),
                         ids=[c[0] for c in arithmetic.hand_worked()])
def test_ouro_arithmetic_by_hand(what, got, want):
    assert got == want, what


def test_the_gates_bias_is_the_vectors_last_element(case):
    """Every other leaf starts on a whole lane at the published widths:
    the one odd element is the tail."""
    params = case["model"].flat.unravel(
        jnp.arange(case["model"].flat.size, dtype=jnp.float32))
    assert float(params["loop_gate_bias"][0]) == case["model"].flat.size - 1


def test_the_seeding_is_std_002_norms_one_and_the_gates_bias_zero():
    model = build(arch="ouro", seed=7, use_flash=False, **TINY)
    params = model.flat.unravel(model.flat.w0)
    block = params["OuroBlock_1"]
    for name in ("attn_norm", "attn_out_norm", "mlp_norm", "mlp_out_norm"):
        assert np.asarray(block[name] == 1.0).all()
    assert np.asarray(params["final_norm"] == 1.0).all()
    assert not np.asarray(params["loop_gate_bias"]).any()
    for leaf in (params["embed"], params["head"], block["wq"],
                 block["w_down"], params["loop_gate"]):
        assert float(jnp.std(leaf)) == pytest.approx(0.02, rel=0.25)


@pytest.mark.parametrize("bias", [0.0, -4.0])
def test_the_gates_bias_is_seeded_where_exit_bias_says(bias):
    """``exit_bias`` is the value of the vector's last element at the
    seed and nothing else of it; with the gate's weight at zero every
    ``lam`` is ``sigmoid(bias)`` and the exit step is the hand-worked
    ``sum_t t p_t`` (3.8934 at -4, where ``p`` is (0.0180, 0.0177,
    0.0173, 0.9470): the loop starts as good as whole)."""
    kw = {**TINY, "loop_steps": 4}
    plain = build(arch="ouro", seed=3, use_flash=False, **kw)
    model = build(arch="ouro", seed=3, use_flash=False, exit_bias=bias, **kw)
    assert float(model.flat.w0[-1]) == bias
    np.testing.assert_array_equal(np.asarray(model.flat.w0[:-1]),
                                  np.asarray(plain.flat.w0[:-1]))
    params = model.flat.unravel(model.flat.w0)
    params["loop_gate"] = jnp.zeros_like(params["loop_gate"])
    w = jax.flatten_util.ravel_pytree(params)[0]
    tokens = jnp.asarray(np.random.RandomState(4).randint(
        0, 256, (2, kw["seq_len"] + 1)), jnp.int32)
    (_loss, stats), _grad = jax.jit(model.value_grad_stats)(w, tokens)
    lam = 1.0 / (1.0 + math.exp(-bias))
    p = [lam, lam * (1 - lam), lam * (1 - lam) ** 2, (1 - lam) ** 3]
    assert sum(p) == pytest.approx(1.0, abs=1e-12)
    want = sum((t + 1) * p_t for t, p_t in enumerate(p))
    assert want == pytest.approx({0.0: 1.875, -4.0: 3.8934}[bias], abs=5e-5)
    assert float(stats["loop_exit_step_mean"]) == pytest.approx(want,
                                                                abs=1e-5)
    assert float(stats["loop_exit_entropy"]) == pytest.approx(
        -sum(p_t * math.log(p_t) for p_t in p), abs=1e-5)


def test_the_seeded_weights_do_not_depend_on_the_training_sequence():
    a = build(arch="ouro", seed=5, use_flash=False, **TINY)
    b = build(arch="ouro", seed=5, use_flash=False,
              **{**TINY, "seq_len": 2 * TINY["seq_len"]})
    np.testing.assert_array_equal(np.asarray(a.flat.w0),
                                  np.asarray(b.flat.w0))


def test_no_pass_at_all_is_refused():
    with pytest.raises(ValueError, match="at least one pass"):
        build(arch="ouro", use_flash=False, **{**TINY, "loop_steps": 0})


# -- through the launcher ------------------------------------------------------------

LAUNCH = dict(
    lm_use_flash=0, lm_eval_every=4, seed=5, device_policy="cpu",
    **FILE["launcher"],
    **{switch: CONFIG[key] for switch, key in FILE["launcher_from"].items()})
COUNTERS = ("loop_exit_step_mean", "loop_loss_drop", "loop_exit_entropy")


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
    steps = CONFIG["total_ut_steps"]
    for span in rounds:
        exit_step, drop, entropy = (span.args[name] for name in COUNTERS)
        assert len(exit_step) == len(drop) == len(entropy) == 1
        assert 1.0 <= exit_step[0] <= steps
        assert 0.0 <= entropy[0] <= math.log(steps) + 1e-6
        assert math.isfinite(drop[0])
    reg = obs.get_registry()
    for name in COUNTERS:
        assert reg.gauge(f"mpit_{name}", layer=0).value == \
            rounds[-1].args[name][0]
    return rounds


def test_a_three_rank_gang_learns_and_carries_the_loops_counters(obs_on):
    """``--np 3 --opt adam`` through ``run_rank``: servers 0 and 2,
    worker 1, the same launcher, trainer, shell, client and servers as
    the other blocks, on threads over the in-process router; the flat
    vector, the plan's cut and the shell take the block with no special
    case."""
    from mpit_tpu.comm.local import LocalRouter
    from mpit_tpu.train import launch

    steps = 12
    cfg = launch.LAUNCH_DEFAULTS.merged(
        np=3, master_freq=2, opt="adam", lr=3e-3, batch=2, lm_steps=steps,
        **LAUNCH)
    kw = build_kw(launch.lm_trainer_cfg(cfg))
    assert kw["arch"] == "ouro" and kw["loop_steps"] == 3
    assert kw["exit_beta"] == 0.1 and kw["dense_width"] == 96
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
    for name in COUNTERS:
        assert worker[name] == rounds[-1].args[name]


def test_a_one_rank_local_run_learns_and_carries_the_loops_counters(obs_on):
    """``--np 1 --opt msgd``: the single-process path hands ``MSGD`` the
    step with the loop's telemetry, and each donated step is a ``round``
    span with the three counters while obs records."""
    from mpit_tpu.train import launch

    steps = 12
    cfg = launch.LAUNCH_DEFAULTS.merged(
        np=1, opt="msgd", mom=0.9, lr=0.1, batch=2, lm_steps=steps,
        **LAUNCH)
    result = launch.run_rank(0, 1, cfg, None)
    assert result["role"] == "local"
    history = result["history"]
    assert history[-1]["avg_loss"] < history[0]["avg_loss"] - 0.1
    rounds = _counters_on_round_spans(obs_on)
    assert len(rounds) == steps
    assert [name for name, _t in rounds[0].marks] == ["step", "telemetry"]
    for name in COUNTERS:
        assert result[name] == rounds[-1].args[name]


# -- a vector that is no whole number of lanes ------------------------------------


@pytest.mark.parametrize("length", [128 * 300, 128 * 300 + 1,
                                    128 * 300 + 127],
                         ids=["whole_lanes", "one_over", "one_under"])
@pytest.mark.parametrize("sug", [False, True], ids=["commit", "retract"])
def test_the_commit_sweeps_a_vector_of_any_length_where_it_lies(length, sug):
    """Over a block of rows: the last block overhangs, what it reads
    past the end is dropped, and the vector keeps its length."""
    from mpit_tpu.ops.fused_update import (fused_nesterov_commit,
                                           fused_nesterov_commit_reference)

    rs = np.random.RandomState(length)
    w, vt, g, s = (jnp.asarray(rs.randn(length), jnp.float32)
                   for _ in range(4))
    kw = {"l2wd": 0.01, "sug": s if sug else None}
    got = jax.jit(lambda *a: fused_nesterov_commit(*a, 0.03, **kw))(w, vt, g)
    want = fused_nesterov_commit_reference(w, vt, g, 0.03, **kw)
    for a, b in zip(got, want):
        assert a.shape == (length,)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)


def test_two_donated_steps_on_an_odd_vector_are_the_undonated_steps():
    """The tiny model's vector is one over a whole number of lanes, as
    the cell's: two steps of ``MSGD`` (donated, the fused commit pinned,
    the next step's lookahead inside it) against the same two steps
    taken undonated by ``msgd_step``, and against ``msgd_lookahead`` /
    ``msgd_commit`` in turn, whose losses and committed vector
    ``msgd_params`` gives back (to rounding here: the CPU's compiler
    contracts the two programs' multiply-adds differently)."""
    from mpit_tpu.optim.msgd import (MSGD, MSGDConfig, msgd_commit,
                                     msgd_init, msgd_lookahead, msgd_step)

    model = build(arch="ouro", seed=3, use_flash=False, **TINY)
    assert model.flat.size % 128 == 1
    cfg = MSGDConfig(lr=0.1, mom=0.9, use_fused=True)
    batches = [jnp.asarray(np.random.RandomState(k).randint(
        0, 256, (2, TINY["seq_len"] + 1)), jnp.int32) for k in (0, 1)]
    opt = MSGD(cfg, model.value_and_grad)
    w = model.flat.w0
    for tokens in batches:
        w, loss = opt.step(w, tokens)
    plain, state = model.flat.w0, msgd_init(model.flat.w0)
    step = jax.jit(lambda w, s, t: msgd_step(model.value_and_grad, w, s,
                                             cfg, t))
    for tokens in batches:
        plain, state, _loss = step(plain, state, tokens)
    np.testing.assert_array_equal(np.asarray(w), np.asarray(plain))
    assert np.asarray(model.flat.w0).shape == (model.flat.size,)  # not donated

    @jax.jit
    def phases(w, state, tok):
        w_la, state = msgd_lookahead(w, state, cfg)
        phase_loss, grad = model.value_and_grad(w_la, tok)
        return (*msgd_commit(w_la, grad, state, cfg), phase_loss)

    committed, state = model.flat.w0, msgd_init(model.flat.w0)
    for tokens in batches:
        committed, state, phase_loss = phases(committed, state, tokens)
    np.testing.assert_allclose(float(loss), float(phase_loss), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(opt.params(w)),
                               np.asarray(committed), rtol=1e-5, atol=1e-6)
    assert not np.allclose(np.asarray(w), np.asarray(committed), atol=1e-4)
