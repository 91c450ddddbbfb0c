"""The dense state-space hybrid on the normal path (``--lm_arch
granite``: ``build(arch="granite")``: ``models/transformer.py``
``GraniteDecoder``) at the ``tiny`` size of
``chipbench/configs/granite-4.0-h-micro-l10.json``, on the CPU: the
program against its plain float32 reference
(``chipbench/reference/granite_plain.py``, which steps the state a
position at a time and shares no code with the program), each of the
four multipliers, the tied head, the gate before the norm and the column
order of ``W_in`` as a mutation the tolerance has to refuse, the scan's
kernels at ONE group with a part of the group a grid step against the
recurrence and the XLA form, and the block through the launcher, locally
and through two parameter servers.
"""

import contextlib
import functools
import json
import math
import pathlib
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.arithmetic import granite as arithmetic
from chipbench.reference import granite_plain as ref
from mpit_tpu import obs
from mpit_tpu.lm.model import build, build_kw
from mpit_tpu.models import transformer
from mpit_tpu.ops import ssd_scan

LOSS_TOL_NATS = 1e-5
GRAD_REL_TOL = 1e-5
SCAN_TOL = 2e-5

ROOT = pathlib.Path(__file__).resolve().parent.parent
FILE = json.loads(
    (ROOT / "chipbench/configs/granite-4.0-h-micro-l10.json").read_text())
CONFIG = {**FILE, **FILE["tiny"]}  # the reference's keys, at the tiny size
MULTIPLIERS = ("embedding_multiplier", "residual_multiplier",
               "attention_multiplier", "logits_scaling")


def sizes(c):
    """``build``'s keywords from the configuration's keys."""
    return dict(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        n_layers=c["num_hidden_layers"], seq_len=c["train_seq"],
        layer_types=arithmetic.layer_types(c),
        conv_kernel=c["mamba_d_conv"], ssm_heads=c["mamba_n_heads"],
        ssm_head_dim=c["mamba_d_head"], ssm_groups=c["mamba_n_groups"],
        ssm_state=c["mamba_d_state"], ssm_chunk=c["scan_chunk"],
        dense_width=c["shared_intermediate_size"],
        norm_eps=c["rms_norm_eps"], embed_scale=c["embedding_multiplier"],
        residual_scale=c["residual_multiplier"],
        attn_scale=c["attention_multiplier"],
        logits_scale=c["logits_scaling"])


TINY = sizes(CONFIG)


def moved(model, scale=0.05, seed=0):
    """The seeded weights moved off their initial values: norm weights
    and the skip off 1, so that one whose weight is ignored shows."""
    rs = np.random.RandomState(seed)
    return model.flat.w0 + scale * jnp.asarray(rs.randn(model.flat.size),
                                               jnp.float32)


def relative(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.fixture(scope="module")
def case():
    model = build(arch="granite", seed=3, use_flash=False, **TINY)
    w = moved(model)
    tokens = jax.random.randint(jax.random.PRNGKey(7),
                                (2, TINY["seq_len"] + 1), 0, 256)
    with jax.default_matmul_precision("highest"):
        (loss, stats), grad = jax.jit(model.value_grad_stats)(w, tokens)
    ref_loss, ref_grad = ref.loss_and_grad_flat(w, model.flat.unravel,
                                                tokens, CONFIG)
    return dict(model=model, w=w, tokens=tokens, loss=loss, stats=stats,
                grad=grad, ref_loss=ref_loss, ref_grad=ref_grad)


# -- (a) the scan's kernels at one group, a part of the group a grid step ---------


def mixer_inputs(length, heads, batch=1, p=64, n=128, seed=0):
    """What the mixer hands the scan, before the step's softplus and the
    rate's exp: ``x, dt, dt_bias, A_log, B, C, D`` with ONE group."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(keys[0], (batch, length, heads, p)),
            jax.random.normal(keys[1], (batch, length, heads)),
            jax.random.uniform(keys[2], (heads,), minval=-4.0, maxval=-2.0),
            jnp.log(jnp.linspace(1.0, 8.0, heads)),
            jax.random.normal(keys[3], (batch, length, 1, n)),
            jax.random.normal(keys[4], (batch, length, 1, n)),
            jax.random.normal(keys[5], (heads,)))


def through(scan):
    """The mixer's way into ``scan(x, dt, a, b, c)`` and its skip."""
    def fn(x, dt, dt_bias, a_log, b, c, d):
        step = jax.nn.softplus(dt + dt_bias)
        return scan(x, step, -jnp.exp(a_log), b, c) + d[:, None] * x
    return fn


def both(fn, args, ct):
    with jax.default_matmul_precision("highest"):
        value, back = jax.vjp(fn, *args)
        return value, back(ct)


ONE_GROUP = [
    # what, heads, the most heads a step, head blocks, length
    ("4 heads in one block", 4, 8, 1, 200),
    ("4 heads in two blocks", 4, 2, 2, 256),
    ("64 heads in eight blocks of 8", 64, 8, 8, 256),
    ("64 heads in four blocks of 16, a ragged end", 64, 16, 4, 200),
]


@pytest.mark.parametrize("what,heads,most,blocks,length", ONE_GROUP,
                         ids=[s[0] for s in ONE_GROUP])
def test_the_scan_kernels_at_one_group_are_the_recurrence_and_the_xla_form(
        what, heads, most, blocks, length, monkeypatch):
    """Forward and all seven gradients (x, dt, dt_bias, A_log, B, C, D),
    at one and at several head blocks: ``dB`` and ``dC`` are sums over a
    group's head blocks."""
    monkeypatch.setattr(ssd_scan, "HEAD_BLOCK", most)
    args = mixer_inputs(length, heads)
    x, b = args[0], args[4]
    assert ssd_scan.takes_kernels(x, b, ssd_scan.CHUNK)
    assert heads // ssd_scan.heads_a_step(x, b, most) == blocks
    ct = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def kernels(x, step, a, b, c):
        return ssd_scan.ssd_scan(x, step, a, b, c)

    text = str(jax.make_jaxpr(through(kernels))(*args))
    assert text.count("pallas_call") == 1
    assert f"grid=(1, {blocks}, {-(-length // 128)})" in text.replace(
        "grid_mapping", ""), what
    got, got_grads = both(through(kernels), args, ct)
    want, want_grads = both(through(ssd_scan.ssd_scan_reference), args, ct)
    xla, xla_grads = both(through(functools.partial(
        ssd_scan.ssd_chunked, chunk=ssd_scan.CHUNK)), args, ct)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert relative(got, want) < SCAN_TOL
    assert relative(got, xla) < SCAN_TOL
    for name, g, w, x_ in zip("x dt dt_bias a_log b c d".split(), got_grads,
                              want_grads, xla_grads):
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert relative(g, w) < 2e-4, name
        assert relative(g, x_) < 2e-4, name


def test_the_skip_rides_in_the_kernels_at_several_head_blocks(monkeypatch):
    monkeypatch.setattr(ssd_scan, "HEAD_BLOCK", 2)
    x, dt, dt_bias, a_log, b, c, d = mixer_inputs(256, 4, batch=2)
    step, rate = jax.nn.softplus(dt + dt_bias), -jnp.exp(a_log)
    got = ssd_scan.ssd_scan(x, step, rate, b, c, skip=d)
    want = ssd_scan.ssd_scan_reference(x, step, rate, b, c) + d[:, None] * x
    assert relative(got, want) < SCAN_TOL
    back = jax.grad(lambda d: jnp.sum(ssd_scan.ssd_scan(
        x, step, rate, b, c, skip=d) ** 2))(d)
    want_back = jax.grad(lambda d: jnp.sum((ssd_scan.ssd_scan_reference(
        x, step, rate, b, c) + d[:, None] * x) ** 2))(d)
    assert relative(back, want_back) < 2e-4


def test_the_state_is_carried_in_float32_and_a_lower_carry_shows(monkeypatch):
    """What the probe's second variant lowers (``probe_granite.py``: the
    state rounded to bf16 where a chunk hands it to the next), and what
    the chip's limit on the gradient cannot refuse (it adds 0.11% to a
    gradient that already reads 1.68% off): held here, where the
    kernels' products are float32 and the carry is all that is
    lowered."""
    x, dt, dt_bias, a_log, b, c, _ = mixer_inputs(512, 4)
    step, rate = jax.nn.softplus(dt + dt_bias), -jnp.exp(a_log)
    want = ssd_scan.ssd_scan_reference(x, step, rate, b, c)
    assert relative(ssd_scan.ssd_scan(x, step, rate, b, c), want) < SCAN_TOL
    kept = ssd_scan._Group.carried
    monkeypatch.setattr(
        ssd_scan._Group, "carried",
        lambda self, *args: kept(self, *args).astype(jnp.bfloat16).astype(
            jnp.float32))
    jax.clear_caches()   # the calls are jits: they keep their first trace
    try:
        low = ssd_scan.ssd_scan(x, step, rate, b, c)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert 1e-4 < relative(low, want) < 5e-2


@pytest.mark.parametrize("heads,p,groups,most,per", [
    (64, 64, 1, 8, 8),      # granite: eight blocks of the one group
    (64, 64, 8, 8, 8),      # nemotron: a group a step, as it was
    (4, 64, 1, 8, 4),       # a narrow group is whole
    (12, 64, 1, 8, 6),      # the largest part that divides it
    (6, 64, 1, 4, 2),       # ... in whole lane tiles (3 x 64 is none)
    (16, 32, 1, 8, 8),      # four heads a lane tile: whole tiles
    (14, 64, 1, 4, 2),
    (3, 128, 1, 2, 1),
])
def test_heads_a_step_is_a_part_of_the_group_in_whole_lane_tiles(
        heads, p, groups, most, per):
    x = jax.ShapeDtypeStruct((1, 128, heads, p), jnp.float32)
    b = jax.ShapeDtypeStruct((1, 128, groups, 128), jnp.float32)
    got = ssd_scan.heads_a_step(x, b, most)
    assert got == per
    assert (heads // groups) % got == 0 and got * p % 128 == 0


def test_the_published_shape_takes_the_kernels_eight_heads_a_step():
    """``(1, 4096, 64, 64)``, one group, state 128: the shapes choose the
    kernels (no flag), and a grid step holds Nemotron's eight heads."""
    x = jax.ShapeDtypeStruct((1, 4096, 64, 64), jnp.float32)
    b = jax.ShapeDtypeStruct((1, 4096, 1, 128), jnp.float32)
    assert ssd_scan.takes_kernels(x, b, FILE["scan_chunk"])
    assert ssd_scan.HEAD_BLOCK == 8
    assert ssd_scan.heads_a_step(x, b, ssd_scan.HEAD_BLOCK) == 8
    dt = jax.ShapeDtypeStruct((1, 4096, 64), jnp.float32)
    a = jax.ShapeDtypeStruct((64,), jnp.float32)
    text = str(jax.make_jaxpr(jax.grad(lambda *args: jnp.sum(
        ssd_scan.ssd_scan(*args)), argnums=(0, 1, 2, 3, 4)))(x, dt, a, b, b))
    assert text.count("pallas_call") == 3
    assert text.count("grid=(1, 8, 32)") == 3


# -- (b) the whole block against the plain reference -------------------------------


def test_loss_and_flat_gradient_equal_the_plain_references(case):
    assert abs(float(case["loss"]) - float(case["ref_loss"])) < LOSS_TOL_NATS
    assert relative(case["grad"], case["ref_grad"]) < GRAD_REL_TOL


def test_the_plain_loss_and_the_loss_with_statistics_are_one_number(case):
    model = case["model"]
    with jax.default_matmul_precision("highest"):
        loss, grad = jax.jit(model.value_and_grad)(case["w"], case["tokens"])
    assert float(loss) == float(case["loss"])
    assert relative(grad, case["grad"]) < 1e-6


def test_every_leaf_of_the_gradient_is_inside_the_tolerance(case):
    """The 2-norm of the whole could hide a small leaf that is wrong:
    the step's bias, ``A_log``, the skip, the convolution's bias."""
    unravel = case["model"].flat.unravel
    got, want = unravel(case["grad"]), unravel(case["ref_grad"])
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want) == 3 * 12 + 8 + 2
    for (path, g), w in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(w)) > 0, name
        assert relative(g, w) < 2e-4, name


def test_the_tables_gradient_is_the_look_ups_plus_the_heads(case):
    """One leaf, two uses: the reference tells them apart (a head that
    hands nothing back; a look-up that hands nothing back) and the
    program's one gradient for the table is their sum."""
    unravel, w, tokens = case["model"].flat.unravel, case["w"], case["tokens"]

    def table_grad(loss_of):
        with jax.default_matmul_precision("highest"):
            return jax.grad(loss_of)(unravel(w))["embed"]

    frozen = jax.lax.stop_gradient
    looked_up = table_grad(lambda p: ref.loss(
        p, tokens, CONFIG, head=frozen(p["embed"])))
    as_head = table_grad(lambda p: ref.loss(
        {**p, "embed": frozen(p["embed"])}, tokens, CONFIG, head=p["embed"]))
    got = unravel(case["grad"])["embed"]
    assert float(jnp.linalg.norm(looked_up)) > 0
    assert float(jnp.linalg.norm(as_head)) > 0
    # rows no token of the batch looks up move by the head alone
    unseen = np.setdiff1d(np.arange(CONFIG["vocab_size"]),
                          np.asarray(tokens[:, :-1]))
    assert not np.asarray(looked_up)[unseen].any()
    assert np.asarray(got)[unseen].any()
    assert relative(got, looked_up + as_head) < 2e-5
    assert relative(got, looked_up) > 0.1 and relative(got, as_head) > 0.1


def _wrong(case, monkeypatch, **replaced):
    for name, fn in replaced.items():
        monkeypatch.setattr(ref, name, fn)
    return ref.loss_and_grad_flat(case["w"], case["model"].flat.unravel,
                                  case["tokens"], CONFIG)


def _norm_before_gate(h, p, config):
    """The mixer with the RMSNorm BEFORE the gate (Qwen3-Next's order)."""
    heads, width = config["mamba_n_heads"], config["mamba_d_head"]
    n = config["mamba_n_groups"] * config["mamba_d_state"]
    batch, seq, _ = h.shape
    inner = heads * width
    projected = h @ p["w_in"]
    z = projected[..., :inner]
    xbc = ref.conv_silu(projected[..., inner:2 * inner + 2 * n],
                        p["conv_w"], p["conv_b"])
    x = xbc[..., :inner].reshape(batch, seq, heads, width)
    b = xbc[..., inner:inner + n].reshape(batch, seq, 1, n)
    c = xbc[..., inner + n:].reshape(batch, seq, 1, n)
    step = jax.nn.softplus(projected[..., 2 * inner + 2 * n:] + p["dt_bias"])
    y = (_RECURRENCE(x, step, -jnp.exp(p["a_log"]), b, c)
         + p["d_skip"][:, None] * x).reshape(batch, seq, inner)
    y = ref.rms_norm(y, p["ssm_norm"], config["rms_norm_eps"])
    return (y * jax.nn.silu(z)) @ p["w_out"]


def _columns_in_another_order(h, p, config):
    """``W_in``'s columns read as ``[xBC | z | dt]``."""
    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    mixed = inner + 2 * config["mamba_n_groups"] * config["mamba_d_state"]
    w = p["w_in"]
    swapped = jnp.concatenate(
        [w[:, mixed:mixed + inner], w[:, :mixed], w[:, inner + mixed:]], 1)
    return _MAMBA(h, {**p, "w_in": swapped}, config)


def _no_skip(h, p, config):
    return _MAMBA(h, {**p, "d_skip": jnp.zeros_like(p["d_skip"])}, config)


def _reversed_conv(u, taps, bias):
    return _CONV(u, taps[::-1], bias)


def _mlp_halves_swapped(h, p, config):
    width = config["shared_intermediate_size"]
    w = p["mlp_in"]
    return _MLP(h, {**p, "mlp_in": jnp.concatenate(
        [w[:, width:], w[:, :width]], 1)}, config)


def _rotated_attention(h, p, config):
    pos = jnp.arange(h.shape[1], dtype=jnp.float32)[None, :, None]
    return _ATTENTION(h * jnp.cos(0.05 * pos), p, config)


def _untied(params, tokens, config, head=None):
    """The head a leaf of its own that hands the table nothing back."""
    return _LOSS(params, tokens, config,
                 head=jax.lax.stop_gradient(params["embed"]))


_MAMBA, _CONV, _RECURRENCE, _ATTENTION, _MLP, _LOSS = (
    ref.mamba, ref.conv_silu, ref.recurrence, ref.attention, ref.gated_mlp,
    ref.loss)
WRONG = {
    "the norm before the gate": dict(mamba=_norm_before_gate),
    "W_in's columns as xBC, z, dt": dict(mamba=_columns_in_another_order),
    "the skip D x left out": dict(mamba=_no_skip),
    "the convolution's taps reversed": dict(conv_silu=_reversed_conv),
    "the MLP's gate and value halves swapped": dict(
        gated_mlp=_mlp_halves_swapped),
    "a positional term in the attention": dict(attention=_rotated_attention),
    "the head untied": dict(loss=_untied),
}


@pytest.mark.parametrize("what", sorted(WRONG))
def test_the_tolerance_refuses(case, what, monkeypatch):
    loss, grad = _wrong(case, monkeypatch, **WRONG[what])
    assert (abs(float(case["loss"]) - float(loss)) > LOSS_TOL_NATS
            or relative(case["grad"], grad) > GRAD_REL_TOL), what


@pytest.mark.parametrize("key,value", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("attention_multiplier", 0.25), ("logits_scaling", 1.0),
    ("embedding_multiplier", 12), ("residual_multiplier", 0.22),
    ("attention_multiplier", 0.015625), ("logits_scaling", 8),
    ("rms_norm_eps", 1e-2)])
def test_the_tolerance_refuses_another_configuration(case, key, value):
    """Each multiplier as 1 (the attention's as ``1 / sqrt(head)``, what
    a block without the key uses), and as the published value where the
    tiny size has another."""
    assert CONFIG[key] != value
    loss, grad = ref.loss_and_grad_flat(
        case["w"], case["model"].flat.unravel, case["tokens"],
        {**CONFIG, key: value})
    assert (abs(float(case["loss"]) - float(loss)) > LOSS_TOL_NATS
            or relative(case["grad"], grad) > GRAD_REL_TOL), key


def test_the_tiny_sizes_have_every_multiplier_off_one_and_off_the_default():
    assert all(CONFIG[key] != 1 for key in MULTIPLIERS)
    head = CONFIG["hidden_size"] // CONFIG["num_attention_heads"]
    assert CONFIG["attention_multiplier"] != 1 / math.sqrt(head)
    assert [FILE[key] for key in MULTIPLIERS] == [12, 0.22, 0.015625, 8]
    assert FILE["tie_word_embeddings"] is True
    assert FILE["mamba_n_groups"] == CONFIG["mamba_n_groups"] == 1


def test_the_attentions_scale_is_the_multiplier_not_the_heads_root():
    """``attn_scale`` 0 is ``1 / sqrt(head_dim)``; the multiplier takes
    its place and does not multiply it."""
    base = {**TINY, "n_layers": 1, "layer_types": "attention"}
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 81), 0, 256)
    head = TINY["d_model"] // TINY["n_heads"]
    losses = []
    for scale in (0.0, 1 / math.sqrt(head), 0.0625):
        model = build(arch="granite", seed=3, use_flash=False,
                      **{**base, "attn_scale": scale})
        losses.append(float(model.loss(moved(model, 0.3), tokens)))
    assert losses[0] == pytest.approx(losses[1], abs=1e-6)
    assert abs(losses[2] - losses[0]) > 1e-5


# -- (c) the configuration, its arithmetic and the seeding -----------------------


def test_the_cuts_layer_types_are_the_published_first_ten():
    published = FILE["published"]["layer_types"]
    assert len(published) == FILE["published"]["num_hidden_layers"] == 40
    assert FILE["layer_types"] == published[:10] == (
        ["mamba"] * 5 + ["attention"] + ["mamba"] * 4)
    assert published.count("attention") == 4
    assert FILE["reduced"] == ["num_hidden_layers", "layer_types",
                               "vocab_size"]
    assert FILE["published"]["vocab_size"] == 8 * FILE["vocab_size"]
    assert FILE["vocab_size"] % 128 == 0


def test_the_built_models_vector_is_the_arithmetics_at_the_tiny_size(case):
    assert case["model"].flat.size == arithmetic.param_count(CONFIG)
    assert arithmetic.param_count(FILE) == 772_160_448


@pytest.mark.parametrize("what,got,want", arithmetic.hand_worked(),
                         ids=[w for w, _, _ in arithmetic.hand_worked()])
def test_granite_arithmetic_by_hand(what, got, want):
    assert got == want, what


def test_the_published_sizes_give_the_issues_vector():
    """The decoder's shapes at the published widths, without a weight:
    772,160,448 elements, the tied table once."""
    published = sizes(FILE)
    module = transformer.GraniteDecoder(**{
        **{k: v for k, v in published.items()
           if k not in ("n_layers", "seq_len", "layer_types")},
        "head_dim": 64, "layer_types": tuple(FILE["layer_types"])})
    sample = jnp.zeros((1, 16), jnp.int32)
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0), sample,
                            sample)["params"]
    count = sum(math.prod(leaf.shape)
                for leaf in jax.tree_util.tree_leaves(shapes))
    assert count == 772_160_448
    assert shapes["embed"].shape == (12544, 2048) and "head" not in shapes
    assert shapes["GraniteBlock_0"]["w_in"].shape == (2048, 8512)
    assert shapes["GraniteBlock_0"]["mlp_in"].shape == (2048, 16384)
    assert shapes["GraniteBlock_5"]["wk"].shape == (2048, 512)


def test_the_seeding_of_the_mixer_the_table_and_everything_else(case):
    params = case["model"].flat.unravel(case["model"].flat.w0)
    heads = TINY["ssm_heads"]
    first = params["GraniteBlock_0"]
    np.testing.assert_allclose(first["a_log"],
                               np.log(np.arange(1, heads + 1)), rtol=1e-6)
    step = np.log1p(np.exp(np.asarray(first["dt_bias"])))
    assert np.all(step >= 0.001 * 0.999) and np.all(step <= 0.1 * 1.001)
    for name in ("d_skip", "ssm_norm", "norm", "mlp_norm"):
        assert np.all(np.asarray(first[name]) == 1.0), name
    # no depth rescale, and the table at the std of every other matrix
    for name in ("w_in", "w_out", "mlp_in", "mlp_out"):
        assert np.std(np.asarray(first[name])) == pytest.approx(
            0.02, rel=0.1), name
    assert np.std(np.asarray(params["GraniteBlock_2"]["wo"])) == \
        pytest.approx(0.02, rel=0.1)
    assert np.std(np.asarray(params["embed"])) == pytest.approx(0.02,
                                                                rel=0.05)
    assert np.std(np.asarray(first["conv_w"])) == pytest.approx(1 / 3,
                                                                rel=0.2)
    assert 0.05 < float(case["stats"]["lm_ssm_decay_mean"][0]) < 0.999
    assert case["stats"]["lm_ssm_decay_mean"].shape == (3,)
    assert case["stats"][transformer.STREAM_RMS].shape == (1,)


def test_the_streams_rms_is_what_the_embedding_multiplier_sets():
    """At the seeded weights the stream enters at ``e x 0.02`` and the
    branches, times ``r``, add to it."""
    model = build(arch="granite", seed=3, use_flash=False, **TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 81), 0, 256)
    (_, stats), _ = model.value_grad_stats(model.flat.w0, tokens)
    rms = float(stats[transformer.STREAM_RMS][0])
    entered = CONFIG["embedding_multiplier"] * 0.02
    assert entered < rms < 3 * entered


def test_the_seeded_weights_do_not_depend_on_the_training_sequence():
    short = build(arch="granite", seed=3, use_flash=False,
                  **{**TINY, "seq_len": 32})
    model = build(arch="granite", seed=3, use_flash=False, **TINY)
    assert np.array_equal(np.asarray(short.flat.w0),
                          np.asarray(model.flat.w0))


@pytest.mark.parametrize("bad", [
    {"layer_types": "mamba,attention"}, {"ssm_groups": 3}, {"ssm_heads": 0},
    {"layer_types": "mamba,moe,attention,mamba"}, {"dense_width": 0},
    {"residual_scale": 0.0}, {"logits_scale": -1.0}, {"attn_scale": -0.5},
    {"rope_theta": 10000.0}, {"n_experts": 8}])
def test_sizes_that_make_no_block_are_refused(bad):
    with pytest.raises((ValueError, TypeError)):
        build(arch="granite", seed=3, use_flash=False, **{**TINY, **bad})


def test_the_steps_operations_carry_the_blocks_scopes(case):
    model = case["model"]
    text = jax.jit(model.value_and_grad).lower(
        case["w"], case["tokens"]).as_text(debug_info=True)
    assert FILE["scopes"] == ["embed", "ssm_proj", "ssm_conv", "ssd_scan",
                              "ssm_norm", "attn", "mlp", "head_loss",
                              "update"]
    for scope in FILE["scopes"]:
        if scope != "update":   # the optimizer's, not the model's
            assert f"/{scope}/" in text, scope


def test_each_sublayer_keeps_what_the_memory_plan_says():
    """A ``mamba`` layer keeps, beside the layer's input and its
    parameters, the scan's result and the stream between its two
    sublayers; the MLP branch keeps its input alone: ``h W_a`` and ``h
    W_b`` are made again."""
    from jax._src.ad_checkpoint import saved_residuals

    block = transformer.GraniteBlock(
        d_model=32, mixer="mamba", n_heads=2, kv_heads=1, head_dim=16,
        ssm_heads=4, ssm_head_dim=8, ssm_groups=1, ssm_state=8,
        dense_width=48, ssm_chunk=16, residual_scale=0.5)
    x = jnp.zeros((2, 48, 32))
    params = block.init(jax.random.PRNGKey(0), x)["params"]
    kept = saved_residuals(
        lambda x, p: block.apply({"params": p}, x)[0], x, params)
    made = sorted(shape.shape for shape, why in kept
                  if "argument" not in why and shape.shape)
    # the scan's result (T x heads x head_dim = 32) and the stream after
    # the mixer (the multiplier is a scalar, left out above); nothing 2 x
    # 48 wide (h W_a | h W_b) and nothing W_in's 100 wide
    assert made == [(2, 48, 32), (2, 48, 32)]


# -- (d) the launcher: locally and through the servers ---------------------------

LAUNCH = dict(
    lm_use_flash=0, seed=5, device_policy="cpu", **FILE["launcher"],
    **{switch: CONFIG[key] for switch, key in FILE["launcher_from"].items()})
DECAY = transformer.SSM_DECAY_MEAN
RMS = transformer.STREAM_RMS


@pytest.fixture
def obs_on():
    obs.configure(enabled=True, reset=True)
    try:
        yield obs.get_recorder()
    finally:
        obs.configure(enabled=None, reset=True)


def test_the_launcher_builds_the_block_from_the_configurations_file():
    from mpit_tpu.train import launch

    cfg = launch.LAUNCH_DEFAULTS.merged(np=1, opt="msgd", **LAUNCH)
    kw = build_kw(launch.lm_trainer_cfg(cfg))
    assert {key: kw[key] for key in TINY} == TINY
    assert kw["arch"] == "granite"
    assert kw["head_dim"] == 0      # the row gives none: d_model / n_heads


def test_a_one_rank_local_run_learns_and_carries_its_statistics(obs_on):
    """``--np 1 --opt msgd``: the single-process path hands ``MSGD`` the
    step with the block's telemetry, and each donated step is a
    ``round`` span with the decay's mean a Mamba layer and the stream's
    rms while obs records."""
    from mpit_tpu.train import launch

    steps = 12
    cfg = launch.LAUNCH_DEFAULTS.merged(
        np=1, opt="msgd", mom=0.9, lr=0.3, batch=2, lm_steps=steps,
        lm_eval_every=4, **LAUNCH)
    result = launch.run_rank(0, 1, cfg, None)
    assert result["role"] == "local"
    history = result["history"]
    # the tied table at std 0.02 under logits_scaling starts the logits
    # near uniform: the first steps learn slowly, at any size
    assert history[-1]["avg_loss"] < history[0]["avg_loss"] - 0.05
    rounds = [s for s in obs_on.spans if s.name == "round"]
    assert len(rounds) == steps
    for span in rounds:
        assert len(span.args[DECAY]) == 3
        assert all(0.05 < x < 0.999 for x in span.args[DECAY])
        assert len(span.args[RMS]) == 1 and span.args[RMS][0] > 0
    assert obs.get_registry().gauge(f"mpit_{RMS}", layer=0).value == \
        rounds[-1].args[RMS][0]
    for name in (DECAY, RMS):
        assert result[name] == rounds[-1].args[name]


@contextlib.contextmanager
def gang(layout, rule, **hyper):
    """Two servers on threads and one client over the in-process
    router, the vector cut by ``layout``."""
    from mpit_tpu.comm.local import LocalRouter
    from mpit_tpu.optim import rules
    from mpit_tpu.ps.client import ParamClient
    from mpit_tpu.ps.server import ParamServer

    nservers = len(layout)
    router = LocalRouter(nservers + 1)
    sranks, crank = list(range(nservers)), nservers
    servers = [ParamServer(r, [crank], router.endpoint(r),
                           rule=rules.make(rule, **hyper)) for r in sranks]
    threads = [threading.Thread(target=s.start, daemon=True) for s in servers]
    for t in threads:
        t.start()
    client = ParamClient(crank, sranks, router.endpoint(crank),
                         seed_servers=True, layout=layout)
    try:
        yield servers, client
    finally:
        for s in servers:
            s.live.stop()
        for t in threads:
            t.join(5)


def test_four_rounds_through_two_servers_are_the_local_sgd_steps(case):
    """One worker and two host servers under ``--opt downpour`` at ``su``
    1: every round ships ``-lr g`` and pulls, so the worker's vector is
    plain SGD's and its losses are the local ``--opt sgd`` run's (the
    rule says so at one worker and ``su`` 1; momentum and a longer
    ``su`` part company), and the tied table, one leaf cut across the
    servers like any other, moves."""
    from mpit_tpu.lm import LmTrainer
    from mpit_tpu.lm.plan import plan
    from mpit_tpu.train import launch

    steps = 4
    common = dict(lr=0.05, batch=2, lm_steps=steps, lm_eval_every=1, mom=0.0,
                  **LAUNCH)
    # the same rank's stream of sequences on both sides
    local = LmTrainer(launch.lm_trainer_cfg(launch.LAUNCH_DEFAULTS.merged(
        np=1, opt="sgd", **common)), rank=2).run()
    model = case["model"]
    layout = plan(model.flat.unravel(model.flat.w0), 2, rule="add").layout
    cfg = launch.lm_trainer_cfg(launch.LAUNCH_DEFAULTS.merged(
        np=3, opt="downpour", su=1, **common))
    with gang(layout, "add") as (servers, client):
        trainer = LmTrainer(cfg, pclient=client, rank=2)
        w0 = np.asarray(trainer.w)
        result = trainer.run()
        master = np.concatenate([np.asarray(s.param) for s in servers])
    assert result["steps"] == steps
    served = [h["avg_loss"] for h in result["history"]]
    alone = [h["avg_loss"] for h in local["history"]]
    assert len(served) == len(alone) == steps
    np.testing.assert_allclose(served, alone, rtol=0, atol=2e-5)
    assert served[-1] < served[0]
    seeded = model.flat.unravel(jnp.asarray(w0))
    after = model.flat.unravel(jnp.asarray(master))
    assert not np.array_equal(np.asarray(seeded["embed"]),
                              np.asarray(after["embed"]))
    for name in ("a_log", "d_skip", "conv_w", "mlp_in"):
        assert not np.array_equal(
            np.asarray(seeded["GraniteBlock_0"][name]),
            np.asarray(after["GraniteBlock_0"][name])), name


def test_a_three_rank_gang_learns_and_carries_the_statistics(obs_on):
    """``--np 3 --opt adam`` through ``run_rank``: servers 0 and 2,
    worker 1, the same launcher, trainer, shell, client and servers as
    the other blocks, on threads over the in-process router."""
    from mpit_tpu.comm.local import LocalRouter
    from mpit_tpu.train import launch

    steps = 10
    cfg = launch.LAUNCH_DEFAULTS.merged(
        np=3, master_freq=2, opt="adam", lr=3e-3, batch=2, lm_steps=steps,
        lm_eval_every=4, **LAUNCH)
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
    assert history[-1]["avg_loss"] < history[0]["avg_loss"] - 0.05
    rounds = [s for s in obs_on.spans if s.name == "round"]
    assert len(rounds) == steps
    assert all(len(r.args[DECAY]) == 3 and len(r.args[RMS]) == 1
               for r in rounds)
    assert worker[DECAY] == rounds[-1].args[DECAY]
