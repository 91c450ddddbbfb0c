"""The gated-delta hybrid on the normal path (``--lm_arch qwen3next``:
``build(arch="qwen3next")``: ``models/transformer.py``
``Qwen3NextDecoder``) at the ``tiny`` size of
``chipbench/configs/qwen3-next-80b-l4e32.json``, on the CPU: the program
against its plain float32 reference
(``chipbench/reference/qwen3next_plain.py``, which steps the state a
position at a time at the key heads' own count and shares no code with
the program), the scan's entry ``ops/delta_rule.py`` ``gdn_scan``
against the recurrence and against ``kda_scan`` on the broadcast decay,
the partial rotation, the offset norms, the norm-then-gate head, the
shared expert's gate, the share test the model-configs guide asks for,
and the block through the launcher, locally and through two parameter
servers.
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

from chipbench.arithmetic import qwen3next as arithmetic
from chipbench.reference import qwen3next_plain as ref
from mpit_tpu import obs
from mpit_tpu.lm.model import build, build_kw
from mpit_tpu.models import transformer
from mpit_tpu.ops import delta_rule
from mpit_tpu.parallel import moe

LOSS_TOL_NATS = 1e-5
GRAD_REL_TOL = 1e-5

ROOT = pathlib.Path(__file__).resolve().parent.parent
FILE = json.loads(
    (ROOT / "chipbench/configs/qwen3-next-80b-l4e32.json").read_text())
CONFIG = {**FILE, **FILE["tiny"]}  # the reference's keys, at the tiny size


def sizes(c):
    """``build``'s keywords from the configuration's keys."""
    return dict(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], n_layers=c["num_hidden_layers"],
        seq_len=c["train_seq"], layer_types=arithmetic.layer_types(c),
        conv_kernel=c["linear_conv_kernel_dim"],
        gdn_key_heads=c["linear_num_key_heads"],
        gdn_value_heads=c["linear_num_value_heads"],
        gdn_key_dim=c["linear_key_head_dim"],
        gdn_value_dim=c["linear_value_head_dim"],
        rotary_factor=c["partial_rotary_factor"],
        n_experts=c["router_experts"], experts_held=c["num_experts"],
        experts_first=c["experts_first"],
        experts_per_tok=c["num_experts_per_tok"],
        expert_width=c["moe_intermediate_size"],
        shared_width=c["shared_expert_intermediate_size"],
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"])


TINY = sizes(CONFIG)
BLOCK = ("d_model", "n_heads", "kv_heads", "head_dim", "gdn_key_heads",
         "gdn_value_heads", "gdn_key_dim", "gdn_value_dim", "n_experts",
         "experts_per_tok", "expert_width", "shared_width", "rope_theta",
         "norm_eps")


def moved(model, scale=0.05, seed=0):
    """The seeded weights moved off their initial values: the offset
    norms' weights off 0 and the head norm's off 1, so that one whose
    weight is ignored, or taken plain where it is an offset, shows."""
    rs = np.random.RandomState(seed)
    return model.flat.w0 + scale * jnp.asarray(rs.randn(model.flat.size),
                                               jnp.float32)


def relative(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.fixture(scope="module")
def case():
    model = build(arch="qwen3next", seed=3, use_flash=False, **TINY)
    w = moved(model)
    tokens = jax.random.randint(jax.random.PRNGKey(7),
                                (2, TINY["seq_len"] + 1), 0, 256)
    with jax.default_matmul_precision("highest"):
        (loss, stats), grad = jax.jit(model.value_grad_stats)(w, tokens)
    ref_loss, ref_grad = ref.loss_and_grad_flat(w, model.flat.unravel,
                                                tokens, CONFIG)
    return dict(model=model, w=w, tokens=tokens, loss=loss, stats=stats,
                grad=grad, ref_loss=ref_loss, ref_grad=ref_grad)


# -- (a) the scan's entry against the recurrence ----------------------------------


def scan_inputs(length, lo, hi, hk=2, hv=4, dk=16, dv=8, batch=2, seed=0):
    """q, k, v, g, beta with the decay ``exp(g)`` between ``lo`` and
    ``hi``, queries and keys of unit length as the block makes them."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(keys[0], (batch, length, hk, dk))) / dk ** 0.5
    k = unit(jax.random.normal(keys[1], (batch, length, hk, dk)))
    v = jax.random.normal(keys[2], (batch, length, hv, dv))
    g = jnp.log(jax.random.uniform(keys[3], (batch, length, hv),
                                   minval=lo, maxval=hi))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (batch, length, hv)))
    return q, k, v, g, beta


def both(fn, args, ct):
    """``fn``'s value and its five gradients under the cotangent."""
    with jax.default_matmul_precision("highest"):
        value, back = jax.vjp(fn, *args)
        return value, back(ct)


# Near 0 the summed log-decays of a chunk are in the hundreds and a
# pair's decay the ``exp`` of the difference of two of them: float32
# carries that to 3e-5 of the pair's weight.
SCANS = [
    ("two value heads a key head, whole chunks", 128, 2, 4, 0.5, 1.0, 2e-5),
    ("two value heads a key head, a ragged end", 150, 2, 4, 0.5, 1.0, 2e-5),
    ("as many value heads as key heads", 128, 2, 2, 0.5, 1.0, 2e-5),
    ("as many value heads, shorter than a chunk", 37, 3, 3, 0.5, 1.0, 2e-5),
    ("four value heads a key head", 80, 1, 4, 0.5, 1.0, 2e-5),
    ("a decay near 0", 128, 2, 4, 0.002, 0.05, 2e-4),
    ("a decay near 1", 150, 2, 4, 0.995, 0.99999, 2e-5),
]


# narrow heads take the XLA form, heads of whole lanes the scalar rule's
# three Mosaic kernels (interpreted here): both are held to the recurrence
WIDTHS = {"narrow": dict(dk=16, dv=8), "whole_lanes": dict(dk=128, dv=128)}
widths = pytest.mark.parametrize("width", sorted(WIDTHS))


@widths
@pytest.mark.parametrize("what,length,hk,hv,lo,hi,tol", SCANS,
                         ids=[s[0] for s in SCANS])
def test_the_chunked_entry_is_the_recurrence_forward_and_backward(
        what, length, hk, hv, lo, hi, tol, width):
    args = scan_inputs(length, lo, hi, hk=hk, hv=hv, **WIDTHS[width])
    ct = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    got, got_grads = both(delta_rule.gdn_scan, args, ct)
    want, want_grads = both(delta_rule.gdn_scan_reference, args, ct)
    assert got.shape == args[2].shape
    assert relative(got, want) < tol, what
    for name, g, w in zip("q k v g beta".split(), got_grads, want_grads):
        assert g.shape == w.shape, name      # at the operands' own shapes
        assert relative(g, w) < 10 * tol, (what, name)


@widths
@pytest.mark.parametrize("hk,hv", [(2, 4), (2, 2)])
@pytest.mark.parametrize("length", [128, 100])
def test_the_entry_agrees_with_the_channel_wise_scan_on_the_broadcast_decay(
        hk, hv, length, width):
    """A scalar decay a head broadcast over the keys' channels, the keys
    repeated for their value heads, is exact: ``kda_scan`` on them and
    ``gdn_scan`` agree to rounding, forward and in the gradients summed
    back to the operands' shapes.  At whole lanes the two are kernels of
    their own: the channel-wise ones' halving against one product under
    a decay matrix."""
    args = scan_inputs(length, 0.3, 1.0, hk=hk, hv=hv, **WIDTHS[width])
    ct = jax.random.normal(jax.random.PRNGKey(3), args[2].shape)

    def broadcast(q, k, v, g, beta):
        q, k = (jnp.repeat(x, hv // hk, axis=2) for x in (q, k))
        return delta_rule.kda_scan(
            q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta)

    got, got_grads = both(delta_rule.gdn_scan, args, ct)
    want, want_grads = both(broadcast, args, ct)
    assert relative(got, want) < 1e-5
    for g, w in zip(got_grads, want_grads):
        assert relative(g, w) < 1e-4


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr and of the jaxprs its
    equations close."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_calls(sub)
    return found


def test_whole_lane_head_widths_take_the_kernels_at_the_operands_own_widths():
    """Head widths of whole lanes run the scalar rule's three Mosaic
    kernels (interpreted here), one forward and two in the rule, and no
    array of a call is wider than the entry's own argument: q and k at
    the key heads, the log-decay and ``beta`` a float a value head; no
    key repeated, no decay broadcast.  Keys of 128 under values of 256,
    so that a width says whose it is."""
    hk, hv, dk, dv, length = 1, 2, 128, 256, 128
    args = scan_inputs(length, 0.5, 1.0, hk=hk, hv=hv, dk=dk, dv=dv, batch=1)
    ct = jax.random.normal(jax.random.PRNGKey(4), args[2].shape)
    own = {hk * dk, hv * dv, hv}

    def rows(avals):     # the arrays with a position a row
        return [a.shape for a in avals
                if len(a.shape) == 3 and a.shape[1] == length]

    forward = _pallas_calls(jax.make_jaxpr(delta_rule.gdn_scan)(*args).jaxpr)
    assert len(forward) == 1
    assert sorted(rows(v.aval for v in forward[0].invars)) == sorted(
        [(1, length, hk * dk)] * 2 + [(1, length, hv * dv)]
        + [(1, length, hv)] * 2)
    rule = _pallas_calls(jax.make_jaxpr(
        lambda *a: jax.vjp(delta_rule.gdn_scan, *a)[1](ct))(*args).jaxpr)
    assert len(rule) == 3               # the forward, it again, the walk back
    for call in rule:
        for shape in rows(v.aval for v in call.invars + call.outvars):
            assert shape[-1] in own, shape
    narrow = scan_inputs(128, 0.5, 1.0)
    assert not _pallas_calls(
        jax.make_jaxpr(delta_rule.gdn_scan)(*narrow).jaxpr)
    got, got_grads = both(delta_rule.gdn_scan, args, ct)
    want, want_grads = both(delta_rule.gdn_scan_reference, args, ct)
    assert relative(got, want) < 2e-5
    for g, w in zip(got_grads, want_grads):
        assert g.shape == w.shape
        assert relative(g, w) < 2e-4


@pytest.mark.parametrize("hk,hv,most,held", [
    (16, 32, 8, 8), (16, 32, 4, 4), (16, 16, 8, 8), (2, 8, 8, 8),
    (3, 12, 8, 4), (1, 16, 8, 16), (6, 6, 8, 6), (5, 10, 8, 2)])
def test_a_grid_step_holds_whole_key_heads(hk, hv, most, held):
    """The value heads of a grid step: whole key heads, as many as
    divide their count and come to ``most`` value heads or fewer, one
    key head at the least; the channel-wise kernels' rule where every
    head has its own keys."""
    per = hv // hk
    assert delta_rule._heads_a_step(hk, per, most) == held
    assert held % per == 0 and hv % held == 0
    assert delta_rule._heads_a_step(hv) == max(
        n for n in range(1, delta_rule.HEADS_A_STEP + 1) if hv % n == 0)


def test_the_heads_a_step_are_read_at_every_call(monkeypatch):
    """The kernels' calls are ``jit``s taken inline, traced once a
    shape: what a trace reads of the module is in its key, so a changed
    :data:`SCALAR_HEADS_A_STEP` is another trace and another grid."""
    args = scan_inputs(128, 0.5, 1.0, hk=2, hv=4, dk=128, dv=128, batch=1)

    def grids():
        jaxpr = jax.make_jaxpr(
            lambda *a: jax.vjp(delta_rule.gdn_scan, *a)[1](a[2]))(*args)
        return [call.params["grid_mapping"].grid
                for call in _pallas_calls(jaxpr.jaxpr)]

    assert grids() == [(1, 1, 2)] * 3       # four value heads in one step
    monkeypatch.setattr(delta_rule, "SCALAR_HEADS_A_STEP", 2)
    assert grids() == [(1, 2, 2)] * 3       # a key head a step


@widths
def test_no_state_crosses_the_rows_of_a_batch(width):
    q, k, v, g, beta = scan_inputs(96, 0.5, 1.0, **WIDTHS[width])
    whole = delta_rule.gdn_scan(q, k, v, g, beta)
    alone = delta_rule.gdn_scan(q[1:], k[1:], v[1:], g[1:], beta[1:])
    np.testing.assert_allclose(whole[1:], alone, atol=1e-6)


@widths
def test_the_backward_rule_keeps_the_five_inputs_and_no_chunk_state(width):
    from jax._src.ad_checkpoint import saved_residuals

    args = scan_inputs(256, 0.5, 1.0, **WIDTHS[width])
    kept = saved_residuals(delta_rule.gdn_scan, *args)
    dk, dv = WIDTHS[width]["dk"], WIDTHS[width]["dv"]
    batch, length, heads = args[4].shape
    # the kernels keep the inputs as they are; the XLA form a key
    # repeated for its heads, cut in chunks
    largest = max(x.size for x in args) if width == "whole_lanes" else \
        batch * length * heads * dk
    for shape, why in kept:
        # never a state ``d_k x d_v`` a chunk or a position, nor a chunk's
        # ``C x C``
        assert math.prod(shape.shape) <= largest, (shape, why)
        assert shape.shape[-2:] not in ((dk, dv), (dv, dk), (64, 64)), (
            shape, why)
    if width == "whole_lanes":
        assert sorted(s.shape for s, _ in kept) == sorted(
            x.shape for x in args)


def test_mismatched_heads_are_refused():
    q, k, v, g, beta = scan_inputs(32, 0.5, 1.0, hk=3, hv=4)
    with pytest.raises(ValueError):
        delta_rule.gdn_scan(q, k, v, g, beta)
    q, k, v, g, beta = scan_inputs(32, 0.5, 1.0)
    with pytest.raises(ValueError):
        delta_rule.gdn_scan(q, k, v, g[..., :2], beta)


@widths
def test_the_sums_dtype_is_read_at_every_call_and_a_lower_one_shows(
        monkeypatch, width):
    """The log-decays are summed in float32; the probe's knob holds the
    sums from a chunk's start in bf16, and that shows, in the XLA form
    and in the kernels."""
    args = scan_inputs(150, 0.5, 1.0, **WIDTHS[width])
    sound = delta_rule.gdn_scan(*args)
    monkeypatch.setattr(delta_rule, "GDN_SUM_DTYPE", jnp.bfloat16)
    low = delta_rule.gdn_scan(*args)
    assert 1e-3 < relative(low, sound) < 1e-1
    held = delta_rule._sums_held_in(args[3], jnp.bfloat16)
    sums = jnp.cumsum(held[:, :64], axis=1)
    assert np.array_equal(np.asarray(sums.astype(jnp.bfloat16), np.float32),
                          np.asarray(sums))


# -- (b) the partial rotation ----------------------------------------------------


def _attend(q, k, v):
    return transformer.default_attn(use_flash=False)(q, k, v)


def _attention_params(rs, d, hq, hkv, hd):
    def mat(*shape):
        return jnp.asarray(rs.randn(*shape) / math.sqrt(shape[0]),
                           jnp.float32)

    return dict(wq=mat(d, hq * hd), wk=mat(d, hkv * hd), wv=mat(d, hkv * hd),
                wo=mat(hq * hd, d))


@pytest.mark.parametrize("rotary", [4, 8])
def test_the_first_dimensions_of_a_head_are_rotated_and_the_rest_pass(rotary):
    """Against the written-out form: dimension ``j < rot / 2`` pairs
    with ``j + rot / 2`` at the angle ``t theta^(-2j / rot)``."""
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(2, 24, 3, 16), jnp.float32)
    theta = 1e4
    inv = transformer.plain_inv_freq(rotary, theta)
    got = jnp.concatenate(
        [transformer.rope_by(x[..., :rotary], inv), x[..., rotary:]], -1)
    want = np.array(x)
    half = rotary // 2
    for t in range(24):
        for j in range(half):
            angle = t * theta ** (-2.0 * j / rotary)
            lo, hi = np.array(x[:, t, :, j]), np.array(x[:, t, :, j + half])
            want[:, t, :, j] = lo * math.cos(angle) - hi * math.sin(angle)
            want[:, t, :, j + half] = hi * math.cos(angle) + lo * math.sin(
                angle)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(got[..., rotary:]),
                                  np.asarray(x[..., rotary:]))
    np.testing.assert_allclose(ref.rotate_first(x, rotary, theta), want,
                               atol=2e-5)


def test_grouped_attention_rotates_a_part_and_a_factor_of_1_is_rope_by():
    rs = np.random.RandomState(1)
    d, hq, hkv, hd = 32, 4, 2, 16
    p = _attention_params(rs, d, hq, hkv, hd)
    h = jnp.asarray(rs.randn(2, 20, d), jnp.float32)
    kw = dict(heads=hq, kv_heads=hkv, head_dim=hd, attn=_attend)
    whole = transformer.grouped_attention(
        h, p["wq"], p["wk"], p["wv"], p["wo"],
        inv_freq=transformer.plain_inv_freq(hd, 1e4), **kw)
    factor_1 = transformer.grouped_attention(
        h, p["wq"], p["wk"], p["wv"], p["wo"],
        inv_freq=transformer.plain_inv_freq(hd, 1e4), rotary=hd, **kw)
    assert np.array_equal(np.asarray(whole), np.asarray(factor_1))
    part = transformer.grouped_attention(
        h, p["wq"], p["wk"], p["wv"], p["wo"],
        inv_freq=transformer.plain_inv_freq(4, 1e4), rotary=4, **kw)
    none = transformer.grouped_attention(
        h, p["wq"], p["wk"], p["wv"], p["wo"], inv_freq=None, **kw)
    assert relative(part, whole) > 1e-3 < relative(part, none)


# -- (c) the norms, the head's gate, the shared expert's gate ------------------------


def test_an_offset_norm_at_zero_is_rms_norm_at_one():
    x = jnp.asarray(np.random.RandomState(2).randn(3, 7, 32), jnp.float32)
    zero, one = jnp.zeros(32), jnp.ones(32)
    assert np.array_equal(
        np.asarray(transformer.rms_norm(x, 1.0 + zero, 1e-6)),
        np.asarray(transformer.rms_norm(x, one, 1e-6)))
    w = jnp.asarray(np.random.RandomState(3).randn(32), jnp.float32)
    np.testing.assert_allclose(transformer.rms_norm(x, 1.0 + w, 1e-6),
                               ref.offset_norm(x, w, 1e-6), rtol=1e-5,
                               atol=1e-6)


def _mixer_params(rs, d, hk, hv, dk, dv, kernel=4):
    mixed = 2 * hk * dk + hv * dv

    def mat(*shape):
        return jnp.asarray(rs.randn(*shape) / math.sqrt(shape[0]),
                           jnp.float32)

    return dict(
        attn_norm=jnp.asarray(0.1 * rs.randn(d), jnp.float32),
        w_qkvz=mat(d, mixed + hv * dv), w_ba=mat(d, 2 * hv),
        conv=jnp.asarray(rs.randn(kernel, mixed) / 3, jnp.float32),
        a_log=jnp.asarray(np.log(rs.uniform(1, 16, hv)), jnp.float32),
        dt_bias=jnp.asarray(rs.randn(hv), jnp.float32),
        o_norm=jnp.asarray(1 + 0.1 * rs.randn(dv), jnp.float32),
        wo=mat(hv * dv, d))


MIXER = dict(key_heads=2, value_heads=4, key_dim=16, value_dim=8)


def test_the_mixer_is_the_plain_references_norm_then_gate():
    """The whole mixer against the reference's, and the head's gate: the
    RMSNorm over a head's values comes **before** ``SiLU(z)`` (Kimi's
    and Nemotron's order differs), with a plain weight."""
    rs = np.random.RandomState(4)
    d = 32
    p = _mixer_params(rs, d, 2, 4, 16, 8)
    x = jnp.asarray(rs.randn(2, 70, d), jnp.float32)
    config = dict(linear_num_key_heads=2, linear_num_value_heads=4,
                  linear_key_head_dim=16, linear_value_head_dim=8,
                  rms_norm_eps=1e-6)
    with jax.default_matmul_precision("highest"):
        got, decay = transformer.gated_delta_mixer(x, p, eps=1e-6, **MIXER)
        want = ref.gated_delta_net(
            ref.offset_norm(x, p["attn_norm"], 1e-6), p, config)
        other = _gate_before_the_head_norm(
            ref.offset_norm(x, p["attn_norm"], 1e-6), p, config)
        offset = transformer.gated_delta_mixer(
            x, {**p, "o_norm": p["o_norm"] - 1.0}, eps=1e-6, **MIXER)[0]
    assert relative(got, want) < 1e-5
    assert 0.0 < float(decay) < 1.0
    assert relative(other, want) > 1e-2      # the order is seen
    assert relative(offset, want) > 1e-2     # the head norm is no offset


def test_a_delta_layer_keeps_its_input_and_the_scans_result_alone():
    from jax._src.ad_checkpoint import saved_residuals

    d = 32
    p = _mixer_params(np.random.RandomState(0), d, 2, 4, 16, 8)
    x = jnp.zeros((2, 48, d))
    mixer = jax.checkpoint(
        functools.partial(transformer.gated_delta_mixer, eps=1e-6, **MIXER),
        policy=jax.checkpoint_policies.save_only_these_names(
            *transformer.GDN_KEPT))
    kept = saved_residuals(lambda x, p: mixer(x, p)[0], x, p)
    made = [shape.shape for shape, why in kept if "argument" not in why]
    assert made == [(2, 48, 4, 8)]
    assert transformer.GDN_KEPT == (delta_rule.GDN_OUT,)


def _sparse_block(**over):
    kw = {name: TINY[name] for name in BLOCK}
    kw.update(mixer="full_attention", rotary=4,
              attn_fn=transformer.default_attn(use_flash=False))
    kw.update(over)
    return transformer.Qwen3NextBlock(**kw)


def test_the_shared_experts_gate_is_a_scalar_a_token():
    """``sigmoid(h w_s)`` multiplies the shared expert's output: with
    ``w_s`` at 0 the shared expert counts a half, and the statistics end
    in the gate's mean."""
    block = _sparse_block()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, TINY["d_model"]))
    params = block.init(jax.random.PRNGKey(5), x)["params"]
    assert params["shared_expert_gate"].shape == (TINY["d_model"], 1)
    assert "router_bias" not in params

    def sparse(p):
        zero = {**p, "wo": jnp.zeros_like(p["wo"])}   # the MLP alone
        y, _, stats = block.apply({"params": zero}, x)
        return y - x, stats

    def shared_alone(p):
        h = ref.offset_norm(x, p["mlp_norm"], TINY["norm_eps"])
        return ref.gated_mlp(h, p["shared_gate"], p["shared_up"],
                             p["shared_down"])

    with jax.default_matmul_precision("highest"):
        off = {**params, "shared_down": jnp.zeros_like(params["shared_down"])}
        routed, _ = sparse(off)
        half, stats = sparse({**params, "shared_expert_gate":
                              jnp.zeros_like(params["shared_expert_gate"])})
        np.testing.assert_allclose(half - routed, 0.5 * shared_alone(params),
                                   atol=1e-6)
        assert float(stats[-1]) == pytest.approx(0.5)
        open_gate = {**params, "shared_expert_gate": 50.0 * jnp.sign(
            params["shared_expert_gate"])}
        _, stats = sparse(open_gate)
    assert len(stats) == 4 and float(stats[-1]) != pytest.approx(0.5)


def test_the_router_takes_10_of_512_by_a_softmax_renormalised():
    rs = np.random.RandomState(1)
    logits = jnp.asarray(rs.randn(50, 512), jnp.float32)
    weights, chosen = moe.route_top_k(jax.nn.softmax(logits, axis=-1), 10,
                                      renormalise=True)
    gates = np.asarray(ref.router_gates(
        logits, jnp.eye(512),
        {"num_experts_per_tok": 10, "norm_topk_prob": True}))
    assert chosen.shape == weights.shape == (50, 10)
    assert np.allclose(np.asarray(jnp.sum(weights, axis=-1)), 1.0, rtol=1e-5)
    rows = np.arange(50)[:, None]
    assert np.allclose(gates[rows, np.asarray(chosen)], np.asarray(weights),
                       rtol=1e-4)
    assert np.count_nonzero(gates) == 50 * 10
    # ties go to the lower index, in the program and in the reference
    tied = jnp.zeros((1, 512))
    assert list(np.asarray(moe.route_top_k(
        jax.nn.softmax(tied, axis=-1), 10)[1][0])) == list(range(10))
    assert np.flatnonzero(np.asarray(ref.router_gates(
        tied, jnp.eye(512), {"num_experts_per_tok": 10,
                             "norm_topk_prob": True}))[0]).tolist() == \
        list(range(10))


# -- (d) the whole block against the plain reference -------------------------------


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
    ``A_log``, ``dt_bias``, the head norms, the shared expert's gate."""
    unravel = case["model"].flat.unravel
    got, want = unravel(case["grad"]), unravel(case["ref_grad"])
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(w)) > 0, name
        assert relative(g, w) < 2e-4, name


def _wrong(case, monkeypatch, **replaced):
    for name, fn in replaced.items():
        monkeypatch.setattr(ref, name, fn)
    return ref.loss_and_grad_flat(case["w"], case["model"].flat.unravel,
                                  case["tokens"], CONFIG)


_NORM, _CONV, _RULE, _ROTATE, _MLP = (
    ref.offset_norm, ref.conv_silu, ref.delta_rule, ref.rotate_first,
    ref.sparse_mlp)


def _plain_norm(x, w, eps):
    return _NORM(x, w - 1.0, eps)


def _reversed_conv(u, taps):
    return _CONV(u, taps[::-1])


def _no_delta_term(q, k, v, g, beta):
    return _RULE(q, k, v, g, jnp.zeros_like(beta))


def _one_decay_for_all_heads(q, k, v, g, beta):
    return _RULE(q, k, v, jnp.broadcast_to(g[..., :1], g.shape), beta)


def _keys_by_halves(q, k, v, g, beta):
    """Value head ``i`` on key head ``i mod H_k``, not ``i // r``."""
    r = v.shape[2] // k.shape[2]
    order = jnp.arange(v.shape[2]).reshape(r, -1).T.reshape(-1)
    back = jnp.argsort(order)
    return _RULE(q, k, v[:, :, order], g[..., order], beta[..., order]
                 )[:, :, back]


def _whole_head_rotated(x, rot, theta):
    return _ROTATE(x, x.shape[-1], theta)


def _ungated_shared(h, p, config):
    closed = 1.0 - jax.nn.sigmoid(h @ p["shared_expert_gate"])
    return _MLP(h, p, config) + closed * ref.gated_mlp(
        h, p["shared_gate"], p["shared_up"], p["shared_down"])


def _gate_before_the_head_norm(h, p, config):
    """The reference's mixer with ``RMSNorm_head(o * SiLU(z))``, Kimi's
    and Nemotron's order, in place of ``RMSNorm_head(o) * SiLU(z)``."""
    hk, hv = int(config["linear_num_key_heads"]), int(
        config["linear_num_value_heads"])
    dk, dv = int(config["linear_key_head_dim"]), int(
        config["linear_value_head_dim"])
    batch, seq, _ = h.shape
    keys, values = hk * dk, hv * dv
    qkvz, ba = h @ p["w_qkvz"], h @ p["w_ba"]
    qkv = _CONV(qkvz[..., :2 * keys + values], p["conv"])
    z = qkvz[..., 2 * keys + values:].reshape(batch, seq, hv, dv)

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    o = _RULE(unit(qkv[..., :keys].reshape(batch, seq, hk, dk))
              / math.sqrt(dk),
              unit(qkv[..., keys:2 * keys].reshape(batch, seq, hk, dk)),
              qkv[..., 2 * keys:].reshape(batch, seq, hv, dv),
              -jnp.exp(p["a_log"]) * jax.nn.softplus(
                  ba[..., hv:] + p["dt_bias"]),
              jax.nn.sigmoid(ba[..., :hv])) * jax.nn.silu(z)
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                     + float(config["rms_norm_eps"])) * p["o_norm"]
    return o.reshape(batch, seq, values) @ p["wo"]


WRONG = {
    "plain weights where the norms are offsets": dict(
        offset_norm=_plain_norm),
    "the convolution's taps reversed": dict(conv_silu=_reversed_conv),
    "the delta term left out": dict(delta_rule=_no_delta_term),
    "one decay for all heads": dict(delta_rule=_one_decay_for_all_heads),
    "value heads on the wrong key heads": dict(delta_rule=_keys_by_halves),
    "the whole head rotated": dict(rotate_first=_whole_head_rotated),
    "the shared expert ungated": dict(sparse_mlp=_ungated_shared),
    "the gate before the head norm": dict(
        gated_delta_net=_gate_before_the_head_norm),
}


@pytest.mark.parametrize("what", sorted(WRONG))
def test_the_tolerance_refuses(case, what, monkeypatch):
    loss, grad = _wrong(case, monkeypatch, **WRONG[what])
    assert (abs(float(case["loss"]) - float(loss)) > LOSS_TOL_NATS
            or relative(case["grad"], grad) > GRAD_REL_TOL), what


@pytest.mark.parametrize("key,value", [
    ("norm_topk_prob", False), ("num_experts_per_tok", 1),
    ("experts_first", 0), ("rms_norm_eps", 1e-2),
    ("partial_rotary_factor", 0.5), ("rope_theta", 1e4),
    ("full_attention_interval", 2)])
def test_the_tolerance_refuses_another_configuration(case, key, value):
    if key == "full_attention_interval":
        # another layer is the full one: its leaves are not there
        with pytest.raises(KeyError):
            ref.loss_and_grad_flat(
                case["w"], case["model"].flat.unravel, case["tokens"],
                {**CONFIG, key: value})
        return
    loss, grad = ref.loss_and_grad_flat(
        case["w"], case["model"].flat.unravel, case["tokens"],
        {**CONFIG, key: value})
    assert (abs(float(case["loss"]) - float(loss)) > LOSS_TOL_NATS
            or relative(case["grad"], grad) > GRAD_REL_TOL), key


# -- (e) the share ---------------------------------------------------------------


def test_the_shares_routed_parts_and_one_gated_shared_expert_are_the_layer():
    """The guide's share test on one sparse layer: over all its experts,
    by the plain reference, it is the sum of what each share's block
    computes for its own experts plus the gated shared expert counted
    once.  Four shares of two experts of eight (the deployment's sixteen
    of 32 of 512, at the tiny size)."""
    c = {**CONFIG, "num_experts": CONFIG["router_experts"],
         "experts_first": 0}
    n, held = c["router_experts"], CONFIG["num_experts"]
    whole = _sparse_block()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 40, c["hidden_size"]))
    params = whole.init(jax.random.PRNGKey(5), x)["params"]
    params = jax.tree_util.tree_map(
        lambda p: p + 0.05 * jax.random.normal(jax.random.PRNGKey(p.size),
                                               p.shape), params)
    params["wo"] = jnp.zeros_like(params["wo"])        # the MLP alone
    experts = ("experts_gate", "experts_up", "experts_down")
    shared = ("shared_gate", "shared_up", "shared_down",
              "shared_expert_gate")

    def share(first, with_shared):
        block = _sparse_block(experts_first=first, experts_held=held,
                              shared_experts=int(with_shared),
                              shared_width=TINY["shared_width"]
                              if with_shared else 0)
        p = {name: value for name, value in params.items()
             if with_shared or name not in shared}
        for name in experts:
            p[name] = params[name][first:first + held]
        return jax.jit(lambda p: block.apply({"params": p}, x)[0])(p) - x

    with jax.default_matmul_precision("highest"):
        routed = sum(share(first, False) for first in range(0, n, held))
        once = share(0, True) - share(0, False)    # the gated shared expert
        h = ref.offset_norm(x, params["mlp_norm"], TINY["norm_eps"])
        want = ref.sparse_mlp(h.reshape(-1, h.shape[-1]), params, c
                              ).reshape(x.shape)
    assert float(jnp.max(jnp.abs(routed))) > 1e-3 < \
        float(jnp.max(jnp.abs(once)))
    assert float(jnp.max(jnp.abs(routed + once - want))) < 1e-5


# -- the vector, the seeding, the scopes ------------------------------------------


def test_the_built_models_vector_is_the_arithmetics_at_the_tiny_size(case):
    assert case["model"].flat.size == arithmetic.param_count(CONFIG)
    whole = {**CONFIG, "num_experts": CONFIG["router_experts"],
             "experts_first": 0}
    model = build(arch="qwen3next", seed=3, use_flash=False, **sizes(whole))
    assert model.flat.size == arithmetic.param_count(whole)


@pytest.mark.parametrize("what,got,want", arithmetic.hand_worked(),
                         ids=[w[0][:60] for w in arithmetic.hand_worked()])
def test_qwen3next_arithmetic_by_hand(what, got, want):
    assert got == want, what


def test_the_published_sizes_give_the_issues_vector():
    """625,667,136 elements from the file's own keys, by the program's
    own shapes (``jax.eval_shape``: nothing of that size is made); the
    scan under test has 16 key heads, 32 value heads and one decay a
    head."""
    from chipbench import run as runner, spec as spec_mod
    from mpit_tpu.lm import archs
    from mpit_tpu.train.launch import lm_trainer_cfg

    cell = spec_mod.load_cell("qwen3next-l4e32-local")
    kw = build_kw(lm_trainer_cfg(runner.launch_config(cell, 1)))
    module = archs.block("qwen3next").make(
        archs.resolve("qwen3next", {k: v for k, v in kw.items()
                                    if k not in ("arch", "seed")}),
        lambda *a, **k: None)
    sample = jnp.zeros((1, 16), jnp.int32)
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0), sample,
                            sample)["params"]
    count = sum(math.prod(leaf.shape)
                for leaf in jax.tree_util.tree_leaves(shapes))
    assert count == arithmetic.param_count(FILE) == 625_667_136
    first, full = shapes["Qwen3NextBlock_0"], shapes["Qwen3NextBlock_3"]
    assert first["w_qkvz"].shape == (2048, 12288)
    assert first["w_ba"].shape == (2048, 64)
    assert first["conv"].shape == (4, 8192)
    assert first["a_log"].shape == first["dt_bias"].shape == (32,)
    assert first["experts_up"].shape == (32, 2048, 512)
    assert first["router"].shape == (2048, 512)
    assert full["wq"].shape == full["wg"].shape == (2048, 4096)
    assert full["wk"].shape == (2048, 512)
    assert full["q_norm"].shape == (256,)
    assert (module.gdn_key_heads, module.gdn_value_heads) == (16, 32)
    assert int(module.head_dim * module.rotary_factor) == 64
    assert "wq" not in first and "w_qkvz" not in full


def test_the_seeding_of_the_mixer_and_of_everything_else(case):
    params = case["model"].flat.unravel(case["model"].flat.w0)
    first, full = params["Qwen3NextBlock_0"], params["Qwen3NextBlock_3"]
    for name in ("attn_norm", "mlp_norm"):              # offsets: at 0
        assert not np.asarray(first[name]).any(), name
        assert not np.asarray(full[name]).any(), name
    for name in ("q_norm", "k_norm"):
        assert not np.asarray(full[name]).any(), name
    assert not np.asarray(params["final_norm"]).any()
    assert np.all(np.asarray(first["o_norm"]) == 1.0)    # plain: at 1
    rate = np.exp(np.asarray(first["a_log"]))
    assert np.all((rate >= 1.0) & (rate <= 16.0))
    step = np.log1p(np.exp(np.asarray(first["dt_bias"])))
    assert np.all((step >= 0.999e-3) & (step <= 0.1001))
    assert np.std(np.asarray(first["w_qkvz"])) == pytest.approx(0.02, rel=0.1)
    assert np.std(np.asarray(first["conv"])) == pytest.approx(1 / 3, rel=0.2)
    assert np.std(np.asarray(params["embed"])) == pytest.approx(8.0, rel=0.1)
    stats = case["stats"]
    assert stats[transformer.GDN_DECAY_MEAN].shape == (3,)
    assert all(0.05 < float(x) < 0.999
               for x in stats[transformer.GDN_DECAY_MEAN])
    assert stats[transformer.SHARED_GATE_MEAN].shape == (4,)
    assert all(0.02 < float(x) < 0.98
               for x in stats[transformer.SHARED_GATE_MEAN])
    for name in transformer.QWEN3NEXT_MOE_STATS:
        assert stats[name].shape == (4,), name
    assert "moe_bias_flips_share" not in stats


def test_the_seeded_weights_do_not_depend_on_the_training_sequence():
    short = build(arch="qwen3next", seed=3, use_flash=False,
                  **{**TINY, "seq_len": 32})
    model = build(arch="qwen3next", seed=3, use_flash=False, **TINY)
    assert np.array_equal(np.asarray(short.flat.w0),
                          np.asarray(model.flat.w0))


@pytest.mark.parametrize("bad", [
    {"layer_types": "linear_attention,full_attention"},
    {"gdn_value_heads": 3}, {"gdn_key_heads": 0}, {"experts_first": 7},
    {"layer_types": "linear_attention,kda,linear_attention,full_attention"},
    {"rotary_factor": 0.0}, {"rotary_factor": 0.45}, {"kda_heads": 2}])
def test_sizes_that_make_no_block_are_refused(bad):
    with pytest.raises((ValueError, TypeError)):
        build(arch="qwen3next", seed=3, use_flash=False, **{**TINY, **bad})


def test_the_steps_operations_carry_the_blocks_scopes(case):
    model = case["model"]
    text = jax.jit(model.value_and_grad).lower(
        case["w"], case["tokens"]).as_text(debug_info=True)
    for scope in FILE["scopes"]:
        if scope != "update":   # the optimizer's, not the model's
            assert f"/{scope}/" in text, scope
    assert {"gdn_proj", "gdn_conv", "gdn_scan", "gdn_out", "attn_gate"} <= \
        set(FILE["scopes"])


def test_the_other_blocks_sparse_branch_is_what_it_was():
    """``shared_sparse_experts`` has a second router and a gate; a block
    that names neither (JoyAI's, Kimi's, Trinity's, Nemotron's) still
    has its selection bias and no gate of the shared expert's."""
    block = transformer.JoyaiBlock(
        d_model=32, n_heads=2, q_rank=8, kv_rank=8, qk_nope=8, qk_rope=4,
        v_head=8, sparse=True, dense_width=0, n_experts=4,
        experts_per_tok=2, expert_width=16, experts_held=2,
        attn_fn=transformer.default_attn(use_flash=False))
    x = jnp.zeros((1, 8, 32))
    shapes = jax.eval_shape(block.init, jax.random.PRNGKey(0), x)["params"]
    assert shapes["router_bias"].shape == (4,)
    assert "shared_expert_gate" not in shapes
    assert len(jax.eval_shape(
        lambda p: block.apply({"params": p}, x)[1], shapes)) == len(
            transformer.JOYAI_MOE_STATS)


# -- through the launcher: locally and through two servers ------------------------

LAUNCH = dict(
    lm_use_flash=0, lm_eval_every=4, seed=5, device_policy="cpu",
    **FILE["launcher"],
    **{switch: CONFIG[key] for switch, key in FILE["launcher_from"].items()})
DECAY, GATE = transformer.GDN_DECAY_MEAN, transformer.SHARED_GATE_MEAN


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
    assert kw["arch"] == "qwen3next"


def test_a_one_rank_local_run_learns_and_carries_its_counters(obs_on):
    """``--np 1 --opt msgd``: the single-process path hands ``MSGD`` the
    step with the block's telemetry, and each donated step is a
    ``round`` span with the decay's mean a delta layer, the shared
    gate's mean and the routing counters a layer while obs records."""
    from mpit_tpu.train import launch

    steps = 12
    cfg = launch.LAUNCH_DEFAULTS.merged(
        np=1, opt="msgd", mom=0.9, lr=0.1, batch=2, lm_steps=steps,
        **LAUNCH)
    result = launch.run_rank(0, 1, cfg, None)
    assert result["role"] == "local"
    history = result["history"]
    assert history[-1]["avg_loss"] < history[0]["avg_loss"] - 0.1
    rounds = [s for s in obs_on.spans if s.name == "round"]
    assert len(rounds) == steps
    for span in rounds:
        assert len(span.args[DECAY]) == 3
        assert all(0.05 < x < 0.999 for x in span.args[DECAY])
        assert len(span.args[GATE]) == 4
        assert all(0.02 < x < 0.98 for x in span.args[GATE])
        for name in transformer.QWEN3NEXT_MOE_STATS:
            assert len(span.args[name]) == 4, name
    assert obs.get_registry().gauge(f"mpit_{DECAY}", layer=1).value == \
        rounds[-1].args[DECAY][1]
    assert obs.get_registry().gauge(f"mpit_{GATE}", layer=3).value == \
        rounds[-1].args[GATE][3]
    for name in (DECAY, GATE) + transformer.QWEN3NEXT_MOE_STATS:
        assert result[name] == rounds[-1].args[name]


@contextlib.contextmanager
def gang(layout, rule):
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
                           rule=rules.make(rule, lr=0.01)) for r in sranks]
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


def test_the_model_through_two_servers_trains_every_kind_of_leaf(case):
    """The ``ps1w`` path at the tiny size: the trainer's own shell under
    ``--opt adam``, two servers on the host.  The master copy moves, the
    loss falls, and the offset norms, ``A_log``, ``dt_bias`` and the
    shared expert's gate all leave their seeds (no leaf of this block is
    out of a gradient's reach)."""
    from mpit_tpu.lm import LmTrainer
    from mpit_tpu.lm.plan import plan
    from mpit_tpu.train import launch

    steps = 8
    cfg = launch.lm_trainer_cfg(launch.LAUNCH_DEFAULTS.merged(
        np=3, opt="adam", lr=3e-3, batch=2, lm_steps=steps, **LAUNCH))
    model = case["model"]
    assert not getattr(model.flat, "plain", None)
    layout = plan(model.flat.unravel(model.flat.w0), 2, rule="adam").layout
    with gang(layout, "adam") as (servers, client):
        trainer = LmTrainer(cfg, pclient=client, rank=2)
        w0 = np.asarray(trainer.w)
        result = trainer.run()
        master = np.concatenate([np.asarray(s.param) for s in servers])
    assert result["steps"] == steps
    history = result["history"]
    assert history[-1]["avg_loss"] < history[0]["avg_loss"] - 0.05
    assert np.abs(master - w0).max() > 1e-3
    seeded = model.flat.unravel(jnp.asarray(w0))
    after = model.flat.unravel(jnp.asarray(master))
    for name in ("attn_norm", "mlp_norm", "a_log", "dt_bias", "o_norm",
                 "shared_expert_gate", "conv", "w_ba"):
        assert not np.array_equal(
            np.asarray(seeded["Qwen3NextBlock_0"][name]),
            np.asarray(after["Qwen3NextBlock_0"][name])), name
    for name in ("q_norm", "k_norm", "wg"):
        assert not np.array_equal(
            np.asarray(seeded["Qwen3NextBlock_3"][name]),
            np.asarray(after["Qwen3NextBlock_3"][name])), name


def test_a_three_rank_gang_learns_and_carries_the_counters(obs_on):
    """``--np 3 --opt adam`` through ``run_rank``: servers 0 and 2,
    worker 1, the same launcher, trainer, shell, client and servers as
    the other blocks, on threads over the in-process router."""
    from mpit_tpu.comm.local import LocalRouter
    from mpit_tpu.train import launch

    steps = 10
    cfg = launch.LAUNCH_DEFAULTS.merged(
        np=3, master_freq=2, opt="adam", lr=3e-3, batch=2, lm_steps=steps,
        **LAUNCH)
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
    assert all(len(r.args[DECAY]) == 3 and len(r.args[GATE]) == 4
               for r in rounds)
    assert worker[DECAY] == rounds[-1].args[DECAY]
