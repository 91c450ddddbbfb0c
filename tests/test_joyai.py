"""The latent-attention block on the normal path (``lm/model.py``
``build(arch="joyai")``: ``models/transformer.py`` ``JoyaiDecoder``:
multi-head latent attention with keys wider than values, a shared expert
beside a share of the routed ones, a multi-token-prediction module and a
loss of its own over both heads) against its plain float32 reference, at
the benchmark configuration's ``tiny`` size on seeded weights; and the
flash kernel at two head widths against ``attention_reference``.  The
reference exists once, as the benchmark's
``chipbench/reference/joyai_plain.py`` (no code shared with the block),
and is imported from there.

Tolerances.  On the CPU both sides multiply in full float32, so they
differ by the rounding of sums taken in another order: under 1e-6 of the
gradient's norm and of a nat as measured here (1.1e-7 and 5e-7).  The
limits are 1e-5.  What they must refuse, each tried below on the
reference itself with one thing wrong, is wrong by 1e-3 or more.  The
kernel in interpret mode against the materialised attention: 3e-6 of
the largest entry, limit 2e-5."""

import functools
import json
import pathlib
import re
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.arithmetic import joyai as arithmetic
from chipbench.reference import joyai_plain as ref
from mpit_tpu import obs
from mpit_tpu.lm.model import build, build_kw
from mpit_tpu.models import transformer
from mpit_tpu.ops.flash_attention import attention_reference, flash_attention

fa = sys.modules["mpit_tpu.ops.flash_attention"]  # the package exports the op

LOSS_TOL_NATS = 1e-5
GRAD_REL_TOL = 1e-5
KERNEL_TOL = 2e-5

ROOT = pathlib.Path(__file__).resolve().parent.parent
FILE = json.loads(
    (ROOT / "chipbench/configs/joyai-flash-48b-l5e8.json").read_text())
CONFIG = {**FILE, **FILE["tiny"]}  # the reference's keys, at the tiny size


def sizes(c):
    """``build``'s keywords from the configuration's keys."""
    return dict(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_layers=c["num_hidden_layers"],
        seq_len=c["train_seq"], q_rank=c["q_lora_rank"],
        kv_rank=c["kv_lora_rank"], qk_nope=c["qk_nope_head_dim"],
        qk_rope=c["qk_rope_head_dim"], v_head=c["v_head_dim"],
        dense_layers=c["first_k_dense_replace"],
        dense_width=c["intermediate_size"], n_experts=c["router_experts"],
        experts_held=c["n_routed_experts"],
        experts_first=c["experts_first"],
        experts_per_tok=c["num_experts_per_tok"],
        expert_width=c["moe_intermediate_size"],
        shared_experts=c["n_shared_experts"],
        route_scale=c["routed_scaling_factor"],
        mtp_layers=c["num_nextn_predict_layers"],
        mtp_weight=c["mtp_loss_weight"], rope_theta=float(c["rope_theta"]),
        norm_eps=c["rms_norm_eps"])


TINY = sizes(CONFIG)


def moved(model, scale=0.05, seed=0):
    """The seeded weights moved off their initial values: norm weights
    off 1, so that a norm whose weight is ignored shows."""
    rs = np.random.RandomState(seed)
    return model.flat.w0 + scale * jnp.asarray(rs.randn(model.flat.size),
                                               jnp.float32)


def relative(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.fixture(scope="module")
def case():
    model = build(arch="joyai", seed=3, use_flash=False, **TINY)
    w = moved(model)
    tokens = jax.random.randint(jax.random.PRNGKey(7),
                                (2, TINY["seq_len"] + 1), 0, 256)
    with jax.default_matmul_precision("highest"):
        (loss, stats), grad = jax.jit(model.value_grad_stats)(w, tokens)
    ref_loss, ref_grad = ref.loss_and_grad_flat(w, model.flat.unravel,
                                                tokens, CONFIG)
    return dict(model=model, w=w, tokens=tokens, loss=loss, stats=stats,
                grad=grad, ref_loss=ref_loss, ref_grad=ref_grad)


# -- (a) the kernel at two head widths -------------------------------------------

# (keys' width, values' width): the published pair, and the pair cut to
# small multiples of 8 by 8 and by 4; heads (4 query over 2 KV: grouped)
WIDTHS = [(192, 128), (24, 16), (48, 32)]


def _qkv(d, dv, hq=2, hkv=2, length=80):
    keys = jax.random.split(jax.random.PRNGKey(d), 4)
    q = jax.random.normal(keys[0], (2, hq, length, d))
    k = jax.random.normal(keys[1], (2, hkv, length, d))
    v = jax.random.normal(keys[2], (2, hkv, length, dv))
    g = jax.random.normal(keys[3], (2, hq, length, dv))
    return q, k, v, g


@pytest.mark.parametrize("fused", ["0", "1"], ids=["two-kernel", "fused"])
@pytest.mark.parametrize("d,dv", WIDTHS)
def test_the_kernel_at_two_widths_is_the_materialised_attention(
        d, dv, fused, monkeypatch):
    """Forward and ``dq``, ``dk``, ``dv`` in interpret mode, causal, on
    blocks smaller than the sequence so that rows cross blocks; the
    output has the values' width, the scale is the keys'."""
    monkeypatch.setenv("MPIT_FA_FUSED_BWD", fused)
    q, k, v, g = _qkv(d, dv, hq=4 if d == 24 else 2)
    # float32 operands, which a named precision keeps (the default's
    # bf16 operands at these widths: tests/test_ops.py, PR 57)
    kernel = functools.partial(flash_attention, causal=True, interpret=True,
                               block_q=32, block_k=128, precision="highest")
    plain = functools.partial(attention_reference, causal=True)
    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(kernel, q, k, v)
        want, want_vjp = jax.vjp(plain, q, k, v)
        got_grads, want_grads = vjp(g), want_vjp(g)
    assert out.shape == q.shape[:-1] + (dv,)
    assert float(jnp.max(jnp.abs(out - want))) < KERNEL_TOL
    for name, a, b in zip(("dq", "dk", "dv"), got_grads, want_grads):
        assert a.shape == b.shape, name
        assert float(jnp.max(jnp.abs(a - b))) < KERNEL_TOL \
            * max(1.0, float(jnp.max(jnp.abs(b)))), name


def _pallas_calls(monkeypatch, fn, *args):
    """What ``fn`` hands ``pl.pallas_call``: a call's block shapes in,
    block shapes out, scratch shapes and output shapes."""
    calls = []
    real = fa.pl.pallas_call

    def spy(kernel, *, grid_spec, out_shape, **kw):
        def blocks(specs):
            specs = specs if isinstance(specs, (list, tuple)) else [specs]
            return [tuple(s.block_shape) for s in specs]

        shapes = out_shape if isinstance(out_shape, (list, tuple)) \
            else [out_shape]
        calls.append({
            "in": blocks(grid_spec.in_specs), "out": blocks(grid_spec.out_specs),
            "scratch": [tuple(s.shape) for s in grid_spec.scratch_shapes],
            "shape": [tuple(s.shape) for s in shapes]})
        return real(kernel, grid_spec=grid_spec, out_shape=out_shape, **kw)

    monkeypatch.setattr(fa.pl, "pallas_call", spy)
    fn(*args)
    return calls


def _blocks_at(d, dv):
    """The blocks of the three calls at ``block_q`` 32 and ``block_k``
    128 with q, k, dq, dk ``d`` wide and v, o, do, dv ``dv`` wide; the
    rows' statistics (``lse``, ``delta``) are 128 lanes whatever the
    head."""
    return {"fwd": ([(32, d), (128, d), (128, dv)], [(32, dv)]),
            "dkdv": ([(128, d), (128, dv), (32, d), (32, dv), (32, 128),
                      (32, 128)], [(128, d), (128, dv)]),
            "dq": ([(32, d), (32, dv), (32, 128), (32, 128), (128, d),
                    (128, dv)], [(32, d)])}


@pytest.mark.parametrize("d,dv", [(128, 128), (64, 64), (192, 128)])
def test_each_operand_is_blocked_at_its_own_width(d, dv, monkeypatch):
    """The blocks ``pallas_call`` is handed, forward and in the
    two-kernel backward: every operand, result and accumulator at the
    width it has (PR 54: a head of 64 or 192 lanes is no longer padded
    to whole 128-lane tiles in front of a call), so at 128 lanes what
    they always were, and at two widths a narrow value pays for no lane
    it does not have."""
    monkeypatch.setenv("MPIT_FA_FUSED_BWD", "0")
    want = _blocks_at(d, dv)
    q, k, v, g = _qkv(d, dv, length=128)
    q, k, v, g = (x[0, 0] for x in (q, k, v, g))
    kernel = functools.partial(flash_attention, causal=True, interpret=True,
                               block_q=32, block_k=128)

    def run(q, k, v):
        out, vjp = jax.vjp(kernel, q, k, v)
        return vjp(g)

    calls = _pallas_calls(monkeypatch, run, q, k, v)
    assert len(calls) == 3
    for call, name in zip(calls, ("fwd", "dkdv", "dq")):
        blocks_in, blocks_out = want[name]
        assert call["in"] == blocks_in, name
        assert call["out"][:len(blocks_out)] == blocks_out, name
    # the forward's accumulator is as wide as the values, the backward's
    # dk and dv scratch each as wide as what it sums, and what the calls
    # return has no lane to cut
    assert calls[0]["scratch"][0] == (32, dv)
    assert calls[1]["scratch"] == [(128, d), (128, dv)]
    assert calls[0]["shape"][0] == (128, dv)
    assert calls[1]["shape"][:2] == [(128, d), (128, dv)]
    assert calls[2]["shape"] == [(128, d)]


def test_equal_widths_take_the_same_path_whichever_way_they_are_said():
    """``v`` as wide as ``k`` is the one-width call: what the two-width
    machinery gives on the shared columns, which are independent of the
    others (to a unit in the last place: interpreted, the ``P V``
    product is the CPU's, which tiles 24 columns otherwise than 16)."""
    q, k, v, _ = _qkv(24, 24)
    kernel = functools.partial(flash_attention, causal=True, interpret=True,
                               block_q=32, block_k=128)
    narrow = kernel(q, k, v[..., :16])
    np.testing.assert_allclose(np.asarray(kernel(q, k, v)[..., :16]),
                               np.asarray(narrow), rtol=1e-6, atol=1e-6)


# -- (b) the decoder against the plain reference ---------------------------------


def test_loss_and_flat_gradient_equal_the_plain_references(case):
    assert abs(float(case["loss"]) - float(case["ref_loss"])) < LOSS_TOL_NATS
    assert relative(case["grad"], case["ref_grad"]) < GRAD_REL_TOL


def test_the_plain_loss_and_the_loss_with_statistics_are_one_number(case):
    model = case["model"]
    with jax.default_matmul_precision("highest"):
        loss, grad = jax.jit(model.value_and_grad)(case["w"], case["tokens"])
    assert float(loss) == float(case["loss"])
    assert np.array_equal(np.asarray(grad), np.asarray(case["grad"]))


def test_every_leaf_of_the_gradient_is_inside_the_tolerance(case):
    """A leaf that is wrong and small beside the whole (a norm's weight,
    the MTP projection) would hide in the flat norm: each leaf against
    its own norm, 1e-4 (a leaf of 48 numbers rounds coarser than the
    whole).  The selection bias has no gradient on either side."""
    unravel = case["model"].flat.unravel
    got, want = unravel(case["grad"]), unravel(case["ref_grad"])
    leaves = jax.tree_util.tree_leaves_with_path(got)
    assert len(leaves) == 12 + 3 * 17 + 3 + 4
    for path, leaf in leaves:
        name = jax.tree_util.keystr(path)
        other = functools.reduce(lambda t, k: t[k.key], path, want)
        if "router_bias" in name:
            assert not np.any(np.asarray(leaf)) and \
                not np.any(np.asarray(other)), name
            continue
        assert float(jnp.linalg.norm(other)) > 0, name
        assert relative(leaf, other) < 1e-4, name


def test_the_kernel_in_the_block_changes_no_number(case):
    """The flash kernel (interpret mode) in place of the materialised
    attention, at the tiny size: the block hands it q and k of 24 and v
    of 16."""
    flash = transformer.default_attn(causal=True, use_flash=True,
                                     interpret=True, precision="highest")
    module = case["model"].module.clone(attn_fn=flash)
    unravel = case["model"].flat.unravel
    tokens = case["tokens"]

    def loss(w):
        return module.apply({"params": unravel(w)}, tokens[:, :-1],
                            tokens[:, 1:])[0]

    with jax.default_matmul_precision("highest"):
        got, grad = jax.jit(jax.value_and_grad(loss))(case["w"])
    assert abs(float(got) - float(case["ref_loss"])) < LOSS_TOL_NATS
    assert relative(grad, case["ref_grad"]) < GRAD_REL_TOL


def _wrong(case, monkeypatch, **replaced):
    for name, fn in replaced.items():
        monkeypatch.setattr(ref, name, fn)
    return ref.loss_and_grad_flat(case["w"], case["model"].flat.unravel,
                                  case["tokens"], CONFIG)


def _halves(x, angle):
    """Rotary over halves where the pairs are interleaved."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _leaking(h, router, bias, top_k, normalise, scale):
    scores = jax.nn.sigmoid(h @ router) + bias   # bias in the weights
    _, chosen = jax.lax.top_k(scores, top_k)
    gates = jnp.zeros_like(scores).at[
        jnp.arange(scores.shape[0])[:, None], chosen].set(
            jnp.take_along_axis(scores, chosen, axis=-1))
    if normalise:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return gates * scale


_rms_norm, _mtp_hidden, _routed = ref.rms_norm, ref.mtp_hidden, \
    ref.routed_experts
WRONG = {
    "rotary over halves, not interleaved pairs": {"rotate_pairs": _halves},
    "no inner norm on the latents": {
        "rms_norm": lambda x, w, eps: x * w if w.shape[0] in (
            CONFIG["q_lora_rank"], CONFIG["kv_lora_rank"])
        else _rms_norm(x, w, eps)},
    "the shared expert left out": {
        "shared_expert": lambda h, p: jnp.zeros_like(h)},
    "the routed sum not scaled": {
        "routed_experts": lambda h, p, c: _routed(
            h, p, {**c, "routed_scaling_factor": 1.0})},
    "the bias leaks into the weights": {"router_gates": _leaking},
    "the MTP pair with the hidden state first": {
        "mtp_hidden": lambda params, x, nxt, c: _mtp_hidden(
            {**params, "mtp_proj": jnp.roll(
                params["mtp_proj"], c["hidden_size"], axis=0)}, x, nxt, c)},
}


@pytest.mark.parametrize("what", sorted(WRONG))
def test_the_tolerance_refuses(case, what, monkeypatch):
    loss, grad = _wrong(case, monkeypatch, **WRONG[what])
    assert (abs(float(loss) - float(case["ref_loss"])) > LOSS_TOL_NATS
            or relative(grad, case["ref_grad"]) > GRAD_REL_TOL), what
    assert relative(grad, case["ref_grad"]) > 1e-3, what


# -- (c) the shares add up to the whole layer -------------------------------------


def test_the_shares_routed_parts_and_one_shared_expert_are_the_whole_layer():
    """The guide's share test: a sparse layer over all its experts, by
    the plain reference, is the sum of what each share's block computes
    for its own experts plus the shared expert counted once (every share
    computes it alike).  Four shares of two experts of eight."""
    c = {**CONFIG, "n_routed_experts": CONFIG["router_experts"],
         "experts_first": 0}
    n, held = c["router_experts"], CONFIG["n_routed_experts"]
    kw = dict(
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        q_rank=c["q_lora_rank"], kv_rank=c["kv_lora_rank"],
        qk_nope=c["qk_nope_head_dim"], qk_rope=c["qk_rope_head_dim"],
        v_head=c["v_head_dim"], sparse=True,
        dense_width=c["intermediate_size"], n_experts=n,
        experts_per_tok=c["num_experts_per_tok"],
        expert_width=c["moe_intermediate_size"],
        route_scale=c["routed_scaling_factor"],
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        attn_fn=transformer.default_attn(use_flash=False))
    whole = transformer.JoyaiBlock(**kw)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, c["hidden_size"]))
    params = whole.init(jax.random.PRNGKey(5), x)["params"]
    params = jax.tree_util.tree_map(
        lambda p: p + 0.05 * jax.random.normal(jax.random.PRNGKey(p.size),
                                               p.shape), params)
    experts = ("experts_gate", "experts_up", "experts_down")
    shared = ("shared_gate", "shared_up", "shared_down")

    def share(first, with_shared, down_scale=1.0):
        block = transformer.JoyaiBlock(
            **kw, experts_first=first, experts_held=held,
            shared_experts=int(with_shared))
        p = {name: value for name, value in params.items()
             if with_shared or name not in shared}
        for name in experts:
            p[name] = params[name][first:first + held]
        p["experts_down"] = p["experts_down"] * down_scale
        return block.apply({"params": p}, x)[0]

    with jax.default_matmul_precision("highest"):
        stream = share(0, False, down_scale=0.0)   # x + attention alone
        routed = sum(share(first, False) - stream
                     for first in range(0, n, held))
        once = share(0, True) - share(0, False)    # the shared expert
        want = ref.layer(x, params, False, c)
    assert float(jnp.max(jnp.abs(routed))) > 1e-3 < \
        float(jnp.max(jnp.abs(once)))
    assert float(jnp.max(jnp.abs(stream + routed + once - want))) < 1e-5


# -- (d) the multi-token-prediction term ------------------------------------------


def test_the_two_heads_losses_are_the_references(case):
    main, mtp = ref.losses(case["model"].flat.unravel(case["w"]),
                           case["tokens"], CONFIG)
    assert float(case["stats"]["lm_main_nll"][0]) == pytest.approx(
        float(main), abs=LOSS_TOL_NATS)
    assert float(case["stats"]["lm_mtp_nll"][0]) == pytest.approx(
        float(mtp), abs=LOSS_TOL_NATS)
    assert float(case["loss"]) == pytest.approx(
        float(main) + CONFIG["mtp_loss_weight"] * float(mtp),
        abs=LOSS_TOL_NATS)


def test_the_mtp_target_is_the_token_after_next_and_the_last_is_masked(case):
    """By hand from the reference's pieces: position ``i`` of the MTP
    head, from ``Emb(t_{i+1})`` and ``x_L,i``, is scored on ``t_{i+2}``
    over positions ``0 .. L - 2``.  Scored on ``t_{i+1}`` (the main
    head's target), or with the last position counted against the
    wrapped-around first token, the number is another."""
    params = case["model"].flat.unravel(case["w"])
    tokens, eps = case["tokens"], CONFIG["rms_norm_eps"]
    targets = tokens[:, 1:]
    with jax.default_matmul_precision("highest"):
        z = ref.mtp_hidden(params, ref.stack(params, tokens[:, :-1], CONFIG),
                           targets, CONFIG)
        nll = functools.partial(ref.head_nll, z, params["mtp_final_norm"],
                                params["head"], eps=eps)
        after_next = jnp.roll(targets, -1, axis=1)
        right = jnp.mean(nll(targets=after_next)[:, :-1])
        same_target = jnp.mean(nll(targets=targets)[:, :-1])
        unmasked = jnp.mean(nll(targets=after_next))
    got = float(case["stats"]["lm_mtp_nll"][0])
    assert got == pytest.approx(float(right), abs=LOSS_TOL_NATS)
    assert abs(got - float(same_target)) > 1e-3
    # one position of 64, near ln of the vocabulary like every other
    assert abs(got - float(unmasked)) > 10 * LOSS_TOL_NATS


def test_the_last_positions_next_token_reaches_no_loss_through_the_mtp(case):
    """The embedding of the last target enters the MTP module at the
    last position alone, which is masked, and attention is causal: its
    gradient through the MTP term is zero.  (Through the main head the
    last target is a target, not an input.)  Read on the table's row of
    a token that occurs nowhere else."""
    model = case["model"]
    tokens = np.array(case["tokens"])
    rare = 300                               # above the byte stream's ids
    tokens[:, -1] = rare
    tokens = jnp.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        grad = jax.jit(model.value_and_grad)(case["w"], tokens)[1]
    table = model.flat.unravel(grad)["embed"]
    assert not np.any(np.asarray(table[rare]))
    assert np.any(np.asarray(table[int(tokens[0, 0])]))


def test_weight_zero_gives_the_main_losss_gradient():
    """``mtp_weight`` 0: the objective is the main head's NLL, its
    gradient the main loss's own, and nothing of the MTP module has
    one."""
    tokens = jax.random.randint(jax.random.PRNGKey(7),
                                (2, TINY["seq_len"] + 1), 0, 256)
    off = build(arch="joyai", seed=3, use_flash=False,
                **{**TINY, "mtp_weight": 0.0})
    on = build(arch="joyai", seed=3, use_flash=False, **TINY)
    w = moved(on)

    def main_alone(w):
        return on.flat.apply_flat(w, tokens[:, :-1],
                                  tokens[:, 1:])[1]["lm_main_nll"][0]

    with jax.default_matmul_precision("highest"):
        loss, grad = jax.jit(off.value_and_grad)(w, tokens)
        want_loss, want = jax.jit(jax.value_and_grad(main_alone))(w)
    assert float(loss) == float(want_loss)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(want),
                               rtol=0, atol=1e-9)
    tree = off.flat.unravel(grad)
    for name in ("mtp_block", "mtp_proj", "mtp_embed_norm",
                 "mtp_hidden_norm", "mtp_final_norm"):
        assert not any(np.any(np.asarray(leaf))
                       for leaf in jax.tree_util.tree_leaves(tree[name])), name
    assert np.any(np.asarray(tree["head"]))


def test_no_mtp_module_is_the_plain_next_token_nll():
    kw = {**TINY, "mtp_layers": 0}
    model = build(arch="joyai", seed=3, use_flash=False, **kw)
    tokens = jax.random.randint(jax.random.PRNGKey(7),
                                (2, TINY["seq_len"] + 1), 0, 256)
    c = {**CONFIG, "num_nextn_predict_layers": 0}
    with jax.default_matmul_precision("highest"):
        (loss, stats), grad = jax.jit(model.value_grad_stats)(
            model.flat.w0, tokens)
    want, want_grad = ref.loss_and_grad_flat(
        model.flat.w0, model.flat.unravel, tokens, c)
    assert "lm_mtp_nll" not in stats
    assert model.flat.size == arithmetic.param_count(c)
    assert abs(float(loss) - float(want)) < LOSS_TOL_NATS
    assert relative(grad, want_grad) < GRAD_REL_TOL


# -- the vector, the seeding, the scopes ------------------------------------------


def test_the_built_models_vector_is_the_arithmetics_at_the_tiny_size(case):
    assert case["model"].flat.size == arithmetic.param_count(CONFIG)
    whole = {**CONFIG, "n_routed_experts": CONFIG["router_experts"],
             "experts_first": 0}
    model = build(arch="joyai", seed=3, use_flash=False, **sizes(whole))
    assert model.flat.size == arithmetic.param_count(whole)


@pytest.mark.parametrize("what,got,want", arithmetic.hand_worked(),
                         ids=[c[0] for c in arithmetic.hand_worked()])
def test_joyai_arithmetic_by_hand(what, got, want):
    assert got == want, what


def test_the_seeding_is_std_002_norms_one_and_the_table_at_eight(case):
    params = case["model"].flat.unravel(case["model"].flat.w0)
    block = params["mtp_block"]
    for name in ("attn_norm", "q_a_norm", "kv_a_norm", "mlp_norm"):
        assert np.all(np.asarray(block[name]) == 1.0), name
    assert float(jnp.std(params["embed"])) == pytest.approx(8.0, rel=0.05)
    for leaf in (params["head"], block["wq_b"], block["experts_gate"],
                 block["shared_up"], params["mtp_proj"]):
        assert float(jnp.std(leaf)) == pytest.approx(0.02, rel=0.1)
    assert np.any(np.asarray(block["router_bias"]))


def test_the_seeded_weights_do_not_depend_on_the_training_sequence():
    short = build(arch="joyai", seed=3, use_flash=False,
                  **{**TINY, "seq_len": 32})
    long = build(arch="joyai", seed=3, use_flash=False, **TINY)
    assert np.array_equal(np.asarray(short.flat.w0), np.asarray(long.flat.w0))


@pytest.mark.parametrize("bad", [
    {"q_rank": 0}, {"qk_rope": 7}, {"experts_first": 7},
])
def test_sizes_that_make_no_block_are_refused(bad):
    with pytest.raises(ValueError):
        build(arch="joyai", use_flash=False, **{**TINY, **bad})


def test_the_steps_operations_carry_the_blocks_scopes(case):
    """``jax.named_scope`` is metadata on the lowered operations: the
    new layers' names are there for the trace's readers, the MTP
    module's operations under ``mtp`` around the layer's own scopes."""
    model = case["model"]
    text = jax.jit(model.value_and_grad).lower(
        case["w"], case["tokens"]).as_text(debug_info=True)
    for scope in ("embed", "mla_proj", "attn", "mlp", "router", "dispatch",
                  "experts", "shared_expert", "head_loss", "mtp/head_loss"):
        assert f"/{scope}/" in text, scope
    for scope in ("mla_proj", "attn", "router", "dispatch", "experts",
                  "shared_expert"):
        assert re.search(rf"/mtp/mtp_block/([\w().]+/)*{scope}/", text), scope
    assert set(FILE["scopes"]) >= {"mla_proj", "attn", "shared_expert"}
    assert "mtp" not in FILE["scopes"]   # a cut across them, read by its
    #                                      own reader (layers/mtp_ms_per_step)


def test_the_attention_keeps_the_kernels_results_and_makes_q_k_v_again():
    """The attention checkpoint's policy: with the kernel, the lowered
    gradient holds one forward call a layer (no recomputed forward) and
    the backward's two."""
    kw = {**TINY, "n_layers": 2}
    model = build(arch="joyai", seed=3, use_flash=False, **kw)
    flash = transformer.default_attn(causal=True, use_flash=True,
                                     interpret=True)
    module = model.module.clone(attn_fn=flash)
    tokens = jnp.zeros((1, TINY["seq_len"] + 1), jnp.int32)

    def loss(w):
        return module.apply({"params": model.flat.unravel(w)},
                            tokens[:, :-1], tokens[:, 1:])[0]

    jaxpr = str(jax.make_jaxpr(jax.grad(loss))(model.flat.w0))
    # three layers with attention (two and the MTP module's): a forward
    # kernel each, and the two-kernel or the fused backward
    assert jaxpr.count("pallas_call") in (3 * 2, 3 * 3)
    assert transformer.JOYAI_ATTN_KEPT == (fa.FLASH_OUT, fa.FLASH_LSE)


# -- the counters on the round spans ----------------------------------------------

LAUNCH = dict(
    lm_use_flash=0, lm_eval_every=4, seed=5, device_policy="cpu",
    **FILE["launcher"],
    **{switch: CONFIG[key] for switch, key in FILE["launcher_from"].items()})
HEADS = ("lm_main_nll", "lm_mtp_nll")
SPARSE_LAYERS = CONFIG["num_hidden_layers"] - 1 + 1   # the MTP module's last


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
        for name in HEADS:
            assert len(span.args[name]) == 1 and span.args[name][0] > 0
        for name in transformer.JOYAI_MOE_STATS:
            assert len(span.args[name]) == SPARSE_LAYERS, name
        assert all(0.0 <= x <= 1.0 for x in span.args["moe_held_rows_share"])
    reg = obs.get_registry()
    for name in HEADS:
        assert reg.gauge(f"mpit_{name}", layer=0).value == \
            rounds[-1].args[name][0]
    assert reg.gauge("mpit_moe_held_rows_share",
                     layer=SPARSE_LAYERS - 1).value == \
        rounds[-1].args["moe_held_rows_share"][-1]
    return rounds


def test_the_launcher_builds_the_block_from_the_configurations_file():
    from mpit_tpu.train import launch

    cfg = launch.LAUNCH_DEFAULTS.merged(np=1, opt="msgd", **LAUNCH)
    kw = build_kw(launch.lm_trainer_cfg(cfg))
    assert {key: kw[key] for key in TINY} == {**TINY, "seq_len": 64}
    assert kw["arch"] == "joyai"


def test_a_three_rank_gang_learns_and_carries_both_heads_losses(obs_on):
    """``--np 3 --opt adam`` through ``run_rank``: servers 0 and 2,
    worker 1, the same launcher, trainer, shell, client and servers as
    the other blocks, on threads over the in-process router."""
    from mpit_tpu.comm.local import LocalRouter
    from mpit_tpu.train import launch

    steps = 12
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
    assert history[-1]["avg_loss"] < history[0]["avg_loss"] - 0.1
    rounds = _counters_on_round_spans(obs_on)
    assert len(rounds) == steps
    for name in HEADS:
        assert worker[name] == rounds[-1].args[name]


def test_a_one_rank_local_run_learns_and_carries_both_heads_losses(obs_on):
    """``--np 1 --opt msgd``: the single-process path hands ``MSGD`` the
    step with the block's telemetry, and each donated step is a
    ``round`` span with the two heads' losses and the routing counters
    while obs records."""
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
    for name in HEADS + transformer.JOYAI_MOE_STATS:
        assert result[name] == rounds[-1].args[name]
