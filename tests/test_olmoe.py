"""The OLMoE block on the normal path (``lm/model.py``
``build(arch="olmoe")``: ``models/transformer.py`` ``OlmoeDecoder`` with
``parallel/moe.py``'s sorted dropless dispatch) against its plain
float32 reference (``chipbench/reference/olmoe_plain.py``, the
benchmark's: dense over experts, dense masked attention, no code
shared), at the benchmark configuration's ``tiny`` size on seeded
weights.

Tolerances.  On the CPU both sides multiply in full float32, so they
differ by the rounding of sums taken in another order: 5e-7 of the
gradient's norm and under 1e-6 nats as measured here.  The limits are
20 and 10 times that.  What they must refuse, each tried below on the
reference itself with one thing wrong, is wrong by 1e-3 or more: a
renormalised top-k, a dropped router weight, the interleaved rotary
convention, and bf16 parameters and activations; a router whose product
alone is one bf16 pass is 2e-4 off (it flips a few top-k choices)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import olmoe_plain as ref
from mpit_tpu.lm.model import build
from mpit_tpu.models import transformer

LOSS_TOL_NATS = 1e-5
GRAD_REL_TOL = 1e-5

TINY = dict(vocab=320, d_model=64, n_heads=4, n_layers=2, seq_len=128,
            n_experts=8, experts_per_tok=2, expert_width=32)
CONFIG = dict(num_attention_heads=4, num_hidden_layers=2,
              num_experts_per_tok=2, rope_theta=10000, rms_norm_eps=1e-5)


@pytest.fixture(scope="module")
def case():
    """The tiny model, seeded weights moved off their initial values
    (norm weights off 1, the routers spread, so that top-k margins are
    not ties), one batch, and both sides' loss and flat gradient."""
    model = build(arch="olmoe", seed=3, **TINY)
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
    """The norm over the whole vector could hide a small leaf (a norm
    weight, the router) that is wrong: leaf by leaf, ten times looser."""
    unravel = case["model"].flat.unravel
    got, want = unravel(case["sys"][1]), unravel(case["ref"][1])
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        err = float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
        assert err <= 10 * GRAD_REL_TOL, (jax.tree_util.keystr(path), err)


def renormalised(gates_fn):
    def gates(h, router, k):
        g = gates_fn(h, router, k)
        return g / jnp.sum(g, axis=-1, keepdims=True)
    return gates


def unweighted(gates_fn):
    return lambda h, router, k: jnp.where(gates_fn(h, router, k) > 0,
                                          1.0 / k, 0.0)


def interleaved(_rotate):
    def rotate(x, theta):
        n, head = x.shape[-2], x.shape[-1]
        freq = theta ** (-jnp.arange(0, head, 2, dtype=jnp.float32) / head)
        angle = jnp.arange(n, dtype=jnp.float32)[:, None] * freq[None, :]
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * jnp.cos(angle) - b * jnp.sin(angle),
                          b * jnp.cos(angle) + a * jnp.sin(angle)],
                         axis=-1).reshape(x.shape)
    return rotate


WRONG = {
    "top-k renormalised": ("router_gates", renormalised),
    "router weights dropped": ("router_gates", unweighted),
    "rotary pairs interleaved": ("rotate", interleaved),
}


@pytest.mark.parametrize("what", sorted(WRONG))
def test_the_tolerance_refuses(case, what, monkeypatch):
    name, replacement = WRONG[what]
    monkeypatch.setattr(ref, name, replacement(getattr(ref, name)))
    bad = ref.loss_and_grad_flat(case["w"], case["model"].flat.unravel,
                                 case["tokens"], CONFIG)
    loss_err, grad_err = errors(bad, case["ref"])
    assert grad_err > 100 * GRAD_REL_TOL, (what, loss_err, grad_err)


def test_the_tolerance_refuses_bf16_parameters_and_activations(case):
    unravel = case["model"].flat.unravel
    low = jax.jit(jax.value_and_grad(lambda flat, tok: ref.loss(
        jax.tree_util.tree_map(lambda p: p.astype(jnp.bfloat16),
                               unravel(flat)), tok, CONFIG)))(
        case["w"], case["tokens"])
    loss_err, grad_err = errors((low[0].astype(jnp.float32),
                                 low[1].astype(jnp.float32)), case["ref"])
    assert loss_err > 10 * LOSS_TOL_NATS and grad_err > 100 * GRAD_REL_TOL


def test_the_tolerance_refuses_a_bf16_router(case, monkeypatch):
    """The system with its router's product in one bf16 pass flips top-k
    membership; at full precision it does not (ROUTER_PRECISION)."""
    model = case["model"]
    real = transformer.jnp.matmul

    def bf16_router(a, b, precision=None):
        if precision == transformer.ROUTER_PRECISION:  # the router's alone
            return real(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
        return real(a, b)

    monkeypatch.setattr(transformer.jnp, "matmul", bf16_router)
    bad = jax.jit(lambda w, t: model.value_and_grad(w, t))(
        case["w"], case["tokens"])
    assert errors(bad, case["ref"])[1] > 10 * GRAD_REL_TOL


# -- RoPE and the query/key norm, by hand -----------------------------------------


def test_rope_two_positions_by_hand():
    """Head width 4, theta 100: frequencies 1 and 100^(-1/2) = 0.1.
    Position 0 is unchanged.  At position 1 the halves (x0, x1 | x2, x3)
    rotate as pairs (x0, x2) by 1 rad and (x1, x3) by 0.1 rad."""
    x = jnp.asarray([[1.0, 2.0, 3.0, 4.0], [1.0, 2.0, 3.0, 4.0]])
    got = transformer.rope(x.reshape(1, 2, 1, 4), 100.0).reshape(2, 4)
    c1, s1, c2, s2 = np.cos(1.0), np.sin(1.0), np.cos(0.1), np.sin(0.1)
    want = [[1.0, 2.0, 3.0, 4.0],
            [1 * c1 - 3 * s1, 2 * c2 - 4 * s2, 3 * c1 + 1 * s1, 4 * c2 + 2 * s2]]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # the reference's own rotary, (batch, heads, seq, head), agrees
    np.testing.assert_allclose(
        ref.rotate(x.reshape(1, 1, 2, 4), 100.0).reshape(2, 4), want,
        rtol=1e-6)


def test_rms_norm_by_hand():
    """(3, 4) has mean square 12.5: 3 / sqrt(12.5), 4 / sqrt(12.5), each
    times its weight; over the whole projected width, not per head."""
    x = jnp.asarray([[3.0, 4.0]])
    weight = jnp.asarray([2.0, 0.5])
    want = [[2 * 3 / np.sqrt(12.5), 0.5 * 4 / np.sqrt(12.5)]]
    np.testing.assert_allclose(transformer.rms_norm(x, weight, 0.0), want,
                               rtol=1e-6)
    np.testing.assert_allclose(ref.rms_norm(x, weight, 0.0), want, rtol=1e-6)


def test_query_key_norm_is_over_the_whole_width_before_the_heads(case):
    """With the query norm's weight doubled the scores double; a norm
    per head of width 16 would be a different function: by hand on the
    reference's attention, whose q norm sees all 64 columns at once."""
    p = case["model"].flat.unravel(case["w"])["OlmoeBlock_0"]
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 64))
    q = x @ p["wq"]
    whole = ref.rms_norm(q, p["q_norm"], 1e-5)
    per_head = ref.rms_norm(q.reshape(1, 8, 4, 16),
                            p["q_norm"].reshape(4, 16), 1e-5).reshape(1, 8, 64)
    assert float(jnp.max(jnp.abs(whole - per_head))) > 1e-2
    # the program's projections, normalised the reference's way, are the
    # program's own normalised queries
    np.testing.assert_allclose(
        transformer.rms_norm(q, p["q_norm"], 1e-5), whole, rtol=1e-5,
        atol=1e-6)


# -- sizes, the flat vector, statistics ---------------------------------------------


def test_the_vector_at_published_widths_is_625_616_896_elements():
    module = transformer.OlmoeDecoder(
        vocab=50304, d_model=2048, n_heads=16, n_layers=1, n_experts=64,
        experts_per_tok=8, expert_width=1024,
        attn_fn=transformer.default_attn(use_flash=False))
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)))["params"]
    sizes = {jax.tree_util.keystr(k): int(np.prod(v.shape)) for k, v in
             jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert sum(sizes.values()) == 625_616_896
    experts = sum(v for k, v in sizes.items() if "experts_" in k)
    assert experts == 402_653_184  # 64% of the vector


def test_the_barriered_unravel_is_ravel_pytrees(case):
    """Values and gradient: the barrier on every leaf's piece is the
    identity, and the hand-written backward is ``ravel_pytree``'s
    transpose.  The model's own unravel is that function."""
    from jax.flatten_util import ravel_pytree

    from mpit_tpu.models import flat as flat_mod

    model = case["model"]
    params = model.flat.unravel(case["w"])
    flat, plain = ravel_pytree(params)
    sliced = flat_mod.leaf_unravel(params)
    np.testing.assert_array_equal(flat, case["w"])

    def through(unravel):
        def f(w):
            return sum(jnp.sum(jnp.sin(leaf)) for leaf in
                       jax.tree_util.tree_leaves(unravel(w)))
        return f

    for a, b in zip(jax.tree_util.tree_leaves(plain(case["w"])),
                    jax.tree_util.tree_leaves(sliced(case["w"]))):
        np.testing.assert_array_equal(a, b)
    for unravel in (sliced, model.flat.unravel):
        np.testing.assert_array_equal(jax.grad(through(unravel))(case["w"]),
                                      jax.grad(through(plain))(case["w"]))


def test_gpt2_is_still_the_default_block_with_no_statistics():
    model = build(vocab=320, seq_len=32)
    assert type(model.module).__name__ == "TinyDecoder"
    assert model.value_grad_stats is None
    with pytest.raises(ValueError, match="unknown LM arch"):
        build(arch="mamba")


def test_stats_are_one_load_a_layer_between_even_and_worst(case):
    model = case["model"]
    (loss, stats), grad = jax.jit(model.value_grad_stats)(
        case["w"], case["tokens"])
    plain_loss, plain_grad = jax.jit(model.value_and_grad)(
        case["w"], case["tokens"])
    assert float(loss) == float(plain_loss)  # the same step, one more output
    np.testing.assert_array_equal(grad, plain_grad)
    load = np.asarray(stats["moe_load_max_over_mean"])
    assert load.shape == (TINY["n_layers"],)
    worst = TINY["n_experts"] / TINY["experts_per_tok"]
    assert np.all(load >= 1.0) and np.all(load <= worst)


# -- through the parameter server ---------------------------------------------------


def test_the_block_trains_through_two_servers_cut_between_experts():
    """The normal path in one process: ``LmTrainer`` with ``arch`` olmoe
    and server-side Adam, two server threads holding the planner's cut
    (inside a stacked expert leaf, between two experts).  The loss
    falls, every push is applied, and nothing of the model's size is
    left on the device by the shell beside the parameters."""
    import threading

    from mpit_tpu.comm.local import LocalRouter
    from mpit_tpu.lm import LmTrainer, plan
    from mpit_tpu.lm.model import build_kw
    from mpit_tpu.ps import ParamClient, ParamServer
    from mpit_tpu.train import launch

    steps = 12
    cfg = launch.LAUNCH_DEFAULTS.merged(
        lm=1, lm_arch="olmoe", lm_d_model=64, lm_heads=4, lm_layers=1,
        lm_seq=64, lm_vocab=320, lm_experts=8, lm_experts_per_tok=2,
        lm_expert_width=64, lm_use_flash=0, lm_steps=steps, lm_eval_every=4,
        batch=2, opt="adam", lr=3e-3, seed=5)
    tcfg = launch.lm_trainer_cfg(cfg)
    assert build_kw(tcfg)["arch"] == "olmoe"
    layout = launch.lm_layout(cfg, 2)
    model = build(use_flash=False, **build_kw(tcfg))
    segments = plan(model.flat.unravel(model.flat.w0), 2).segments
    inside = [s for s in segments if s.offset < layout[1].offset < s.end]
    assert inside and inside[0].unit == 64 * 64  # between two experts

    router = LocalRouter(3)
    servers = [ParamServer(r, [2], router.endpoint(r),
                           rule=launch.server_rule_for(cfg)) for r in (0, 1)]
    threads = [threading.Thread(target=s.start, daemon=True) for s in servers]
    for t in threads:
        t.start()
    client = ParamClient(2, [0, 1], router.endpoint(2), seed_servers=True,
                         layout=layout)
    try:
        trainer = LmTrainer(tcfg, pclient=client, rank=2)
        result = trainer.run()
    finally:
        for s in servers:
            s.live.stop()
        for t in threads:
            t.join(20)
    history = result["history"]
    assert history[-1]["avg_loss"] < history[0]["avg_loss"] - 0.1
    assert [s.grads_applied for s in servers] == [steps, steps]
    assert [(s.offset, s.size) for s in servers] == \
        [(s.offset, s.size) for s in layout]
    assert trainer.model.flat.w0 is None       # handed to the optimizer
    assert trainer.optimizer.accum is None     # su 1: nothing accumulates
