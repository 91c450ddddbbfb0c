"""The balanced sparse block on the normal path (``lm/model.py``
``build(arch="trinity")``: ``models/transformer.py`` ``TrinityDecoder``:
four norms a layer, a gated attention output, window layers with rotary
positions beside full layers with none, a sigmoid router whose
selection bias **moves by a rule of its own**) against its plain float32
reference, at the benchmark configuration's ``tiny`` size on seeded
weights; the rule (``parallel/moe.py`` ``balance_step``) against the
reference's numpy, to the bit; **the leaf no optimizer owns**: the
vector's plain ranges (``models/flat.py`` ``plain_ranges``) through the
local step, fused and unfused, and through the servers' rules.  The
reference exists once, as the benchmark's
``chipbench/reference/trinity_plain.py`` (no code shared with the
block), and is imported from there.

Tolerances.  On the CPU both sides multiply in full float32 and differ
by the rounding of sums taken in another order: 3e-7 of the gradient's
norm and 1e-6 nats as measured here.  The limits are 1e-5.  What they
must refuse, each tried below on the reference itself with one thing
wrong, is wrong by 1e-3 or more; what they cannot refuse, a wrong rule,
has a comparison of its own."""

import contextlib
import functools
import hashlib
import json
import pathlib
import re
import threading

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run as runner, spec as spec_mod
from chipbench.arithmetic import trinity as arithmetic
from chipbench.reference import trinity_plain as ref
from mpit_tpu import obs
from mpit_tpu.comm.local import LocalRouter
from mpit_tpu.ft import FTConfig, split_plain_tail, with_plain_tail
from mpit_tpu.lm import archs
from mpit_tpu.lm.model import build, build_kw
from mpit_tpu.models import transformer
from mpit_tpu.models.flat import plain_ranges
from mpit_tpu.ops.flash_attention import flash_attention
from mpit_tpu.optim import EAMSGD, MSGD, Downpour, RuleShell, rules
from mpit_tpu.optim.msgd import (
    MSGDConfig, msgd_commit, msgd_init, msgd_lookahead, msgd_params,
    msgd_step,
)
from mpit_tpu.parallel import moe
from mpit_tpu.ps import ParamClient, ParamServer
from mpit_tpu.ps.sharding import Shard

LOSS_TOL_NATS = 1e-5
GRAD_REL_TOL = 1e-5

ROOT = pathlib.Path(__file__).resolve().parent.parent
FILE = json.loads(
    (ROOT / "chipbench/configs/trinity-mini-26b-l5e8.json").read_text())
CONFIG = {**FILE, **FILE["tiny"]}  # the reference's keys, at the tiny size
RATE = CONFIG["load_balance_coeff"]


def sizes(c):
    """``build``'s keywords from the configuration's keys."""
    return dict(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], n_layers=c["num_hidden_layers"],
        layer_types=c["layer_types_here"], window=c["sliding_window"],
        dense_layers=c["num_dense_layers"], dense_width=c["intermediate_size"],
        seq_len=c["train_seq"], n_experts=c["router_experts"],
        experts_held=c["num_experts"], experts_first=c["experts_first"],
        experts_per_tok=c["num_experts_per_tok"],
        expert_width=c["moe_intermediate_size"],
        shared_experts=c["num_shared_experts"], route_scale=c["route_scale"],
        bias_rate=c["load_balance_coeff"], embed_scale=c["embed_scale"],
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"])


TINY = sizes(CONFIG)
BLOCK_FIELDS = ("d_model", "n_heads", "kv_heads", "head_dim", "dense_width",
                "n_experts", "experts_per_tok", "expert_width",
                "shared_experts", "route_scale", "bias_rate", "rope_theta",
                "norm_eps")
SPARSE = [f"TrinityBlock_{i}" for i in range(1, 5)]


def moved(model, scale=0.05, seed=0):
    """The seeded weights moved off their initial values: norm weights
    off 1, so that one that is ignored shows."""
    rs = np.random.RandomState(seed)
    return model.flat.w0 + scale * jnp.asarray(rs.randn(model.flat.size),
                                               jnp.float32)


def relative(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.fixture(scope="module")
def case():
    model = build(arch="trinity", seed=3, use_flash=False, **TINY)
    w = moved(model)
    tokens = jax.random.randint(jax.random.PRNGKey(7),
                                (2, TINY["seq_len"] + 1), 0, 256)
    with jax.default_matmul_precision("highest"):
        (loss, stats), grad = jax.jit(model.value_grad_stats)(w, tokens)
        ref_loss, ref_grad = ref.loss_and_grad_flat(
            w, model.flat.unravel, tokens, CONFIG)
        _, _, counted = jax.jit(
            lambda w: ref.loss_grads_counts(model.flat.unravel(w), tokens,
                                            CONFIG))(w)
    return dict(model=model, w=w, tokens=tokens, loss=loss, stats=stats,
                grad=grad, ref_loss=ref_loss, ref_grad=ref_grad,
                counted=counted)


# -- (a) the program against the plain reference -----------------------------------


def test_the_tiny_size_is_the_issues(case):
    assert (TINY["d_model"], TINY["n_heads"], TINY["kv_heads"],
            TINY["head_dim"], TINY["window"]) == (64, 4, 2, 16, 16)
    assert (TINY["n_experts"], TINY["experts_per_tok"],
            TINY["experts_held"], TINY["shared_experts"]) == (8, 2, 2, 1)
    assert TINY["layer_types"].split(",") == [
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention"]
    assert (TINY["dense_layers"], TINY["seq_len"], TINY["vocab"]) == (
        1, 64, 320)
    assert len(case["model"].flat.plain) == 4


def test_loss_and_flat_gradient_equal_the_plain_references(case):
    assert abs(float(case["loss"]) - float(case["ref_loss"])) < LOSS_TOL_NATS
    assert relative(case["grad"], case["ref_grad"]) < GRAD_REL_TOL


def test_the_plain_loss_and_the_loss_with_statistics_are_one_number(case):
    with jax.default_matmul_precision("highest"):
        loss, grad = jax.jit(case["model"].value_and_grad)(
            case["w"], case["tokens"])
    assert float(loss) == float(case["loss"])
    assert np.array_equal(np.asarray(grad), np.asarray(case["grad"]))


def test_every_leaf_of_the_gradient_is_inside_the_tolerance(case):
    unravel = case["model"].flat.unravel
    got, want = unravel(case["grad"]), unravel(case["ref_grad"])
    checked = 0
    for block, leaves in want.items():
        for name, leaf in (leaves.items() if isinstance(leaves, dict)
                           else [(block, leaves)]):
            mine = got[block][name] if isinstance(leaves, dict) else got[block]
            assert float(jnp.linalg.norm(mine - leaf)) <= 2e-5 * max(
                float(jnp.linalg.norm(leaf)), 1e-3), (block, name)
            checked += 1
    # a dense layer of 14 leaves, four sparse of 19, table, norm, head
    assert checked == 14 + 4 * 19 + 3


def test_the_kernel_in_the_block_changes_no_number(case):
    """The flash kernels (interpret mode), windowed and full, in place
    of the materialised attention, in every layer."""
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
    for start, stop in case["model"].flat.plain:   # the rule's slots too
        assert np.array_equal(np.asarray(grad[start:stop]),
                              np.asarray(case["ref_grad"][start:stop]))


WRONG_CONFIG = {
    "the input left unscaled": {"mup_enabled": False},
    "rotary positions on the full layer too": {
        "layer_types": ["sliding_attention"] * 5, "sliding_window": 1 << 20},
    "the window left out": {"sliding_window": 1 << 20},
    "a window of half the size": {"sliding_window": 8},
    "the route scale left out": {"route_scale": 1.0},
    "the chosen scores not normalised": {"route_norm": False},
    "the shared expert left out": {"num_shared_experts": 0},
    "another rotary base": {"rope_theta": 500.0},
}
WRONG_CODE = {
    "no rotary positions on the window layers": {
        "rotate": lambda x, theta: x},
    "the bias left out of the selection": {
        "router_gates": lambda h, router, bias, config, gates=ref.router_gates:
        gates(h, router, 0.0 * bias, config)},
}


@pytest.mark.parametrize("what", sorted(WRONG_CONFIG))
def test_the_tolerance_refuses_another_function(case, what):
    loss, grad = ref.loss_and_grad_flat(
        case["w"], case["model"].flat.unravel, case["tokens"],
        {**CONFIG, **WRONG_CONFIG[what]})
    assert (abs(float(loss) - float(case["ref_loss"])) > LOSS_TOL_NATS
            or relative(grad, case["ref_grad"]) > GRAD_REL_TOL), what
    assert relative(grad, case["ref_grad"]) > 1e-3, what


@pytest.mark.parametrize("what", sorted(WRONG_CODE))
def test_the_tolerance_refuses_a_part_left_out(case, what, monkeypatch):
    for name, fn in WRONG_CODE[what].items():
        monkeypatch.setattr(ref, name, fn)
    loss, grad = ref.loss_and_grad_flat(
        case["w"], case["model"].flat.unravel, case["tokens"], CONFIG)
    assert relative(grad, case["ref_grad"]) > 1e-3, what


def test_the_rules_own_comparison_refuses_what_the_norm_may_not(case):
    """One expert's step with the wrong sign moves the flat gradient by
    ``2 rate`` in one of its elements, which at the published widths is
    under the chip's tolerance of the gradient's norm
    (``chipbench/reference/probe_trinity.py`` prints both): the rule's
    own comparison refuses it, and a doubled rate, and counts that are
    off by more than the near ties."""
    model = case["model"]
    for block, (start, stop) in zip(SPARSE, model.flat.plain):
        counts = np.asarray(case["counted"][block])
        step = -np.asarray(case["grad"][start:stop])
        held = ref.rule_agrees(counts, counts, step, RATE, near_ties=0)
        assert held == {"counts_max_off": 0, "counts_ok": True,
                        "own_step_exact": True,
                        "clear_experts": held["clear_experts"],
                        "clear_signs_ok": True}
        assert held["clear_experts"] >= 6
        flipped = step.copy()
        flipped[int(np.argmax(np.abs(counts - counts.mean())))] *= -1
        assert not ref.rule_agrees(counts, counts, flipped, RATE,
                                   near_ties=0)["own_step_exact"]
        assert not ref.rule_agrees(counts, counts, 2 * step, RATE,
                                   near_ties=0)["own_step_exact"]
        # another stream's counts: two rows' choices fell elsewhere
        other = counts.copy()
        other[[0, 1]] += [2, -2]
        assert ref.rule_agrees(counts, other, step, RATE,
                               near_ties=2)["counts_ok"]
        assert not ref.rule_agrees(counts, other, step, RATE,
                                   near_ties=1)["counts_ok"]
        swapped = counts[::-1].copy()
        assert not ref.rule_agrees(counts, swapped, step, RATE,
                                   near_ties=0)["clear_signs_ok"]


def test_the_counters(case):
    stats = case["stats"]
    assert set(stats) == set(transformer.JOYAI_MOE_STATS) | set(
        transformer.BIAS_RULE_STATS)
    for name in stats:
        assert stats[name].shape == (4,), name
    params = case["model"].flat.unravel(case["w"])
    for i, block in enumerate(SPARSE):
        counts = np.asarray(case["counted"][block])
        assert counts.sum() == 2 * 64 * 2          # rows x k
        assert float(stats["moe_load_max_over_mean"][i]) == pytest.approx(
            counts.max() * 8 / counts.sum())
        assert float(stats["moe_bias_abs_mean"][i]) == pytest.approx(
            float(jnp.mean(jnp.abs(params[block]["router_bias"]))))
        assert float(stats["moe_bias_step_nonzero_share"][i]) == \
            pytest.approx(np.mean(counts * 8 != counts.sum()))
    assert float(jnp.min(stats["moe_bias_flips_share"])) > 0.0


# -- (b) the rule --------------------------------------------------------------------


@pytest.mark.parametrize("experts,rows,seed", [
    (8, 128, 0), (128, 65536, 1), (128, 65536, 2), (64, 4096, 3), (12, 77, 4),
    (128, 128, 5)])
def test_the_rule_is_the_references_numpy_to_the_bit(experts, rows, seed):
    rs = np.random.RandomState(seed)
    chosen = rs.randint(0, experts, size=(rows // 8, 8)).astype(np.int32)
    counts = moe.expert_counts(jnp.asarray(chosen), experts)
    assert counts.dtype == jnp.int32
    assert np.array_equal(np.asarray(counts),
                          np.bincount(chosen.reshape(-1), minlength=experts))
    step = np.asarray(jax.jit(moe.balance_step, static_argnums=1)(
        counts, RATE))
    want = ref.balance_step(np.asarray(counts), RATE)
    assert step.dtype == want.dtype == np.float32
    assert np.array_equal(step, want)
    assert np.array_equal(
        np.asarray(ref.balance_step(counts, RATE, jnp)), want)
    assert abs(float(step.astype(np.float64).sum())) < 1e-8
    assert float(np.abs(step).max()) <= 2 * RATE


def test_a_count_on_the_mean_has_no_sign_and_takes_the_centring_alone():
    counts = jnp.asarray([4, 4, 1, 7, 4, 6, 2, 4], jnp.int32)   # mean 4
    step = np.asarray(moe.balance_step(counts, RATE))
    signs = np.asarray([0, 0, 1, -1, 0, -1, 1, 0], np.float32)
    assert np.array_equal(step, np.float32(RATE) * (signs - signs.mean()))
    even = np.asarray(moe.balance_step(jnp.full((8,), 5, jnp.int32), RATE))
    assert not even.any()                # every count on the mean: no step
    lopsided = np.asarray(moe.balance_step(
        jnp.asarray([9, 1, 1, 1], jnp.int32), RATE))
    assert np.allclose(lopsided, RATE * np.asarray([-1.5, 0.5, 0.5, 0.5]))


def test_the_load_statistic_is_a_function_of_the_one_count():
    chosen = jnp.asarray([[0, 1], [0, 2], [0, 3]], jnp.int32)
    counts = moe.expert_counts(chosen, 4)
    assert np.asarray(counts).tolist() == [3, 1, 1, 1]
    assert float(moe.load_max_over_mean(counts, chosen.size)) == 2.0


def test_no_gradient_reaches_the_bias_and_its_slot_is_minus_the_step(case):
    model, grad = case["model"], case["grad"]
    for block, (start, stop) in zip(SPARSE, model.flat.plain):
        want = ref.balance_step(np.asarray(case["counted"][block]), RATE)
        assert np.array_equal(np.asarray(grad[start:stop]), -want)
    # with the rate at 0 the block is JoyAI's: the slots are zero
    still = build(arch="trinity", seed=3, use_flash=False,
                  **{**TINY, "bias_rate": 0.0})
    assert still.flat.plain == () and not hasattr(still.value_and_grad,
                                                  "plain")
    _, g = jax.jit(still.value_and_grad)(case["w"], case["tokens"])
    for start, stop in model.flat.plain:
        assert not np.asarray(g[start:stop]).any()


def test_the_plain_ranges_are_the_bias_leaves_extents(case):
    model = case["model"]
    params = model.flat.unravel(model.flat.w0)
    assert model.flat.plain == plain_ranges(params)
    assert model.value_and_grad.plain == model.value_grad_stats.plain == \
        model.flat.plain
    for block, (start, stop) in zip(SPARSE, model.flat.plain):
        assert stop - start == TINY["n_experts"]
        assert np.array_equal(np.asarray(model.flat.w0[start:stop]),
                              np.asarray(params[block]["router_bias"]))
    assert rules.plain_of(lambda w: w) == ()


def synthetic(plain, n, experts=8, held=False):
    """A stand-in for a model's ``value_and_grad`` over a vector of
    ``n``: a cheap loss whose gradient depends on ``w`` and on the
    step's number ``t``, and in the plain ranges minus the rule's step
    of counts drawn from ``t``; ``held``: zeros there instead (a block
    whose bias nothing moves)."""
    def counts(t, i):
        key = jax.random.fold_in(jax.random.PRNGKey(11), 64 * t + i)
        return jax.random.randint(key, (experts,), 0, 60, jnp.int32)

    def vgf(w, t):
        g = 1e-2 * w + 1e-2 * jnp.sin(0.01 * jnp.arange(n) + t)
        for i, (start, stop) in enumerate(plain):
            step = moe.balance_step(counts(t, i), RATE)
            g = g.at[start:stop].set(0.0 * step if held else -step)
        return 0.5e-2 * jnp.sum(w * w), g

    vgf.counts = counts
    if not held:
        vgf.plain = plain
    return vgf


def steps_of(vgf, t):
    """The rule's steps of the synthetic step ``t``, in numpy by the
    reference's twin, a plain range each."""
    return [ref.balance_step(np.asarray(vgf.counts(t, i)), RATE)
            for i in range(len(vgf.plain))]


PLAIN = ((40, 48), (300, 308), (1400, 1408), (2000, 2008))
N = 2560


@pytest.mark.parametrize("fused", [False, True], ids=["unfused", "fused"])
def test_k_local_steps_move_the_bias_by_the_sum_of_the_steps_exactly(fused):
    """``mom`` 0.9 and ``l2wd`` > 0: the plain ranges take neither, nor
    the learning rate; their velocity stays zero; every other element is
    what the same step gives without plain ranges."""
    cfg = MSGDConfig(lr=0.05, mom=0.9, l2wd=1e-3, use_fused=fused)
    vgf, bare = synthetic(PLAIN, N), synthetic(PLAIN, N, held=True)
    w0 = jnp.asarray(np.random.RandomState(0).randn(N), jnp.float32)
    step = jax.jit(lambda w, s, t: msgd_step(vgf, w, s, cfg, t))
    step_bare = jax.jit(lambda w, s, t: msgd_step(bare, w, s, cfg, t))
    inside = np.zeros(N, bool)
    for start, stop in PLAIN:
        inside[start:stop] = True
    # the kernel's sweep is one program either way: to the bit.  XLA's
    # own fusions of two programs may contract a multiply-add
    # differently: to a rounding
    same = np.array_equal if fused else functools.partial(
        np.testing.assert_allclose, rtol=3e-7, atol=1e-9)
    w, state = w0, msgd_init(w0)
    want = [np.asarray(w0[start:stop]) for start, stop in PLAIN]
    for t in range(5):
        # the same pair through the step without plain ranges: every
        # other element is its result
        w_bare, state_bare, _ = step_bare(w, state, jnp.int32(t))
        w, state, _ = step(w, state, jnp.int32(t))
        assert same(np.asarray(w)[~inside],
                    np.asarray(w_bare)[~inside]) is not False
        assert same(np.asarray(state["vt"])[~inside],
                    np.asarray(state_bare["vt"])[~inside]) is not False
        want = [b + d for b, d in zip(want, steps_of(vgf, t))]
        committed = np.asarray(msgd_params(w, state, cfg))
        for (start, stop), b in zip(PLAIN, want):
            assert np.array_equal(committed[start:stop], b)
            assert np.array_equal(np.asarray(w[start:stop]), b)
            assert not np.asarray(state["vt"][start:stop]).any()
    assert not np.array_equal(want[0], np.asarray(w0[40:48]))


def test_without_plain_ranges_the_step_is_traced_as_it_was():
    cfg = MSGDConfig(lr=0.05, mom=0.9, l2wd=1e-3, use_fused=False)
    bare = synthetic(PLAIN, N, held=True)
    w0 = jnp.zeros((N,), jnp.float32)
    text = str(jax.make_jaxpr(
        lambda w, s, t: msgd_step(bare, w, s, cfg, t))(
            w0, msgd_init(w0), jnp.int32(0)))
    assert "bias_rule" not in text
    two = jax.make_jaxpr(lambda w, g, s: msgd_commit(w, g, s, cfg))(
        w0, w0, msgd_init(w0))
    same = jax.make_jaxpr(lambda w, g, s: msgd_commit(w, g, s, cfg, ()))(
        w0, w0, msgd_init(w0))
    assert str(two) == str(same)


def test_the_models_local_steps_move_the_bias_by_its_own_steps(case):
    """The real block through ``MSGD`` (the trainer's optimizer), three
    steps: each moves every bias by minus its slot of that step's
    gradient, exactly, whatever ``lr``, ``mom`` and ``l2wd``."""
    model = case["model"]
    cfg = MSGDConfig(lr=0.1, mom=0.9, l2wd=1e-3, use_fused=False)
    vgf = jax.jit(model.value_and_grad)
    opt = MSGD(cfg, model.value_grad_stats, has_aux=True)
    w = case["w"]
    state = msgd_init(w)
    for t in range(3):
        tokens = jnp.roll(case["tokens"], t, axis=1)
        w_la, _ = msgd_lookahead(w, state, cfg)
        _, grad = vgf(w_la, tokens)
        before = np.asarray(w)
        w, _loss = opt.step(w, tokens)
        state = opt.state
        for start, stop in model.flat.plain:
            assert np.array_equal(
                np.asarray(w[start:stop]),
                before[start:stop] - np.asarray(grad[start:stop]))
            assert np.abs(np.asarray(grad[start:stop])).max() > 0
            assert not np.asarray(state["vt"][start:stop]).any()
    assert np.abs(np.asarray(state["vt"])).max() > 0


# -- (c) through the servers ------------------------------------------------------------


@contextlib.contextmanager
def gang(layout, rule, nclients=1, chunk_bytes=0, server_plain=None):
    """Two servers on threads and ``nclients`` clients over the
    in-process router, the vector cut by ``layout``."""
    nservers = len(layout)
    n = nservers + nclients
    router = LocalRouter(n)
    sranks, cranks = list(range(nservers)), list(range(nservers, n))
    ft = FTConfig(op_deadline_s=30.0, chunk_bytes=chunk_bytes) \
        if chunk_bytes else None
    made = (rules.make(rule, plain=server_plain or (), lr=0.01)
            if rule != "add" else rules.make("add"))
    servers = [ParamServer(r, cranks, router.endpoint(r), rule=made, ft=ft)
               for r in sranks]
    threads = [threading.Thread(target=s.start, daemon=True) for s in servers]
    for t in threads:
        t.start()
    clients = [ParamClient(r, sranks, router.endpoint(r),
                           seed_servers=(r == cranks[0]), layout=layout,
                           ft=ft) for r in cranks]
    try:
        yield servers, clients
    finally:
        for s in servers:
            s.live.stop()
        for t in threads:
            t.join(5)


def the_cut(plain, n):
    """Two shards: the first two plain ranges on server 0, the others on
    server 1, and server 1's first range astride a chunk boundary of the
    chunked stream (chunks of 1024 elements from the shard's start)."""
    start = plain[2][0] + 4 - 1024
    assert plain[1][1] <= start
    return [Shard(0, start), Shard(start, n - start)]


PS_CASES = [
    # what, shell, server rule, su, chunk bytes
    ("downpour su 1 on plain add", "downpour", "add", 1, 0),
    ("downpour su 2 on plain add", "downpour", "add", 2, 0),
    ("downpour su 1, chunked", "downpour", "add", 1, 4096),
    ("adam su 1", "rule", "adam", 1, 0),
    ("adam su 2", "rule", "adam", 2, 0),
    ("rmsprop su 1", "rule", "rmsprop", 1, 0),
    ("rmsprop su 2, chunked: a range astride a chunk", "rule", "rmsprop", 2,
     4096),
    ("adadelta su 1, chunked", "rule", "adadelta", 1, 4096),
]


def shell_of(kind, vgf, client, su):
    if kind == "downpour":
        return Downpour(vgf, client, lr=0.05, l2wd=1e-3, su=su)
    return RuleShell(vgf, client, su=su, mode="global")


def pushed(kind, su, vgf, steps):
    """What the shell ships for the plain ranges at each sync, in the
    shell's own float32 arithmetic: ``[(range -> array), ...]``."""
    out, acc = [], None
    for t in range(steps):
        d = steps_of(vgf, t)
        term = d if kind == "downpour" else [-x for x in d]
        if su == 1:
            out.append(term)
            continue
        acc = term if acc is None else [a + x for a, x in zip(acc, term)]
        if t % su == 0:      # the first step syncs: what has accumulated
            out.append(acc)
            acc = [np.zeros_like(x) for x in acc]
    return out


@pytest.mark.parametrize("what,kind,rule,su,chunk", PS_CASES,
                         ids=[c[0] for c in PS_CASES])
def test_the_servers_move_the_bias_by_the_pushed_steps_and_keep_no_slot(
        what, kind, rule, su, chunk):
    """One worker and two servers; a plain range on each server, one
    astride a chunk boundary.  The master copy's plain ranges move by
    exactly the sum of the pushed steps, the servers' slots there stay
    zero, the pull brings the moved bias back, and every other element
    is what the same gang gives without plain ranges."""
    steps = 5
    vgf, bare = synthetic(PLAIN, N), synthetic(PLAIN, N, held=True)
    layout = the_cut(PLAIN, N)
    assert (PLAIN[2][0] - layout[1].offset) // 1024 != (
        PLAIN[2][1] - 1 - layout[1].offset) // 1024
    w0 = jnp.asarray(np.random.RandomState(1).randn(N), jnp.float32)
    inside = np.zeros(N, bool)
    for start, stop in PLAIN:
        inside[start:stop] = True

    def run(fn):
        with gang(layout, rule, chunk_bytes=chunk) as (servers, (client,)):
            shell = shell_of(kind, fn, client, su)
            w = shell.start(w0)
            for t in range(steps):
                w, _ = shell.step(w, jnp.int32(t))
            shell.stop()
            master = np.concatenate([np.asarray(s.param) for s in servers])
            slots = {name: np.concatenate(
                [np.asarray(s.rule_state[name]) for s in servers])
                for name, leaf in (servers[0].rule_state or {}).items()
                if np.ndim(leaf)}
            return np.asarray(w), master, slots, [s.rule for s in servers]

    w, master, slots, made = run(vgf)
    w_bare, master_bare, slots_bare, made_bare = run(bare)
    assert all(r.plain == (PLAIN if rule != "add" else ()) for r in made)
    assert all(r.plain == () for r in made_bare)
    want = [np.asarray(w0[start:stop]) for start, stop in PLAIN]
    for push in pushed(kind, su, vgf, steps):
        want = [b + x if kind == "downpour" else b - x
                for b, x in zip(want, push)]
    synced = su == 1 or (steps - 1) % su == 0
    for (start, stop), b in zip(PLAIN, want):
        assert np.array_equal(master[start:stop], b), what
        if synced:                      # the last step pulled
            assert np.array_equal(w[start:stop], b), what
        for name, slot in slots.items():
            assert not slot[start:stop].any(), (what, name)
    # (two programs on the worker and on the servers: XLA may contract a
    # multiply-add in one of them, so to a rounding and not to the bit)
    np.testing.assert_allclose(master[~inside], master_bare[~inside],
                               rtol=2e-6, atol=1e-8)
    for name, slot in slots.items():
        np.testing.assert_allclose(slot[~inside], slots_bare[name][~inside],
                                   rtol=2e-5, atol=1e-8)
        assert np.abs(slot[~inside]).max() > 0
    assert not np.array_equal(master[40:48], np.asarray(w0[40:48]))


@pytest.mark.parametrize("kind,rule", [("downpour", "add"), ("rule", "adam")])
def test_two_workers_pushes_add(kind, rule):
    vgf = synthetic(PLAIN, N)
    layout = the_cut(PLAIN, N)
    w0 = jnp.asarray(np.random.RandomState(2).randn(N), jnp.float32)
    with gang(layout, rule, nclients=2) as (servers, clients):
        shells = [shell_of(kind, vgf, client, 1) for client in clients]
        ws = [None, None]

        def begin(i):   # a server serves nobody before all have announced
            ws[i] = shells[i].start(w0)

        starters = [threading.Thread(target=begin, args=(i,), daemon=True)
                    for i in range(2)]
        for t in starters:
            t.start()
        for t in starters:
            t.join(60)
        assert not any(t.is_alive() for t in starters)
        order = [(0, 0), (1, 5), (1, 6), (0, 1), (1, 7)]   # worker, step
        for worker, t in order:
            ws[worker], _ = shells[worker].step(ws[worker], jnp.int32(t))
        for shell in shells:
            shell.stop()
        master = np.concatenate([np.asarray(s.param) for s in servers])
    want = [np.asarray(w0[start:stop]) for start, stop in PLAIN]
    for _worker, t in order:
        want = [b + d for b, d in zip(want, steps_of(vgf, t))]
    for (start, stop), b in zip(PLAIN, want):
        assert np.array_equal(master[start:stop], b)
        assert np.array_equal(np.asarray(ws[1][start:stop]), b)


def test_easgd_moves_the_bias_elastically_and_the_local_rule_still_applies():
    vgf = synthetic(PLAIN, N)
    layout = the_cut(PLAIN, N)
    w0 = jnp.asarray(np.random.RandomState(3).randn(N), jnp.float32)
    mva = 0.25
    with gang(layout, "add") as (servers, (client,)):
        opt = EAMSGD(vgf, client, lr=0.05, mom=0.9, l2wd=1e-3, mva=mva, su=1)
        w = opt.start(w0)
        local = [np.asarray(w0[start:stop]) for start, stop in PLAIN]
        center = [b.copy() for b in local]
        for t in range(4):
            w, _ = opt.step(w, jnp.int32(t))
            sug = [np.float32(mva) * (b - c) for b, c in zip(local, center)]
            center = [c + s for c, s in zip(center, sug)]
            local = [b + d - s for b, d, s in zip(local, steps_of(vgf, t),
                                                  sug)]
            for (start, stop), b in zip(PLAIN, local):
                assert np.allclose(np.asarray(w[start:stop]), b, rtol=0,
                                   atol=2e-7)
                assert not np.asarray(opt.state["vt"][start:stop]).any()
        opt.stop()
        master = np.concatenate([np.asarray(s.param) for s in servers])
    for (start, stop), c in zip(PLAIN, center):
        assert np.allclose(master[start:stop], c, rtol=0, atol=2e-7)
    assert np.abs(center[0] - np.asarray(w0[40:48])).max() > 1e-4


def test_the_announcement_carries_the_ranges_and_no_other_model_has_a_tail():
    cinfo = np.asarray([100, 50, 0], np.int64)
    assert with_plain_tail(cinfo, ()) is cinfo
    sent = with_plain_tail(cinfo, PLAIN)
    head, plain = split_plain_tail(sent)
    assert np.array_equal(head, cinfo) and plain == PLAIN
    assert split_plain_tail(cinfo)[1] == ()
    with pytest.raises(ValueError):
        split_plain_tail(sent[5:])   # a tail that lost two of its words


def test_a_rounding_codec_is_refused_with_plain_ranges():
    router = LocalRouter(2)
    client = ParamClient(1, [0], router.endpoint(1), codec="int8")
    with pytest.raises(ValueError, match="plain ranges"):
        client.announce_plain(PLAIN)
    client.announce_plain(())   # no ranges: nothing to refuse


def test_the_model_through_the_servers_moves_the_bias_there(case):
    """The real block, the trainer's own shell (``LmTrainer.optimizer``
    under ``--opt adam``), two servers: the master copy's biases move by
    the pushed steps, the servers' slots there stay zero."""
    from mpit_tpu.lm import LmTrainer
    from mpit_tpu.lm.plan import plan
    from mpit_tpu.train import launch

    cfg = launch.lm_trainer_cfg(launch.LAUNCH_DEFAULTS.merged(
        np=3, opt="adam", lr=3e-3, batch=2, lm_steps=3, **LAUNCH))
    model = case["model"]
    layout = plan(model.flat.unravel(model.flat.w0), 2, rule="adam").layout
    on = [sum(s.offset <= start < s.end for start, _ in model.flat.plain)
          for s in layout]
    assert min(on) >= 1          # a bias leaf on each server
    with gang(layout, "adam") as (servers, (client,)):
        trainer = LmTrainer(cfg, pclient=client, rank=2)
        w0 = np.asarray(trainer.w)
        result = trainer.run()
        master = np.concatenate([np.asarray(s.param) for s in servers])
        m = np.concatenate([np.asarray(s.rule_state["m"]) for s in servers])
        assert all(s.rule.plain == model.flat.plain for s in servers)
    assert result["steps"] == 3
    for start, stop in model.flat.plain:
        moved_by = master[start:stop] - w0[start:stop]
        assert np.abs(moved_by).max() <= 3 * 2 * RATE * (1 + 1e-5)
        assert np.abs(moved_by).max() >= RATE * 0.5
        assert not m[start:stop].any()
        assert np.array_equal(np.asarray(trainer.w[start:stop]),
                              master[start:stop])
    assert np.abs(m).max() > 0


# -- (d) positions and masks ---------------------------------------------------------------


def test_a_full_layer_is_position_free_and_a_window_layer_is_not():
    """The rows' positions turned by a constant: a window layer's
    rotated queries and keys change, a full layer's do not exist, so its
    scores are what they were."""
    b, length, d = 1, 24, TINY["d_model"]
    kw = dict(heads=4, kv_heads=2, head_dim=16)
    seen = {}

    def attn(q, k, v, window=None):
        seen["q"], seen["k"] = q, k
        return jnp.zeros(q.shape)

    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    h = jax.random.normal(keys[0], (b, length, d))
    wq, wg = (jax.random.normal(k, (d, 64)) for k in keys[1:3])
    wk, wv = (jax.random.normal(k, (d, 32)) for k in keys[3:5])
    wo = jax.random.normal(keys[5], (64, d))
    for window, inv_freq in ((8, transformer.plain_inv_freq(16, 10000.0)),
                             (0, None)):
        transformer.grouped_attention(
            h, wq, wk, wv, wo, inv_freq=inv_freq, attn=attn, window=window,
            gate=wg, **kw)
        here = dict(seen)
        # the same rows three positions later: a longer sequence's tail
        pad = jnp.concatenate([jnp.zeros((b, 3, d)), h], axis=1)
        transformer.grouped_attention(
            pad, wq, wk, wv, wo, inv_freq=inv_freq, attn=attn, window=window,
            gate=wg, **kw)
        same = all(np.allclose(np.asarray(seen[x][:, 3:]),
                               np.asarray(here[x]), atol=1e-6) for x in "qk")
        assert same == (window == 0)


MASK_CASES = [(300, 40, 64, 128), (256, 64, 64, 128), (520, 200, 128, 256),
              (130, 130, 64, 128), (200, 1, 64, 128), (96, 16, None, None)]


@pytest.mark.parametrize("length,window,bq,bk", MASK_CASES)
def test_the_window_is_the_references_mask_element_by_element(
        length, window, bq, bk):
    """``i - W < j <= i``: the kernels' walk (interpreted) against a
    softmax over the reference's materialised pairs, and the pairs
    themselves."""
    mask = np.asarray(ref.live_pairs(length, window))
    i, j = np.indices((length, length))
    assert np.array_equal(mask, (j <= i) & (j > i - window))
    assert mask.sum() == arithmetic.window_pairs(length, window)
    keys = jax.random.split(jax.random.PRNGKey(length), 3)
    q = jax.random.normal(keys[0], (1, 4, length, 16))
    k = jax.random.normal(keys[1], (1, 2, length, 16))
    v = jax.random.normal(keys[2], (1, 2, length, 16))

    def dense(q, k, v):
        k, v = (jnp.repeat(x, 2, axis=1) for x in (k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / 4.0
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), -1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    def kernels(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=bq, block_k=bk, interpret=True,
                               precision="highest")

    with jax.default_matmul_precision("highest"):
        got, want = kernels(q, k, v), dense(q, k, v)
        # a key's gradient is zero for every query that does not see it:
        # the last key is seen by the last min(window, 1) .. rows alone
        d_got = jax.grad(lambda k: jnp.sum(kernels(q, k, v) ** 2))(k)
        d_want = jax.grad(lambda k: jnp.sum(dense(q, k, v) ** 2))(k)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    assert float(jnp.max(jnp.abs(d_got - d_want))) < 1e-4


# -- (e) the gate ----------------------------------------------------------------------------


def test_with_a_zero_gate_the_branch_is_half_the_ungated_one(case):
    b, length, d = 2, 32, TINY["d_model"]
    kw = dict(heads=4, kv_heads=2, head_dim=16,
              inv_freq=transformer.plain_inv_freq(16, 10000.0),
              attn=transformer.default_attn(use_flash=False), window=8)
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    h = jax.random.normal(keys[0], (b, length, d))
    wq, wg = (0.1 * jax.random.normal(k, (d, 64)) for k in keys[1:3])
    wk, wv = (0.1 * jax.random.normal(k, (d, 32)) for k in keys[3:5])
    wo = jax.random.normal(keys[5], (64, d))
    with jax.default_matmul_precision("highest"):
        plain = transformer.grouped_attention(h, wq, wk, wv, wo, **kw)
        half = transformer.grouped_attention(h, wq, wk, wv, wo,
                                             gate=0.0 * wg, **kw)
        gated = transformer.grouped_attention(h, wq, wk, wv, wo, gate=wg,
                                              **kw)
    assert np.allclose(np.asarray(half), 0.5 * np.asarray(plain), atol=1e-5)
    assert float(jnp.max(jnp.abs(gated - half))) > 1e-3


# -- (f) the shares add up to the whole layer; every share takes the same step -------------


def test_the_shares_parts_are_the_whole_layer_and_every_share_counts_alike():
    """The guide's share test on one sparse layer: the layer over all its
    experts, by the plain reference, is the sum of what each share's
    block computes for its own experts, the shared expert and the stream
    counted once; and the router is whole on every share, so every
    share's counts, and the step the rule makes of them, are the same."""
    c = {**CONFIG, "num_experts": CONFIG["router_experts"],
         "experts_first": 0, "num_hidden_layers": 1, "num_dense_layers": 0,
         "layer_types": ["sliding_attention"], "mup_enabled": False}
    n, held = c["router_experts"], CONFIG["num_experts"]
    kw = {name: TINY[name] for name in BLOCK_FIELDS}
    kw.update(window=TINY["window"], sparse=True,
              attn_fn=transformer.default_attn(use_flash=False))
    whole = transformer.TrinityBlock(**kw)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 40, c["hidden_size"]))
    params = whole.init(jax.random.PRNGKey(5), x)["params"]
    params = jax.tree_util.tree_map(
        lambda p: p + 0.05 * jax.random.normal(jax.random.PRNGKey(p.size),
                                               p.shape), params)
    # the out-norm's weight at one: the norm of a sum is not the sum of
    # norms, so the parts are taken before it
    experts = ("experts_gate", "experts_up", "experts_down")

    class Branch(nn.Module):
        """A sparse layer's MLP branch before its out-norm (the norm of
        a sum is not the sum of norms, so the parts are taken here)."""
        experts_first: int
        experts_held: int
        d_model: int = kw["d_model"]
        n_experts: int = kw["n_experts"]
        experts_per_tok: int = kw["experts_per_tok"]
        expert_width: int = kw["expert_width"]
        shared_experts: int = kw["shared_experts"]
        route_scale: float = kw["route_scale"]
        bias_rate: float = kw["bias_rate"]
        norm_eps: float = kw["norm_eps"]

        @nn.compact
        def __call__(self, x):
            return transformer.shared_sparse_experts(
                self, x, self.param("mlp_norm", nn.initializers.ones,
                                    (self.d_model,)))[0]

    def branch(first, count, routed=1.0, shared=1.0):
        """The sparse branch's output before its out-norm, and the
        bias's slot of its gradient."""
        block = Branch(experts_first=first, experts_held=count)
        p = {name: params[name] for name in (
            "mlp_norm", "router", "router_bias", "shared_gate", "shared_up",
            "shared_down")}
        for name in experts:
            p[name] = params[name][first:first + count]
        p["experts_down"] = p["experts_down"] * routed
        p["shared_down"] = p["shared_down"] * shared

        def out(p):
            return block.apply({"params": p}, x)

        y = jax.jit(out)(p)
        slot = jax.jit(jax.grad(lambda p: jnp.sum(out(p))))(p)["router_bias"]
        return y, slot

    with jax.default_matmul_precision("highest"):
        shared, _ = branch(0, held, routed=0.0)
        parts = [branch(first, held, shared=0.0)
                 for first in range(0, n, held)]
        full, full_slot = branch(0, n)
        h = ref.rms_norm(x, params["mlp_norm"], c["rms_norm_eps"])
        want, counts = ref.sparse_mlp(h.reshape(-1, h.shape[-1]), params, c)
    routed = [y for y, _ in parts]
    assert len(routed) == 4
    assert all(float(jnp.max(jnp.abs(part))) > 1e-3 for part in routed)
    assert float(jnp.max(jnp.abs(shared))) > 1e-3
    total = shared + sum(routed)
    assert float(jnp.max(jnp.abs(total - full))) < 1e-5
    assert float(jnp.max(jnp.abs(total.reshape(want.shape) - want))) < 1e-5
    step = -ref.balance_step(np.asarray(counts), RATE)
    assert np.array_equal(np.asarray(full_slot), step)
    for _, slot in parts:
        assert np.array_equal(np.asarray(slot), step)


def test_the_router_takes_8_of_128_by_sigmoid_plus_bias_scaled():
    rs = np.random.RandomState(1)
    logits = jnp.asarray(rs.randn(50, 128), jnp.float32)
    bias = jnp.asarray(0.5 * rs.randn(128), jnp.float32)
    scores = jax.nn.sigmoid(logits)
    weights, chosen = moe.route_top_k(
        scores, 8, renormalise=True, bias=bias,
        eps=transformer.JOYAI_ROUTE_EPS, scale=FILE["route_scale"])
    c = {**FILE, "num_experts_per_tok": 8}
    gates, picked = ref.router_gates(logits, jnp.eye(128), bias, c)
    gates = np.asarray(gates)
    assert np.allclose(np.asarray(jnp.sum(weights, axis=-1)),
                       FILE["route_scale"], rtol=1e-5)
    rows = np.arange(50)[:, None]
    assert np.allclose(gates[rows, np.asarray(chosen)], np.asarray(weights),
                       rtol=1e-4)
    assert np.count_nonzero(gates) == 50 * 8
    assert np.array_equal(np.asarray(jnp.sum(picked, 0)),
                          np.asarray(moe.expert_counts(chosen, 128)))
    assert float(moe.bias_flips_share(scores, chosen)) > 0.05


# -- (g) every other block's step and every server's apply are the parent's ----------------

# ``tests/test_keye.py`` and ``tests/test_sdar.py`` hold seven cells'
# tiny steps to their parents' digests; here the eighth block's (sha256
# of the same text, as the parent commit of PR 53 printed it; since PR
# 57 that PR's, as ``tests/test_keye.py`` says), the local
# step that commits them, and the servers' applies.
PARENTS_STEP = {
    "sdar-l6e8-local": "32a5060c8aaa8cc9",
}
PARENTS_MSGD_STEP = {
    "joyai-l5e8-local": "1cf44eeef5aa13d1",
}


def tiny_loss(cell_name):
    cell = spec_mod.load_cell(cell_name)
    cell.config.update(cell.config["tiny"])
    model = runner.build_model(cell, seed=1, lm_use_flash=0)
    module = model.module.clone(attn_fn=transformer.default_attn(
        causal=True, use_flash=True, interpret=True))
    tokens = jnp.zeros((2, model.seq_len + 1), jnp.int32)
    unravel = model.flat.unravel

    def loss(w):
        return module.apply({"params": unravel(w)}, tokens[:, :-1],
                            tokens[:, 1:])[0]

    return model, loss


def digest(jaxpr):
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("cell_name", sorted(PARENTS_STEP))
def test_a_block_without_the_rule_lowers_to_the_parents_step(cell_name):
    model, loss = tiny_loss(cell_name)
    assert model.flat.plain == ()
    assert digest(jax.make_jaxpr(jax.value_and_grad(loss))(
        model.flat.w0)) == PARENTS_STEP[cell_name]


@pytest.mark.parametrize("cell_name", sorted(PARENTS_MSGD_STEP))
def test_a_block_with_a_bias_and_no_rate_commits_as_the_parent_did(cell_name):
    """JoyAI's block has the bias and no rule: its local step, the model
    and the two phases round it, is the parent's to the character."""
    model, loss = tiny_loss(cell_name)
    assert model.flat.plain == () and plain_ranges(
        model.flat.unravel(model.flat.w0)) != ()
    cfg = MSGDConfig(lr=0.03, mom=0.9, use_fused=False)
    vgf = jax.value_and_grad(loss)
    assert digest(jax.make_jaxpr(
        lambda w, s: msgd_step(vgf, w, s, cfg))(
            model.flat.w0, msgd_init(model.flat.w0))) == \
        PARENTS_MSGD_STEP[cell_name]


@pytest.mark.parametrize("name", rules.names())
def test_a_rule_without_plain_ranges_is_the_function_it_was(name):
    rule = rules.make(name)
    assert rule.plain == ()
    assert rules.apply_at(rule, 0) is rule.apply
    assert rules.apply_at(rule, 12345) is rule.apply
    assert rules.with_plain(rule, ()).apply is rule.apply
    ranged = rules.make(name, plain=PLAIN)
    assert ranged.apply is not None
    assert ranged.plain == (() if name == "add" else PLAIN)


@pytest.mark.parametrize("name", [n for n in rules.names() if n != "add"])
def test_a_rule_with_plain_ranges_moves_them_by_the_gradient_alone(name):
    rule = rules.make(name, plain=PLAIN)
    rs = np.random.RandomState(4)
    p = jnp.asarray(rs.randn(N), jnp.float32)
    g = jnp.asarray(rs.randn(N), jnp.float32)
    inside = np.asarray(rules.in_plain(PLAIN, 0, N))
    assert inside.sum() == 32
    got, state = jax.jit(rules.apply_at(rule, 0))(p, g, rule.init(p))
    want, want_state = jax.jit(rule.apply)(p, g, rule.init(p))
    assert np.array_equal(np.asarray(got)[inside], np.asarray(p - g)[inside])
    # (two programs: XLA may contract a multiply-add in one of them)
    np.testing.assert_allclose(np.asarray(got)[~inside],
                               np.asarray(want)[~inside], rtol=1e-6)
    for key, leaf in state.items():
        if np.ndim(leaf):
            assert not np.asarray(leaf)[inside].any(), key
            np.testing.assert_allclose(
                np.asarray(leaf)[~inside],
                np.asarray(want_state[key])[~inside], rtol=1e-6)
    # a piece of the vector: the ranges are cut by where it lies
    lo = 1000
    piece, _ = jax.jit(lambda p, g, s, at: rules.apply_at(rule, at)(p, g, s))(
        p[lo:lo + 512], g[lo:lo + 512], rule.init(p[lo:lo + 512]),
        jnp.int32(lo))
    np.testing.assert_allclose(np.asarray(piece),
                               np.asarray(got)[lo:lo + 512], rtol=1e-6)
    at = 1400 - lo
    assert np.array_equal(np.asarray(piece)[at:at + 8],
                          np.asarray(p - g)[1400:1408])


# -- (h) the file, the vector, the seeding, the scopes, what is kept ----------------------


def test_the_files_keys_are_the_catalogs_but_for_the_reduced_and_the_added():
    catalog = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "load_balance_coeff": 0.001,
        "max_position_embeddings": 131072, "model_type": "afmoe",
        "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 32, "num_expert_groups": 1,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "num_limited_groups": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 2048, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_mm": True}
    for key, value in catalog.items():
        assert FILE[key] == value, key
    reduced = {"num_hidden_layers": (5, 32), "num_dense_layers": (1, 2),
               "num_experts": (8, 128), "vocab_size": (25024, 200192)}
    assert FILE["reduced"] == list(reduced) + ["layer_types"]
    for key, (here, published) in reduced.items():
        assert (FILE[key], FILE["published"][key]) == (here, published), key
    assert FILE["layer_types"] == FILE["published"]["layer_types"][1:6]
    assert len(FILE["published"]["layer_types"]) == 32
    assert FILE["vocab_size"] * 8 == FILE["published"]["vocab_size"]
    added = {"router_experts", "experts_first", "train_seq",
             "layer_types_here", "embed_scale"}
    housekeeping = {"name", "source", "reduced", "published", "deployment",
                    "assumed", "why", "reference", "arithmetic", "scopes",
                    "tiny", "launcher", "launcher_from"}
    assert set(FILE) == set(catalog) | set(reduced) | {"layer_types"} \
        | added | housekeeping
    assert FILE["embed_scale"] == pytest.approx(2048 ** 0.5)
    assert "16 v5e chips" in FILE["deployment"]
    assumed = " ".join(FILE["assumed"])
    for said in ("FOUR RMSNorms", "GATE", "NO positional term",
                 "mup_enabled true", "THE RULE", "DEPARTURES", "std 0.02",
                 "momentum SGD", "8 / sqrt(2048)"):
        assert said in assumed, said


def test_the_built_models_vector_is_the_arithmetics_at_the_tiny_size(case):
    assert case["model"].flat.size == arithmetic.param_count(CONFIG)


@pytest.mark.parametrize("what,got,want", arithmetic.hand_worked(),
                         ids=[c[0] for c in arithmetic.hand_worked()])
def test_trinity_arithmetic_by_hand(what, got, want):
    assert got == want, what


def test_the_seeding(case):
    params = case["model"].flat.unravel(case["model"].flat.w0)
    block = params["TrinityBlock_2"]
    for name in ("attn_norm", "attn_out_norm", "mlp_norm", "mlp_out_norm",
                 "q_norm", "k_norm"):
        assert np.all(np.asarray(block[name]) == 1.0), name
    scaled = params["embed"] * TINY["embed_scale"]
    assert float(jnp.std(scaled)) == pytest.approx(
        transformer.TRINITY_EMBED_RMS, rel=0.05)
    for leaf in (params["head"], block["wq"], block["wg"], block["router"],
                 block["experts_gate"], block["shared_up"]):
        assert float(jnp.std(leaf)) == pytest.approx(0.02, rel=0.15)
    assert float(jnp.std(block["router_bias"])) == pytest.approx(0.02,
                                                                  rel=0.6)
    assert "router" not in params["TrinityBlock_0"]


def test_the_seeded_weights_do_not_depend_on_the_training_sequence():
    short = build(arch="trinity", seed=3, use_flash=False,
                  **{**TINY, "seq_len": 32})
    long = build(arch="trinity", seed=3, use_flash=False, **TINY)
    assert np.array_equal(np.asarray(short.flat.w0), np.asarray(long.flat.w0))


@pytest.mark.parametrize("bad", [
    {"window": 0}, {"bias_rate": -0.001}, {"embed_scale": 0.0},
    {"experts_first": 7}, {"layer_types": "sliding_attention"},
    {"layer_types": "conv,conv,conv,conv,conv"},
])
def test_sizes_that_make_no_block_are_refused(bad):
    with pytest.raises(ValueError):
        build(arch="trinity", use_flash=False, **{**TINY, **bad})


def test_a_size_of_another_block_is_refused():
    with pytest.raises(TypeError, match="trinity takes no block_len"):
        build(arch="trinity", use_flash=False, **{**TINY, "block_len": 4})
    with pytest.raises(TypeError, match="joyai takes no bias_rate"):
        build(arch="joyai", use_flash=False, bias_rate=0.001)


def test_the_steps_operations_carry_the_blocks_scopes(case):
    model = case["model"]
    text = jax.jit(model.value_and_grad).lower(
        case["w"], case["tokens"]).as_text(debug_info=True)
    for scope in FILE["scopes"]:
        if scope != "update":   # the optimizer's, not the model's
            assert f"/{scope}/" in text or f"/{scope}\"" in text, scope
    assert {"attn_gate", "bias_rule", "dense_mlp", "attn_window"} <= set(
        FILE["scopes"])


def test_a_layer_keeps_its_input_and_the_kernels_two_alone():
    from jax._src.ad_checkpoint import saved_residuals

    b, length = 2, 48
    # eight heads of 16 over a stream of 64: q and the gate are twice
    # the stream's width, and nothing else is
    kw = {**{name: TINY[name] for name in BLOCK_FIELDS}, "n_heads": 8}
    flash = transformer.default_attn(causal=True, use_flash=True,
                                     interpret=True)
    block = transformer.TrinityBlock(**kw, window=16, sparse=True,
                                     attn_fn=flash)
    x = jnp.ones((b, length, TINY["d_model"]))
    p = block.init(jax.random.PRNGKey(0), x)["params"]
    kept = saved_residuals(
        lambda x, p: jnp.sum(block.apply({"params": p}, x)[0]), x, p)
    wide = b * length * 8 * TINY["head_dim"]
    big = [shape.shape for shape, why in kept
           if "argument" not in why and int(np.prod(shape.shape)) >= wide]
    assert big == [(b, TINY["kv_heads"], 8 // TINY["kv_heads"], length,
                    TINY["head_dim"])], big     # the flash rule's output


LAUNCH = dict(
    lm_use_flash=0, lm_eval_every=4, seed=5, device_policy="cpu",
    **FILE["launcher"],
    **{switch: CONFIG[key] for switch, key in FILE["launcher_from"].items()})


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
    assert kw["arch"] == "trinity"
    assert {name: kw[name] for name in TINY} == TINY
    assert set(kw) - {"arch", "seed"} == set(archs.sizes_of("trinity"))


def test_a_one_rank_local_run_learns_and_the_rule_runs(obs_on):
    """``--np 1 --opt msgd``: the single-process path hands ``MSGD`` the
    step with the block's telemetry and its plain ranges; each donated
    step is a ``round`` span with the routing's counters and the rule's
    two, and the biases' mean size moves."""
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
    names = transformer.JOYAI_MOE_STATS + transformer.BIAS_RULE_STATS
    for span in rounds:
        for name in names:
            assert len(span.args[name]) == 4, name
    first, last = (np.asarray(r.args["moe_bias_abs_mean"])
                   for r in (rounds[0], rounds[-1]))
    assert np.all(first != last)
    assert np.abs(last - first).max() <= (steps - 1) * 2 * RATE
    assert result["moe_bias_abs_mean"] == rounds[-1].args["moe_bias_abs_mean"]
    assert obs.get_registry().gauge(
        "mpit_moe_bias_abs_mean", layer=0).value == pytest.approx(last[0])


def test_a_three_rank_gang_learns_with_the_bias_moved_on_the_servers(obs_on):
    """``--np 3 --opt adam`` through ``run_rank``: servers 0 and 2,
    worker 1, the same launcher, trainer, shell, client and servers as
    the other blocks; the worker's pulled biases move round by round."""
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
    assert history[-1]["avg_loss"] < history[0]["avg_loss"] - 0.1
    rounds = [s for s in obs_on.spans if s.name == "round"]
    assert len(rounds) == steps
    sizes_seen = [tuple(r.args["moe_bias_abs_mean"]) for r in rounds]
    assert len(set(sizes_seen)) == steps     # the pulled bias moves
