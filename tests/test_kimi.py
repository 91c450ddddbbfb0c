"""The linear-attention hybrid on the normal path (``lm/model.py``
``build(arch="kimi")``: ``models/transformer.py`` ``KimiDecoder``: Kimi
Delta Attention on three layers of four, latent attention without a
query latent and without positions on the fourth, a shared expert beside
a share of the routed ones) against its plain float32 reference, at the
benchmark configuration's ``tiny`` size on seeded weights; and the
chunked scan of ``ops/delta_rule.py`` against the recurrence token by
token.  The reference exists once, as the benchmark's
``chipbench/reference/kimi_plain.py`` (no code shared with the block),
and is imported from there.

Tolerances.  On the CPU both sides multiply in full float32, so they
differ by the rounding of sums taken in another order: 1e-7 of the
gradient's norm and exactly in the loss as measured here.  The limits
are 1e-5.  What they must refuse, each tried below on the reference
itself with one thing wrong, is wrong by 1e-3 or more.  The chunked scan
against the recurrence: 1e-6 of each result's norm at decays in
(0.2, 0.999), limit 2e-5; the log-decay's gradient is a difference of
summed cotangents and rounds coarser where the decays are extreme (its
own limits below)."""

import functools
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.arithmetic import kimi as arithmetic
from chipbench.reference import kimi_plain as ref
from mpit_tpu import obs
from mpit_tpu.lm.model import build, build_kw
from mpit_tpu.models import transformer
from mpit_tpu.ops import delta_rule
from mpit_tpu.parallel import moe

LOSS_TOL_NATS = 1e-5
GRAD_REL_TOL = 1e-5
SCAN_TOL = 2e-5

ROOT = pathlib.Path(__file__).resolve().parent.parent
FILE = json.loads(
    (ROOT / "chipbench/configs/kimi-linear-48b-l5e8.json").read_text())
CONFIG = {**FILE, **FILE["tiny"]}  # the reference's keys, at the tiny size


def sizes(c):
    """``build``'s keywords from the configuration's keys."""
    linear = c["linear_attn_config"]
    return dict(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_layers=c["num_hidden_layers"],
        seq_len=c["train_seq"], layer_types=arithmetic.layer_types(c),
        kda_heads=linear["num_heads"], kda_head_dim=linear["head_dim"],
        conv_kernel=linear["short_conv_kernel_size"],
        q_rank=c["q_lora_rank"] or 0, kv_rank=c["kv_lora_rank"],
        qk_nope=c["qk_nope_head_dim"], qk_rope=c["qk_rope_head_dim"],
        v_head=c["v_head_dim"],
        dense_layers=c["first_k_dense_replace"],
        dense_width=c["intermediate_size"], n_experts=c["router_experts"],
        experts_held=c["num_experts"], experts_first=c["experts_first"],
        experts_per_tok=c["num_experts_per_token"],
        expert_width=c["moe_intermediate_size"],
        shared_experts=c["num_shared_experts"],
        route_scale=c["routed_scaling_factor"],
        rope_theta=0.0 if c["mla_use_nope"] else float(c["rope_theta"]),
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
    model = build(arch="kimi", seed=3, use_flash=False, **TINY)
    w = moved(model)
    tokens = jax.random.randint(jax.random.PRNGKey(7),
                                (2, TINY["seq_len"] + 1), 0, 256)
    with jax.default_matmul_precision("highest"):
        (loss, stats), grad = jax.jit(model.value_grad_stats)(w, tokens)
    ref_loss, ref_grad = ref.loss_and_grad_flat(w, model.flat.unravel,
                                                tokens, CONFIG)
    return dict(model=model, w=w, tokens=tokens, loss=loss, stats=stats,
                grad=grad, ref_loss=ref_loss, ref_grad=ref_grad)


# -- (a) the chunked scan against the recurrence ---------------------------------


def scan_inputs(length, lo, hi, batch=2, heads=3, dk=16, dv=8, seed=0):
    """q, k as the block hands them (L2-normalised a head, q scaled), v,
    a log-decay with ``alpha`` uniform in ``(lo, hi)``, ``beta`` in
    (0, 1)."""
    keys = jax.random.split(jax.random.PRNGKey(seed + length), 5)
    shape = (batch, length, heads, dk)
    q = transformer.l2_norm(jax.random.normal(keys[0], shape)) * dk ** -0.5
    k = transformer.l2_norm(jax.random.normal(keys[1], shape))
    v = jax.random.normal(keys[2], (batch, length, heads, dv))
    g = jnp.log(jax.random.uniform(keys[3], shape, minval=lo, maxval=hi))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], shape[:3]))
    return q, k, v, g, beta


def both(fn, args, ct):
    def weighted(*of):
        out = fn(*of)
        return jnp.sum(out * ct), out

    with jax.default_matmul_precision("highest"):
        grads, out = jax.jit(jax.grad(
            weighted, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
    return out, grads


# (what, length, alpha's range, heads, the log-decay's gradient's limit)
SCANS = [
    ("two chunks and a part", 150, (0.2, 0.999), 3, SCAN_TOL),
    ("shorter than a chunk", 10, (0.2, 0.999), 3, SCAN_TOL),
    ("shorter than a sub-block's multiple", 40, (0.2, 0.999), 3, SCAN_TOL),
    ("one whole chunk", 64, (0.2, 0.999), 3, SCAN_TOL),
    ("two groups of heads", 100, (0.2, 0.999), 12, SCAN_TOL),
    # exp(-G) of a chunk would overflow here: 64 x ln(1e-6) = -884
    ("decays near 0", 130, (1e-30, 1e-6), 3, None),
    ("decays near 1", 100, (0.99999, 1.0), 3, 1e-2),
    ("decays all over", 200, (1e-12, 1.0), 3, 1e-4),
]


# the head widths: narrow ones take the XLA form, whole lanes the kernels
NARROW, LANES = (16, 8), (128, 128)
# each row of SCANS at both; "two groups of heads" on the kernels is two
# grid steps' worth of heads (6: two groups of 3), not the XLA form's 12
SCAN_CASES = [(*scan, *NARROW) for scan in SCANS] + [
    (f"{what}, on the kernels", length, alpha, min(heads, 6), g_tol, *LANES)
    for what, length, alpha, heads, g_tol in SCANS] + [
    # the state is d_v x d_k: keys of two lanes' worth over values of one
    ("keys twice as wide as values, on the kernels", 100, (0.2, 0.999), 2,
     SCAN_TOL, 256, 128)]


def _same(what, got, wanted, g_tol):
    """Output and five gradients of one form against another's."""
    (out, grads), (want, want_grads) = got, wanted
    assert bool(jnp.all(jnp.isfinite(out))), what
    assert relative(out, want) < SCAN_TOL, what
    for name, mine, theirs in zip("qkvgb", grads, want_grads):
        assert bool(jnp.all(jnp.isfinite(mine))), (what, name)
        if name != "g":
            assert relative(mine, theirs) < SCAN_TOL, (what, name)
        elif g_tol is not None:
            assert relative(mine, theirs) < g_tol, (what, name)
        else:
            # near 0 the state is gone before it is read: the gradient
            # is nothing beside the others', on both sides
            scale = float(jnp.linalg.norm(want_grads[2]))
            assert float(jnp.linalg.norm(mine - theirs)) < 1e-6 * scale


@pytest.mark.parametrize("what,length,alpha,heads,g_tol,dk,dv", SCAN_CASES,
                         ids=[s[0] for s in SCAN_CASES])
def test_the_chunked_scan_is_the_recurrence_forward_and_backward(
        what, length, alpha, heads, g_tol, dk, dv):
    """At a narrow head the XLA form, at a head of whole lanes the
    Mosaic kernels (interpreted here): each against the recurrence
    token by token, and the kernels against the XLA form on the same
    inputs too."""
    args = scan_inputs(length, *alpha, heads=heads, dk=dk, dv=dv)
    ct = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    got = both(delta_rule.kda_scan, args, ct)
    _same(what, got, both(delta_rule.kda_scan_reference, args, ct), g_tol)
    if dk % 128 == 0:
        _same(what, got, both(delta_rule.kda_scan_xla, args, ct), g_tol)


@pytest.mark.parametrize("dk,dv", [NARROW, LANES], ids=["xla", "kernels"])
def test_no_state_crosses_the_sequences_of_a_batch(dk, dv):
    args = scan_inputs(90, 0.5, 0.999, batch=2, heads=2, dk=dk, dv=dv)
    scan = jax.jit(delta_rule.kda_scan)
    whole = scan(*args)
    for row in range(2):
        alone = scan(*(x[row:row + 1] for x in args))
        assert np.allclose(np.asarray(whole[row]), np.asarray(alone[0]),
                           rtol=0, atol=1e-6)


@pytest.mark.parametrize("dk,dv", [NARROW, LANES], ids=["xla", "kernels"])
def test_the_state_carries_what_a_chunk_saw_into_the_next(dk, dv):
    """A value written in the first chunk is read in the third (no
    decay, one key): the scan over chunk states, not the chunks alone."""
    length = 3 * delta_rule.CHUNK
    k = jnp.zeros((1, length, 1, dk)).at[:, :, :, 0].set(1.0)
    v = jnp.zeros((1, length, 1, dv)).at[:, 0].set(1.0)
    beta = jnp.zeros((1, length, 1)).at[:, 0].set(1.0)
    out = jax.jit(delta_rule.kda_scan)(k, k, v, jnp.zeros_like(k), beta)
    assert np.allclose(np.asarray(out[0, :, 0]), 1.0)


def test_a_chunks_pairs_part_ways_at_one_level_each():
    """The kernels' tables: every pair ``s < t`` of a chunk belongs to
    one level, and there the two exponents' sums are the log-decays
    from ``s`` to ``t``, each once: ``G_t - G_s``."""
    sums, sums_t, lev = map(np.asarray, delta_rule._tables())
    assert np.array_equal(sums_t, sums.T)
    chunk, levels = delta_rule.CHUNK, delta_rule.LEVELS
    assert sums.shape == ((levels + 1) * chunk, chunk)
    assert np.array_equal(sums[:chunk], np.tril(np.ones((chunk, chunk))))
    t, s = np.tril_indices(chunk, -1)
    assert set(lev[t, s]) == set(range(levels))
    assert np.all(lev[np.triu_indices(chunk, 1)] == -1)
    assert np.all(np.diag(lev) == levels)
    g = np.random.RandomState(0).rand(chunk)
    through = (sums @ g).reshape(levels + 1, chunk)
    assert np.allclose(through[1 + lev[t, s], t] + through[1 + lev[t, s], s],
                       through[0, t] - through[0, s])


def test_a_chunk_on_bf16_operands_is_the_float32_one_to_their_rounding():
    """What the kernels compute on the chip and interpret mode does not
    run: the chunk's products on bf16 operands, the tables' on three
    bf16 parts of the other operand stacked along the contraction.  On
    plain values, no kernel: forward and backward within bf16's rounding
    of the float32 arithmetic, and the tables' products within
    float32's."""
    q, k, v, g, beta = (x[0, :, 0] for x in scan_inputs(
        64, 0.2, 0.999, batch=1, heads=1, dk=128, dv=128))
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    state, do, dafter = (jax.random.normal(key, shape) for key, shape in zip(
        keys, ((128, 128), (64, 128), (128, 128))))
    exact, rounded = (delta_rule._Chunk(*delta_rule._tables(one_pass), one_pass)
                      for one_pass in (False, True))
    assert rounded.sums.dtype == jnp.bfloat16
    for table, x in ((0, g), (1, jnp.tile(g, (delta_rule.LEVELS + 1, 1)))):
        tables = [(c.sums, c.sums_t)[table] for c in (exact, rounded)]
        want, got = (c.table(t, x) for c, t in zip((exact, rounded), tables))
        assert relative(got, want) < 1e-6
    args = (q, k, v, g, beta[:, None], state)
    (o, after, made), (o2, after2, _) = delta_rule._in_step(
        [c.forward(*args) for c in (exact, rounded)])
    assert 1e-5 < relative(o2, o) < 1e-2 > relative(after2, after)
    want, got = delta_rule._in_step(
        [c.backward(*args, made["t"], do, dafter) for c in (exact, rounded)])
    for mine, theirs in zip(got, want):
        assert 1e-5 < relative(mine, theirs) < 2e-2


def test_the_heads_of_a_grid_step_go_stage_by_stage():
    """``_in_step`` takes every generator to its next ``yield`` in turn:
    the order in which the heads' products are written, which is the
    order the kernel's compiler runs them in."""
    order = []

    def head(name, stages):
        for stage in range(stages):
            order.append((name, stage))
            yield
        return name.upper()

    assert delta_rule._in_step([head("a", 3), head("b", 1), head("c", 2)]) \
        == ["A", "B", "C"]
    assert order == [("a", 0), ("b", 0), ("c", 0), ("a", 1), ("c", 1),
                     ("a", 2)]


def test_the_triangular_solve_is_the_inverse_and_its_rule_the_inverses():
    rs = np.random.RandomState(3)
    n = jnp.asarray(np.tril(rs.randn(2, 3, 64, 64), -1) * 0.3, jnp.float32)
    eye = jnp.eye(64)
    with jax.default_matmul_precision("highest"):
        got = delta_rule.unit_lower_inverse(n)
        assert float(jnp.max(jnp.abs((eye + n) @ got - eye))) < 1e-4
        ct = jnp.asarray(rs.randn(2, 3, 64, 64), jnp.float32)
        mine = jax.grad(lambda m: jnp.sum(
            delta_rule.unit_lower_inverse(m) * ct))(n)
        plain = jax.grad(lambda m: jnp.sum(
            jnp.linalg.inv(eye + m) * ct))(n)
    assert relative(mine, plain) < 1e-4


@pytest.mark.parametrize("length, chunks, chunk", [
    (20, 1, 2 * delta_rule.SUB),            # a short sequence: one chunk
    (delta_rule.CHUNK, 1, delta_rule.CHUNK),
    (200, 4, delta_rule.CHUNK)])
def test_a_sequence_is_cut_into_chunks_of_whole_sub_blocks(length, chunks,
                                                           chunk):
    """The chunk is the module's one size, but for a sequence shorter
    than it: that one is a single chunk of whole sub-blocks."""
    from jax._src.ad_checkpoint import saved_residuals

    args = scan_inputs(length, 0.5, 0.9, batch=1)
    kept = [shape.shape for shape, _ in
            saved_residuals(delta_rule.kda_scan, *args) if shape.ndim]
    assert {shape[3:5] for shape in kept} == {(chunks, chunk)}


def test_the_backward_rule_keeps_the_five_inputs_and_no_chunk_state():
    """The rule's residuals: q, k, v, g and beta in the chunks' layout
    and nothing else, whatever the length."""
    from jax._src.ad_checkpoint import saved_residuals

    args = scan_inputs(200, 0.5, 0.9, batch=1)
    kept = [shape for shape, _ in saved_residuals(delta_rule.kda_scan, *args)
            if shape.ndim]   # not the padding's zeros
    assert len(kept) == 5
    assert sum(int(np.prod(s.shape)) for s in kept) <= sum(
        256 * x.size // 200 for x in args)


def test_the_kernels_rule_keeps_the_five_inputs_as_they_are_handed():
    """At a head of whole lanes: the five arguments themselves, row-major
    and unpadded; no chunk state, no chunk matrix."""
    from jax._src.ad_checkpoint import saved_residuals

    args = scan_inputs(200, 0.5, 0.9, batch=1, heads=2, dk=128, dv=128)
    kept = saved_residuals(delta_rule.kda_scan, *args)
    assert sorted(shape.shape for shape, _ in kept) == sorted(
        x.shape for x in args)
    assert all("argument" in why for _, why in kept)


def _equations(jaxpr, primitive, above=""):
    """Every equation of ``primitive`` in ``jaxpr`` and the jaxprs its
    equations hold (a checkpoint's, a custom rule's), each with its
    whole name stack: an inner jaxpr's stacks start at its equation's."""
    for eqn in jaxpr.eqns:
        stack = f"{above}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == primitive:
            yield eqn, stack
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub, primitive, stack)


@pytest.mark.parametrize("head_dim,kernels", [
    (128, ["_backward_kernel", "_forward_kernel", "_forward_kernel"]),
    (16, [])], ids=["the_cells_width", "a_16_wide_head"])
def test_a_kda_layers_step_holds_the_scans_three_kernels(head_dim, kernels):
    """The counter that says the kernels engage is static, the choice
    being made while the step is traced: the gradient of a ``KimiBlock``
    with a ``kda`` mixer at the cell's KDA width holds, under the scope
    ``kda_scan``, the forward kernel once (the mixer's checkpoint keeps
    its result) and the rule's two, the forward that writes the chunks'
    starting states and solves and the walk back; at a 16-wide head
    none."""
    kw = {name: TINY[name] for name in (
        "d_model", "n_heads", "q_rank", "kv_rank", "qk_nope", "qk_rope",
        "v_head", "dense_width", "n_experts", "experts_per_tok",
        "expert_width", "conv_kernel", "route_scale", "norm_eps")}
    block = transformer.KimiBlock(
        **kw, mixer="kda", sparse=False, kda_heads=2, kda_head_dim=head_dim,
        attn_fn=transformer.default_attn(use_flash=False))
    x = jnp.zeros((1, 80, TINY["d_model"]))
    params = jax.eval_shape(block.init, jax.random.PRNGKey(0), x)["params"]
    params = jax.tree_util.tree_map(
        lambda leaf: jnp.zeros(leaf.shape, leaf.dtype), params)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: jnp.sum(
        block.apply({"params": p}, x)[0])))(params)
    calls = list(_equations(jaxpr.jaxpr, "pallas_call"))
    assert sorted(eqn.params["jaxpr"].debug_info.func_name
                  for eqn, _ in calls) == kernels
    for _, stack in calls:
        assert "kda_scan" in stack, stack
    kept = [[out.aval.shape for out in eqn.outvars] for eqn, _ in calls
            if len(eqn.outvars) == 3]
    if kernels:   # the rule's forward alone writes the chunks' states
        #           and solves, for the walk back and no longer
        assert kept == [[(1, 128, 2 * 128), (1, 2, 2, 128, 128),
                         (1, 2, 2, 64, 64)]]


# sha256 of ``str(make_jaxpr(value_and_grad(loss)))`` (addresses blanked)
# of ``kda_scan`` at the cell's KDA shape (32 heads of 128, one sequence),
# **as the parent commit of PR 62 printed it**: that PR gave the scalar
# decay of ``gdn_scan`` Mosaic kernels of its own beside these, and
# ``_Chunk.solve``, ``_in_step``, ``_Calls`` and ``_whole_chunks`` serve
# both; the channel-wise program must still trace to what it was, to the
# character, as the chip compiles it (``use_interpret`` steered off) and
# interpreted, on whole chunks and with a ragged end.  A PR that changes
# the channel-wise kernels on purpose records the new digests here and
# says so.
PARENTS_KDA = {
    ("compiled", 8192): "0d07be5013763523",
    ("compiled", 8150): "55918b446ba9af20",
    ("interpreted", 8192): "aea0249fde720cea",
    ("interpreted", 8150): "425b5d97b2ffb359",
}


@pytest.mark.parametrize("how,length", sorted(PARENTS_KDA))
def test_the_channel_wise_scan_traces_to_the_parents_program(
        how, length, monkeypatch):
    import hashlib
    import re

    monkeypatch.setattr(delta_rule, "use_interpret",
                        lambda flag: how == "interpreted")
    wide = jax.ShapeDtypeStruct((1, length, 32, 128), jnp.float32)
    beta = jax.ShapeDtypeStruct((1, length, 32), jnp.float32)

    def loss(q, k, v, g, beta):
        return jnp.sum(delta_rule.kda_scan(q, k, v, g, beta) ** 2)

    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(
        jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))(
            wide, wide, wide, wide, beta)))
    assert text.count("pallas_call") >= 3
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARENTS_KDA[how, length]


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
    """A leaf that is wrong and small beside the whole (``A_log``, a
    norm's weight, ``dt_bias``) would hide in the flat norm: each leaf
    against its own norm, 1e-4.  The selection bias has no gradient on
    either side."""
    unravel = case["model"].flat.unravel
    got, want = unravel(case["grad"]), unravel(case["ref_grad"])
    leaves = jax.tree_util.tree_leaves_with_path(got)
    # a KDA mixer 16, the latent attention 6, a norm before each MLP,
    # the dense MLP 3, a sparse one 8; table, final norm, head
    assert len(leaves) == 4 * 16 + 6 + 5 + 3 + 4 * 8 + 3
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
    attention in the one latent-attention layer: q and k of 24, v of
    16."""
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


def _no_delta(state, q, k, v, g, beta):
    state = jnp.exp(g)[..., None] * state + beta[..., None, None] \
        * jnp.einsum("bhk,bhv->bhkv", k, v)
    return state, jnp.einsum("bhk,bhkv->bhv", q, state)


_decay, _conv, _routed, _rms = ref.log_decay, ref.causal_conv, \
    ref.routed_experts, ref.rms_norm


def _scalar_decay(h, p, heads):
    g = _decay(h, p, heads)
    return jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)


WRONG = {
    "the delta term left out": {"delta_step": _no_delta},
    "a scalar decay a head, not a channel's": {"log_decay": _scalar_decay},
    "the convolution reversed": {
        "causal_conv": lambda u, taps: _conv(u, taps[::-1])},
    "dt_bias left out of the decay": {
        "log_decay": lambda h, p, heads: _decay(
            h, {**p, "dt_bias": jnp.zeros_like(p["dt_bias"])}, heads)},
    "no norm on the heads' outputs": {
        "rms_norm": lambda x, w, eps: x * w if w.shape[0] == CONFIG[
            "linear_attn_config"]["head_dim"] and x.ndim == 4
        else _rms(x, w, eps)},
    "the shared expert left out": {
        "shared_expert": lambda h, p: jnp.zeros_like(h)},
    "the routed sum not scaled": {
        "routed_experts": lambda h, p, c: _routed(
            h, p, {**c, "routed_scaling_factor": 1.0})},
}


@pytest.mark.parametrize("what", sorted(WRONG))
def test_the_tolerance_refuses(case, what, monkeypatch):
    loss, grad = _wrong(case, monkeypatch, **WRONG[what])
    assert (abs(float(loss) - float(case["ref_loss"])) > LOSS_TOL_NATS
            or relative(grad, case["ref_grad"]) > GRAD_REL_TOL), what
    assert relative(grad, case["ref_grad"]) > 1e-3, what


# -- (c) the latent attention without a query latent, without positions ----------


def _latent(q_rank, rope):
    c = CONFIG
    d, heads = c["hidden_size"], c["num_attention_heads"]
    nope, rot, vd, rank = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                           c["v_head_dim"], c["kv_lora_rank"])
    rs = np.random.RandomState(4)

    def made(*shape):
        return jnp.asarray(rs.randn(*shape) * 0.2, jnp.float32)

    p = {"attn_norm": 1 + made(d), "wkv_a": made(d, rank + rot),
         "kv_a_norm": 1 + made(rank), "wkv_b": made(rank, heads * (nope + vd)),
         "wo": made(heads * vd, d)}
    if q_rank:
        p.update(wq_a=made(d, q_rank), q_a_norm=1 + made(q_rank),
                 wq_b=made(q_rank, heads * (nope + rot)))
    else:
        p["wq"] = made(d, heads * (nope + rot))
    x = made(2, 40, d)
    call = functools.partial(
        transformer.latent_attention, heads=heads, qk_nope=nope,
        qk_rope=rot, v_head=vd, eps=c["rms_norm_eps"],
        inv_freq=transformer.plain_inv_freq(rot, 10000.0) if rope else None,
        attn=transformer.default_attn(use_flash=False))
    return call, x, p


def test_latent_attention_without_query_latent_or_positions_is_the_plain_one():
    call, x, p = _latent(q_rank=0, rope=False)
    with jax.default_matmul_precision("highest"):
        got = call(x, p)
        want = ref.latent_attention(
            ref.rms_norm(x, p["attn_norm"], CONFIG["rms_norm_eps"]), p,
            CONFIG)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    # and the positions are really absent: the causal prefix of a
    # sequence shifted by a position gives the same rows one later
    shifted = jnp.concatenate([x[:, :1], x[:, :-1]], axis=1)
    with jax.default_matmul_precision("highest"):
        later = call(shifted, p)
    a, b = np.asarray(later[:, 2:]), np.asarray(got[:, 1:-1])
    assert not np.allclose(a, b, atol=1e-4)   # the first row is seen twice
    lone = call(x[:, :1], p)
    assert np.allclose(np.asarray(lone[:, 0]), np.asarray(got[:, 0]),
                       atol=1e-5)


def _parents_latent_attention(x, p, *, heads, qk_nope, qk_rope, v_head,
                              inv_freq, eps, attn):
    """``latent_attention`` as the parent commit has it, to the letter
    (PR 38): what JoyAI's step must still lower to."""
    rms_norm, rope_interleaved = transformer.rms_norm, \
        transformer.rope_interleaved
    b, l, _ = x.shape
    with jax.named_scope("mla_proj"):
        h = rms_norm(x, p["attn_norm"], eps)
        q = rms_norm(h @ p["wq_a"], p["q_a_norm"], eps) @ p["wq_b"]
        q = q.reshape(b, l, heads, qk_nope + qk_rope)
        kv_a = h @ p["wkv_a"]
        kv_rank = kv_a.shape[-1] - qk_rope
        kv = rms_norm(kv_a[..., :kv_rank], p["kv_a_norm"], eps) @ p["wkv_b"]
        kv = kv.reshape(b, l, heads, qk_nope + v_head)
        k_rope = rope_interleaved(kv_a[..., None, kv_rank:], inv_freq)
        q = jnp.concatenate(
            [q[..., :qk_nope], rope_interleaved(q[..., qk_nope:], inv_freq)],
            axis=-1)
        k = jnp.concatenate(
            [kv[..., :qk_nope],
             jnp.broadcast_to(k_rope, (b, l, heads, qk_rope))], axis=-1)
        v = kv[..., qk_nope:]
    with jax.named_scope("attn"):
        return attn(q, k, v).reshape(b, l, heads * v_head) @ p["wo"]


def test_with_joyais_sizes_the_latent_attention_is_the_parents_jaxpr():
    """A query latent and rotary positions: the jaxpr of the function,
    forward and gradient, is the parent's to the character."""
    call, x, p = _latent(q_rank=48, rope=True)
    parent = functools.partial(_parents_latent_attention, **call.keywords)
    assert str(jax.make_jaxpr(call)(x, p)) == \
        str(jax.make_jaxpr(parent)(x, p))

    def grad_of(fn):
        return jax.make_jaxpr(jax.grad(lambda x, p: jnp.sum(fn(x, p) ** 2),
                                       argnums=(0, 1)))(x, p)

    assert str(grad_of(call)) == str(grad_of(parent))


# -- (d) the shares add up to the whole layer; the router's scale ----------------


def test_the_shares_routed_parts_and_one_shared_expert_are_the_whole_layer():
    """The guide's share test on a KDA layer with a sparse MLP: over all
    its experts, by the plain reference, it is the sum of what each
    share's block computes for its own experts plus the shared expert
    counted once.  Four shares of two experts of eight."""
    c = {**CONFIG, "num_experts": CONFIG["router_experts"],
         "experts_first": 0}
    n, held = c["router_experts"], CONFIG["num_experts"]
    kw = {name: TINY[name] for name in (
        "d_model", "n_heads", "kda_heads", "kda_head_dim", "q_rank",
        "kv_rank", "qk_nope", "qk_rope", "v_head", "dense_width",
        "n_experts", "experts_per_tok", "expert_width", "conv_kernel",
        "route_scale", "norm_eps")}
    kw.update(mixer="kda", sparse=True,
              attn_fn=transformer.default_attn(use_flash=False))
    whole = transformer.KimiBlock(**kw)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 40, c["hidden_size"]))
    params = whole.init(jax.random.PRNGKey(5), x)["params"]
    params = jax.tree_util.tree_map(
        lambda p: p + 0.05 * jax.random.normal(jax.random.PRNGKey(p.size),
                                               p.shape), params)
    experts = ("experts_gate", "experts_up", "experts_down")
    shared = ("shared_gate", "shared_up", "shared_down")

    def share(first, with_shared, down_scale=1.0):
        block = transformer.KimiBlock(
            **kw, experts_first=first, experts_held=held,
            shared_experts=int(with_shared))
        p = {name: value for name, value in params.items()
             if with_shared or name not in shared}
        for name in experts:
            p[name] = params[name][first:first + held]
        p["experts_down"] = p["experts_down"] * down_scale
        return jax.jit(lambda p: block.apply({"params": p}, x)[0])(p)

    with jax.default_matmul_precision("highest"):
        stream = share(0, False, down_scale=0.0)   # x + the mixer alone
        routed = sum(share(first, False) - stream
                     for first in range(0, n, held))
        once = share(0, True) - share(0, False)    # the shared expert
        want = ref.layer(x, params, 2, c)          # layer 2: KDA, sparse
    assert float(jnp.max(jnp.abs(routed))) > 1e-3 < \
        float(jnp.max(jnp.abs(once)))
    assert float(jnp.max(jnp.abs(stream + routed + once - want))) < 1e-5


def test_the_router_takes_8_of_256_renormalised_and_scaled_by_2_446():
    rs = np.random.RandomState(1)
    scores = jax.nn.sigmoid(jnp.asarray(rs.randn(50, 256), jnp.float32))
    bias = jnp.asarray(0.02 * rs.randn(256), jnp.float32)
    weights, chosen = moe.route_top_k(
        scores, 8, renormalise=True, bias=bias,
        eps=transformer.JOYAI_ROUTE_EPS, scale=2.446)
    gates = np.asarray(ref.router_gates(
        jnp.log(scores / (1 - scores)), jnp.eye(256), bias, 8, True, 2.446))
    assert chosen.shape == weights.shape == (50, 8)
    assert np.allclose(np.asarray(jnp.sum(weights, axis=-1)), 2.446,
                       rtol=1e-5)
    rows = np.arange(50)[:, None]
    assert np.allclose(gates[rows, np.asarray(chosen)], np.asarray(weights),
                       rtol=1e-4)
    assert np.count_nonzero(gates) == 50 * 8


# -- the vector, the seeding, the scopes, what is kept ---------------------------


def test_the_built_models_vector_is_the_arithmetics_at_the_tiny_size(case):
    assert case["model"].flat.size == arithmetic.param_count(CONFIG)
    whole = {**CONFIG, "num_experts": CONFIG["router_experts"],
             "experts_first": 0}
    model = build(arch="kimi", seed=3, use_flash=False, **sizes(whole))
    assert model.flat.size == arithmetic.param_count(whole)


@pytest.mark.parametrize("what,got,want", arithmetic.hand_worked(),
                         ids=[c[0] for c in arithmetic.hand_worked()])
def test_kimi_arithmetic_by_hand(what, got, want):
    assert got == want, what


def test_the_arithmetics_chunk_is_the_operators():
    assert FILE["kda_chunk"] == delta_rule.CHUNK
    assert delta_rule.CHUNK % delta_rule.SUB == 0


def test_the_seeding_of_the_decay_and_of_everything_else(case):
    params = case["model"].flat.unravel(case["model"].flat.w0)
    block = params["KimiBlock_1"]
    for name in ("attn_norm", "o_norm", "mlp_norm"):
        assert np.all(np.asarray(block[name]) == 1.0), name
    assert float(jnp.std(params["embed"])) == pytest.approx(8.0, rel=0.05)
    for leaf in (params["head"], block["wq"], block["wf_b"], block["wg_a"],
                 block["experts_gate"], block["shared_up"],
                 params["KimiBlock_3"]["wq"]):
        assert float(jnp.std(leaf)) == pytest.approx(0.02, rel=0.15)
    assert float(jnp.std(block["conv_k"])) == pytest.approx(1 / 3, rel=0.2)
    assert np.any(np.asarray(block["router_bias"]))
    rate = np.exp(np.asarray(block["a_log"]))
    assert rate.min() >= 1.0 and rate.max() <= 16.0
    step = np.asarray(jax.nn.softplus(block["dt_bias"]))
    assert step.min() >= 1e-3 * 0.999 and step.max() <= 0.1 * 1.001
    # so the decay lies in about 0.2 .. 0.999, and its mean well inside
    alpha = np.exp(-rate[:, None] * step.reshape(len(rate), -1))
    assert alpha.min() > 0.19 and alpha.max() < 0.9991
    assert 0.05 < float(case["stats"]["lm_kda_decay_mean"][0]) < 0.999
    assert case["stats"]["lm_kda_decay_mean"].shape == (4,)
    assert "wq_a" not in params["KimiBlock_3"]   # no query latent


def test_the_seeded_weights_do_not_depend_on_the_training_sequence():
    short = build(arch="kimi", seed=3, use_flash=False,
                  **{**TINY, "seq_len": 32})
    long = build(arch="kimi", seed=3, use_flash=False, **TINY)
    assert np.array_equal(np.asarray(short.flat.w0), np.asarray(long.flat.w0))


@pytest.mark.parametrize("bad", [
    {"kv_rank": 0}, {"qk_rope": 7}, {"experts_first": 7}, {"kda_heads": 0},
    {"layer_types": "kda,kda"}, {"layer_types": "kda,conv,kda,kda,kda"},
])
def test_sizes_that_make_no_block_are_refused(bad):
    with pytest.raises(ValueError):
        build(arch="kimi", use_flash=False, **{**TINY, **bad})


def test_a_query_latent_and_rotary_positions_are_still_a_kimi_block():
    model = build(arch="kimi", seed=3, use_flash=False,
                  **{**TINY, "q_rank": 24, "rope_theta": 1e4})
    params = model.flat.unravel(model.flat.w0)
    assert {"wq_a", "q_a_norm", "wq_b"} <= set(params["KimiBlock_3"])
    with_none = build(arch="kimi", seed=3, use_flash=False,
                      **{**TINY, "q_rank": 24})
    tokens = jnp.zeros((1, TINY["seq_len"] + 1), jnp.int32).at[0, ::3].set(7)
    assert float(model.loss(model.flat.w0, tokens)) != \
        float(with_none.loss(model.flat.w0, tokens))


def test_the_steps_operations_carry_the_blocks_scopes(case):
    model = case["model"]
    text = jax.jit(model.value_and_grad).lower(
        case["w"], case["tokens"]).as_text(debug_info=True)
    for scope in FILE["scopes"]:
        if scope != "update":   # the optimizer's, not the model's
            assert f"/{scope}/" in text, scope
    assert {"kda_proj", "kda_scan", "kda_out"} <= set(FILE["scopes"])


def test_a_kda_layer_keeps_its_input_and_the_scans_output_alone():
    """The mixer's checkpoint: beside the layer's input and its
    parameters, the one array kept for the backward pass is the scan's
    output (``T x heads x head_dim``); q, k, v, g, beta and the gates
    are made again."""
    from jax._src.ad_checkpoint import saved_residuals

    heads, hd, d = TINY["kda_heads"], TINY["kda_head_dim"], TINY["d_model"]
    x = jnp.zeros((2, 48, d))
    p = {"attn_norm": jnp.ones(d), "wq": jnp.ones((d, heads * hd)),
         "wk": jnp.ones((d, heads * hd)), "wv": jnp.ones((d, heads * hd)),
         "conv_q": jnp.ones((4, heads * hd)),
         "conv_k": jnp.ones((4, heads * hd)),
         "conv_v": jnp.ones((4, heads * hd)), "wf_a": jnp.ones((d, hd)),
         "wf_b": jnp.ones((hd, heads * hd)), "a_log": jnp.zeros(heads),
         "dt_bias": jnp.zeros(heads * hd), "w_beta": jnp.ones((d, heads)),
         "wg_a": jnp.ones((d, hd)), "wg_b": jnp.ones((hd, heads * hd)),
         "o_norm": jnp.ones(hd), "wo": jnp.ones((heads * hd, d))}
    mixer = jax.checkpoint(
        functools.partial(transformer.delta_attention, heads=heads,
                          head_dim=hd, eps=1e-5),
        policy=jax.checkpoint_policies.save_only_these_names(
            *transformer.KDA_KEPT))
    kept = saved_residuals(lambda x, p: mixer(x, p)[0], x, p)
    made = [shape.shape for shape, why in kept if "argument" not in why]
    assert made == [(2, 48, heads, hd)]
    assert transformer.KDA_KEPT == (delta_rule.KDA_OUT,)


# -- the counters on the round spans ----------------------------------------------

LAUNCH = dict(
    lm_use_flash=0, lm_eval_every=4, seed=5, device_policy="cpu",
    **FILE["launcher"],
    **{switch: CONFIG[key] for switch, key in FILE["launcher_from"].items()})
DECAY = transformer.KDA_DECAY_MEAN


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
    assert kw["arch"] == "kimi"


def test_a_one_rank_local_run_learns_and_carries_the_decays_mean(obs_on):
    """``--np 1 --opt msgd``: the single-process path hands ``MSGD`` the
    step with the block's telemetry, and each donated step is a
    ``round`` span with the decay's mean a KDA layer and the routing
    counters a sparse layer while obs records."""
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
        assert len(span.args[DECAY]) == 4
        assert all(0.05 < x < 0.999 for x in span.args[DECAY])
        for name in transformer.JOYAI_MOE_STATS:
            assert len(span.args[name]) == 4, name
    assert obs.get_registry().gauge(f"mpit_{DECAY}", layer=3).value == \
        rounds[-1].args[DECAY][3]
    for name in (DECAY,) + transformer.JOYAI_MOE_STATS:
        assert result[name] == rounds[-1].args[name]
