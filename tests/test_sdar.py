"""Block-diffusion training on the normal path (``lm/model.py``
``build(arch="sdar")``: ``models/transformer.py`` ``SdarDecoder``: a
noised and a clean copy of every sequence in one pass of ``2 L`` rows, a
mask that is not inside the causal triangle, the cross-entropy of the
masked positions weighted by their block's count, over Qwen3-MoE layers
with a share of the experts) against its plain float32 reference, at the
benchmark configuration's ``tiny`` size on seeded weights; the flash
kernels under the mask (``ops/flash_attention.py`` ``blockdiff``,
interpreted) against the reference's materialised one, element by
element; the noise against the reference's numpy, to the bit.  The
reference exists once, as the benchmark's
``chipbench/reference/sdar_plain.py`` (no code shared with the block),
and is imported from there.

Tolerances.  On the CPU both sides multiply in full float32 and differ
by the rounding of sums taken in another order: 1e-7 of the gradient's
norm and 1e-6 nats as measured here.  The limits are 1e-5.  What they
must refuse, each tried below on the reference itself with one thing
wrong, is wrong by 2e-4 or more."""

import fractions
import functools
import hashlib
import importlib
import json
import math
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run as runner, spec as spec_mod
from chipbench.arithmetic import sdar as arithmetic
from chipbench.reference import sdar_plain as ref
from chipbench.traffic.packed_bytes import packed_batch
from mpit_tpu import obs
from mpit_tpu.lm import archs
from mpit_tpu.lm.model import build, build_kw
from mpit_tpu.models import transformer
from mpit_tpu.ops.flash_attention import (
    attention_reference, flash_attention, flash_call_counts,
    flash_step_counts,
)

# the module (``mpit_tpu.ops`` exports the function under its name)
fa = importlib.import_module("mpit_tpu.ops.flash_attention")

LOSS_TOL_NATS = 1e-5
GRAD_REL_TOL = 1e-5

ROOT = pathlib.Path(__file__).resolve().parent.parent
FILE = json.loads(
    (ROOT / "chipbench/configs/sdar-30b-l6e8.json").read_text())
CONFIG = {**FILE, **FILE["tiny"]}  # the reference's keys, at the tiny size


def sizes(c):
    """``build``'s keywords from the configuration's keys."""
    return dict(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], n_layers=c["num_hidden_layers"],
        seq_len=c["train_seq"], block_len=c["block_length"],
        mask_id=c["mask_token_id"], noise_seed=c["noise_seed"],
        n_experts=c["router_experts"], experts_held=c["num_experts"],
        experts_first=c["experts_first"],
        experts_per_tok=c["num_experts_per_tok"],
        expert_width=c["moe_intermediate_size"],
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"])


TINY = sizes(CONFIG)
BLOCK_FIELDS = ("d_model", "n_heads", "kv_heads", "head_dim", "n_experts",
                "experts_per_tok", "expert_width", "block_len", "rope_theta",
                "norm_eps")


def moved(model, scale=0.05, seed=0):
    """The seeded weights moved off their initial values: norm weights
    off 1, so that one that is ignored shows."""
    rs = np.random.RandomState(seed)
    return model.flat.w0 + scale * jnp.asarray(rs.randn(model.flat.size),
                                               jnp.float32)


def relative(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def interpreted(precision="highest"):
    return transformer.default_attn(causal=True, use_flash=True,
                                    interpret=True, precision=precision)


@pytest.fixture(scope="module")
def case():
    model = build(arch="sdar", seed=3, use_flash=False, **TINY)
    w = moved(model)
    tokens = jax.random.randint(jax.random.PRNGKey(7),
                                (2, TINY["seq_len"] + 1), 0, 256)
    with jax.default_matmul_precision("highest"):
        (loss, stats), grad = jax.jit(model.value_grad_stats)(w, tokens)
    ref_loss, ref_grad = ref.loss_and_grad_flat(w, model.flat.unravel,
                                                tokens, CONFIG)
    return dict(model=model, w=w, tokens=tokens, loss=loss, stats=stats,
                grad=grad, ref_loss=ref_loss, ref_grad=ref_grad)


# -- (a) the program against the plain reference ------------------------------------


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
    """Each leaf against its own norm, 1e-4 (a norm's weight would hide
    in the flat norm).  **The last layer's sparse branch may have a
    gradient of exactly zero, on both sides**: the loss reads the last
    layer at the masked rows alone, which all carry the mask id's row of
    the table (at std 8.0 the largest thing in their stream), so the
    router sends them all to the same experts, and where none of those
    is held here no row with a gradient reaches a held expert (this
    seed's case; every earlier layer is reached through the attention
    of the layers after it)."""
    unravel = case["model"].flat.unravel
    got, want = unravel(case["grad"]), unravel(case["ref_grad"])
    leaves = jax.tree_util.tree_leaves_with_path(got)
    # a layer: attention 7, the sparse MLP 5; table, final norm, head
    assert len(leaves) == TINY["n_layers"] * 12 + 3
    last, zero = f"SdarBlock_{TINY['n_layers'] - 1}", []
    for path, leaf in leaves:
        name = jax.tree_util.keystr(path)
        other = functools.reduce(lambda t, k: t[k.key], path, want)
        if not float(jnp.linalg.norm(other)):
            assert not np.any(np.asarray(leaf)), name
            zero.append(name)
            continue
        assert relative(leaf, other) < 1e-4, name
    assert len(zero) in (0, 5) and all(
        last in name and ("experts" in name or "router" in name
                          or "mlp_norm" in name) for name in zero)


def test_the_kernel_in_the_block_changes_no_number(case):
    """The flash kernels (interpret mode) that walk the mask's live
    tiles in place of the materialised attention, in every layer."""
    module = case["model"].module.clone(attn_fn=interpreted())
    unravel = case["model"].flat.unravel
    tokens = case["tokens"]

    def loss(w):
        return module.apply({"params": unravel(w)}, tokens[:, :-1],
                            tokens[:, 1:])[0]

    with jax.default_matmul_precision("highest"):
        got, grad = jax.jit(jax.value_and_grad(loss))(case["w"])
    assert abs(float(got) - float(case["ref_loss"])) < LOSS_TOL_NATS
    assert relative(grad, case["ref_grad"]) < GRAD_REL_TOL


def test_the_targets_are_read_by_nothing(case):
    model, tokens = case["model"], case["tokens"]
    other = tokens.at[:, -1].set((tokens[:, -1] + 1) % 256)
    with jax.default_matmul_precision("highest"):
        loss = jax.jit(model.loss)(case["w"], other)
    assert float(loss) == float(case["loss"])


# the reference's own, before a test replaces them
VISIBLE, LOSS, ROTATE = ref.visible, ref.loss, ref.rotate


def _sees_own_clean_copy(n, b):
    r, c = jnp.arange(2 * n)[:, None], jnp.arange(2 * n)[None, :]
    own = (c < n) & (c // b == r // b)
    past = (c >= n) & ((c - n) // b <= r // b)
    clean = (c >= n) & ((c - n) // b <= (r - n) // b)
    return jnp.where(r < n, own | past, clean)


def _causal_rows(n, b):
    r = jnp.arange(2 * n)
    return r[None, :] <= r[:, None]


def _clean_sees_noised(n, b):
    r, c = jnp.arange(2 * n)[:, None], jnp.arange(2 * n)[None, :]
    return VISIBLE(n, b) | ((r >= n) & (c < n)
                                & (c // b == (r - n) // b))


def _unweighted(params, ids, masked, count, config):
    return LOSS(params, ids, masked,
                jnp.full_like(count, (config["block_length"] + 1) / 2.0,
                              jnp.float32), config)


def _shifted(params, ids, masked, count, config):
    block = int(config["block_length"])
    noised = jnp.where(masked, int(config["mask_token_id"]), ids)
    logp = jax.nn.log_softmax(ref.logits(params, noised, ids, config),
                              axis=-1)
    nll = -jnp.take_along_axis(logp, jnp.roll(ids, -1, axis=1)[..., None],
                               axis=-1)[..., 0]
    per_row = jnp.sum(jnp.where(masked, nll / count, 0.0), axis=1)
    return jnp.mean(per_row) / (ids.shape[1] // block)


WRONG = {
    "a noised block that sees its own clean copy":
        {"visible": _sees_own_clean_copy},
    "plain causal attention over the 2 L rows": {"visible": _causal_rows},
    "a clean row that sees its block's noised copy":
        {"visible": _clean_sees_noised},
    "the loss without its 1 / c": {"loss": _unweighted},
    "the targets shifted by one": {"loss": _shifted},
    "no norm on the heads' queries and keys":
        {"head_norm": lambda x, weight, eps: x},
    "rows at positions r and not r mod L":
        {"rotate": lambda x, theta, period: ROTATE(x, theta, x.shape[1])},
}


@pytest.mark.parametrize("what", sorted(WRONG))
def test_the_tolerance_refuses(case, what, monkeypatch):
    """The reference with one thing wrong is outside the limits the
    program is inside."""
    for name, fn in WRONG[what].items():
        monkeypatch.setattr(ref, name, fn)
    loss, grad = ref.loss_and_grad_flat(case["w"], case["model"].flat.unravel,
                                        case["tokens"], CONFIG)
    # (the weakest is the clean row that sees a noised key: it reaches
    # the loss only through later layers' keys and values, 2e-4)
    assert (abs(float(loss) - float(case["ref_loss"])) > 10 * LOSS_TOL_NATS
            or relative(grad, case["ref_grad"]) > 10 * GRAD_REL_TOL)


def test_the_counters_are_the_formulas(case):
    stats, block = case["stats"], TINY["block_len"]
    nll = [f"diff_nll_c{c}" for c in range(1, block + 1)]
    assert set(stats) == {"diff_masked_share", *nll} | set(
        transformer.JOYAI_MOE_STATS[:3])   # the jnp attention has no tiles
    for name in transformer.JOYAI_MOE_STATS[:3]:
        assert stats[name].shape == (TINY["n_layers"],), name
    assert float(stats["diff_masked_share"]) == (block + 1) / (2 * block)
    # the loss is the mean over the counts of their masked positions'
    # mean NLL: every count has as many blocks, and weight x count is 1
    by_count = np.asarray([float(stats[name]) for name in nll])
    assert np.all(by_count > 0)
    assert float(np.mean(by_count)) == pytest.approx(float(case["loss"]),
                                                     rel=1e-5)


# -- (b) the kernels' live set is M, and no dead tile is visited ----------------------

MASK_CASES = [
    # what, L, B, hq, hkv, block_q, block_k
    ("one tile straddles both halves", 64, 4, 2, 1, 128, 128),
    ("a tile a half", 128, 4, 4, 2, 128, 128),
    ("two tiles a half, grouped 2 over 1", 256, 4, 2, 1, 128, 128),
    ("blocks of 8, grouped 4 over 2", 256, 8, 4, 2, 128, 128),
    ("a half of one and a half tiles", 192, 4, 2, 2, 128, 128),
    ("blocks of 2, unequal tiles", 128, 2, 4, 1, 64, 128),
    ("blocks of 16, a tile a block's multiple", 256, 16, 2, 1, 64, 128),
    ("blocks of 32, half a tile", 192, 32, 2, 1, 64, 128),
]


def tiles_of(mask, bq, bk):
    """``(q blocks, kv blocks)`` bool: the tiles with a true entry, rows
    and keys padded to whole tiles."""
    lq, lk = mask.shape
    pad = np.zeros((-(-lq // bq) * bq, -(-lk // bk) * bk), bool)
    pad[:lq, :lk] = mask
    return pad.reshape(pad.shape[0] // bq, bq, pad.shape[1] // bk, bk).any(
        axis=(1, 3))


@pytest.mark.parametrize("what,half,block,hq,hkv,bq,bk", MASK_CASES,
                         ids=[c[0] for c in MASK_CASES])
def test_the_kernels_live_set_is_the_references_mask(what, half, block, hq,
                                                     hkv, bq, bk):
    """Zero queries and keys make the attention a uniform mean over the
    live keys, and one-hot values make the output the live set itself:
    row ``r`` reads ``1 / |live(r)|`` at its live keys and 0 elsewhere,
    in every head, which is ``M`` element by element; the jnp reference
    attention under the same keyword too."""
    rows = 2 * half
    mask = np.asarray(ref.visible(half, block))
    want = mask / mask.sum(axis=1, keepdims=True)
    q = jnp.zeros((1, hq, rows, 8))
    k = jnp.zeros((1, hkv, rows, 8))
    v = jnp.broadcast_to(jnp.eye(rows), (1, hkv, rows, rows))
    got = flash_attention(q, k, v, blockdiff=(half, block), block_q=bq,
                          block_k=bk, interpret=True)
    assert np.allclose(np.asarray(got), want[None, None], atol=1e-6)
    plain = attention_reference(q, k, v, blockdiff=(half, block))
    assert np.allclose(np.asarray(plain), want[None, None], atol=1e-6)
    assert np.array_equal(np.asarray(got) > 0, np.broadcast_to(
        mask, got.shape))


@pytest.mark.parametrize("fused", ["0", "1"], ids=["two kernels", "fused"])
@pytest.mark.parametrize("what,half,block,hq,hkv,bq,bk", MASK_CASES,
                         ids=[c[0] for c in MASK_CASES])
def test_the_gradient_under_the_mask_is_exact(what, half, block, hq, hkv, bq,
                                              bk, fused, monkeypatch):
    """Forward and both backward schedules against the materialised
    masked softmax, on random operands."""
    monkeypatch.setenv("MPIT_FA_FUSED_BWD", fused)
    keys = jax.random.split(jax.random.PRNGKey(half + block), 4)
    q, g = (jax.random.normal(key, (2, hq, 2 * half, 16))
            for key in keys[:2])
    k, v = (jax.random.normal(key, (2, hkv, 2 * half, 16))
            for key in keys[2:])
    mask = ref.visible(half, block)

    def dense(q, k, v):
        k, v = (jnp.repeat(x, hq // hkv, axis=1) for x in (k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / 4.0
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", p, v) * g)

    def kernel(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, blockdiff=(half, block), block_q=bq, block_k=bk,
            interpret=True, precision="highest") * g)

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(kernel, (0, 1, 2))(q, k, v)
        want = jax.value_and_grad(dense, (0, 1, 2))(q, k, v)
    for mine, theirs in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want)):
        assert float(jnp.max(jnp.abs(mine - theirs))) < 1e-4 * max(
            1.0, float(jnp.max(jnp.abs(theirs))))


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkdv", "fused"])
@pytest.mark.parametrize("what,half,block,hq,hkv,bq,bk", MASK_CASES,
                         ids=[c[0] for c in MASK_CASES])
def test_no_dead_tile_is_visited(what, half, block, hq, hkv, bq, bk, kernel):
    """The walk's own count of the tiles it runs a product on is the
    count of the tiles in which the reference's mask has a true entry,
    and the tiles are those: no dead one, never one of the
    clean-to-noised quadrant."""
    live = tiles_of(np.asarray(ref.visible(half, block)), bq, bk)
    counts = flash_step_counts(
        kernel, (1, hq, 2 * half, 16), (1, hkv, 2 * half, 16), jnp.float32,
        blockdiff=(half, block), block_q=bq, block_k=bk)
    assert counts["live"] == counts["nonempty"] == hq * int(live.sum())
    assert counts["rect"] == hq * live.size
    assert counts["live"] <= counts["visited"] <= counts["rect"]
    kv_outer = fa._KERNEL_WALKS[kernel][0]
    walk = fa._Walk(kv_outer, False, None, bq, bk, *live.shape,
                    hq // hkv, blockdiff=(half, block))
    entries, lengths = walk.table()
    walked = np.zeros_like(live)
    for row, (line, n) in enumerate(zip(entries, lengths)):
        for entry in line[:n]:
            at = (entry // 2, row) if kv_outer else (row, entry // 2)
            assert not walked[at]   # no tile twice
            walked[at] = True
        assert np.all(line[n:] // 2 == (line[n - 1] // 2 if n else 0))
    assert np.array_equal(walked, live)
    clean_q, noised_k = np.arange(live.shape[0]) * bq >= half, \
        (np.arange(live.shape[1]) + 1) * bk <= half
    assert not walked[np.ix_(clean_q, noised_k)].any()


def test_the_cells_calls_visit_80_tiles_of_256_a_head():
    """At the cell's shapes on the float32 tiles of 512: a forward, a dq
    and a dk/dv call a layer, 80 live tiles a head each of the 256."""
    c = FILE
    counts = flash_call_counts(
        (1, c["num_attention_heads"], 2 * c["train_seq"], c["head_dim"]),
        (1, c["num_key_value_heads"], 2 * c["train_seq"], c["head_dim"]),
        jnp.float32, blockdiff=(c["train_seq"], c["block_length"]))
    heads = c["num_attention_heads"]
    assert counts["live"] == counts["nonempty"] == 3 * heads * 80
    assert counts["rect"] == 3 * heads * 256
    assert arithmetic.live_tiles(c["train_seq"], c["block_length"], 512) == \
        (80, 256)
    causal = flash_step_counts(
        "fwd", (1, heads, 8192, 128), (1, 4, 8192, 128), jnp.float32,
        causal=True)
    assert causal["live"] == heads * 136   # what a causal walk would run


def test_no_square_of_the_rows_is_in_the_lowered_step():
    """No ``(2 L, 2 L)`` operand, mask, bias or bit set: with the kernel
    in the block nothing of the step has two axes of ``2 L`` (at 96
    positions, so that ``2 L`` is no lane count of a row statistic)."""
    model = build(arch="sdar", seed=3, use_flash=False,
                  **{**TINY, "seq_len": 96})
    module = model.module.clone(attn_fn=interpreted(None))
    unravel = model.flat.unravel
    tokens = jnp.asarray(packed_batch(2, 0, 2, 96))
    text = str(jax.make_jaxpr(jax.value_and_grad(
        lambda w: module.apply({"params": unravel(w)}, tokens[:, :-1],
                               tokens[:, 1:])[0]))(model.flat.w0))
    rows = 2 * 96
    assert "pallas_call" in text
    assert not re.search(rf"\[(\d+,)*{rows},{rows}\]", text)
    square = str(jax.make_jaxpr(jax.value_and_grad(model.loss))(
        model.flat.w0, tokens))
    assert re.search(rf"\[(\d+,)*{rows},{rows}\]", square)  # the jnp path's


@pytest.mark.parametrize("bad", [
    dict(causal=True), dict(window=8), dict(blockdiff=(64, 5)),
    dict(blockdiff=(60, 3)), dict(blockdiff=(48, 4)), dict(q_offset=4),
])
def test_the_mask_is_the_calls_whole_geometry(bad):
    q = jnp.zeros((1, 2, 128, 8))
    kw = {"blockdiff": (64, 4), **bad}
    with pytest.raises(ValueError):
        flash_attention(q, q, q, interpret=True, **kw)


def test_under_the_mask_the_backward_is_two_kernels_unless_forced(
        monkeypatch):
    shapes = ((1, 4, 256, 16), (1, 2, 256, 16))
    monkeypatch.delenv("MPIT_FA_FUSED_BWD", raising=False)
    assert not fa._use_fused_bwd(*shapes, 16, jnp.float32, None, None, None,
                                 blockdiff=True)
    assert fa._use_fused_bwd(*shapes, 16, jnp.float32, None, None, None)
    monkeypatch.setenv("MPIT_FA_FUSED_BWD", "1")
    assert fa._use_fused_bwd(*shapes, 16, jnp.float32, None, None, None,
                             blockdiff=True)


# -- (c) no leak ----------------------------------------------------------------------


@pytest.fixture(scope="module", params=["jnp", "kernel"])
def stack(request):
    """The layers alone, from an embedded stream ``u (1, 2 L, d)`` to
    the stream after the last layer: what every logit is a row-wise
    function of."""
    half, block = 32, TINY["block_len"]
    attn = (transformer.default_attn(use_flash=False)
            if request.param == "jnp" else interpreted())
    layer = transformer.SdarBlock(
        **{name: TINY[name] for name in BLOCK_FIELDS},
        experts_first=TINY["experts_first"],
        experts_held=TINY["experts_held"], attn_fn=attn)
    u = jax.random.normal(jax.random.PRNGKey(4), (1, 2 * half,
                                                  TINY["d_model"]))
    params = [jax.tree_util.tree_map(
        lambda p, i=i: p + 0.05 * jax.random.normal(
            jax.random.PRNGKey(p.size + i), p.shape),
        layer.init(jax.random.PRNGKey(i), u)["params"]) for i in range(2)]

    @jax.jit
    def run(u):
        with jax.default_matmul_precision("highest"):
            for p in params:
                u = layer.apply({"params": p}, u)[0]
        return u

    return dict(run=run, u=u, half=half, block=block)


def rows_of(half, block, b, clean):
    first = b * block + (half if clean else 0)
    return slice(first, first + block)


def changed(a, b, rows):
    return float(jnp.max(jnp.abs(a[0, rows] - b[0, rows])))


def test_a_blocks_clean_copy_does_not_reach_its_noised_copy(stack):
    """Changing ``x0`` inside block ``b`` leaves the stream (so the
    logits) of noised block ``b`` and of everything before it unchanged;
    the noised blocks after it, and the clean ones from it on, move."""
    run, u, half, block = (stack[k] for k in ("run", "u", "half", "block"))
    b, n = 3, half // block
    base = run(u)
    moved_u = u.at[0, rows_of(half, block, b, clean=True)].add(1.0)
    out = run(moved_u)
    for other in range(n):
        noised = changed(out, base, rows_of(half, block, other, False))
        clean = changed(out, base, rows_of(half, block, other, True))
        assert (noised == 0.0) == (other <= b), other
        assert (clean == 0.0) == (other < b), other


def test_a_noised_block_reaches_itself_alone(stack):
    run, u, half, block = (stack[k] for k in ("run", "u", "half", "block"))
    b, n = 2, half // block
    base = run(u)
    out = run(u.at[0, rows_of(half, block, b, clean=False)].add(1.0))
    for other in range(n):
        assert (changed(out, base, rows_of(half, block, other, False))
                == 0.0) == (other != b), other
        assert changed(out, base, rows_of(half, block, other, True)) == 0.0


def test_nothing_after_a_block_reaches_it(stack):
    """Changing any row, noised or clean, of the blocks after ``b``
    leaves block ``b``'s stream, both copies, unchanged."""
    run, u, half, block = (stack[k] for k in ("run", "u", "half", "block"))
    b = 4
    base = run(u)
    later = jnp.arange(half) >= (b + 1) * block
    out = run(u + jnp.concatenate([later, later])[None, :, None] * 1.0)
    for other in range(b + 1):
        assert changed(out, base, rows_of(half, block, other, False)) == 0.0
        assert changed(out, base, rows_of(half, block, other, True)) == 0.0
    assert changed(out, base, rows_of(half, block, b + 1, False)) > 0.0


def test_the_gradient_through_the_clean_half_reaches_only_earlier_blocks(
        stack):
    """What noised block ``b``'s stream owes the inputs: its own noised
    rows and the clean rows of the blocks strictly before it, nothing
    else."""
    run, u, half, block = (stack[k] for k in ("run", "u", "half", "block"))
    b, n = 5, half // block
    grad = jax.grad(lambda u: jnp.sum(
        run(u)[0, rows_of(half, block, b, False)] ** 2))(u)
    for other in range(n):
        noised = float(jnp.max(jnp.abs(
            grad[0, rows_of(half, block, other, False)])))
        clean = float(jnp.max(jnp.abs(
            grad[0, rows_of(half, block, other, True)])))
        assert (noised > 0.0) == (other == b), other
        assert (clean > 0.0) == (other < b), other


# -- (d) the noise ------------------------------------------------------------------------


@pytest.mark.parametrize("length,block,seed", [
    (64, 4, 51), (4096, 4, 51), (256, 8, 7), (64, 2, 0), (48, 4, 3),
    (4096, 4, 2**31 + 5),
])
def test_the_noise_is_the_references_to_the_bit(length, block, seed):
    """The program's, with and without ``jit``, against the reference's
    numpy: the same positions, the same counts; every count to as many
    blocks where the length allows; ``(B + 1) / (2 B)`` of every row
    where it does."""
    ids = packed_batch(11, 3, 3, length)[:, :-1]
    want_masked, want_count = ref.noise(ids, seed, block)
    for fn in (transformer.block_noise, jax.jit(
            transformer.block_noise, static_argnums=(1, 2))):
        masked, count = fn(jnp.asarray(ids), seed, block)
        assert np.array_equal(np.asarray(masked), want_masked)
        assert np.array_equal(np.asarray(count), want_count)
    n = length // block
    per_block = want_masked.reshape(3, n, block).sum(axis=-1)
    assert np.array_equal(per_block, want_count[:, ::block])
    assert np.all((per_block >= 1) & (per_block <= block))
    if n % block == 0:
        for row in per_block:
            assert np.array_equal(np.bincount(row, minlength=block + 1)[1:],
                                  np.full(block, n // block))
        assert np.all(want_masked.mean(axis=1) == (block + 1) / (2 * block))


def test_the_noise_is_a_function_of_the_row_and_the_seed():
    ids = packed_batch(5, 0, 4, 256)[:, :-1]
    masked, _ = ref.noise(ids, 51, 4)
    assert len({row.tobytes() for row in masked}) == 4   # rows differ
    again, _ = ref.noise(ids[::-1], 51, 4)
    assert np.array_equal(again[::-1], masked)   # a row's own, wherever
    other, _ = ref.noise(ids, 52, 4)
    assert not np.array_equal(other, masked)
    one = ids.copy()
    one[0, 200] ^= 1   # one id of one row: that row's noise, no other's
    moved_masked, _ = ref.noise(one, 51, 4)
    assert not np.array_equal(moved_masked[0], masked[0])
    assert np.array_equal(moved_masked[1:], masked[1:])
    # every position is masked about as often: the sets are uniform
    many = ref.noise(packed_batch(9, 1, 64, 256)[:, :-1], 51, 4)[0]
    assert np.all(np.abs(many.mean(axis=0) - 0.625) < 0.25)
    assert abs(many.reshape(64, 64, 4).mean(axis=(0, 1)) - 0.625).max() < 0.03


def test_the_stream_never_draws_the_mask_id():
    grid = packed_batch(3, 0, 8, 4096)
    assert grid.max() < 256 <= FILE["tiny"]["mask_token_id"] \
        < FILE["mask_token_id"] == FILE["vocab_size"] - 1


def test_the_noised_copy_carries_the_mask_id_where_masked(case):
    """The decoder's own noise: the table is read at the mask id's row
    exactly where the reference masks."""
    model, tokens = case["model"], case["tokens"]
    params = model.flat.unravel(case["w"])
    masked, _ = ref.noise(np.asarray(tokens[:, :-1]), TINY["noise_seed"],
                          TINY["block_len"])
    grad = jax.grad(lambda e: model.module.apply(
        {"params": {**params, "embed": e}}, tokens[:, :-1],
        tokens[:, 1:])[0])(params["embed"])
    assert float(jnp.max(jnp.abs(grad[TINY["mask_id"]]))) > 0
    assert int(masked.sum()) == masked.size * 5 // 8
    never = np.setdiff1d(np.arange(TINY["vocab"]), np.append(
        np.asarray(tokens), TINY["mask_id"]))
    assert not np.any(np.asarray(grad)[never])


# -- (e) the estimator's weights ---------------------------------------------------------


def beta_integral(block, c):
    """``int_0^1 t^(c-1) (1 - t)^(block-c) dt`` exactly, term by term of
    the binomial expansion."""
    return sum(fractions.Fraction((-1) ** j * math.comb(block - c, j),
                                  c + j) for j in range(block - c + 1))


@pytest.mark.parametrize("block", [2, 4, 8])
def test_the_count_forms_weights_are_the_linear_schedules_integrals(block):
    """A set of ``c`` of a block's ``B`` positions is masked with
    probability ``t^c (1 - t)^(B - c)`` and weighted ``1 / t``: over ``t``
    uniform that is ``1 / (c C(B, c))``, which is what a uniform count,
    a uniform set of that size and the weight ``1 / c`` give, ``B`` times
    over; and the count form's weight times count is 1."""
    total = fractions.Fraction(0)
    for c in range(1, block + 1):
        exact = beta_integral(block, c)
        assert exact == fractions.Fraction(1, c * math.comb(block, c))
        assert ref.count_weight(block, c) == pytest.approx(float(exact),
                                                           rel=1e-12)
        drawn = fractions.Fraction(1, block) * fractions.Fraction(
            1, math.comb(block, c)) * fractions.Fraction(1, c)
        assert drawn * block == exact
        total += math.comb(block, c) * c * exact   # sets x positions x weight
    assert total == block   # every position's weight integrates to 1


# -- (f) the shares add up to the whole layer --------------------------------------------


def test_the_shares_routed_parts_are_the_whole_layer_and_nothing_is_twice():
    """The guide's share test: the layer over all its experts, by the
    plain reference, is the sum of what each share's block computes for
    its own experts.  Four shares of two experts of eight; there is no
    shared expert, so what every share computes alike (attention, the
    router) is the stream, counted once."""
    c = {**CONFIG, "num_experts": CONFIG["router_experts"],
         "experts_first": 0}
    n, held, half = c["router_experts"], CONFIG["num_experts"], 20
    kw = {name: TINY[name] for name in BLOCK_FIELDS}
    kw["attn_fn"] = transformer.default_attn(use_flash=False)
    whole = transformer.SdarBlock(**kw)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 2 * half,
                                                  c["hidden_size"]))
    params = whole.init(jax.random.PRNGKey(5), x)["params"]
    params = jax.tree_util.tree_map(
        lambda p: p + 0.05 * jax.random.normal(jax.random.PRNGKey(p.size),
                                               p.shape), params)
    experts = ("experts_gate", "experts_up", "experts_down")
    assert not any("shared" in name for name in params)

    def share(first, down_scale=1.0):
        block = transformer.SdarBlock(**kw, experts_first=first,
                                      experts_held=held)
        p = dict(params)
        for name in experts:
            p[name] = params[name][first:first + held]
        p["experts_down"] = p["experts_down"] * down_scale
        return jax.jit(lambda p: block.apply({"params": p}, x)[0])(p)

    with jax.default_matmul_precision("highest"):
        stream = share(0, down_scale=0.0)   # x + the attention alone
        routed = [share(first) - stream for first in range(0, n, held)]
        want = ref.layer(x, params, ref.visible(half, c["block_length"]), c)
    assert len(routed) == 4
    assert all(float(jnp.max(jnp.abs(part))) > 1e-3 for part in routed)
    assert float(jnp.max(jnp.abs(stream + sum(routed) - want))) < 1e-5


# -- (g) the other blocks' steps are the parent's ------------------------------------------

# As ``tests/test_keye.py`` (e): sha256 of
# ``str(make_jaxpr(value_and_grad(loss)))`` (addresses blanked) of each
# cell's block at its ``tiny`` size, the interpreted flash kernels in
# place of the reference attention, **as the parent commit of PR 51
# printed it**: the walk gained a table and the attention a keyword, and
# a call without the block-diffusion mask must still trace to the
# program it was, to the character (a selection's too).  (Since PR 57
# the digests are that PR's, here and there: ``tests/test_keye.py``;
# Keye's is PR 60's, which made the indexer one Mosaic kernel on
# purpose.)
PARENTS_STEP = {
    "keye-l6e8-local": "ce0ac9dd614a14d3",
    "kimi-linear-l5e8-local": "8a0acdd925172b3e",
    "olmoe-l1-ps1w-su1": "38c59ae0597d4cb8",
}


@pytest.mark.parametrize("cell_name", sorted(PARENTS_STEP))
def test_a_block_without_the_mask_lowers_to_the_parents_step(cell_name):
    cell = spec_mod.load_cell(cell_name)
    cell.config.update(cell.config["tiny"])
    model = runner.build_model(cell, seed=1, lm_use_flash=0)
    module = model.module.clone(attn_fn=transformer.default_attn(
        causal=True, use_flash=True, interpret=True))
    tokens = jnp.zeros((2, model.seq_len + 1), jnp.int32)
    unravel = model.flat.unravel
    own = archs.block(cell.config["launcher"]["lm_arch"]).loss == \
        archs.OWN_LOSS

    def loss(w):
        if own:
            return module.apply({"params": unravel(w)}, tokens[:, :-1],
                                tokens[:, 1:])[0]
        logp = module.apply({"params": unravel(w)}, tokens[:, :-1])
        return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None],
                                             axis=-1))

    text = re.sub(r"0x[0-9a-f]+", "0x", str(
        jax.make_jaxpr(jax.value_and_grad(loss))(model.flat.w0)))
    assert "pallas_call" in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARENTS_STEP[cell_name]


def test_olmoes_step_at_its_own_precision_is_the_parents():
    """OLMoE's block hands its attention a precision
    (``transformer.ATTN_KERNEL_PRECISION``: float32 operands at more than
    one pass), and a call that names one keeps the operands it has (PR
    57: every other call's are rounded to bf16 by the op).  With that
    attention its tiny step traces to the program it was **as the parent
    commit of PR 57 printed it**, and nothing in it is bf16."""
    cell = spec_mod.load_cell("olmoe-l1-ps1w-su1")
    cell.config.update(cell.config["tiny"])
    model = runner.build_model(cell, seed=1, lm_use_flash=0)
    module = model.module.clone(attn_fn=transformer.default_attn(
        causal=True, use_flash=True, interpret=True,
        precision=transformer.ATTN_KERNEL_PRECISION))
    tokens = jnp.zeros((2, model.seq_len + 1), jnp.int32)

    def loss(w):
        logp = module.apply({"params": model.flat.unravel(w)},
                            tokens[:, :-1])
        return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None],
                                             axis=-1))

    text = re.sub(r"0x[0-9a-f]+", "0x", str(
        jax.make_jaxpr(jax.value_and_grad(loss))(model.flat.w0)))
    assert "pallas_call" in text and "bf16" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        "cdb491eafc95f7f3"


# -- (h) the file, the vector, the seeding, the scopes, what is kept ----------------------


def test_the_files_keys_are_the_catalogs_but_for_the_three_reduced():
    """Every published key under its own name and value but the three
    cuts, the published values beside them; four keys of the pass and
    two of the share are added, and nothing else of the model's."""
    catalog = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 32768,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "sdar_moe", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    path = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if path.exists():
        rows = [json.loads(line) for line in path.read_text().splitlines()]
        entry = next(r for r in rows if r["name"] == "SDAR-30B-A3B-Chat")
        assert entry["config"] == catalog
        assert entry["source_url"] == FILE["source"]
        assert entry["not_given"] == ["block length", "noise schedule"]
    assert all(key in FILE for key in catalog)
    differ = sorted(k for k, v in catalog.items() if FILE[k] != v)
    assert differ == sorted(FILE["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert FILE["published"] == {k: catalog[k] for k in FILE["reduced"]}
    assert (FILE["num_hidden_layers"], FILE["num_experts"],
            FILE["vocab_size"]) == (6, 8, 151936 // 8)
    harness = {"name", "source", "reduced", "published", "deployment",
               "assumed", "why", "reference", "arithmetic", "scopes", "tiny",
               "launcher", "launcher_from"}
    added = set(FILE) - set(catalog) - harness
    assert added == {"train_seq", "block_length", "mask_token_id",
                     "noise_seed", "router_experts", "experts_first"}
    assert (FILE["train_seq"], FILE["block_length"], FILE["mask_token_id"],
            FILE["router_experts"], FILE["experts_first"]) == (
                4096, 4, 18991, 128, 0)
    said = " ".join(FILE["assumed"])
    for word in ("block_length 4", "count form", "mask_token_id 18991",
                 "no shift", "pure function", "momentum SGD", "std 8.0",
                 "packed documents", "adaptation"):
        assert word in said, word
    assert "16 v5e chips" in FILE["deployment"]


def test_the_built_models_vector_is_the_arithmetics_at_the_tiny_size(case):
    assert case["model"].flat.size == arithmetic.param_count(CONFIG)
    whole = {**CONFIG, "num_experts": CONFIG["router_experts"],
             "experts_first": 0}
    model = build(arch="sdar", seed=3, use_flash=False, **sizes(whole))
    assert model.flat.size == arithmetic.param_count(whole)
    assert model.seq_len == CONFIG["train_seq"]   # tokens, not rows


@pytest.mark.parametrize("what,got,want", arithmetic.hand_worked(),
                         ids=[c[0] for c in arithmetic.hand_worked()])
def test_sdar_arithmetic_by_hand(what, got, want):
    assert got == want, what


@pytest.mark.parametrize("half,block,tile", [
    (4096, 4, 512), (256, 4, 128), (256, 8, 128), (1024, 16, 256)])
def test_the_arithmetics_pairs_and_tiles_are_the_references_masks(
        half, block, tile):
    if half <= 1024:
        mask = np.asarray(ref.visible(half, block))
        assert int(mask.sum()) == arithmetic.live_pairs(half, block)
        assert (int(tiles_of(mask, tile, tile).sum()), (2 * half // tile) ** 2
                ) == arithmetic.live_tiles(half, block, tile)
    live, _ = fa._blockdiff_tiles(half, block, tile, tile, 2 * half // tile,
                                  2 * half // tile)
    assert (int(live.sum()), live.size) == arithmetic.live_tiles(
        half, block, tile)


def test_the_seeding(case):
    params = case["model"].flat.unravel(case["model"].flat.w0)
    block = params["SdarBlock_1"]
    for name in ("attn_norm", "q_norm", "k_norm", "mlp_norm"):
        assert np.all(np.asarray(block[name]) == 1.0), name
    assert float(jnp.std(params["embed"])) == pytest.approx(8.0, rel=0.05)
    for leaf in (params["head"], block["wq"], block["router"],
                 block["experts_gate"]):
        assert float(jnp.std(leaf)) == pytest.approx(0.02, rel=0.15)


def test_the_seeded_weights_do_not_depend_on_the_training_sequence():
    short = build(arch="sdar", seed=3, use_flash=False,
                  **{**TINY, "seq_len": 32})
    long = build(arch="sdar", seed=3, use_flash=False, **TINY)
    assert np.array_equal(np.asarray(short.flat.w0), np.asarray(long.flat.w0))


@pytest.mark.parametrize("bad", [
    {"block_len": 3}, {"block_len": 32}, {"block_len": 0},
    {"seq_len": 66}, {"mask_id": 1024}, {"mask_id": -2},
    {"experts_first": 7},
])
def test_sizes_that_make_no_block_are_refused(bad):
    with pytest.raises(ValueError):
        build(arch="sdar", use_flash=False, **{**TINY, **bad})


def test_a_size_of_another_block_is_refused():
    with pytest.raises(TypeError, match="sdar takes no index_topk"):
        build(arch="sdar", use_flash=False, **{**TINY, "index_topk": 8})


def test_the_last_row_is_the_mask_ids_default():
    model = build(arch="sdar", seed=3, use_flash=False,
                  **{**TINY, "mask_id": -1})
    tokens = jax.random.randint(jax.random.PRNGKey(7),
                                (2, TINY["seq_len"] + 1), 0, 256)
    named = build(arch="sdar", seed=3, use_flash=False, **TINY)
    assert TINY["mask_id"] == TINY["vocab"] - 1
    assert float(model.loss(model.flat.w0, tokens)) == float(
        named.loss(named.flat.w0, tokens))


def test_the_steps_operations_carry_the_blocks_scopes(case):
    model = case["model"]
    text = jax.jit(model.value_and_grad).lower(
        case["w"], case["tokens"]).as_text(debug_info=True)
    for scope in FILE["scopes"]:
        if scope != "update":   # the optimizer's, not the model's
            assert f"/{scope}/" in text, scope
    assert {"noise", "attn"} <= set(FILE["scopes"])


def test_a_layer_keeps_its_input_and_the_kernels_two_alone():
    """The attention's checkpoint: beside the layer's input and its
    parameters, what is kept for the backward pass is the flash rule's
    output and row log-sum-exp; q, k, v are made again."""
    from jax._src.ad_checkpoint import saved_residuals

    b, half = 2, 24
    x = jnp.ones((b, 2 * half, TINY["d_model"]))
    block = transformer.SdarBlock(**{name: TINY[name]
                                     for name in BLOCK_FIELDS})
    p = block.init(jax.random.PRNGKey(0), x)["params"]
    attend = jax.checkpoint(
        functools.partial(
            transformer.blockdiff_attention, heads=TINY["n_heads"],
            kv_heads=TINY["kv_heads"], head_dim=TINY["head_dim"],
            block=TINY["block_len"], theta=TINY["rope_theta"],
            eps=TINY["norm_eps"], attn=interpreted(None)),
        policy=jax.checkpoint_policies.save_only_these_names(
            *transformer.JOYAI_ATTN_KEPT))
    kept = saved_residuals(attend, x, p)
    made = sorted((str(shape.dtype), shape.shape) for shape, why in kept
                  if "argument" not in why and shape.ndim > 1)
    hq, hkv, hd = TINY["n_heads"], TINY["kv_heads"], TINY["head_dim"]
    assert made == sorted([
        ("float32", (b, hkv, hq // hkv, 2 * half, hd)),        # flash_out
        ("float32", (b, hkv, hq // hkv, 2 * half)),            # flash_lse
    ])


# -- the counters on the round spans, the launcher, the servers ---------------------------

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
    assert {key: kw[key] for key in TINY} == TINY
    assert kw["arch"] == "sdar"
    assert "sdar" in archs.ARCHS and archs.sizes_of("sdar")[-3:] == (
        "block_len", "mask_id", "noise_seed")
    assert archs.block("sdar").loss == archs.OWN_LOSS


def test_a_one_rank_local_run_learns_and_carries_the_passs_counters(obs_on):
    """``--np 1 --opt msgd``: the single-process path hands ``MSGD`` the
    step with the block's telemetry, and each donated step is a
    ``round`` span with the noise's share, the NLL by count and the
    routing's three a layer while obs records."""
    from mpit_tpu.train import launch

    steps = 24   # the bound learns the bytes' range first: 5.76 to 5.6
    cfg = launch.LAUNCH_DEFAULTS.merged(
        np=1, opt="msgd", mom=0.9, lr=0.1, batch=4, lm_steps=steps,
        **LAUNCH)
    result = launch.run_rank(0, 1, cfg, None)
    assert result["role"] == "local"
    history = result["history"]
    assert history[-1]["avg_loss"] < history[0]["avg_loss"] - 0.05
    rounds = [s for s in obs_on.spans if s.name == "round"]
    assert len(rounds) == steps
    nll = [f"diff_nll_c{c}" for c in range(1, TINY["block_len"] + 1)]
    for span in rounds:
        assert span.args["diff_masked_share"] == [0.625]
        for name in nll:
            assert len(span.args[name]) == 1 and span.args[name][0] > 0
        for name in transformer.JOYAI_MOE_STATS[:3]:
            assert len(span.args[name]) == TINY["n_layers"], name
    assert obs.get_registry().gauge("mpit_diff_masked_share",
                                    layer=0).value == 0.625
    assert result["diff_nll_c1"] == rounds[-1].args["diff_nll_c1"]


def test_the_kernels_tiles_are_on_the_spans_where_the_kernel_runs(obs_on):
    """With the flash kernel in the block (here interpreted: the model
    is built with the kernel pinned off a TPU) the step records the
    tiles its attention calls visit and those that are live, constants
    of the lowered calls, one entry a layer."""
    from mpit_tpu.optim.msgd import MSGD, MSGDConfig

    model = build(arch="sdar", seed=3, use_flash=False, **TINY)
    module = model.module.clone(attn_fn=interpreted(None))
    unravel = model.flat.unravel
    vgf = jax.value_and_grad(
        lambda w, tokens: module.apply({"params": unravel(w)},
                                       tokens[:, :-1], tokens[:, 1:]),
        has_aux=True)
    tokens = jnp.asarray(packed_batch(1, 0, 2, TINY["seq_len"]))
    opt = MSGD(MSGDConfig(lr=0.01, mom=0.9), vgf, has_aux=True)
    opt.step(model.flat.w0, tokens)
    span = [s for s in obs_on.spans if s.name == "round"][-1]
    visited, live = (span.args[name]
                     for name in transformer.SDAR_TILE_STATS)
    assert visited == live and len(live) == TINY["n_layers"]
    # one tile of 128 holds the 128 rows: a forward, a dq and a dk/dv
    # call, two sequences, four query heads
    assert live[0] == 3 * 2 * TINY["n_heads"]
    reader = spec_mod.load_reader(ROOT, spec_mod.load_bench(),
                                  "blockdiff_dead_tiles_pct")

    class Tree:
        def rounds(self):
            return [span]

    from chipbench.layers import spantree
    assert reader({spantree.CACHE_KEY: Tree()}) == 0.0


def test_the_block_trains_through_two_servers_cut_between_experts():
    """The normal path in one process: ``LmTrainer`` with ``arch`` sdar
    and server-side Adam, two server threads holding the planner's cut
    (inside a stacked expert leaf, between two experts).  The loss
    falls and every push is applied."""
    import threading

    from mpit_tpu.comm.local import LocalRouter
    from mpit_tpu.lm import LmTrainer, plan
    from mpit_tpu.ps import ParamClient, ParamServer
    from mpit_tpu.train import launch

    steps = 24
    cfg = launch.LAUNCH_DEFAULTS.merged(
        **{**LAUNCH, "lm_layers": 1, "lm_experts_held": 0,
           "lm_experts_first": 0, "lm_expert_width": 64,
           # a table of 320 rows puts the halves' boundary in the experts
           "lm_vocab": 320, "lm_mask_id": 319},
        lm_steps=steps, batch=4, opt="adam", lr=3e-3)
    tcfg = launch.lm_trainer_cfg(cfg)
    assert build_kw(tcfg)["arch"] == "sdar"
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
    assert history[-1]["avg_loss"] < history[0]["avg_loss"] - 0.05
    assert [s.grads_applied for s in servers] == [steps, steps]
    assert [(s.offset, s.size) for s in servers] == \
        [(s.offset, s.size) for s in layout]
