"""Learned sparse attention on the normal path (``lm/model.py``
``build(arch="keye")``: ``models/transformer.py`` ``KeyeDecoder``: a
lightning indexer that scores every earlier position, an exact top-k a
query, grouped attention over the chosen keys alone, a share of the
experts behind a softmax router) against its plain float32 reference, at
the benchmark configuration's ``tiny`` size on seeded weights; the
selection of ``ops/index_select.py`` against a stable sort; and the
flash kernels that mask by the selection's bits
(``ops/flash_attention.py``, interpreted) against a materialised masked
softmax.  The reference exists once, as the benchmark's
``chipbench/reference/keye_plain.py`` (no code shared with the block),
and is imported from there.

Tolerances.  On the CPU both sides multiply in full float32, so they
choose the same sets and differ by the rounding of sums taken in another
order: 1e-7 of the gradient's norm and exactly in the loss as measured
here.  The limits are 1e-5.  What they must refuse, each tried below on
the reference itself with one thing wrong, is wrong by 1e-3 or more."""

import functools
import hashlib
import json
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run as runner, spec as spec_mod
from chipbench.arithmetic import keye as arithmetic
from chipbench.reference import keye_plain as ref
from mpit_tpu import obs
from mpit_tpu.lm import archs
from mpit_tpu.lm.model import build, build_kw
from mpit_tpu.models import transformer
from mpit_tpu.ops import index_select, select_bits
from mpit_tpu.ops.flash_attention import attention_reference, flash_attention
from mpit_tpu.parallel import moe

LOSS_TOL_NATS = 1e-5
GRAD_REL_TOL = 1e-5

ROOT = pathlib.Path(__file__).resolve().parent.parent
FILE = json.loads(
    (ROOT / "chipbench/configs/keye-vl2-30b-l6e8.json").read_text())
CONFIG = {**FILE, **FILE["tiny"]}  # the reference's keys, at the tiny size


def sizes(c):
    """``build``'s keywords from the configuration's keys."""
    sa = c["sa_config"]
    return dict(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], n_layers=c["num_hidden_layers"],
        seq_len=c["train_seq"], index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
        n_experts=c["router_experts"], experts_held=c["num_experts"],
        experts_first=c["experts_first"],
        experts_per_tok=c["num_experts_per_tok"],
        expert_width=c["moe_intermediate_size"],
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"])


TINY = sizes(CONFIG)
INDEX_LEAVES = ("index_wq", "index_wk", "index_ww", "index_k_norm",
                "index_k_bias")


def moved(model, scale=0.05, seed=0):
    """The seeded weights moved off their initial values: norm weights
    off 1 and the LayerNorm's bias off 0, so that one that is ignored
    shows."""
    rs = np.random.RandomState(seed)
    return model.flat.w0 + scale * jnp.asarray(rs.randn(model.flat.size),
                                               jnp.float32)


def relative(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.fixture(scope="module")
def case():
    model = build(arch="keye", seed=3, use_flash=False, **TINY)
    w = moved(model)
    tokens = jax.random.randint(jax.random.PRNGKey(7),
                                (2, TINY["seq_len"] + 1), 0, 256)
    with jax.default_matmul_precision("highest"):
        (loss, stats), grad = jax.jit(model.value_grad_stats)(w, tokens)
    ref_loss, ref_grad = ref.loss_and_grad_flat(w, model.flat.unravel,
                                                tokens, CONFIG)
    return dict(model=model, w=w, tokens=tokens, loss=loss, stats=stats,
                grad=grad, ref_loss=ref_loss, ref_grad=ref_grad)


# -- (a) the selection against a stable sort --------------------------------------


def index_inputs(batch, length, heads=3, dim=8, seed=0, planted=False):
    """Queries, the one key head and the heads' weights; ``planted``
    rounds all three to whole numbers, so that many scores of a row are
    equal to the bit."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    qi = jax.random.normal(keys[0], (batch, length, heads, dim))
    ki = jax.random.normal(keys[1], (batch, length, dim))
    w = jax.random.normal(keys[2], (batch, length, heads))
    return tuple(jnp.round(x) for x in (qi, ki, w)) if planted \
        else (qi, ki, w)


SELECT_CASES = [
    # what, batch, length, topk, rows a block, planted ties
    ("planted ties", 2, 64, 16, 16, True),
    ("no ties", 1, 64, 16, 64, False),
    ("a length that is no whole number of row blocks", 1, 100, 24, 32, True),
    ("topk over the length: every earlier position", 2, 48, 64, 16, False),
    ("topk one", 1, 40, 1, 8, True),
    ("a row block that straddles topk", 1, 64, 24, 16, False),
    ("a length over one column tile that is no whole number of tiles", 1, 70,
     8, 16, False),
    ("ties at the threshold whose equals lie in different column tiles", 1,
     96, 20, 8, True),
    ("two sequences with different keys in one call", 2, 80, 12, 16, False),
    ("a length past one group of 4096 keys' words", 1, 4224, 300, 256, False),
]


@pytest.mark.parametrize("what,batch,length,topk,rows,planted", SELECT_CASES,
                         ids=[c[0] for c in SELECT_CASES])
def test_the_selection_is_the_stable_sorts(what, batch, length, topk, rows,
                                           planted, monkeypatch):
    """The bisection's sets are the stable sort's to the key: the lower
    position wins a tie, and a row has exactly ``min(t + 1, topk)``."""
    monkeypatch.setattr(index_select, "ROWS", rows)
    qi, ki, w = index_inputs(batch, length, planted=planted)
    words, kept, overlap = jax.jit(
        lambda *a: index_select.index_select(*a, topk))(qi, ki, w)
    got = np.asarray(select_bits.unpack(words, length))
    want = np.asarray(index_select.index_select_reference(qi, ki, w, topk))
    assert np.array_equal(got, want), what
    if "different column tiles" in what:
        assert equals_across_tiles(qi[0], ki[0], w[0], want[0], rows)
    per_row = np.minimum(np.arange(length) + 1, topk)
    assert np.array_equal(got.sum(-1), np.broadcast_to(per_row, got.shape[:2]))
    assert not np.triu(got, 1).any()   # nothing above the diagonal
    assert words.shape == (batch, length, select_bits.words_of(length))
    assert float(kept) == pytest.approx(
        per_row.sum() / (length * (length + 1) / 2), rel=1e-6)
    late = np.arange(length) >= topk
    near = np.tril(np.ones((length, length), bool)) & ~np.tril(
        np.ones((length, length), bool), -topk)
    if late.any():
        assert float(overlap) == pytest.approx(
            (got & near)[:, late].sum() / got[:, late].sum(), rel=1e-6)


def equals_across_tiles(qi, ki, w, chosen, tile):
    """Whether some row's lowest chosen score has equals among the row's
    candidates that are not all taken and lie in different tiles of
    ``tile`` columns: the case the ties' second bisection exists for."""
    scores = np.asarray(index_select.index_scores(qi, ki, w))
    for t, row in enumerate(chosen):
        equals = np.flatnonzero(
            scores[t, :t + 1] == scores[t, :t + 1][row[:t + 1]].min())
        if not row[equals].all() and len(set(equals // tile)) > 1:
            return True
    return False


def test_the_kernels_tiles_of_scores_are_index_scores_to_the_bit():
    """A tile of the kernel's scores, made inside a Pallas body from
    refs (interpreted), against ``index_scores`` of the same keys, on
    random float32 inputs: one function, one order of the heads' sum, so
    the sets of the tests' reference and of the kernel are made from
    the same numbers.  (Tile by tile and both compiled: how the CPU's
    own product sums 8 terms depends on the operands' shapes, and a
    compiled multiply-add rounds once where two operations round twice,
    each in the last bit.)"""
    from jax.experimental import pallas as pl

    length, tile, heads = 96, 32, 5
    qi, ki, w = (x[0] for x in index_inputs(1, length, heads=heads, seed=4))

    def body(q_ref, k_ref, w_ref, out_ref):
        out_ref[...] = index_select._tile_scores(
            k_ref[...], heads, lambda h: (q_ref[h], w_ref[h:h + 1, :]),
            index_select.SCORE_PRECISION)

    tiles = pl.pallas_call(
        body, grid=(length // tile,),
        in_specs=[pl.BlockSpec((heads, 8, length), lambda t: (0, 0, 0)),
                  pl.BlockSpec((tile, 8), lambda t: (t, 0)),
                  pl.BlockSpec((heads, length), lambda t: (0, 0))],
        out_specs=pl.BlockSpec((tile, length), lambda t: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((length, length), jnp.float32),
        interpret=True)(qi.transpose(1, 2, 0), ki, w.T)
    bits = lambda x: np.asarray(x).view(np.uint32)
    for t in range(length // tile):
        keys = slice(t * tile, (t + 1) * tile)
        assert np.array_equal(
            bits(tiles[keys].T),
            bits(jax.jit(index_select.index_scores)(qi, ki[keys], w))), t
    # and the sum over the heads is taken in their order, in float32
    by_hand = sum(np.maximum(np.asarray(qi)[:, h] @ np.asarray(ki).T, 0)
                  * np.asarray(w)[:, h:h + 1] for h in range(heads))
    np.testing.assert_allclose(np.asarray(tiles).T, by_hand, rtol=1e-5,
                               atol=1e-5)


def test_a_lowered_score_precision_is_another_trace(monkeypatch):
    """``chipbench/reference/probe_keye.py`` plants its fault by setting
    ``SCORE_PRECISION`` after a sound run in the same process at the
    same shapes: the precision is part of the key of the kernel's own
    ``jit``, so the second build's products are the planted ones and
    not the first trace's."""
    monkeypatch.setattr(index_select, "ROWS", 16)
    qi, ki, w = index_inputs(1, 32)

    def products():
        text = str(jax.make_jaxpr(
            lambda *a: index_select.index_select(*a, 8))(qi, ki, w))
        return {p for p in ("HIGHEST", "DEFAULT")
                if f"precision=(Precision.{p}" in text}

    assert products() == {"HIGHEST"}
    monkeypatch.setattr(index_select, "SCORE_PRECISION",
                        jax.lax.Precision.DEFAULT)
    assert products() == {"DEFAULT"}
    monkeypatch.undo()
    monkeypatch.setattr(index_select, "ROWS", 16)
    assert products() == {"HIGHEST"}


def test_a_sequence_past_the_chips_vmem_is_refused():
    """The keys of a block and the whole key head live in VMEM: past
    the chip's 128 MiB the call says so before Mosaic does, and the
    longest sequence the docstring names is let through."""
    def shapes(length):
        return (jax.ShapeDtypeStruct((1, length, 16, 64), jnp.float32),
                jax.ShapeDtypeStruct((1, length, 64), jnp.float32),
                jax.ShapeDtypeStruct((1, length, 16), jnp.float32))

    select = lambda *a: index_select.index_select(*a, 2048)
    assert jax.eval_shape(select, *shapes(54272))[0].shape == (
        1, 54272, select_bits.words_of(54272))
    with pytest.raises(ValueError, match="MiB of VMEM"):
        jax.eval_shape(select, *shapes(54272 + 256))
    # the cell's shape: the keys 8, the key head twice 8, the other
    # blocks twice 2.5 and the stock 16 MiB
    assert index_select._vmem_bytes(
        16, 8192, 64, 256, select_bits.words_of(8192)) == pytest.approx(
            34.5 * 2**20, rel=0.01)


def test_all_scores_equal_is_the_lowest_positions(monkeypatch):
    """Weights of zero make every score zero: of a row's ``t + 1`` equal
    candidates the ``topk`` lowest positions are taken, never the most
    recent."""
    monkeypatch.setattr(index_select, "ROWS", 8)
    qi, ki, w = index_inputs(1, 32)
    words, _, overlap = index_select.index_select(qi, ki, 0.0 * w, 4)
    got = np.asarray(select_bits.unpack(words, 32))[0]
    for t in range(32):
        assert np.flatnonzero(got[t]).tolist() == list(range(min(t + 1, 4)))
    assert float(overlap) < 0.2


def test_minus_zero_ties_with_zero():
    """``-0.0`` and ``0.0`` are one score: the sort's order, not the
    bits'."""
    scores = jnp.asarray([[0.0, -0.0, -0.0, 0.0, -1.0, 1.0]])
    got = index_select.top_k_mask(scores, jnp.ones_like(scores, bool),
                                  jnp.asarray([3]))
    assert np.asarray(got)[0].tolist() == [True, True, False, False, False,
                                           True]


@pytest.mark.parametrize("length", [1, 100, 4096, 4097])
def test_the_bits_are_the_set(length):
    rs = np.random.RandomState(length)
    chosen = jnp.asarray(rs.rand(3, length) < 0.4)
    words = select_bits.pack(chosen)
    assert words.shape == (3, select_bits.words_of(length))
    assert words.dtype == jnp.int32
    assert np.array_equal(np.asarray(select_bits.unpack(words, length)),
                          np.asarray(chosen))
    # key c is bit (c % 4096) // 128 of word (c // 4096) * 128 + c % 128
    c = length - 1
    bit = (np.asarray(words).astype(np.uint32)[
        :, (c // 4096) * 128 + c % 128] >> ((c % 4096) // 128)) & 1
    assert np.array_equal(bit.astype(bool), np.asarray(chosen)[:, c])


@pytest.mark.parametrize("keys", [1, 4096, 4097, 8192, 12289])
def test_the_arithmetics_words_are_the_programs(keys):
    assert arithmetic.select_words(keys) == select_bits.words_of(keys)


def test_the_selection_has_no_gradient(monkeypatch):
    monkeypatch.setattr(index_select, "ROWS", 8)
    qi, ki, w = index_inputs(1, 24)
    grads = jax.grad(lambda *a: index_select.index_select(*a, 8)[1],
                     argnums=(0, 1, 2))(qi, ki, w)
    assert all(not np.any(np.asarray(g)) for g in grads)


# -- (b) the selected attention against a materialised masked softmax -------------


def dense(q, k, v, mask):
    """Masked softmax attention over whole rows: ``q (B, Hq, L, D)``,
    ``k, v (B, Hkv, L, D)``, ``mask (B, L, L)``."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


ATTN_CASES = [
    # what, batch, query heads, KV heads, length, head, topk, block_q, block_k
    ("topk over the length: the plain causal result", 2, 2, 2, 96, 16, 128,
     None, None),
    ("topk under the length", 1, 2, 2, 64, 16, 16, None, None),
    ("a length that is no whole number of blocks", 1, 4, 2, 300, 16, 40, 64,
     128),
    ("batch 2: no key crosses sequences", 2, 2, 1, 200, 16, 24, 64, 128),
    ("4 query over 2 KV heads: the mask goes by position", 1, 4, 2, 260, 16,
     32, 64, 128),
    ("8 folded heads over blocks of 256 keys", 1, 8, 1, 520, 32, 64, 128, 256),
]


@pytest.mark.parametrize("what,b,hq,hkv,length,d,topk,bq,bk", ATTN_CASES,
                         ids=[c[0] for c in ATTN_CASES])
def test_the_selected_attention_is_the_materialised_masked_softmax(
        what, b, hq, hkv, length, d, topk, bq, bk, monkeypatch):
    """Forward and the gradients of q, k, v, interpreted, and the jnp
    reference path beside it."""
    monkeypatch.setattr(index_select, "ROWS", 64)
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(keys[0], (b, hq, length, d))
    k = jax.random.normal(keys[1], (b, hkv, length, d))
    v = jax.random.normal(keys[2], (b, hkv, length, d))
    ct = jax.random.normal(keys[3], (b, hq, length, d))
    select = index_select.index_select(
        *index_inputs(b, length, seed=length), topk)[0]
    mask = select_bits.unpack(select, length)
    if topk >= length:
        assert np.array_equal(
            np.asarray(mask[0]), np.tril(np.ones((length, length), bool)))

    def both(fn):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(
                lambda q, k, v: jnp.sum(fn(q, k, v) * ct), (0, 1, 2))(q, k, v)

    want, want_grads = both(lambda q, k, v: dense(q, k, v, mask))
    paths = {
        "kernels": lambda q, k, v: flash_attention(
            q, k, v, causal=True, select=select, block_q=bq, block_k=bk,
            interpret=True, precision="highest"),
        "reference": lambda q, k, v: attention_reference(
            q, k, v, causal=True, select=select)}
    for path, fn in paths.items():
        got, grads = both(fn)
        assert abs(float(got - want)) < 1e-4 * max(1.0, abs(float(want))), path
        for name, g, wanted in zip("qkv", grads, want_grads):
            assert float(jnp.max(jnp.abs(g - wanted))) < 2e-5, (path, name)
    if topk >= length:   # and the kernels' own plain causal call
        plain = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                                interpret=True, precision="highest")
        chosen = paths["kernels"](q, k, v)
        assert float(jnp.max(jnp.abs(plain - chosen))) < 1e-6


def test_a_tile_with_no_chosen_pair_is_skipped_and_changes_nothing():
    """A selection of the 40 most recent positions leaves the far causal
    tiles of 128 keys without a pair: the kernels skip them, forward and
    backward, and the numbers are the masked softmax's."""
    length, d = 400, 16
    t = np.arange(length)
    near = (t[None, :] <= t[:, None]) & (t[:, None] - t[None, :] < 40)
    select = select_bits.pack(jnp.asarray(near)[None])
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    q, k, v = (jax.random.normal(key, (1, 2, length, d)) for key in keys)
    assert not near[256:384, 0:128].any()   # a causal tile without a pair

    def both(fn):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(
                lambda q, k, v: jnp.sum(fn(q, k, v) ** 2), (0, 1, 2))(q, k, v)

    got, grads = both(lambda q, k, v: flash_attention(
        q, k, v, causal=True, select=select, block_q=128, block_k=128,
        interpret=True, precision="highest"))
    want, want_grads = both(lambda q, k, v: dense(q, k, v, near[None]))
    windowed, _ = both(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=40, block_q=128, block_k=128,
        interpret=True, precision="highest"))
    assert abs(float(got - want)) < 1e-4 * float(want)
    assert abs(float(got - windowed)) < 1e-4 * float(want)
    for g, wanted in zip(grads, want_grads):
        assert float(jnp.max(jnp.abs(g - wanted))) < 2e-5


def test_a_block_of_keys_that_does_not_divide_a_group_of_words_is_refused():
    q = jnp.zeros((1, 1, 384, 16))
    select = jnp.zeros((1, 384, 128), jnp.int32)
    with pytest.raises(ValueError, match="block_k"):
        flash_attention(q, q, q, causal=True, select=select, block_k=384,
                        interpret=True)


def test_a_selection_is_a_sequences_and_never_a_heads():
    """``(B, Lq, words)`` beside ``k (B, Hkv, Lk, D)``, nothing else."""
    q = jnp.zeros((2, 2, 64, 16))
    select = select_bits.pack(jnp.tril(jnp.ones((2, 2, 64, 64), bool)))
    for wrong in (select, select[0, 0]):
        with pytest.raises(ValueError, match="a selection is"):
            flash_attention(q, q, q, causal=True, select=wrong,
                            interpret=True)


def test_under_a_selection_the_backward_is_two_kernels_whatever_the_lever(
        monkeypatch):
    monkeypatch.setenv("MPIT_FA_FUSED_BWD", "1")
    q = jnp.zeros((1, 2, 64, 16))
    select = select_bits.pack(jnp.tril(jnp.ones((1, 64, 64), bool)))
    text = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(flash_attention(
        q, q, q, causal=True, select=select, interpret=True))))(q))
    assert text.count("pallas_call") == 3   # forward, dk/dv, dq
    plain = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(flash_attention(
        q, q, q, causal=True, interpret=True))))(q))
    assert plain.count("pallas_call") == 2  # forward, the fused sweep


# -- (c) the decoder against the plain reference -----------------------------------


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
    in the flat norm).  **The indexer's five leaves have a gradient of
    exactly zero on both sides**: the selection is piecewise constant."""
    unravel = case["model"].flat.unravel
    got, want = unravel(case["grad"]), unravel(case["ref_grad"])
    leaves = jax.tree_util.tree_leaves_with_path(got)
    # a layer: attention 7, the indexer 5, the sparse MLP 5; table,
    # final norm, head
    assert len(leaves) == TINY["n_layers"] * 17 + 3
    zero = 0
    for path, leaf in leaves:
        name = jax.tree_util.keystr(path)
        other = functools.reduce(lambda t, k: t[k.key], path, want)
        if any(f"'{index}'" in name for index in INDEX_LEAVES):
            assert not np.any(np.asarray(leaf)) and \
                not np.any(np.asarray(other)), name
            zero += 1
            continue
        assert float(jnp.linalg.norm(other)) > 0, name
        assert relative(leaf, other) < 1e-4, name
    assert zero == TINY["n_layers"] * len(INDEX_LEAVES)


def test_the_kernel_in_the_block_changes_no_number(case):
    """The flash kernels (interpret mode) that mask by the bits in place
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


def _causal(x, p, c):
    t = jnp.arange(x.shape[1])
    return jnp.broadcast_to(t[None, :] <= t[:, None],
                            (x.shape[0], x.shape[1], x.shape[1]))


def _recent(x, p, c):
    t = jnp.arange(x.shape[1])
    near = (t[None, :] <= t[:, None]) & (
        t[:, None] - t[None, :] < c["sa_config"]["topk"])
    return jnp.broadcast_to(near, (x.shape[0], *near.shape))


_layer_norm = ref.layer_norm

WRONG = {
    "the selection left out": {"selection": _causal},
    "the most recent positions in place of the indexer's choice": {
        "selection": _recent},
    "the ReLU left out of the score": {
        "index_scores": lambda qi, ki, w: jnp.sum(
            jnp.einsum("rhd,kd->rhk", qi, ki) * w[:, :, None], axis=1)},
    "the head norm left out": {"head_norm": lambda x, weight, eps: x},
    "the indexer's key not normed": {
        "layer_norm": lambda x, weight, bias, eps: x},
    "the indexer's LayerNorm without its bias": {
        "layer_norm": lambda x, weight, bias, eps: _layer_norm(
            x, weight, 0.0 * bias, eps)},
    "the top-8 not renormalised": {
        "router_gates": functools.partial(
            lambda gates, h, router, top_k, renormalise: gates(
                h, router, top_k, False), ref.router_gates)},
}


@pytest.mark.parametrize("what", sorted(WRONG))
def test_the_tolerance_refuses(case, what, monkeypatch):
    for name, fn in WRONG[what].items():
        monkeypatch.setattr(ref, name, fn)
    loss, grad = ref.loss_and_grad_flat(
        case["w"], case["model"].flat.unravel, case["tokens"], CONFIG)
    assert (abs(float(loss) - float(case["ref_loss"])) > LOSS_TOL_NATS
            or relative(grad, case["ref_grad"]) > GRAD_REL_TOL), what
    assert relative(grad, case["ref_grad"]) > 1e-3, what


def test_the_counters_are_the_formulas(case):
    seq, topk = TINY["seq_len"], TINY["index_topk"]
    stats = case["stats"]
    assert set(stats) == set(transformer.KEYE_DSA_STATS) | set(
        transformer.JOYAI_MOE_STATS[:3])
    for name in stats:
        assert stats[name].shape == (TINY["n_layers"],), name
    kept = arithmetic.selected_pairs(seq, topk) / arithmetic.causal_pairs(seq)
    assert np.allclose(np.asarray(stats["lm_dsa_kept_share"]), kept,
                       rtol=1e-6)
    overlap = np.asarray(stats["lm_dsa_window_overlap"])
    assert np.all((overlap > 0.2) & (overlap < 0.9))


# -- (d) the shares add up to the whole layer; the router --------------------------


def test_the_shares_routed_parts_are_the_whole_layer_and_nothing_is_twice():
    """The guide's share test: the layer over all its experts, by the
    plain reference, is the sum of what each share's block computes for
    its own experts.  Four shares of two experts of eight; there is no
    shared expert, so what every share computes alike (attention, the
    indexer, the router) is the stream, counted once."""
    c = {**CONFIG, "num_experts": CONFIG["router_experts"],
         "experts_first": 0, "num_hidden_layers": 1}
    n, held = c["router_experts"], CONFIG["num_experts"]
    kw = {name: TINY[name] for name in (
        "d_model", "n_heads", "kv_heads", "head_dim", "index_heads",
        "index_head_dim", "index_topk", "n_experts", "experts_per_tok",
        "expert_width", "rope_theta", "norm_eps")}
    kw["attn_fn"] = transformer.default_attn(use_flash=False)
    whole = transformer.KeyeBlock(**kw)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 40, c["hidden_size"]))
    params = whole.init(jax.random.PRNGKey(5), x)["params"]
    params = jax.tree_util.tree_map(
        lambda p: p + 0.05 * jax.random.normal(jax.random.PRNGKey(p.size),
                                               p.shape), params)
    experts = ("experts_gate", "experts_up", "experts_down")
    assert not any("shared" in name for name in params)

    def share(first, down_scale=1.0):
        block = transformer.KeyeBlock(**kw, experts_first=first,
                                      experts_held=held)
        p = dict(params)
        for name in experts:
            p[name] = params[name][first:first + held]
        p["experts_down"] = p["experts_down"] * down_scale
        return jax.jit(lambda p: block.apply({"params": p}, x)[0])(p)

    with jax.default_matmul_precision("highest"):
        stream = share(0, down_scale=0.0)   # x + the attention alone
        routed = [share(first) - stream for first in range(0, n, held)]
        want = ref.layers({"embed": x.reshape(-1, x.shape[-1]),
                           "KeyeBlock_0": params},
                          jnp.arange(x.shape[0] * x.shape[1]).reshape(
                              x.shape[:2]), c)   # the one layer, uncut
    assert len(routed) == 4
    assert all(float(jnp.max(jnp.abs(part))) > 1e-3 for part in routed)
    assert float(jnp.max(jnp.abs(stream + sum(routed) - want))) < 1e-5


def test_the_router_takes_8_of_128_by_softmax_renormalised():
    rs = np.random.RandomState(1)
    logits = jnp.asarray(rs.randn(50, 128), jnp.float32)
    weights, chosen = moe.route_top_k(jax.nn.softmax(logits, axis=-1), 8,
                                      renormalise=True)
    gates = np.asarray(ref.router_gates(logits, jnp.eye(128), 8, True))
    assert chosen.shape == weights.shape == (50, 8)
    assert np.allclose(np.asarray(jnp.sum(weights, axis=-1)), 1.0, rtol=1e-5)
    rows = np.arange(50)[:, None]
    assert np.allclose(gates[rows, np.asarray(chosen)], np.asarray(weights),
                       rtol=1e-4)
    assert np.count_nonzero(gates) == 50 * 8


# -- (e) the other blocks' steps are the parent's ------------------------------------

# sha256 of ``str(make_jaxpr(value_and_grad(loss)))`` (addresses blanked)
# of each cell's block at its ``tiny`` size, the interpreted flash
# kernels in place of the reference attention, **as the parent commit of
# PR 46 printed it**: the kernels gained an operand, and a call without a
# selection must still trace to the program it was, to the character.  A
# PR that changes one of these blocks or the kernels on purpose records
# the new digest here and says so.  (PR 52 did: the kernels' bodies read
# the row statistics whole, in every lane, so every digest of this table
# and of ``tests/test_sdar.py``'s is that PR's; the programs' numbers
# are the parent's bit for bit on the chip, PERF.md section 6.  PR 54
# did again: the tiny sizes' heads are narrower than a 128-lane tile and
# go to the kernels at the width they have, no pad in front of a call
# and no slice behind it, so every digest of this table, of
# ``tests/test_sdar.py``'s and of ``tests/test_trinity.py``'s two is
# PR 54's; a 128-wide call still traces to its parent's program
# (``tests/test_ops.py`` ``PARENTS_128_WIDE``), and on the chip the
# results are the parent's to the last printed digit, PERF.md section 6.
# PR 57 did a third time: a call at the default precision rounds q, k, v
# and dO to bf16 at the head of the op's rules and tiles by that dtype,
# so every digest of the three tables is PR 57's; a call that names a
# precision still traces to the parent's program
# (``tests/test_ops.py`` ``PARENTS_128_WIDE``), and on the chip the
# bf16 operands give the float32 operands' bits at the same tile.)
PARENTS_STEP = {
    "mellum2-l4e8-local": "0ec308be12de951d",
    "lfm2-l5e8-local": "56d30a65f216f0fb",
    "ouro-l6-local": "b8d78e1f61d17001",
    "joyai-l5e8-local": "a50c60fbad1cf7fd",
}


@pytest.mark.parametrize("cell_name", sorted(PARENTS_STEP))
def test_a_block_without_a_selection_lowers_to_the_parents_step(cell_name):
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


# -- the vector, the seeding, the scopes, what is kept ---------------------------


def test_the_built_models_vector_is_the_arithmetics_at_the_tiny_size(case):
    assert case["model"].flat.size == arithmetic.param_count(CONFIG)
    whole = {**CONFIG, "num_experts": CONFIG["router_experts"],
             "experts_first": 0}
    model = build(arch="keye", seed=3, use_flash=False, **sizes(whole))
    assert model.flat.size == arithmetic.param_count(whole)


@pytest.mark.parametrize("what,got,want", arithmetic.hand_worked(),
                         ids=[c[0] for c in arithmetic.hand_worked()])
def test_keye_arithmetic_by_hand(what, got, want):
    assert got == want, what


def test_the_files_keys_are_the_catalogs_but_for_the_three_reduced():
    assert FILE["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    assert FILE["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                 "vocab_size": 151936}
    for key, value in {
            "hidden_size": 2048, "num_attention_heads": 32,
            "num_key_value_heads": 4, "head_dim": 128,
            "moe_intermediate_size": 768, "num_experts_per_tok": 8,
            "intermediate_size": 6144, "num_local_experts": 128,
            "rope_theta": 10000000, "rms_norm_eps": 1e-06,
            "norm_topk_prob": True, "tie_word_embeddings": False,
            "max_position_embeddings": 262144}.items():
        assert FILE[key] == value, key
    assert FILE["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    assert FILE["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert FILE["vocab_size"] * 8 == FILE["published"]["vocab_size"]
    assert FILE["tiny"]["sa_config"]["topk"] < FILE["tiny"]["train_seq"]


def test_the_seeding(case):
    params = case["model"].flat.unravel(case["model"].flat.w0)
    block = params["KeyeBlock_1"]
    for name in ("attn_norm", "q_norm", "k_norm", "index_k_norm",
                 "mlp_norm"):
        assert np.all(np.asarray(block[name]) == 1.0), name
    assert not np.any(np.asarray(block["index_k_bias"]))
    assert float(jnp.std(params["embed"])) == pytest.approx(8.0, rel=0.05)
    for leaf in (params["head"], block["wq"], block["index_wq"],
                 block["index_ww"], block["router"], block["experts_gate"]):
        assert float(jnp.std(leaf)) == pytest.approx(0.02, rel=0.15)


def test_the_seeded_weights_do_not_depend_on_the_training_sequence():
    short = build(arch="keye", seed=3, use_flash=False,
                  **{**TINY, "seq_len": 32})
    long = build(arch="keye", seed=3, use_flash=False, **TINY)
    assert np.array_equal(np.asarray(short.flat.w0), np.asarray(long.flat.w0))


@pytest.mark.parametrize("bad", [
    {"index_heads": 0}, {"index_head_dim": 7}, {"index_topk": 0},
    {"experts_first": 7},
])
def test_sizes_that_make_no_block_are_refused(bad):
    with pytest.raises(ValueError):
        build(arch="keye", use_flash=False, **{**TINY, **bad})


def test_a_size_of_another_block_is_refused():
    with pytest.raises(TypeError, match="keye takes no window"):
        build(arch="keye", use_flash=False, **{**TINY, "window": 8})


def test_the_steps_operations_carry_the_blocks_scopes(case):
    model = case["model"]
    text = jax.jit(model.value_and_grad).lower(
        case["w"], case["tokens"]).as_text(debug_info=True)
    for scope in FILE["scopes"]:
        if scope != "update":   # the optimizer's, not the model's
            assert f"/{scope}/" in text, scope
    assert {"index", "attn"} <= set(FILE["scopes"])


def test_a_layer_keeps_its_input_the_kernels_two_and_the_bits_alone():
    """The attention's checkpoint: beside the layer's input and its
    parameters, what is kept for the backward pass is the flash rule's
    output and row log-sum-exp and the selection's words; q, k, v are
    made again and the indexer is not run again."""
    from jax._src.ad_checkpoint import saved_residuals

    b, length = 2, 48
    block = transformer.KeyeBlock(**{name: TINY[name] for name in (
        "d_model", "n_heads", "kv_heads", "head_dim", "index_heads",
        "index_head_dim", "index_topk", "n_experts", "experts_per_tok",
        "expert_width", "rope_theta", "norm_eps")})
    x = jnp.ones((b, length, TINY["d_model"]))
    p = block.init(jax.random.PRNGKey(0), x)["params"]
    flash = transformer.default_attn(causal=True, use_flash=True,
                                     interpret=True)
    attend = jax.checkpoint(
        functools.partial(
            transformer.selected_attention, heads=TINY["n_heads"],
            kv_heads=TINY["kv_heads"], head_dim=TINY["head_dim"],
            index_heads=TINY["index_heads"],
            index_head_dim=TINY["index_head_dim"], topk=TINY["index_topk"],
            theta=TINY["rope_theta"], eps=TINY["norm_eps"], attn=flash),
        policy=jax.checkpoint_policies.save_only_these_names(
            *transformer.KEYE_ATTN_KEPT))
    kept = saved_residuals(lambda x, p: attend(x, p)[0], x, p)
    # (the rotary tables' frequencies, 8 floats each, are constants)
    made = sorted((str(shape.dtype), shape.shape) for shape, why in kept
                  if "argument" not in why and shape.ndim > 1)
    hq, hkv, hd = TINY["n_heads"], TINY["kv_heads"], TINY["head_dim"]
    assert made == sorted([
        ("float32", (b, hkv, hq // hkv, length, hd)),        # flash_out
        ("float32", (b, hkv, hq // hkv, length)),            # flash_lse
        ("int32", (b, length, select_bits.words_of(length))),  # the bits
    ])
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda x, p: jnp.sum(attend(x, p)[0]), argnums=(0, 1)))(x, p))
    # the indexer once, forward only: one Mosaic call and, inside it,
    # the two bisections (no map over blocks of rows is left)
    assert jaxpr.count("name=_select_blocks") == 1
    assert jaxpr.count("scan[") == 2


# -- the counters on the round spans ----------------------------------------------

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
    assert kw["arch"] == "keye"
    assert "keye" in archs.ARCHS and archs.sizes_of("keye")[-3:] == (
        "index_heads", "index_head_dim", "index_topk")


def test_a_one_rank_local_run_learns_and_carries_the_selections_counters(
        obs_on):
    """``--np 1 --opt msgd``: the single-process path hands ``MSGD`` the
    step with the block's telemetry, and each donated step is a
    ``round`` span with the selection's two counters and the routing's
    three a layer while obs records."""
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
    kept, overlap = transformer.KEYE_DSA_STATS
    layers = TINY["n_layers"]
    formula = arithmetic.selected_pairs(
        TINY["seq_len"], TINY["index_topk"]) / arithmetic.causal_pairs(
            TINY["seq_len"])
    names = transformer.KEYE_DSA_STATS + transformer.JOYAI_MOE_STATS[:3]
    for span in rounds:
        for name in names:
            assert len(span.args[name]) == layers, name
        assert all(x == pytest.approx(formula, rel=1e-6)
                   for x in span.args[kept])
        assert all(0.2 < x < 0.9 for x in span.args[overlap])
    assert obs.get_registry().gauge(f"mpit_{kept}", layer=1).value == \
        rounds[-1].args[kept][1]
    for name in names:
        assert result[name] == rounds[-1].args[name]
