"""The state-space hybrid on the normal path (``--lm_arch nemotron``:
``build(arch="nemotron")``: ``models/transformer.py``
``NemotronDecoder``) at the ``tiny`` size of
``chipbench/configs/nemotron-3-nano-30b-l9e8.json``, on the CPU: the
program against its plain float32 reference
(``chipbench/reference/nemotron_plain.py``, which steps the state a
position at a time and shares no code with the program), the chunked
scan of ``ops/ssd_scan.py`` against the recurrence, the convolution with
its bias, the gated group norm, the experts of two matrices at an inner
width that is no whole tile, the share test the model-configs guide asks
for, and the block through the launcher, locally and through two
parameter servers.
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

from chipbench.arithmetic import nemotron as arithmetic
from chipbench.reference import nemotron_plain as ref
from mpit_tpu import obs
from mpit_tpu.lm.model import build, build_kw
from mpit_tpu.models import transformer
from mpit_tpu.ops import short_conv, ssd_scan
from mpit_tpu.parallel import moe

LOSS_TOL_NATS = 1e-5
GRAD_REL_TOL = 1e-5
SCAN_TOL = 2e-5

ROOT = pathlib.Path(__file__).resolve().parent.parent
FILE = json.loads(
    (ROOT / "chipbench/configs/nemotron-3-nano-30b-l9e8.json").read_text())
CONFIG = {**FILE, **FILE["tiny"]}  # the reference's keys, at the tiny size


def sizes(c):
    """``build``'s keywords from the configuration's keys."""
    return dict(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], n_layers=c["num_hidden_layers"],
        seq_len=c["train_seq"], layer_types=arithmetic.layer_types(c),
        conv_kernel=c["conv_kernel"], ssm_heads=c["mamba_num_heads"],
        ssm_head_dim=c["mamba_head_dim"], ssm_groups=c["n_groups"],
        ssm_state=c["ssm_state_size"], ssm_chunk=c["chunk_size"],
        n_experts=c["router_experts"], experts_held=c["n_routed_experts"],
        experts_first=c["experts_first"],
        experts_per_tok=c["num_experts_per_tok"],
        expert_width=c["moe_intermediate_size"],
        shared_experts=c["n_shared_experts"],
        shared_width=c["moe_shared_expert_intermediate_size"],
        route_scale=c["routed_scaling_factor"], norm_eps=c["norm_eps"],
        init_depth=c["rescale_depth"])


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
    model = build(arch="nemotron", seed=3, use_flash=False, **TINY)
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


def scan_inputs(length, lo, hi, batch=2, heads=4, p=8, groups=2, n=16,
                seed=0):
    """x, dt, a, b, c with the decay ``exp(dt a)`` of head 0 between
    ``lo`` and ``hi`` (the other heads' rates are 2, 3, 4 times its)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(keys[0], (batch, length, heads, p))
    b = jax.random.normal(keys[1], (batch, length, groups, n))
    c = jax.random.normal(keys[2], (batch, length, groups, n))
    dt = jax.random.uniform(keys[3], (batch, length, heads),
                            minval=-math.log(hi), maxval=-math.log(lo))
    return x, dt, -jnp.arange(1.0, heads + 1), b, c


def both(fn, args, ct):
    """``fn``'s value and its five gradients under the cotangent."""
    with jax.default_matmul_precision("highest"):
        value, back = jax.vjp(fn, *args)
        return value, back(ct)


# Near 0 the summed log-decays are in the hundreds and a pair's decay is
# the ``exp`` of the difference of two of them: float32 carries the
# difference to 3e-5, and the rates' gradient, a sum of such terms times
# the summed steps, to a thousandth (a pair on the diagonal has the
# difference 0 exactly and its two halves cancel only to rounding).
SCANS = [
    # what, length, chunk, the decay's range, the gradients' tolerance
    ("whole chunks", 64, 16, (0.5, 0.999), 2e-4),
    ("a last chunk that is not whole", 50, 16, (0.5, 0.999), 2e-4),
    ("one chunk longer than the row", 33, 128, (0.5, 0.999), 2e-4),
    ("a decay near 1", 48, 16, (0.9999, 0.999999), 2e-4),
    ("a decay near 0", 40, 16, (1e-9, 1e-3), 3e-3),
    ("a decay near 0, chunks of 7", 40, 7, (1e-9, 1e-3), 3e-3),
    ("one position a chunk", 12, 1, (0.2, 0.99), 2e-4),
]


@pytest.mark.parametrize("what,length,chunk,decay,g_tol", SCANS,
                         ids=[s[0] for s in SCANS])
def test_the_chunked_scan_is_the_recurrence_forward_and_backward(
        what, length, chunk, decay, g_tol):
    args = scan_inputs(length, *decay)
    ct = jax.random.normal(jax.random.PRNGKey(9),
                           args[0].shape)
    got, got_grads = both(
        lambda *a: ssd_scan.ssd_scan(*a, chunk=chunk), args, ct)
    want, want_grads = both(ssd_scan.ssd_scan_reference, args, ct)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert relative(got, want) < SCAN_TOL
    for name, g, w in zip("x dt a b c".split(), got_grads, want_grads):
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert relative(g, w) < g_tol, name


def test_no_state_crosses_the_rows_of_a_batch():
    x, dt, a, b, c = scan_inputs(40, 0.5, 0.999)
    both_rows = ssd_scan.ssd_scan(x, dt, a, b, c, chunk=16)
    alone = ssd_scan.ssd_scan(x[1:], dt[1:], a, b[1:], c[1:], chunk=16)
    np.testing.assert_allclose(both_rows[1:], alone, atol=1e-5)


def test_the_state_carries_what_a_chunk_saw_into_every_later_one():
    x, dt, a, b, c = scan_inputs(64, 0.9, 0.999)
    y = ssd_scan.ssd_scan(x, dt, a, b, c, chunk=16)
    other = ssd_scan.ssd_scan(x.at[:, 3].add(1.0), dt, a, b, c, chunk=16)
    changed = jnp.max(jnp.abs(other - y), axis=(0, 2, 3))
    assert float(jnp.max(changed[:3])) == 0.0     # causal
    assert float(jnp.min(changed[jnp.asarray([3, 20, 40, 63])])) > 1e-6


def test_the_backward_rule_keeps_the_five_inputs_and_no_chunk_state():
    from jax._src.ad_checkpoint import saved_residuals

    args = scan_inputs(64, 0.5, 0.999)
    kept = saved_residuals(
        lambda *a: ssd_scan.ssd_scan(*a, chunk=16), *args)
    assert sorted(shape.shape for shape, _ in kept) == sorted(
        a.shape for a in args)


def test_the_log_decays_are_summed_in_float32_and_a_lower_sum_shows(
        monkeypatch):
    """What the probe's first variant lowers: the sum's dtype is read at
    every call, and bf16 there is seen in the result."""
    args = scan_inputs(64, 0.9, 0.999)
    want = ssd_scan.ssd_scan(*args, chunk=32)
    assert ssd_scan.SUM_DTYPE == jnp.float32
    monkeypatch.setattr(ssd_scan, "SUM_DTYPE", jnp.bfloat16)
    low = ssd_scan.ssd_scan(*args, chunk=32)
    assert 1e-4 < relative(low, want) < 5e-2


# The same recurrence as the Mosaic kernels (``ops/ssd_scan.py``: widths
# of whole lanes, chunks of 128), in Pallas interpret mode here: against
# the recurrence and against the XLA form, which the shapes above keep
# taking.  At chunks of 128 a decay near 0 sums log-decays into the
# thousands, so the rates' gradient agrees with the recurrence to a
# hundredth and with the XLA form, which sums the same, far closer.
KERNEL_SHAPE = dict(heads=4, p=64, groups=2, n=128)
KERNEL_SCANS = [
    # what, length, the decay's range, the shape, the rates' tolerance
    ("whole chunks", 256, (0.5, 0.999), KERNEL_SHAPE, 2e-4),
    ("a last chunk that is not whole", 200, (0.5, 0.999), KERNEL_SHAPE,
     2e-4),
    ("one chunk", 128, (0.5, 0.999), KERNEL_SHAPE, 2e-4),
    ("one chunk longer than the row", 100, (0.5, 0.999), KERNEL_SHAPE, 2e-4),
    ("a decay near 1", 256, (0.9999, 0.999999), KERNEL_SHAPE, 2e-4),
    ("a decay near 0", 256, (1e-9, 1e-3), KERNEL_SHAPE, 3e-2),
    ("a head a lane tile", 256, (0.5, 0.999),
     dict(heads=2, p=128, groups=2, n=128), 2e-4),
    ("four heads a lane tile", 256, (0.5, 0.999),
     dict(heads=8, p=32, groups=2, n=128), 2e-4),
]


def runs_kernels(fn, *args) -> bool:
    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


@pytest.mark.parametrize("what,length,decay,shape,a_tol", KERNEL_SCANS,
                         ids=[s[0] for s in KERNEL_SCANS])
def test_the_scan_kernels_are_the_recurrence_and_the_xla_form(
        what, length, decay, shape, a_tol):
    args = scan_inputs(length, *decay, **shape)
    # the skip ``D x`` rides in the kernels: a sixth input and gradient
    args += (jax.random.normal(jax.random.PRNGKey(5), (shape["heads"],)),)
    ct = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def skipped(scan):
        return lambda *a: scan(*a[:5]) + a[5][:, None] * a[0]

    assert ssd_scan.takes_kernels(args[0], args[3], ssd_scan.CHUNK)
    assert runs_kernels(jax.grad(lambda *a: jnp.sum(ssd_scan.ssd_scan(*a))),
                        *args[:5])
    got, got_grads = both(
        lambda *a: ssd_scan.ssd_scan(*a[:5], skip=a[5]), args, ct)
    want, want_grads = both(skipped(ssd_scan.ssd_scan_reference), args, ct)
    xla, xla_grads = both(skipped(functools.partial(
        ssd_scan.ssd_chunked, chunk=ssd_scan.CHUNK)), args, ct)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert relative(got, want) < SCAN_TOL
    assert relative(got, xla) < SCAN_TOL
    for name, g, w, x in zip("x dt a b c skip".split(), got_grads,
                             want_grads, xla_grads):
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert relative(g, w) < (a_tol if name == "a" else 2e-4), name
        assert relative(g, x) < 2e-4, name


def kernel_rows_do_not_see_each_other(_):
    x, dt, a, b, c = scan_inputs(200, 0.5, 0.999, **KERNEL_SHAPE)
    both_rows = ssd_scan.ssd_scan(x, dt, a, b, c)
    alone = ssd_scan.ssd_scan(x[1:], dt[1:], a, b[1:], c[1:])
    np.testing.assert_allclose(both_rows[1:], alone, atol=1e-5)


def kernel_positions_see_no_later_one(_):
    x, dt, a, b, c = scan_inputs(384, 0.99, 0.9999, **KERNEL_SHAPE)
    y = ssd_scan.ssd_scan(x, dt, a, b, c)
    other = ssd_scan.ssd_scan(x.at[:, 130].add(1.0), dt, a, b, c)
    changed = jnp.max(jnp.abs(other - y), axis=(0, 2, 3))
    assert float(jnp.max(changed[:130])) == 0.0     # causal
    # its own chunk, the next one and the last position
    assert float(jnp.min(changed[jnp.asarray([130, 200, 300, 383])])) > 1e-6


def kernel_sums_are_float32_and_a_lower_sum_shows(monkeypatch):
    args = scan_inputs(256, 0.9, 0.999, **KERNEL_SHAPE)
    ct = jnp.ones(args[0].shape)
    want, want_grads = both(ssd_scan.ssd_scan, args, ct)
    assert ssd_scan.SUM_DTYPE == jnp.float32
    monkeypatch.setattr(ssd_scan, "SUM_DTYPE", jnp.bfloat16)
    low, low_grads = both(ssd_scan.ssd_scan, args, ct)
    assert 1e-4 < relative(low, want) < 5e-2
    assert 1e-4 < relative(low_grads[1], want_grads[1]) < 2e-1   # the step's


def narrow_widths_and_other_chunks_take_the_xla_form(_):
    loss = jax.grad(lambda *a, **kw: jnp.sum(ssd_scan.ssd_scan(*a, **kw)))
    wide = scan_inputs(256, 0.5, 0.999, **KERNEL_SHAPE)
    assert runs_kernels(loss, *wide)
    assert not runs_kernels(functools.partial(loss, chunk=64), *wide)
    for shape in (dict(), dict(heads=4, p=16, groups=2, n=128),
                  dict(heads=4, p=64, groups=2, n=16),
                  dict(heads=2, p=64, groups=2, n=128)):
        narrow = scan_inputs(256, 0.5, 0.999, **shape)
        assert not ssd_scan.takes_kernels(narrow[0], narrow[3],
                                          ssd_scan.CHUNK), shape
        assert not runs_kernels(loss, *narrow), shape


KERNEL_PROPERTIES = [kernel_rows_do_not_see_each_other,
                     kernel_positions_see_no_later_one,
                     kernel_sums_are_float32_and_a_lower_sum_shows,
                     narrow_widths_and_other_chunks_take_the_xla_form]


@pytest.mark.parametrize("check", KERNEL_PROPERTIES,
                         ids=[c.__name__ for c in KERNEL_PROPERTIES])
def test_the_scan_kernels_keep_what_the_scan_promises(check, monkeypatch):
    check(monkeypatch)


# sha256 of ``str(make_jaxpr(value_and_grad(loss)))`` (addresses blanked)
# of ``ssd_scan`` at the cell's shape (64 heads of 64 in 8 groups, state
# 128, one sequence, with the skip), **as the parent commit of PR 65
# printed it**: that PR gave the kernels a part of a group a grid step
# (``heads_a_step``: Granite's one group of 64 heads is eight blocks of
# 8) and ``_Calls`` serves both; a group of 8 is one block, and the
# program must still trace to what it was, to the character, as the chip
# compiles it (``use_interpret`` steered off) and interpreted, on whole
# chunks and with a ragged end.  A PR that changes these kernels on
# purpose records the new digests here and says so.
PARENTS_SSD = {
    ("compiled", 8192): "f42e50765c893e33",
    ("compiled", 8150): "92ff9bbb9bf353f0",
    ("interpreted", 8192): "407f10bf161be671",
    ("interpreted", 8150): "bc4866407e7088f6",
}


@pytest.mark.parametrize("how,length", sorted(PARENTS_SSD))
def test_the_scan_at_eight_groups_traces_to_the_parents_program(
        how, length, monkeypatch):
    import hashlib
    import re

    monkeypatch.setattr(ssd_scan, "use_interpret",
                        lambda flag: how == "interpreted")
    jax.clear_caches()   # the calls are jits: no trace of another mode
    wide = jax.ShapeDtypeStruct((1, length, 64, 64), jnp.float32)
    step = jax.ShapeDtypeStruct((1, length, 64), jnp.float32)
    head = jax.ShapeDtypeStruct((64,), jnp.float32)
    shared = jax.ShapeDtypeStruct((1, length, 8, 128), jnp.float32)
    assert ssd_scan.heads_a_step(wide, shared, ssd_scan.HEAD_BLOCK) == 8

    def loss(x, dt, a, b, c, d):
        return jnp.sum(ssd_scan.ssd_scan(x, dt, a, b, c, skip=d) ** 2)

    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(
        jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4, 5)))(
            wide, step, head, shared, shared, head)))
    assert text.count("pallas_call") >= 3
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARENTS_SSD[how, length]
    jax.clear_caches()


def test_mismatched_shapes_are_refused():
    x, dt, a, b, c = scan_inputs(16, 0.5, 0.9, heads=4, groups=2)
    with pytest.raises(ValueError):
        ssd_scan.ssd_scan(x, dt, a, b[:, :, :1].repeat(3, 2), c)
    with pytest.raises(ValueError):
        ssd_scan.ssd_scan(x, dt[:, :8], a, b, c)


# -- (b) the convolution with its bias, the gated group norm ----------------------


@pytest.mark.parametrize("length", [1, 3, 4, 29])
def test_the_convolution_with_bias_is_four_shifted_products(length):
    rs = np.random.RandomState(length)
    u = jnp.asarray(rs.randn(2, length, 12), jnp.float32)
    taps = jnp.asarray(rs.randn(4, 12), jnp.float32)
    bias = jnp.asarray(rs.randn(12), jnp.float32)
    got = short_conv.causal_conv_silu(u, taps, bias)
    want = np.zeros((2, length, 12), np.float32)
    for t in range(length):
        for j in range(4):
            src = t - 3 + j           # the last tap on the current position
            if src >= 0:
                want[:, t] += np.asarray(taps[j]) * np.asarray(u[:, src])
    want = want + np.asarray(bias)
    want = want / (1 + np.exp(-want))
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(ref.conv_silu(u, taps, bias), want, atol=1e-5)
    # the taps alone are LFM2's and Kimi's convolution still
    lone = short_conv.causal_depthwise_conv(
        u, jnp.zeros_like(taps).at[3].set(1))
    np.testing.assert_array_equal(lone, u)


def _mixer_params(rs, d, heads, hd, groups, n, kernel=4):
    inner, mixed = heads * hd, heads * hd + 2 * groups * n

    def normal(*shape, scale=0.3):
        return jnp.asarray(scale * rs.randn(*shape), jnp.float32)

    return {"norm": 1 + normal(d), "w_in": normal(d, inner + mixed + heads),
            "conv_w": normal(kernel, mixed), "conv_b": normal(mixed),
            "dt_bias": normal(heads) - 2.0, "a_log": normal(heads),
            "d_skip": 1 + normal(heads), "ssm_norm": 1 + normal(inner),
            "w_out": normal(inner, d)}


def test_the_gated_norm_is_over_each_groups_channels_gate_first():
    """One mixer by the program against the reference's, and against the
    norm written out: with every weight 1 and ``W_out`` the identity the
    output's mean square over each group's channels is 1."""
    d, heads, hd, groups, n = 32, 4, 8, 2, 8
    rs = np.random.RandomState(0)
    p = _mixer_params(rs, d, heads, hd, groups, n)
    x = jnp.asarray(rs.randn(2, 24, d), jnp.float32)
    c = {"mamba_num_heads": heads, "mamba_head_dim": hd, "n_groups": groups,
         "ssm_state_size": n, "layer_norm_epsilon": 1e-5}
    mixer = functools.partial(
        transformer.state_space_mixer, heads=heads, head_dim=hd,
        groups=groups, state=n, chunk=8, eps=1e-5)
    with jax.default_matmul_precision("highest"):
        got, decay = mixer(x, p)
        want = ref.mamba(ref.rms_norm(x, p["norm"], 1e-5), p, c)
        p1 = {**p, "ssm_norm": jnp.ones(heads * hd),
              "w_out": jnp.eye(heads * hd, d)}
        unit, _ = mixer(x, p1)
    assert relative(got, want) < 1e-5
    assert 0.0 < float(decay) < 1.0
    per_group = jnp.mean(jnp.square(unit.reshape(2, 24, groups, -1)), -1)
    np.testing.assert_allclose(per_group, 1.0, rtol=1e-3)
    # the gate comes first: with z = 0 everywhere SiLU(z) = 0 gates all
    closed = {**p, "w_in": p["w_in"].at[:, :heads * hd].set(0)}
    assert float(jnp.max(jnp.abs(mixer(x, closed)[0]))) < 1e-6


@pytest.mark.parametrize("groups", [1, 2, 8])
def test_the_group_norm_as_products_is_the_norm_over_each_groups_channels(
        groups):
    """``group_rms_norm`` takes a group's mean square and its way back
    as two products with the groups' membership, named exact whatever
    the ambient precision: value and both gradients are ``rms_norm``'s
    over the ``(..., groups, width)`` view."""
    rs = np.random.RandomState(groups)
    x = jnp.asarray(3.0 * rs.randn(2, 24, 64), jnp.float32)
    w = jnp.asarray(1 + 0.3 * rs.randn(64), jnp.float32)
    ct = jnp.asarray(rs.randn(2, 24, 64), jnp.float32)

    def viewed(x, w):
        return transformer.rms_norm(
            x.reshape(2, 24, groups, -1), w.reshape(groups, -1), 1e-5
        ).reshape(x.shape)

    got, back = jax.vjp(
        lambda x, w: transformer.group_rms_norm(x, w, groups, 1e-5), x, w)
    want, want_back = jax.vjp(viewed, x, w)
    assert relative(got, want) < 1e-6
    for g, v in zip(back(ct), want_back(ct)):
        assert relative(g, v) < 1e-5


# -- (c) experts of two matrices at a width that is no whole tile ----------------


@pytest.mark.parametrize("first", [None, 2], ids=["all_held", "a_share"])
def test_relu2_experts_at_a_width_off_the_tile_are_the_dense_loop(first):
    """58 columns inside: no multiple of 8, let alone of a lane tile."""
    t, d, f, e, held = 24, 16, 58, 6, 3
    rs = np.random.RandomState(2)
    rows = jnp.asarray(rs.randn(t, d), jnp.float32)
    count = held if first is not None else e
    wu = jnp.asarray(0.3 * rs.randn(count, d, f), jnp.float32)
    wd = jnp.asarray(0.3 * rs.randn(count, f, d), jnp.float32)
    sizes = jnp.asarray([4, 4, 4, 4, 4, 4], jnp.int32)

    def dense(rows, wu, wd):
        out, at = [], 0
        for g, n in enumerate(np.asarray(sizes)):
            idx = g - (first or 0)
            piece = rows[at:at + n]
            if 0 <= idx < count:
                out.append(jnp.square(jnp.maximum(piece @ wu[idx], 0))
                           @ wd[idx])
            else:
                out.append(jnp.zeros((n, d)))
            at += n
        return jnp.concatenate(out)

    def ours(rows, wu, wd):
        return moe.relu2_experts(rows, sizes, wu, wd, first)

    ct = jnp.asarray(rs.randn(t, d), jnp.float32)
    got, got_grads = both(ours, (rows, wu, wd), ct)
    want, want_grads = both(dense, (rows, wu, wd), ct)
    np.testing.assert_allclose(got, want, atol=1e-5)
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, w, atol=1e-4)


@pytest.mark.parametrize("x,tile", [
    (1856, 640),     # 14.5 lane tiles: three tiles, the last masked
    (2688, 896),     # 21 lane tiles: whole tiles, as ever
    (3712, 128),     # 29 lane tiles: a prime count, a lane tile each
    (1024, 1024), (2304, 768), (128, 128)])
def test_the_tile_of_a_dimension(x, tile):
    assert moe._gmm_tile(x) == tile
    assert tile % 128 == 0 and -(-x // tile) * tile - x < 128


@pytest.mark.parametrize("m,k,n,fits", [
    (6144, 2688, 1856, True), (6144, 1856, 2688, True),
    # narrow dimensions must be whole lanes, and a long one whole
    # half-tiles: what the parent refused it still refuses
    (256, 2300, 896, False), (256, 2304, 900, False), (512, 64, 32, False),
    (256, 1000, 128, False), (6144, 2688, 1850, False),
    (100, 2688, 1856, False)])
def test_the_kernels_take_a_masked_last_tile_of_half_a_lane_tile(m, k, n,
                                                                fits):
    assert moe.pallas_fits(m, k, n) is fits


def test_the_cells_window_of_rows_is_whole_row_tiles():
    c = FILE
    rows = c["num_experts_per_tok"] * c["train_seq"]
    window = moe.held_window(rows, c["hidden_size"], c["n_routed_experts"],
                             c["router_experts"])
    assert window == 6144 and window % moe.GMM_TILE_M == 0
    assert moe.pallas_fits(window, c["hidden_size"],
                           c["moe_intermediate_size"])


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
    the step, ``A_log``, the skip, the convolution's bias."""
    unravel = case["model"].flat.unravel
    got, want = unravel(case["grad"]), unravel(case["ref_grad"])
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            assert not np.asarray(g).any() and not np.asarray(w).any(), name
            continue
        assert float(jnp.linalg.norm(w)) > 0, name
        assert relative(g, w) < 2e-4, name


def _wrong(case, monkeypatch, **replaced):
    for name, fn in replaced.items():
        monkeypatch.setattr(ref, name, fn)
    return ref.loss_and_grad_flat(case["w"], case["model"].flat.unravel,
                                  case["tokens"], CONFIG)


def _no_skip(h, p, config):
    return _MAMBA(h, {**p, "d_skip": jnp.zeros_like(p["d_skip"])}, config)


def _no_conv_bias(u, taps, bias):
    return _CONV(u, taps, jnp.zeros_like(bias))


def _reversed_conv(u, taps, bias):
    return _CONV(u, taps[::-1], bias)


def _one_rate_for_all_heads(x, step, rate, b, c):
    return _RECURRENCE(x, step, -jnp.ones_like(rate), b, c)


def _gated_experts(h, w_up, w_down):
    return jax.nn.silu(h @ w_up) @ w_down


def _rotated_attention(h, p, config):
    pos = jnp.arange(h.shape[1], dtype=jnp.float32)[None, :, None]
    return _ATTENTION(h * jnp.cos(0.05 * pos), p, config)


_MAMBA, _CONV, _RECURRENCE, _ATTENTION = (
    ref.mamba, ref.conv_silu, ref.recurrence, ref.attention)
WRONG = {
    "the skip D x left out": dict(mamba=_no_skip),
    "the convolution's bias left out": dict(conv_silu=_no_conv_bias),
    "the convolution's taps reversed": dict(conv_silu=_reversed_conv),
    "every head decaying at the rate -1": dict(
        recurrence=_one_rate_for_all_heads),
    "experts with a SiLU and no square": dict(relu2=_gated_experts),
    "a positional term in the attention": dict(attention=_rotated_attention),
}


@pytest.mark.parametrize("what", sorted(WRONG))
def test_the_tolerance_refuses(case, what, monkeypatch):
    loss, grad = _wrong(case, monkeypatch, **WRONG[what])
    assert (abs(float(case["loss"]) - float(loss)) > LOSS_TOL_NATS
            or relative(case["grad"], grad) > GRAD_REL_TOL), what


@pytest.mark.parametrize("key,value", [
    ("routed_scaling_factor", 1.0), ("norm_topk_prob", False),
    ("num_experts_per_tok", 1), ("n_shared_experts", 0),
    ("experts_first", 0), ("layer_norm_epsilon", 1e-2)])
def test_the_tolerance_refuses_another_configuration(case, key, value):
    loss, grad = ref.loss_and_grad_flat(
        case["w"], case["model"].flat.unravel, case["tokens"],
        {**CONFIG, key: value})
    assert (abs(float(case["loss"]) - float(loss)) > LOSS_TOL_NATS
            or relative(case["grad"], grad) > GRAD_REL_TOL), key


# -- (e) the share ---------------------------------------------------------------


def test_the_shares_routed_parts_and_one_shared_expert_are_the_whole_layer():
    """The guide's share test on one sparse layer: over all its experts,
    by the plain reference, it is the sum of what each share's block
    computes for its own experts plus the shared expert counted once.
    Four shares of two experts of eight (the deployment's sixteen of
    eight of 128, at the tiny size)."""
    c = {**CONFIG, "n_routed_experts": CONFIG["router_experts"],
         "experts_first": 0}
    n, held = c["router_experts"], CONFIG["n_routed_experts"]
    kw = {name: TINY[name] for name in (
        "d_model", "n_heads", "kv_heads", "head_dim", "ssm_heads",
        "ssm_head_dim", "ssm_groups", "ssm_state", "n_experts",
        "experts_per_tok", "expert_width", "route_scale", "norm_eps")}
    kw.update(kind="moe")
    whole = transformer.NemotronBlock(**kw, shared_width=TINY["shared_width"])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 40, c["hidden_size"]))
    params = whole.init(jax.random.PRNGKey(5), x)["params"]
    params = jax.tree_util.tree_map(
        lambda p: p + 0.05 * jax.random.normal(jax.random.PRNGKey(p.size),
                                               p.shape), params)
    experts, shared = ("experts_up", "experts_down"), ("shared_up",
                                                       "shared_down")

    def share(first, with_shared):
        block = transformer.NemotronBlock(
            **kw, experts_first=first, experts_held=held,
            shared_experts=int(with_shared),
            shared_width=TINY["shared_width"] if with_shared else 0)
        p = {name: value for name, value in params.items()
             if with_shared or name not in shared}
        for name in experts:
            p[name] = params[name][first:first + held]
        return jax.jit(lambda p: block.apply({"params": p}, x)[0])(p) - x

    with jax.default_matmul_precision("highest"):
        routed = sum(share(first, False) for first in range(0, n, held))
        once = share(0, True) - share(0, False)    # the shared expert
        want = ref.layer(x, params, "moe", c) - x
    assert float(jnp.max(jnp.abs(routed))) > 1e-3 < \
        float(jnp.max(jnp.abs(once)))
    assert float(jnp.max(jnp.abs(routed + once - want))) < 1e-5


def test_the_router_takes_6_of_128_renormalised_and_scaled_by_2_5():
    rs = np.random.RandomState(1)
    scores = jax.nn.sigmoid(jnp.asarray(rs.randn(50, 128), jnp.float32))
    bias = jnp.asarray(0.02 * rs.randn(128), jnp.float32)
    weights, chosen = moe.route_top_k(
        scores, 6, renormalise=True, bias=bias,
        eps=transformer.JOYAI_ROUTE_EPS, scale=2.5)
    gates = np.asarray(ref.router_gates(
        jnp.log(scores / (1 - scores)), jnp.eye(128), bias,
        {"num_experts_per_tok": 6, "norm_topk_prob": True,
         "routed_scaling_factor": 2.5}))
    assert chosen.shape == weights.shape == (50, 6)
    assert np.allclose(np.asarray(jnp.sum(weights, axis=-1)), 2.5, rtol=1e-5)
    rows = np.arange(50)[:, None]
    assert np.allclose(gates[rows, np.asarray(chosen)], np.asarray(weights),
                       rtol=1e-4)
    assert np.count_nonzero(gates) == 50 * 6


# -- the vector, the seeding, the scopes, what is kept ---------------------------


def test_the_built_models_vector_is_the_arithmetics_at_the_tiny_size(case):
    assert case["model"].flat.size == arithmetic.param_count(CONFIG)
    whole = {**CONFIG, "n_routed_experts": CONFIG["router_experts"],
             "experts_first": 0}
    model = build(arch="nemotron", seed=3, use_flash=False, **sizes(whole))
    assert model.flat.size == arithmetic.param_count(whole)


@pytest.mark.parametrize("what,got,want", arithmetic.hand_worked(),
                         ids=[w[0][:60] for w in arithmetic.hand_worked()])
def test_nemotron_arithmetic_by_hand(what, got, want):
    assert got == want, what


def test_the_published_sizes_give_the_issues_vector():
    """666,963,456 elements from the file's own keys, by the program's
    own shapes (``jax.eval_shape``: nothing of that size is made)."""
    from chipbench import run as runner, spec as spec_mod

    cell = spec_mod.load_cell("nemotron3-l9e8-local")
    cfg = runner.launch_config(cell, 1)
    from mpit_tpu.train.launch import lm_trainer_cfg

    kw = build_kw(lm_trainer_cfg(cfg))
    from mpit_tpu.lm import archs

    module = archs.block("nemotron").make(
        archs.resolve("nemotron", {k: v for k, v in kw.items()
                                   if k not in ("arch", "seed")}),
        lambda *a, **k: None)
    sample = jnp.zeros((1, 16), jnp.int32)
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0), sample,
                            sample)["params"]
    count = sum(math.prod(leaf.shape)
                for leaf in jax.tree_util.tree_leaves(shapes))
    assert count == arithmetic.param_count(FILE) == 666_963_456
    assert shapes["NemotronBlock_1"]["experts_up"].shape == (8, 2688, 1856)
    assert shapes["NemotronBlock_0"]["w_in"].shape == (2688, 10304)


def test_the_seeding_of_the_mixer_and_of_everything_else(case):
    params = case["model"].flat.unravel(case["model"].flat.w0)
    heads = TINY["ssm_heads"]
    first = params["NemotronBlock_0"]
    np.testing.assert_allclose(first["a_log"],
                               np.log(np.arange(1, heads + 1)), rtol=1e-6)
    step = np.log1p(np.exp(np.asarray(first["dt_bias"])))
    assert np.all(step >= CONFIG["time_step_min"] * 0.999)
    assert np.all(step <= CONFIG["time_step_max"] * 1.001)
    assert CONFIG["time_step_floor"] < CONFIG["time_step_min"]  # inert
    assert np.all(np.asarray(first["d_skip"]) == 1.0)
    assert np.all(np.asarray(first["ssm_norm"]) == 1.0)
    # rescale_prenorm_residual: the mixers' output projections alone
    depth = math.sqrt(CONFIG["rescale_depth"])
    assert np.std(np.asarray(first["w_out"])) == pytest.approx(
        0.02 / depth, rel=0.1)
    assert np.std(np.asarray(params["NemotronBlock_3"]["wo"])) == \
        pytest.approx(0.02 / depth, rel=0.1)
    assert np.std(np.asarray(first["w_in"])) == pytest.approx(0.02, rel=0.1)
    assert np.std(np.asarray(params["NemotronBlock_1"]["experts_down"])) \
        == pytest.approx(0.02, rel=0.1)
    assert np.std(np.asarray(params["NemotronBlock_1"]["router_bias"])) \
        == pytest.approx(0.02, rel=0.5)
    assert 0.05 < float(case["stats"]["lm_ssm_decay_mean"][0]) < 0.999
    assert case["stats"]["lm_ssm_decay_mean"].shape == (2,)
    for name in transformer.JOYAI_MOE_STATS:
        assert case["stats"][name].shape == (2,), name


def test_the_seeded_weights_do_not_depend_on_the_training_sequence():
    short = build(arch="nemotron", seed=3, use_flash=False,
                  **{**TINY, "seq_len": 32})
    model = build(arch="nemotron", seed=3, use_flash=False, **TINY)
    assert np.array_equal(np.asarray(short.flat.w0),
                          np.asarray(model.flat.w0))


@pytest.mark.parametrize("bad", [
    {"layer_types": "mamba,moe"}, {"ssm_groups": 3}, {"ssm_heads": 0},
    {"experts_first": 7}, {"layer_types": "mamba,moe,kda,attention,moe"},
    {"rope_theta": 10000.0}])
def test_sizes_that_make_no_block_are_refused(bad):
    with pytest.raises((ValueError, TypeError)):
        build(arch="nemotron", seed=3, use_flash=False, **{**TINY, **bad})


def test_the_steps_operations_carry_the_blocks_scopes(case):
    model = case["model"]
    text = jax.jit(model.value_and_grad).lower(
        case["w"], case["tokens"]).as_text(debug_info=True)
    for scope in FILE["scopes"]:
        if scope != "update":   # the optimizer's, not the model's
            assert f"/{scope}/" in text, scope
    assert {"ssm_proj", "ssm_conv", "ssd_scan", "ssm_norm"} <= set(
        FILE["scopes"])


def test_a_mamba_layer_keeps_its_input_and_the_scans_output_alone():
    """The mixer's checkpoint: beside the layer's input and its
    parameters, the one array kept for the backward pass is the scan's
    output (``T x heads x head_dim`` floats, as the row-major view the
    kernels write, with the skip in it); z, x, B, C and the step are
    made again."""
    from jax._src.ad_checkpoint import saved_residuals

    d, heads, hd, groups, n = 32, 4, 8, 2, 8
    p = _mixer_params(np.random.RandomState(0), d, heads, hd, groups, n)
    x = jnp.zeros((2, 48, d))
    mixer = jax.checkpoint(
        functools.partial(transformer.state_space_mixer, heads=heads,
                          head_dim=hd, groups=groups, state=n, chunk=16,
                          eps=1e-5),
        policy=jax.checkpoint_policies.save_only_these_names(
            *transformer.SSM_KEPT))
    kept = saved_residuals(lambda x, p: mixer(x, p)[0], x, p)
    made = [shape.shape for shape, why in kept if "argument" not in why]
    assert made == [(2, 48, heads * hd)]
    assert transformer.SSM_KEPT == (ssd_scan.SSD_OUT,)


def test_the_other_blocks_experts_are_the_three_matrices_they_were():
    """``shared_sparse_experts`` has a second form; a block that names
    none (JoyAI's, Kimi's, Trinity's) makes gate, up and down, routed
    and shared, in the order and at the shapes it always did."""
    block = transformer.JoyaiBlock(
        d_model=32, n_heads=2, q_rank=8, kv_rank=8, qk_nope=8, qk_rope=4,
        v_head=8, sparse=True, dense_width=0, n_experts=4,
        experts_per_tok=2, expert_width=16, experts_held=2,
        attn_fn=transformer.default_attn(use_flash=False))
    x = jnp.zeros((1, 8, 32))
    shapes = jax.eval_shape(block.init, jax.random.PRNGKey(0), x)["params"]
    assert {name: leaf.shape for name, leaf in shapes.items()
            if name.startswith(("experts_", "shared_"))} == {
        "experts_gate": (2, 32, 16), "experts_up": (2, 32, 16),
        "experts_down": (2, 16, 32), "shared_gate": (32, 16),
        "shared_up": (32, 16), "shared_down": (16, 32)}
    assert transformer.EXPERT_FORMS["swiglu"][1] is moe.swiglu_experts


# -- through the launcher: locally and through two servers ------------------------

LAUNCH = dict(
    lm_use_flash=0, lm_eval_every=4, seed=5, device_policy="cpu",
    **FILE["launcher"],
    **{switch: CONFIG[key] for switch, key in FILE["launcher_from"].items()})
DECAY = transformer.SSM_DECAY_MEAN


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
    assert kw["arch"] == "nemotron"


def test_a_one_rank_local_run_learns_and_carries_the_decays_mean(obs_on):
    """``--np 1 --opt msgd``: the single-process path hands ``MSGD`` the
    step with the block's telemetry, and each donated step is a
    ``round`` span with the decay's mean a Mamba layer and the routing
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
        assert len(span.args[DECAY]) == 2
        assert all(0.05 < x < 0.999 for x in span.args[DECAY])
        for name in transformer.JOYAI_MOE_STATS:
            assert len(span.args[name]) == 2, name
    assert obs.get_registry().gauge(f"mpit_{DECAY}", layer=1).value == \
        rounds[-1].args[DECAY][1]
    for name in (DECAY,) + transformer.JOYAI_MOE_STATS:
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


def test_the_model_through_two_servers_trains_and_leaves_the_bias(case):
    """The ``ps1w`` path at the tiny size: the trainer's own shell under
    ``--opt adam``, two servers on the host.  The master copy moves, the
    loss falls, and the selection biases, which no gradient reaches and
    no rule moves (``bias_rate`` 0: no plain range), stay to the bit
    where they were seeded."""
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
    for name, leaves in seeded.items():
        if "router_bias" in leaves:
            assert np.array_equal(np.asarray(leaves["router_bias"]),
                                  np.asarray(after[name]["router_bias"]))
            assert not np.array_equal(np.asarray(leaves["router"]),
                                      np.asarray(after[name]["router"]))


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
    assert all(len(r.args[DECAY]) == 2 for r in rounds)
    assert worker[DECAY] == rounds[-1].args[DECAY]
