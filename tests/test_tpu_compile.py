"""Compiles for a *described* v5e, no chip attached (the
on-chip-measurement guide, section 2): the Pallas grouped product of
``parallel/moe.py`` at OLMoE's published shapes, forward and backward;
the flash kernels at LFM2's attention shape (32 query over 8 KV heads of
64 at 8192), at Mellum's sliding layer's (32 over 4 heads of 128
under a window of 1024) and at the default tiles of float32 and bf16
operands under both backward schedules, with no pad and no slice of an
operand's size round the calls at head widths of 64 and 192 (PR 54), and a dense and a
latent-attention block whose q, k, v and dO are rounded to bf16 by the
fusions that make them, not by a pass of their own (PR 57); the delta rule's three kernels at Kimi's KDA
shape (32 heads of 128 at 8192); the state-space scan's three at Nemotron's shape (8 groups of 8 heads)
and at Granite's (ONE group of 64 heads, eight a grid step), and the whole donated step of Granite's cell
under its stated size (PR 65); and the msgd commit over LFM2's vector, whose length is
whole lanes and no whole number of blocks, and over Ouro's, which is no
whole number of lanes, with ``w`` and ``vt`` donated.  What interpret mode cannot show: that the tiles fit the chip's fast
memory and the kernels lower.  A compile that passes is not a chip run
and says nothing about time.

The topology is described inside a fixture, never at import, and all
such tests live in this one file: only one process at a time may load
the TPU's compiler (the guide says why)."""

import math
import re

import jax
import jax.numpy as jnp
import pytest

ROWS, D, F, E = 8 * 4096, 2048, 1024, 64  # one sequence's assignments


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("k,n", [(D, F), (F, D)], ids=["up", "down"])
def test_the_pallas_grouped_product_compiles_at_published_shapes(one_chip, k, n):
    from mpit_tpu.parallel import moe

    def loss(rows, w, sizes):
        return jnp.sum(moe.pallas_grouped_dot(rows, w, sizes) ** 2)

    args = (jax.ShapeDtypeStruct((ROWS, k), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((E, k, n), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((E,), jnp.int32, sharding=one_chip))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*args).compile()
    text = compiled.as_text()
    # the product, its rows' gradient and its weights' gradient
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    d_rows, d_w = compiled.output_shardings  # both gradients come out
    assert moe.pallas_fits(ROWS, k, n)
    # float32 results: the weights' gradient is never rounded to bf16
    assert "f32[64,%d,%d]" % (k, n) in text


def sweeps_of(text, least):
    """The ``pad`` and ``slice`` operations of a compiled program, inside
    its fusions or not, whose result holds at least ``least`` elements:
    a head padded to whole 128-lane tiles in front of a flash kernel, or
    ``dq`` / ``dk`` cut back to the head's width behind one, is such an
    operation (PR 54 took them out; the row statistics' ``[:, 0]`` and
    the prefetched ranges are far smaller than any operand)."""
    found = []
    for line in text.splitlines():
        op = re.search(r"= \w+\[([\d,]*)\]\S* (pad|slice)\(", line)
        if op and math.prod(map(int, op.group(1).split(","))) >= least:
            found.append(line.strip()[:200])
    return found


def block_shapes(fn, *operands):
    """The ``(rows, width)`` of every block of every ``pallas_call``
    that ``fn`` traces to."""
    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    return {tuple(getattr(dim, "block_size", dim)
                  for dim in mapping.block_shape[-2:])
            for call in calls(jax.make_jaxpr(fn)(*operands).jaxpr)
            for mapping in call.params["grid_mapping"].block_mappings}


@pytest.mark.parametrize("kv_heads,width,window", [(8, 64, None),
                                                   (4, 128, 1024)],
                         ids=["32_over_8_heads_of_64",
                              "32_over_4_heads_of_128_window_1024"])
def test_flash_attention_compiles_at(one_chip, kv_heads, width, window):
    """LFM2's attention layer (PR 32): a group's four query heads folded
    into the kernel's rows, the head 64 lanes wide and blocked as it is
    (PR 54: no operand is padded to the lanes in front of a call and no
    gradient cut back behind it); and Mellum's sliding layer at its
    published shape (PR 33): a group of eight under a window of 1024,
    the inner grid axis the window's static bound and the index maps
    reading the prefetched offsets, every block 128 wide as it always
    was.  Forward and the backward kernels lower for the chip, k and v
    at the KV heads' size."""
    from mpit_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, window=window,
                                       interpret=False) ** 2)

    q = jax.ShapeDtypeStruct((1, 32, 8192, width), jnp.float32,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, kv_heads, 8192, width), jnp.float32,
                              sharding=one_chip)
    grads = jax.grad(loss, argnums=(0, 1, 2))
    compiled = jax.jit(grads).lower(q, kv, kv).compile()
    text = compiled.as_text()
    calls = text.count('custom_call_target="tpu_custom_call"')
    # forward, and the fused (no window) or the two backward kernels
    assert calls >= (2 if window is None else 3)
    dq, dk, dv = compiled.output_shardings
    assert not sweeps_of(text, least=math.prod(kv.shape))
    # the rows' statistics are the kernels' own format, 128 lanes; the
    # operands are bf16 (PR 57): 1024 rows a tile, 512 under a window
    block = 1024 if window is None else 512
    assert block_shapes(grads, q, kv, kv) == {(block, width), (block, 128)}


@pytest.mark.parametrize("fused", ["1", "0"], ids=["fused", "two_kernel"])
@pytest.mark.parametrize("dtype,precision,blocks,width,dv", [
    (jnp.float32, "highest", (512, 512), 128, 128),
    (jnp.float32, "highest", (512, 512), 192, 128),
    (jnp.float32, None, (1024, 1024), 128, 128),
    (jnp.float32, None, (1024, 512), 192, 128),
    (jnp.bfloat16, None, (1024, 1024), 128, 128)],
    ids=["f32_512x512", "f32_512x512_keys_of_192_lanes",
         "f32_in_bf16_operands_1024x1024",
         "f32_in_bf16_operands_1024x512_keys_of_192_lanes",
         "bf16_1024x1024"])
def test_the_flash_kernels_compile_at_the_default_tiles(
        one_chip, monkeypatch, dtype, precision, blocks, width, dv, fused):
    """The forward and both backward schedules lower for the chip at
    the default tiles: ``(512, 512)`` on float32 operands, which a
    caller keeps by naming a ``precision`` (at keys of 128 lanes and at
    JoyAI's 192 over values of 128, blocked at 192 as they lie: PR 54),
    and on the bf16 operands every other call has (PR 57: float32
    arrays at the default precision are rounded by the op, the results
    stay float32) ``(1024, 1024)``, ``(1024, 512)`` where the keys are
    wider than a lane tile; with the row statistics read whole
    and laid side by side against the tile (PR 52: ``_lanes``; a
    concatenation along the lanes at whole vregs), under the scoped-VMEM
    budget ``_vmem_auto`` asks for at those tiles, which is the stock
    one.  No ``pad`` and no ``slice`` of an operand's size stands round
    the calls, and a 128-wide call's blocks are 128 wide as before."""
    import importlib

    fa = importlib.import_module("mpit_tpu.ops.flash_attention")
    monkeypatch.setenv("MPIT_FA_FUSED_BWD", fused)
    monkeypatch.delenv("MPIT_FA_VMEM_MB", raising=False)
    operand = fa.operand_dtype(dtype, precision)
    assert operand == (jnp.float32 if precision else jnp.bfloat16)
    assert fa._default_blocks(operand, width) == blocks
    assert fa._vmem_auto(*blocks) == 0.0   # no raise is asked for

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(
            q, k, v, causal=True, interpret=False,
            precision=precision).astype(jnp.float32) ** 2)

    operands = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape in ((1, 8, 4096, width), (1, 8, 4096, width),
                              (1, 8, 4096, dv))]
    grads = jax.grad(loss, argnums=(0, 1, 2))
    text = jax.jit(grads).lower(*operands).compile().as_text()
    calls = text.count('custom_call_target="tpu_custom_call"')
    assert calls == (2 if fused == "1" else 3)
    assert not sweeps_of(text, least=math.prod(operands[2].shape))
    bq, bk = blocks
    assert block_shapes(grads, *operands) == {
        (bq, width), (bq, dv), (bq, 128), (bk, width), (bk, dv)}


def lone_converts(text, least):
    """The fusions of a compiled program that do nothing but round an
    array of at least ``least`` elements to bf16: a ``convert`` over
    parameters and bitcasts alone (a bitcast moves nothing: a fusion
    that transposes holds a ``copy`` or a ``transpose``), or such a
    ``convert`` outside any fusion.  The flash kernels' operands are
    rounded at the head of the op's rules (``operand_dtype``), and XLA
    is to fold that into whatever writes the operand (the transposes to
    heads-major, a projection's output); a fusion like these would be a
    pass over ``(B, H, L, D)`` that the float32 kernels did not have."""
    found = []
    for body in re.split(r"\n(?=%?[\w.]+ \(.*\) -> .*\{\n|ENTRY )", text):
        lines = [l.strip() for l in body.splitlines()[1:] if " = " in l]
        rounds = [l for l in lines if (op := re.search(
            r"= bf16\[([\d,]+)\]\S* convert\(", l)) and math.prod(
                map(int, op.group(1).split(","))) >= least]
        if body.startswith("ENTRY"):
            found += [l[:160] for l in rounds]
        elif rounds and not [l for l in lines if l not in rounds
                             and " parameter(" not in l
                             and " bitcast(" not in l]:
            found.append(rounds[0][:160])
    return found


@pytest.mark.parametrize("block", ["dense", "latent"])
def test_the_operands_rounding_rides_on_the_fusions_that_make_them(
        one_chip, block):
    """PR 57: at the default precision the flash kernels take q, k, v
    and dO in bf16 (``ops/flash_attention.py`` ``operand_dtype``) and
    write float32.  In the compiled step of a dense block and of a
    latent-attention block (keys of 192 lanes over values of 128) every
    kernel call reads bf16 operands, and no fusion stands in front of
    one that only rounds an operand: the ``convert`` sits in the fusion
    that transposes to heads-major (or in the projection's own output),
    which writes half the bytes it wrote."""
    from mpit_tpu.models import transformer

    attn = transformer.default_attn(causal=True, use_flash=True,
                                    interpret=False)
    b, l, d, heads = 1, 2048, 512, 4
    if block == "dense":
        module = transformer.DecoderBlock(d_model=d, n_heads=heads,
                                          attn_fn=attn)
        widths = {(b, heads, l, d // heads)}
    else:
        module = transformer.JoyaiBlock(
            d_model=d, n_heads=heads, q_rank=192, kv_rank=128, qk_nope=128,
            qk_rope=64, v_head=128, sparse=False, dense_width=1024,
            n_experts=0, experts_per_tok=0, expert_width=0, attn_fn=attn)
        widths = {(b, heads, l, 192), (b, heads, l, 128)}
    x = jax.ShapeDtypeStruct((b, l, d), jnp.float32, sharding=one_chip)
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one_chip),
        jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0),
                                           jnp.zeros(x.shape))))

    def loss(p, x):
        out = module.apply(p, x)
        return jnp.sum((out[0] if isinstance(out, tuple) else out) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) >= 2
    for call in calls:  # what a kernel writes is float32
        assert "bf16[" not in call.split(" custom-call(")[0], call[:200]
    for shape in widths:  # what it reads is bf16 (the batch of 1 squeezed)
        dims = ",".join(map(str, shape[1:]))
        assert re.search(r"bf16\[(%d,)?%s\]" % (b, dims), text), shape
    assert not lone_converts(text, least=b * heads * l * 64)


def test_the_delta_rules_kernels_compile_at_kimis_shape(one_chip, monkeypatch):
    """Kimi's KDA layer (PR 44): 32 heads of 128 at 8192, the forward
    kernel and the rule's two (the forward that writes the 128 chunk
    states and solves a head, the walk back), on the row-major inputs: blocks of
    ``(64, 4 x 128)`` and the tables fit the chip's fast memory and every
    product lowers, the solve's at full float32 precision among them.
    The choice of interpret mode asks for the backend, which is the CPU
    here: steered in the test."""
    from mpit_tpu.ops import delta_rule

    monkeypatch.setattr(delta_rule, "use_interpret", lambda flag: False)
    wide = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.float32,
                                sharding=one_chip)
    beta = jax.ShapeDtypeStruct((1, 8192, 32), jnp.float32,
                                sharding=one_chip)

    def loss(q, k, v, g, beta):
        return jnp.sum(delta_rule.kda_scan(q, k, v, g, beta) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        wide, wide, wide, wide, beta).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert "f32[1,32,128,128,128]" in text   # the chunks' starting states
    assert "f32[1,32,128,64,64]" in text     # and their solves
    assert len(compiled.output_shardings) == 5


@pytest.mark.parametrize("n", [486_062_464, 509_661_185],
                         ids=["lfm2s_whole_lanes", "ouros_one_over"])
@pytest.mark.parametrize("mom_next", [None, 0.9],
                         ids=["commit", "commit_and_lookahead"])
def test_the_donated_commit_keeps_no_copy_of_the_vector(one_chip, n, mom_next):
    """486,062,464 elements are whole lanes and 14,833.45 blocks,
    509,661,185 are one over a whole number of lanes: the commit sweeps
    either where it lies, in 1-D blocks with an overhanging last one
    and under no other view, so with ``w`` and ``vt`` donated the
    program holds no third vector (a pad, or a slice of the aligned
    prefix, would copy each operand whole), with the next step's
    lookahead on the same blocks or without."""
    from mpit_tpu.ops.fused_update import fused_nesterov_commit

    vec = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda w, vt, g: fused_nesterov_commit(w, vt, g, 0.03,
                                               mom_next=mom_next,
                                               interpret=False),
        donate_argnums=(0, 1)).lower(vec, vec, vec).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * 4 * n   # both, to the tile
    assert mem.temp_size_in_bytes < 4 * n // 8
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert f"f32[{n // 128},128]" not in text


@pytest.mark.parametrize("n", [486_062_464, 509_661_185],
                         ids=["lfm2s_whole_lanes", "ouros_one_over"])
def test_the_local_step_sweeps_its_vector_once(one_chip, monkeypatch, n):
    """``MSGD``'s program is still ``jit__lambda`` (the benchmark finds
    the step by that name), and outside the model it is one kernel over
    the vector: no fusion writes two arrays of its length (the lookahead
    pass did: the scaled velocity and the displaced point)."""
    from mpit_tpu.optim.msgd import MSGD, MSGDConfig

    def vgf(w, target):
        return 0.5 * jnp.sum((w - target) ** 2), w - target

    # the described chip is not the default backend: take its branch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    opt = MSGD(MSGDConfig(lr=0.01, mom=0.9), vgf)
    vec = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    state = {"k": jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
             "vt": vec}
    lowered = opt._step.lower(vec, state, vec)
    assert "module @jit__lambda" in lowered.as_text()
    assert lowered.as_text().count("tpu_custom_call") == 1
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    results = [line.split(" fusion(")[0] for line in text.splitlines()
               if " fusion(" in line]
    assert results and all(r.count(f"f32[{n}]") <= 1 for r in results)
    assert f"f32[{n // 128},128]" not in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * 4 * n
    assert mem.temp_size_in_bytes < 4 * n + 4 * n // 8   # the gradient


@pytest.mark.parametrize("l2wd", [0.0, 1e-4], ids=["plain", "decayed"])
def test_plain_ranges_cost_the_local_step_no_copy_of_the_vector(
        one_chip, monkeypatch, l2wd):
    """A step whose vector has plain ranges (``optim/msgd.py``
    ``plain_commit``; Trinity's four bias leaves at its 504,147,712
    elements) is still one kernel over the vector with ``w`` and ``vt``
    updated where they lie: the ranges' next values are read before the
    commit, behind a barrier.  Read after it they cost a copy of the
    whole vector before the kernel, another after the writes and a
    vector more of temporaries (PR 53: 12 ms of a 339 ms step on the
    chip)."""
    from mpit_tpu.optim.msgd import MSGD, MSGDConfig

    n = 504_147_712
    plain = ((115622400, 115622528), (199779200, 199779328),
             (283936000, 283936128), (368092800, 368092928))

    def vgf(w, target):
        return 0.5 * jnp.sum((w - target) ** 2), w - target

    vgf.plain = plain
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    opt = MSGD(MSGDConfig(lr=0.03, mom=0.9, l2wd=l2wd), vgf)
    vec = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    state = {"k": jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
             "vt": vec}
    compiled = opt._step.lower(vec, state, vec).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    copies = [line for line in text.splitlines() if " copy(" in line
              and f"f32[{n}]" in line.split(" copy(")[0]]
    assert not copies, copies
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * 4 * n
    assert mem.temp_size_in_bytes < 4 * n + 4 * n // 8   # the gradient


# -- the state-space hybrid (PR 58) ---------------------------------------------


@pytest.mark.parametrize("k,n", [(2688, 1856), (1856, 2688)],
                         ids=["up", "down"])
def test_the_grouped_product_compiles_at_an_inner_width_of_14_5_lane_tiles(
        one_chip, k, n):
    """Nemotron's experts: 1856 columns inside, which no multiple of 128
    divides.  The kernels take it as it is, its last tile masked (640 x
    3 = 1920 computed): forward, the rows' gradient and the weights'
    compile for the chip over a window of 6144 rows and 8 held experts
    from a group offset, no operand is padded to whole lanes in front of
    a call and no gradient cut back behind one."""
    from mpit_tpu.parallel import moe

    rows, held, groups = 6144, 8, 10
    assert moe.pallas_fits(rows, k, n)
    assert 1856 in (k, n) and moe._gmm_tile(1856) == 640

    def loss(x, w, sizes):
        return jnp.sum(moe.grouped_dot(x, w, sizes, 1) ** 2)

    args = (jax.ShapeDtypeStruct((rows, k), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((held, k, n), jnp.float32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((groups,), jnp.int32, sharding=one_chip))
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"    # ``grouped_dot`` asks
    try:
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            *args).compile()
    finally:
        jax.default_backend = real
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert "f32[8,%d,%d]" % (k, n) in text
    assert not sweeps_of(text, held * k * n)


def test_the_state_space_scan_compiles_at_the_published_shape(one_chip):
    """64 heads of 64 over 8 groups' B and C of 128 at 8192 positions in
    chunks of 128: forward and the operator's own rule are three Mosaic
    calls for the chip (the forward kernel; in the rule the walk that
    makes the 64 chunk-start states again and the walk back), no array
    holds a ``128 x 128`` matrix a head and chunk, and what the rule
    keeps meanwhile is those states and the layouts of its operands
    (504 MB as this is written), where the XLA form was allowed 3.4
    GB."""
    from mpit_tpu.ops import ssd_scan

    def loss(x, dt, a, b, c):
        return jnp.sum(ssd_scan.ssd_scan(x, dt, a, b, c) ** 2)

    def of(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    args = (of(1, 8192, 64, 64), of(1, 8192, 64), of(64,),
            of(1, 8192, 8, 128), of(1, 8192, 8, 128))
    assert ssd_scan.takes_kernels(args[0], args[3], ssd_scan.CHUNK)
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"    # ``use_interpret`` asks
    try:
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            *args).compile()
    finally:
        jax.default_backend = real
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert not re.findall(r"f32\[[0-9,]*128,128\]", text)
    assert "f32[1,8,64,128,512]" in text       # the chunk-start states
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


def test_the_state_space_scan_compiles_at_one_group_of_64_heads(one_chip):
    """Granite's shape: 64 heads of 64 under ONE group's B and C of 128
    at 4096 positions.  A grid step holds eight of the group's heads
    (``heads_a_step``), so the three calls lower within scoped VMEM as
    Nemotron's bodies do (the whole group a step would hold 4 MB of
    decay matrices and as much of masked products at once), the
    chunk-start states are eight blocks' side by side, and ``dB`` and
    ``dC`` leave the walk back as eight parts a position."""
    from mpit_tpu.ops import ssd_scan

    def loss(x, dt, a, b, c, d):
        return jnp.sum(ssd_scan.ssd_scan(x, dt, a, b, c, skip=d) ** 2)

    def of(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    args = (of(1, 4096, 64, 64), of(1, 4096, 64), of(64,),
            of(1, 4096, 1, 128), of(1, 4096, 1, 128), of(64,))
    assert ssd_scan.takes_kernels(args[0], args[3], ssd_scan.CHUNK)
    assert ssd_scan.heads_a_step(args[0], args[3], ssd_scan.HEAD_BLOCK) == 8
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"    # ``use_interpret`` asks
    try:
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))).lower(
            *args).compile()
    finally:
        jax.default_backend = real
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert not re.findall(r"f32\[[0-9,]*128,128\]", text)
    assert "f32[1,8,32,128,512]" in text       # the chunk-start states
    assert "f32[1,4096,1024]" in text          # dB, dC: eight parts of 128
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


def test_the_granite_cells_step_is_under_the_stated_size(one_chip):
    """``chipbench/rehearse_compile_granite.py``: the donated step of
    ``granite4h-l10-local`` at its real shapes (772,160,448 elements,
    4096 positions), no weight made here, compiled for the described
    chip: with the seeded vector beside it at most the script's target,
    and the scan's three bodies, the attention's pair and the commit
    kernel among its Mosaic calls."""
    import jax as _jax

    from chipbench import rehearse_compile_granite as script

    lowerings, vector_gb = script.programs()
    name, beside, lower = lowerings[0]
    assert (name, beside) == ("msgd_step, donated", 1)
    assert vector_gb == 772_160_448 * 4 / 1e9
    real = _jax.default_backend
    _jax.default_backend = lambda: "tpu"
    try:
        lowered = lower()
        assert lowered.as_text().count("tpu_custom_call") >= 3 + 2 + 1
        alone = script._size(lowered.compile())
    finally:
        _jax.default_backend = real
    assert alone + beside * vector_gb <= script.TARGET_GB
    assert alone > 3 * vector_gb       # the vector, its momentum, the gradient


def test_the_indexer_compiles_at_the_published_shape(one_chip):
    """16 heads of 64 against one key head at 8192 positions, the top
    2048 a query: the selection is one Mosaic call for the chip whose
    VMEM holds a block's ``8192 x 256`` keys (8 MB) beside its operands,
    no array of the program holds a row block's scores, and what leaves
    is the bits and two counts a row."""
    from mpit_tpu.ops import index_select

    def of(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    real = jax.default_backend
    jax.default_backend = lambda: "tpu"    # ``use_interpret`` asks
    try:
        compiled = jax.jit(
            lambda *a: index_select.index_select(*a, 2048)).lower(
                of(1, 8192, 16, 64), of(1, 8192, 64),
                of(1, 8192, 16)).compile()
    finally:
        jax.default_backend = real
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "s32[1,8192,256]" in text                  # the bits
    assert not re.findall(r"[fsu]32\[(1,)?256,8192\]", text)  # no scores
    assert "while" not in text and "conditional" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 80e6


# -- the gated-delta hybrid (PR 61) ----------------------------------------------


def test_flash_attention_compiles_at_16_over_2_heads_of_256(one_chip):
    """Qwen3-Next's full layer: a group's eight query heads folded into
    the kernel's rows (as Keye's), keys **and values** 256 wide, two
    lane tiles each, which no kernel here had run on the value side
    (JoyAI's keys are 192 -> 256 lanes, its values 128).  The tiles
    ``_default_blocks`` gives a width past one lane tile hold in the
    chip's fast memory in the forward and the backward bodies, k and v
    at the KV heads' size, nothing padded or cut round the calls."""
    from mpit_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=False) ** 2)

    q = jax.ShapeDtypeStruct((1, 16, 8192, 256), jnp.float32,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 2, 8192, 256), jnp.float32,
                              sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 2
    assert len(compiled.output_shardings) == 3
    assert "f32[1,2,8192,256]" in text         # dk, dv at the KV heads
    assert not sweeps_of(text, least=math.prod(kv.shape))


@pytest.mark.parametrize("k,n", [(2048, 512), (512, 2048)],
                         ids=["up", "down"])
def test_the_grouped_product_compiles_at_32_held_experts_of_512(
        one_chip, k, n):
    """Qwen3-Next's experts: a router 512 wide and 10 a token are 81,920
    sorted assignments a layer at 8192 tokens, of which the held window
    moves twice the uniform share; 32 held experts whose inner width is
    one tile of four lanes see 160 rows each on average, **less than a
    row tile**, so most row tiles span two experts or three.  Forward,
    the rows' gradient and the weights' compile for the chip over the
    window's rows and 32 held experts from a group offset among the 512
    group sizes."""
    from mpit_tpu.parallel import moe

    held, groups, assignments = 32, 512, 81_920
    rows = moe.held_window(assignments, 2048, held, groups)
    assert rows == 10_240                  # twice the uniform 5,120
    assert moe.pallas_fits(rows, k, n)

    def loss(x, w, sizes):
        return jnp.sum(moe.grouped_dot(x, w, sizes, 7) ** 2)

    args = (jax.ShapeDtypeStruct((rows, k), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((held, k, n), jnp.float32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((groups,), jnp.int32, sharding=one_chip))
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"    # ``grouped_dot`` asks
    try:
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            *args).compile()
    finally:
        jax.default_backend = real
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert "f32[32,%d,%d]" % (k, n) in text
    assert not sweeps_of(text, held * k * n)


def test_the_gated_delta_scan_compiles_at_the_published_shape(
        one_chip, monkeypatch):
    """Qwen3-Next's Gated DeltaNet layer: 16 key heads under 32 value
    heads of 128 at 8192, one scalar decay a value head, as the scalar
    rule's own three kernels (PR 62): the forward, the forward again
    writing the 128 chunk states and solves a value head, the walk
    back.  Every call reads q and k at ``(8192, 16 x 128)``, v at
    ``(8192, 32 x 128)`` and the log-decay and ``beta`` at ``(8192,
    32)``: no key is repeated and no decay broadcast to ``(1, 8192, 32,
    128)`` anywhere in the program, and the five gradients come back at
    the operands' own shapes."""
    from mpit_tpu.ops import delta_rule

    monkeypatch.setattr(delta_rule, "use_interpret", lambda flag: False)

    def of(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    keys, values, small = of(1, 8192, 16, 128), of(1, 8192, 32, 128), \
        of(1, 8192, 32)

    def loss(q, k, v, g, beta):
        return jnp.sum(delta_rule.gdn_scan(q, k, v, g, beta) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        keys, keys, values, small, small).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert "f32[1,32,128,128,128]" in text   # the chunks' starting states
    assert "f32[1,32,128,64,64]" in text     # and their solves
    read = re.findall(
        r"operand_layout_constraints=\{((?:[^{}]|\{[^}]*\})*)\}", text)
    assert len(read) == 3
    for operands in read:     # what a call reads first: q, k, v, g, beta
        assert re.findall(r"f32\[([\d,]+)\]", operands)[:5] == [
            "1,8192,2048", "1,8192,2048", "1,8192,4096", "1,8192,32",
            "1,8192,32"]
    # the first form's decay over the keys' channels and its keys
    # repeated for their value heads: a ``broadcast_in_dim`` each, by
    # whatever instruction the compiler made of it
    assert not re.search(r"= f32\[1,8192,[\d,]*(4096|128)\]\S* \w+\("
                         r"[^\n]*broadcast_in_dim", text)
    shapes = [tuple(s.shape) for s in jax.eval_shape(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
        keys, keys, values, small, small)]
    assert shapes == [keys.shape, keys.shape, values.shape, small.shape,
                      small.shape]
