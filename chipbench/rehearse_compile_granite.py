"""``rehearse_compile_ouro.py``'s two programs for ``granite4h-l10-local``
and the sizes that decide whether the cell runs: compiles the cell's
worker step at its real shapes for the described chip (``v5e:2x2``, one
device) without the chip, and prints the compiler's
``memory_analysis()`` and the count of ``tpu_custom_call``s:

    JAX_PLATFORMS=cpu python3 -m chipbench.rehearse_compile_granite [--reference]

The block closes its own loss, so the model comes from the program's own
builder by the cell's launch config, with the Mosaic-pinned attention in
place of the reference attention, ``jax.default_backend`` answered
``tpu`` while it lowers: the donated ``msgd_step`` the window runs
(``optim/msgd.py``) and ``value_and_grad`` as the reference check lowers
it.  This cell's vector is the largest a local cell trains (772,160,448
elements, 3.09 GB), so each program's size is also printed **with what
lies beside it on the chip** and held to :data:`TARGET_GB`: beside the
step the seeded vector (the model object keeps it, and the first call
steps on a copy: the chip's peak), beside the check's
``value_and_grad``, whose argument is that vector, the reference's
gradient.  It is also
where the scan's kernels at one group of 64 heads, a part of the group a
grid step, are first handed to the kernels' compiler inside a whole
step.  ``--reference`` compiles the plain reference's own program for
the described chip as well (the seeded vector is among its arguments).
``tests/test_tpu_compile.py`` holds the step to the target.  A compile
that passes is not a chip run.
"""

from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

CELL = "granite4h-l10-local"
#: what the step with the seeded vector beside it may take (ISSUE 65:
#: what Qwen3-Next's cell runs at), of a limit of 16.9 on the chip
TARGET_GB = 15.5


def _size(compiled) -> float:
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes) / 1e9


def programs(reference: bool = False):
    """``[(name, vectors beside it, lower)]`` and the vector's size in
    GB: the donated step, the check's ``value_and_grad`` and, asked for,
    the plain reference's program, each lowered for one device of the
    described chip.  The model is the program's own block by the cell's
    launch config, **its parameters as shapes alone**: no weight of the
    3 GB vector is made here."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import run as runner, spec as spec_mod
    from mpit_tpu.lm import archs
    from mpit_tpu.lm.model import build_kw
    from mpit_tpu.models.flat import leaf_unravel
    from mpit_tpu.models.transformer import default_attn
    from mpit_tpu.optim.msgd import MSGDConfig, msgd_step
    from mpit_tpu.train.launch import lm_trainer_cfg

    cell = spec_mod.load_cell(CELL)
    cfg = runner.launch_config(cell, 1)
    seq, batch = int(cfg.lm_seq), int(cell.traffic["batch"])
    given = build_kw(lm_trainer_cfg(cfg))
    arch = given.pop("arch")
    del given["seed"]
    module = archs.block(arch).make(
        archs.resolve(arch, given),
        lambda precision=None: default_attn(
            causal=True, use_flash=True, interpret=False,
            precision=precision))
    sample = jnp.zeros((1, archs.SAMPLE_LEN), jnp.int32)
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0), sample,
                            sample)["params"]
    unravel = leaf_unravel(shapes)
    n = sum(leaf.size for leaf in jax.tree_util.tree_leaves(shapes))

    def loss(w, tokens):
        return module.apply({"params": unravel(w)}, tokens[:, :-1],
                            tokens[:, 1:])[0]

    vgf = jax.value_and_grad(loss)
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    w = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=chip)
    tokens = jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32, sharding=chip)
    state = {"k": jax.ShapeDtypeStruct((), jnp.int32, sharding=chip),
             "vt": w}
    mcfg = MSGDConfig(lr=float(cfg.lr), mom=float(cfg.mom),
                      mommax=float(cfg.mommax), momdecay=float(cfg.momdecay))
    step = jax.jit(lambda w, s, t: msgd_step(vgf, w, s, mcfg, t),
                   donate_argnums=(0, 1))
    out = [("msgd_step, donated", 1, lambda: step.lower(w, state, tokens)),
           ("value_and_grad, as the reference check lowers it", 1,
            lambda: jax.jit(vgf).lower(w, tokens))]
    if reference:
        ref = cell.reference()

        def plain(flat, tok):
            nll, grads = jax.value_and_grad(ref.loss)(unravel(flat), tok,
                                                      cell.config)
            return nll, jnp.concatenate([
                leaf.reshape(-1)
                for leaf in jax.tree_util.tree_leaves(grads)])

        def lower_plain():
            with jax.default_matmul_precision("highest"):
                return jax.jit(plain).lower(w, jax.ShapeDtypeStruct(
                    (1, seq + 1), jnp.int32, sharding=chip))

        out.append(("the plain reference's loss and gradient", 0,
                    lower_plain))
    return out, n * 4 / 1e9


def main(reference: bool = False) -> dict:
    """Prints each program's size alone and with the vectors that lie
    beside it; returns ``{program: GB with them}``."""
    import jax

    lowerings, vector_gb = programs(reference)
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    held = {}
    try:
        for name, vectors_beside, lower in lowerings:
            t0 = time.monotonic()
            lowered = lower()
            calls = lowered.as_text().count("tpu_custom_call")
            alone = _size(lowered.compile())
            held[name] = alone + vectors_beside * vector_gb
            print(f"{CELL}: {name}: compiled in "
                  f"{time.monotonic() - t0:.1f} s: {alone:.3f} GB, "
                  f"{held[name]:.3f} GB with the {vectors_beside} vector(s) "
                  f"of {vector_gb:.3f} GB beside it, target {TARGET_GB}: "
                  f"{'fits' if held[name] <= TARGET_GB else 'DOES NOT FIT'}"
                  f"; tpu_custom_call in the lowered text {calls}",
                  flush=True)
    finally:
        jax.default_backend = real
    return held


if __name__ == "__main__":
    main(reference="--reference" in sys.argv)
