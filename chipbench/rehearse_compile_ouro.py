"""``rehearse_compile.py`` for a block that closes its own loss: compiles
``ouro-l6-local``'s worker step at its real shapes for the described chip
(``v5e:2x2``, one device) without the chip, and prints the compiler's
``memory_analysis()`` and the count of ``tpu_custom_call``s:

    JAX_PLATFORMS=cpu python3 -m chipbench.rehearse_compile_ouro [--undonated]

``rehearse_compile.py`` closes a next-token NLL of its own over the
module's output, which a decoder that is called with the targets and
returns its loss does not have; this file is that one with the loss
taken from the module, and nothing else of it changed: the model from
the program's own builder by the cell's launch config, the Mosaic-pinned
attention in place of the reference attention, ``jax.default_backend``
answered ``tpu`` while it lowers.  Two programs: the donated
``msgd_step`` the window runs (``optim/msgd.py``; ``--undonated`` lowers
it as ``rehearse_compile.py`` does) and ``value_and_grad`` as the
reference check lowers it.  Run by hand, not by the tests.  A compile
that passes is not a chip run.  Beside the step the process keeps the
seeded vector the model object holds; beside ``value_and_grad`` at the
check, that vector and the reference's gradient.
"""

from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

CELL = "ouro-l6-local"


def main(donate: bool = True) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import run as runner, spec as spec_mod
    from mpit_tpu.models.transformer import default_attn
    from mpit_tpu.optim.msgd import MSGDConfig, msgd_step

    cell = spec_mod.load_cell(CELL)
    mix = cell.traffic
    cfg = runner.launch_config(cell, 1)
    seq, batch = int(cfg.lm_seq), int(mix["batch"])
    shapes = runner.build_model(cell, seed=1, lm_use_flash=0)
    module = shapes.module.clone(
        attn_fn=default_attn(causal=True, use_flash=True, interpret=False))
    unravel, n = shapes.flat.unravel, int(shapes.flat.size)

    def loss(w, tokens):
        return module.apply({"params": unravel(w)}, tokens[:, :-1],
                            tokens[:, 1:])[0]

    vgf = jax.value_and_grad(loss)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    w = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=chip)
    tokens = jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32, sharding=chip)
    state = {"k": jax.ShapeDtypeStruct((), jnp.int32, sharding=chip),
             "vt": w}
    mcfg = MSGDConfig(lr=float(cfg.lr), mom=float(cfg.mom),
                      mommax=float(cfg.mommax), momdecay=float(cfg.momdecay))
    step = jax.jit(lambda w, s, t: msgd_step(vgf, w, s, mcfg, t),
                   donate_argnums=(0, 1) if donate else ())
    programs = (
        ("msgd_step, donated" if donate else "msgd_step, undonated",
         lambda: step.lower(w, state, tokens)),
        ("value_and_grad, as the reference check lowers it",
         lambda: jax.jit(vgf).lower(w, tokens)))
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        for name, lower in programs:
            t0 = time.monotonic()
            lowered = lower()
            in_text = lowered.as_text().count("tpu_custom_call")
            compiled = lowered.compile()
            mem = compiled.memory_analysis()
            total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                     + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
            print(f"{CELL}: {name}: {n} elements, {batch} x {seq}, "
                  f"compiled in {time.monotonic() - t0:.1f} s: arguments "
                  f"{mem.argument_size_in_bytes / 1e9:.3f} GB, outputs "
                  f"{mem.output_size_in_bytes / 1e9:.3f}, aliased "
                  f"{mem.alias_size_in_bytes / 1e9:.3f}, temporaries "
                  f"{mem.temp_size_in_bytes / 1e9:.3f}, total "
                  f"{total / 1e9:.3f} GB; tpu_custom_call in the lowered "
                  f"text {in_text}", flush=True)
    finally:
        jax.default_backend = real


if __name__ == "__main__":
    main(donate="--undonated" not in sys.argv)
