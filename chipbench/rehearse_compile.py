"""Compiles each cell's worker step at its real shapes for the described
chip (``v5e:2x2``, one device) without the chip, and prints the
compiler's ``memory_analysis()`` and the count of ``tpu_custom_call``s.
It is what fixes the batch before chip time is spent:

    JAX_PLATFORMS=cpu python3 -m chipbench.rehearse_compile [--batch=N] [cell ...]

Run by hand, not by the tests (it loads the TPU's compiler, which one
process at a time may do).  A compile that passes is not a chip run: it
says that the program fits and that the kernels are Mosaic's, nothing
about time.  The memory it prints is one program's; the process keeps
beside it the flat vector, the seeded initial vector the model object
holds and, where ``su`` > 1, the accumulator, which the line
``resident beside the program`` adds.

The step is the one the worker runs: ``jit(value_and_grad(loss))`` under
a parameter server (``optim/shells.py`` ``RuleShell``), the whole
``msgd_step`` in a local cell (``optim/msgd.py``).  The program asks
``jax.default_backend()`` whether to use the compiled kernels; here that
says ``cpu``, so this script answers ``tpu`` for it while it lowers (the
on-chip-measurement guide, section 2: steer such code from the script,
not through an option of the program).
"""

from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def compile_cell(name: str, batch_override: int = 0) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import flops, run as runner, spec as spec_mod
    from mpit_tpu.models.transformer import default_attn
    from mpit_tpu.optim.msgd import MSGDConfig, msgd_step

    cell = spec_mod.load_cell(name)
    mix = cell.traffic
    batch = batch_override or int(mix["batch"])
    seq = int(runner.launch_config(cell, 1).lm_seq)
    # the model from the program's own builder, by the cell's launch
    # config, with the reference attention (its initialisation runs the
    # model, here on the CPU; shapes do not depend on the attention,
    # lm_layout does the same), then the same loss over that module with
    # the Mosaic-pinned attention in its place
    shapes = runner.build_model(cell, seed=1, lm_use_flash=0)
    module = shapes.module.clone(
        attn_fn=default_attn(causal=True, use_flash=True, interpret=False))

    def loss(w, tokens):
        logp = module.apply({"params": shapes.flat.unravel(w)}, tokens[:, :-1])
        nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return jnp.mean(nll)

    vgf = jax.value_and_grad(loss)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    n = int(shapes.flat.size)
    w = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=chip)
    tokens = jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32, sharding=chip)
    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"  # see the module's docstring
    try:
        t0 = time.monotonic()
        if mix["launcher"]["opt"] in ("sgd", "msgd"):
            mcfg = MSGDConfig(lr=mix["lr"], mom=mix["launcher"].get("mom", 0.0))
            state = {"k": jax.ShapeDtypeStruct((), jnp.int32, sharding=chip),
                     "vt": w}
            lowered = jax.jit(
                lambda w, state, tok: msgd_step(vgf, w, state, mcfg, tok)
            ).lower(w, state, tokens)
            resident = 2  # the vector's twin in the model object, and...
            what = "msgd_step (lookahead, forward+backward, fused commit)"
        else:
            lowered = jax.jit(vgf).lower(w, tokens)
            resident = 2 + (1 if int(mix["su"]) > 1 else 0)
            what = "value_and_grad(loss)"
        compiled = lowered.compile()
        took = time.monotonic() - t0
    finally:
        jax.default_backend = real_backend
    mem = compiled.memory_analysis()
    calls = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    beside = (resident - 1) * n * 4
    print(f"{name}: {what} at batch {batch} x {seq}, {n} parameters "
          f"({n * 4 / 1e6:.1f} MB), compiled for {topo.devices[0].device_kind}"
          f" in {took:.1f} s (no chip; not a chip run)")
    print(f"  memory_analysis: arguments {mem.argument_size_in_bytes / 1e9:.3f}"
          f" GB, outputs {mem.output_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB, aliased "
          f"{mem.alias_size_in_bytes / 1e9:.3f} GB: program total "
          f"{total / 1e9:.3f} GB")
    print(f"  resident beside the program: {beside / 1e9:.3f} GB; together "
          f"{(total + beside) / 1e9:.3f} GB of "
          f"{flops.load_peaks('TPU v5 lite')['hbm_bytes'] / 1e9:.1f} GB")
    least = {family: kernel["least_calls"] for family, kernel in
             cell.arithmetic().kernels(cell.config, batch).items()}
    print(f"  tpu_custom_call in the compiled step: {calls} (the "
          f"configuration's kernel families need at least {least})")


def main(argv) -> int:
    from chipbench import spec as spec_mod

    batch = next((int(a[8:]) for a in argv if a.startswith("--batch=")), 0)
    names = ([a for a in argv if not a.startswith("--")]
             or [w["name"] for w in spec_mod.load_bench()["workloads"]])
    for name in names:
        compile_cell(name, batch)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
