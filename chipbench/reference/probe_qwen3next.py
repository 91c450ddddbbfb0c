"""How the tolerances of ``qwen3next_plain`` were set.  Run by hand on
the chip:

    chiprun -- python3 -m chipbench.reference.probe_qwen3next [--variant] [seed ...]

At the published widths of ``qwen3next-l4e32-local``'s configuration, on
one seeded sequence of 8192 a seed: the system's loss and gradient (the
program's own model by the cell's launch config, Mosaic kernels, float32
in memory, the precisions ``models/transformer.py`` states) against
``qwen3next_plain`` at full float32 precision, one JSON line with the
loss gap, the gradient's relative gap and the block's counters at the
seeded weights.  ``--variant`` adds what the tolerances have to refuse,
**a computation at a lower precision than the file states**, a line
each: the system with the scan's summed log-decays held in bf16
(``ops/delta_rule.py`` ``GDN_SUM_DTYPE`` lowered for that one build: a
sum of up to 64 log-decays then carries three digits, and every decay
inside a chunk is the ``exp`` of a difference of two such sums), the system with the router's product at one bf16 pass
(``ROUTER_PRECISION`` lowered: membership of the ten flips where two
of 512 probabilities are close), and
the reference's own arithmetic with parameters and activations held in
bf16 (the nearest precision below the configuration's).  Each of the
three has to come out as not correct by the gradient's limit.

``--tiny`` rehearses the script on the CPU at the configuration's small
size (no number of it is a device number).
"""

from __future__ import annotations

import json
import sys

CELL = "qwen3next-l4e32-local"


def main(seeds, tiny: bool = False, variants: bool = False) -> None:
    import jax
    import jax.numpy as jnp

    from chipbench import compare, run as runner, spec as spec_mod
    from chipbench.traffic.packed_bytes import packed_batch
    from mpit_tpu.models import transformer
    from mpit_tpu.ops import delta_rule
    from mpit_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()  # the seeds share every program
    cell = spec_mod.load_cell(CELL)
    if tiny:
        cell.config.update(cell.config["tiny"])
        cell.traffic["launcher"].update(lm_use_flash=0)
    config, ref = cell.config, cell.reference()
    seq = int(runner.launch_config(cell, 0).lm_seq)

    def say(what, seed, sys_loss, sys_grad, ref_loss, ref_grad, **more):
        print(json.dumps({
            "what": what, "seed": seed,
            "device": jax.devices()[0].device_kind,
            **compare.compare(sys_loss, sys_grad, ref_loss, ref_grad, ref),
            **more}), flush=True)

    def system(seed, what, w0, tokens, ref_loss, ref_grad):
        model = runner.build_model(cell, seed)
        model.flat.w0 = None  # the caller's is the one vector kept
        (sys_loss, stats), sys_grad = jax.jit(model.value_grad_stats)(
            w0, tokens)
        say(what, seed, sys_loss, sys_grad, ref_loss, ref_grad,
            **{name: [round(float(x), 4) for x in value]
               for name, value in stats.items()})

    def lowered(module, name, value):
        """``module.name`` at ``value`` for one build of the system."""
        kept = getattr(module, name)
        setattr(module, name, value)
        return lambda: setattr(module, name, kept)

    for seed in seeds:
        model = runner.build_model(cell, seed)
        w0, unravel = model.flat.w0, model.flat.unravel
        del model
        tokens = jnp.asarray(packed_batch(seed + 1_000_003, 0, 1, seq))
        ref_loss, ref_grad = ref.loss_and_grad_flat(w0, unravel, tokens,
                                                    config)
        system(seed, "system", w0, tokens, ref_loss, ref_grad)
        for what, module, name, value in (
                ("system, the scan's log-decays summed in bf16",
                 delta_rule, "GDN_SUM_DTYPE", jnp.bfloat16),
                ("system, the router's product at one bf16 pass",
                 transformer, "ROUTER_PRECISION",
                 jax.lax.Precision.DEFAULT)) if variants else ():
            restore = lowered(module, name, value)
            try:
                system(seed, what, w0, tokens, ref_loss, ref_grad)
            finally:
                restore()
        if variants:
            def low(flat, tok):
                nll, grads = jax.value_and_grad(ref.loss)(
                    jax.tree_util.tree_map(
                        lambda p: p.astype(jnp.bfloat16), unravel(flat)),
                    tok, config)
                return nll.astype(jnp.float32), jnp.concatenate(
                    [leaf.reshape(-1).astype(jnp.float32)
                     for leaf in jax.tree_util.tree_leaves(grads)])

            low_loss, low_grad = jax.jit(low)(w0, tokens)
            say("reference, parameters and activations in bf16", seed,
                low_loss, low_grad, ref_loss, ref_grad)
            del low_grad
        del ref_grad
        stats = jax.devices()[0].memory_stats() or {}
        print(json.dumps({"seed": seed, "peak_bytes_in_use":
                          int(stats.get("peak_bytes_in_use", 0))}), flush=True)


if __name__ == "__main__":
    main([int(a) for a in sys.argv[1:] if not a.startswith("--")] or [1, 2, 3],
         tiny="--tiny" in sys.argv, variants="--variant" in sys.argv)
