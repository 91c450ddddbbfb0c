"""How the tolerances of ``granite_plain`` were set.  Run by hand on the
chip:

    chiprun -- python3 -m chipbench.reference.probe_granite [--variant] [seed ...]

At the published widths of ``granite4h-l10-local``'s configuration, on
one seeded sequence of 4096 a seed: the system's loss and gradient (the
program's own model by the cell's launch config, Mosaic kernels, float32
in memory, the precisions ``models/transformer.py`` states) against
``granite_plain`` at full float32 precision, one JSON line with the loss
gap, the gradient's relative gap and the block's counters at the seeded
weights.  ``--variant`` adds what the tolerances have to refuse, a line
each, every one of which has to come out as not correct by at least one
limit:

- **computations at a lower precision than the file states**: the
  system with the scan's log-decays summed in bf16 (``ops/ssd_scan.py``
  ``SUM_DTYPE`` lowered for that one build), the system with the state
  rounded to bf16 where a chunk hands it to the next (the carry at the
  products' default precision: ``_Group.carried`` wrapped for that one
  build), and the reference's own arithmetic with parameters and
  activations held in bf16 (the nearest precision below the
  configuration's);
- **a multiplier read wrong**, the system built from the configuration
  with that one key changed: ``attention_multiplier`` as 1/8 (the
  softmax scale a block without the key would use), and
  ``residual_multiplier``, ``embedding_multiplier`` and
  ``logits_scaling`` as 1;
- **the head untied**: the reference's own gradient with the head's
  part of the table's gradient left out, which is what a program whose
  head were a leaf of its own, outside the table, would hand over for
  the table (the program has no such build to lower: the tied leaf is
  the only one it has).

``--tiny`` rehearses the script on the CPU at the configuration's small
size (no number of it is a device number).
"""

from __future__ import annotations

import json
import sys

CELL = "granite4h-l10-local"
#: a multiplier read wrong: the configuration's key and the wrong value
MISREAD = (("attention_multiplier", 0.125), ("residual_multiplier", 1.0),
           ("embedding_multiplier", 1.0), ("logits_scaling", 1.0))


def main(seeds, tiny: bool = False, variants: bool = False) -> None:
    import jax
    import jax.numpy as jnp

    from chipbench import compare, run as runner, spec as spec_mod
    from chipbench.traffic.packed_bytes import packed_batch
    from mpit_tpu.ops import ssd_scan
    from mpit_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()  # the seeds share every program
    cell = spec_mod.load_cell(CELL)
    if tiny:
        cell.config.update(cell.config["tiny"])
        cell.traffic["launcher"].update(lm_use_flash=0)
    config, ref = dict(cell.config), cell.reference()
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

    def sums_in_bf16():
        kept, ssd_scan.SUM_DTYPE = ssd_scan.SUM_DTYPE, jnp.bfloat16
        return lambda: setattr(ssd_scan, "SUM_DTYPE", kept)

    def carry_in_bf16():
        """The state a chunk hands on rounded to bf16 (the XLA form: its
        carry's product at the default precision); the kernels sit in
        ``jit``s of their own, which keep their first trace of a shape."""
        kept = (ssd_scan._Group.carried, ssd_scan.CARRY_PRECISION)

        def carried(self, *args):
            return kept[0](self, *args).astype(jnp.bfloat16).astype(
                jnp.float32)

        ssd_scan._Group.carried = carried
        ssd_scan.CARRY_PRECISION = jax.lax.Precision.DEFAULT
        jax.clear_caches()

        def restore():
            ssd_scan._Group.carried, ssd_scan.CARRY_PRECISION = kept
            jax.clear_caches()
        return restore

    def misread(key, value):
        cell.config[key] = value
        return lambda: cell.config.update({key: config[key]})

    lowered = [("system, the scan's log-decays summed in bf16",
                sums_in_bf16),
               ("system, the state rounded to bf16 between chunks",
                carry_in_bf16)] + [
        (f"system, {key} read as {value}",
         lambda key=key, value=value: misread(key, value))
        for key, value in MISREAD]

    def flat_grad(loss_of, unravel, flat, tok, cast=lambda p: p):
        nll, grads = jax.value_and_grad(loss_of)(
            jax.tree_util.tree_map(cast, unravel(flat)), tok)
        return nll.astype(jnp.float32), jnp.concatenate(
            [leaf.reshape(-1).astype(jnp.float32)
             for leaf in jax.tree_util.tree_leaves(grads)])

    def plain(params, tok):
        return ref.loss(params, tok, config)

    def untied(params, tok):
        """The table's gradient from the look-up alone: the head reads
        the table's value and hands nothing back."""
        return ref.loss(params, tok, config,
                        head=jax.lax.stop_gradient(params["embed"]))

    for seed in seeds:
        model = runner.build_model(cell, seed)
        w0, unravel = model.flat.w0, model.flat.unravel
        del model
        tokens = jnp.asarray(packed_batch(seed + 1_000_003, 0, 1, seq))
        ref_loss, ref_grad = ref.loss_and_grad_flat(w0, unravel, tokens,
                                                    config)
        system(seed, "system", w0, tokens, ref_loss, ref_grad)
        for what, lower in lowered if variants else ():
            restore = lower()
            try:
                system(seed, what, w0, tokens, ref_loss, ref_grad)
            finally:
                restore()
        if variants:
            low_loss, low_grad = jax.jit(
                lambda flat, tok: flat_grad(
                    plain, unravel, flat, tok,
                    lambda p: p.astype(jnp.bfloat16)))(w0, tokens)
            say("reference, parameters and activations in bf16", seed,
                low_loss, low_grad, ref_loss, ref_grad)
            del low_grad
            with jax.default_matmul_precision("highest"):
                one_loss, one_grad = jax.jit(
                    lambda flat, tok: flat_grad(untied, unravel, flat,
                                                tok))(w0, tokens)
            say("reference, the head's part of the table's gradient left "
                "out (the head untied)", seed, one_loss, one_grad, ref_loss,
                ref_grad)
            del one_grad
        del ref_grad
        stats = jax.devices()[0].memory_stats() or {}
        print(json.dumps({"seed": seed, "peak_bytes_in_use":
                          int(stats.get("peak_bytes_in_use", 0))}), flush=True)


if __name__ == "__main__":
    main([int(a) for a in sys.argv[1:] if not a.startswith("--")] or [1, 2, 3],
         tiny="--tiny" in sys.argv, variants="--variant" in sys.argv)
