"""How the tolerances of ``mellum_plain`` were set.  Run by hand on the
chip:

    chiprun -- python3 -m chipbench.reference.probe_mellum [seed ...]

At the published widths of ``mellum2-l4e8-local``'s configuration, on
one seeded sequence of 8192 a seed: the system's loss and gradient (the
program's own model by the cell's launch config, Mosaic kernels, float32
in memory, the precisions ``models/transformer.py`` states) against
``mellum_plain`` at full float32 precision, and beside it what the
tolerances have to refuse, each the reference itself with one thing
wrong: a window one key too long (``<= 1024`` for ``< 1024``), the top-8
not renormalised, YaRN's ``attention_factor`` left out, and the
reference's own arithmetic with parameters and activations held in bf16
(the nearest precision below the configuration's).  One JSON line each,
with the routing's two counters at the seeded weights.  ``--tiny``
rehearses the script on the CPU at the configuration's small size (no
number of it is a device number); ``--two`` keeps the two readings a
limit is set between, the system and the bf16 reference, for more seeds
at a fraction of the time.  (Whether the attention path needs more
than one bf16 pass, as the OLMoE block's does, was read with an earlier
state of the block: PERF.md section 6, PR 30.)
"""

from __future__ import annotations

import json
import sys

CELL = "mellum2-l4e8-local"


def main(seeds, tiny: bool = False, two: bool = False) -> None:
    import jax
    import jax.numpy as jnp

    from chipbench import compare, run as runner, spec as spec_mod
    from chipbench.traffic.packed_bytes import packed_batch
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

    def wrong(w0, unravel, tokens, changed=None, **replaced):
        """The reference with functions replaced or keys changed."""
        kept = {name: getattr(ref, name) for name in replaced}
        for name, fn in replaced.items():
            setattr(ref, name, fn)
        try:
            return ref.loss_and_grad_flat(w0, unravel, tokens,
                                          {**config, **(changed or {})})
        finally:
            for name, fn in kept.items():
                setattr(ref, name, fn)

    def one_more_key(n, window):
        t, j = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
        mask = j <= t
        return mask & (t - j <= window) if window else mask

    full = config["rope_parameters"]["full_attention"]
    no_factor = {"rope_parameters": {
        **config["rope_parameters"],
        "full_attention": {**full, "attention_factor": 1.0}}}

    for seed in seeds:
        model = runner.build_model(cell, seed)
        w0, unravel = model.flat.w0, model.flat.unravel
        tokens = jnp.asarray(packed_batch(seed + 1_000_003, 0, 1, seq))
        ref_loss, ref_grad = ref.loss_and_grad_flat(w0, unravel, tokens,
                                                    config)
        (sys_loss, stats), sys_grad = jax.jit(model.value_grad_stats)(
            w0, tokens)
        say("system", seed, sys_loss, sys_grad, ref_loss, ref_grad,
            **{name: [round(float(x), 4) for x in value]
               for name, value in stats.items()})
        del sys_grad
        for what, kw in () if two else (
                ("reference, window one key too long",
                 {"visible": one_more_key}),
                ("reference, top-8 not renormalised",
                 {"changed": {"norm_topk_prob": False}}),
                ("reference, no attention_factor", {"changed": no_factor})):
            bad = wrong(w0, unravel, tokens, **kw)
            say(what, seed, *bad, ref_loss, ref_grad)
            del bad
        low_loss, low_grad = jax.jit(jax.value_and_grad(
            lambda flat, tok: ref.loss(
                jax.tree_util.tree_map(lambda p: p.astype(jnp.bfloat16),
                                       unravel(flat)), tok, config)
        ))(w0, tokens)
        say("reference, parameters and activations in bf16", seed,
            low_loss.astype(jnp.float32), low_grad.astype(jnp.float32),
            ref_loss, ref_grad)
        del low_grad, ref_grad
        stats = jax.devices()[0].memory_stats() or {}
        print(json.dumps({"seed": seed, "peak_bytes_in_use":
                          int(stats.get("peak_bytes_in_use", 0))}), flush=True)


if __name__ == "__main__":
    main([int(a) for a in sys.argv[1:] if not a.startswith("--")] or [1, 2, 3],
         tiny="--tiny" in sys.argv, two="--two" in sys.argv)
