"""How the tolerances of ``sdar_plain`` were set.  Run by hand on the
chip:

    chiprun -- python3 -m chipbench.reference.probe_sdar [seed ...]

At the published widths of ``sdar-l6e8-local``'s configuration, on one
seeded sequence of 4096 a seed (8192 rows a layer): the system's loss
and gradient (the program's own model by the cell's launch config, the
noise made on the device, Mosaic kernels that walk the block-diffusion
mask's live tiles, float32 in memory, the precisions
``models/transformer.py`` states) against ``sdar_plain`` at full float32
precision, and beside it what the tolerances have to refuse.  **A
product at a lower precision than the file states**: the system with
the router's product at one bf16 pass (``ROUTER_PRECISION`` lowered for
that one build); the reference with its attention's softmax in bf16;
the reference with its logits, log-softmax and loss in bf16; and the
reference's own arithmetic with parameters and activations held in bf16
(the nearest precision below the configuration's).  **The reference
with one thing wrong**: a noised block that sees its own clean copy
(``<=`` for ``<``); plain causal attention over the 2 L rows; the loss
without its ``1 / c`` (every masked position weighted alike); the
targets shifted by one (the next-token objective).  One JSON line each,
the system's with the block's counters at the seeded weights.

``--tiny`` rehearses the script on the CPU at the configuration's small
size (no number of it is a device number); ``--two`` keeps the readings
a limit of ``correct`` is set between.
"""

from __future__ import annotations

import json
import math
import sys

CELL = "sdar-l6e8-local"


def main(seeds, tiny: bool = False, two: bool = False) -> None:
    import jax
    import jax.numpy as jnp

    from chipbench import compare, run as runner, spec as spec_mod
    from chipbench.traffic.packed_bytes import packed_batch
    from mpit_tpu.models import transformer
    from mpit_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()  # the seeds share every program
    cell = spec_mod.load_cell(CELL)
    if tiny:
        cell.config.update(cell.config["tiny"])
        cell.traffic["launcher"].update(lm_use_flash=0)
    config, ref = cell.config, cell.reference()
    seq = int(runner.launch_config(cell, 0).lm_seq)
    block = int(config["block_length"])

    def say(what, seed, sys_loss, sys_grad, ref_loss, ref_grad, **more):
        print(json.dumps({
            "what": what, "seed": seed,
            "device": jax.devices()[0].device_kind,
            **compare.compare(sys_loss, sys_grad, ref_loss, ref_grad, ref),
            **more}), flush=True)

    def wrong(w0, unravel, tokens, **replaced):
        """The reference with functions replaced."""
        kept = {name: getattr(ref, name) for name in replaced}
        for name, fn in replaced.items():
            setattr(ref, name, fn)
        try:
            return ref.loss_and_grad_flat(w0, unravel, tokens, config)
        finally:
            for name, fn in kept.items():
                setattr(ref, name, fn)

    def sees_own_clean_copy(n, b):
        r, c = jnp.arange(2 * n)[:, None], jnp.arange(2 * n)[None, :]
        own = (c < n) & (c // b == r // b)
        past = (c >= n) & ((c - n) // b <= r // b)
        clean = (c >= n) & ((c - n) // b <= (r - n) // b)
        return jnp.where(r < n, own | past, clean)

    def causal_rows(n, b):
        r = jnp.arange(2 * n)
        return r[None, :] <= r[:, None]

    @jax.checkpoint
    def bf16_softmax(q, k, v, mask):
        scores = jnp.einsum("bhqd,bkd->bhqk", q, k) / math.sqrt(q.shape[-1])
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores.astype(jnp.bfloat16), axis=-1)
        return jnp.einsum("bhqk,bkd->bhqd", p.astype(jnp.float32), v)

    def loss_with(ids, masked, weight, logp, targets):
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        per_row = jnp.sum(jnp.where(masked, nll * weight, 0.0), axis=1)
        return (jnp.mean(per_row) / (ids.shape[1] // block)).astype(
            jnp.float32)

    def noised_logits(params, ids, masked):
        return ref.logits(params, jnp.where(
            masked, int(config["mask_token_id"]), ids), ids, config)

    def bf16_loss(params, ids, masked, count, config):
        logp = jax.nn.log_softmax(
            noised_logits(params, ids, masked).astype(jnp.bfloat16), axis=-1)
        return loss_with(ids, masked, (1.0 / count).astype(jnp.bfloat16),
                         logp, ids)

    def unweighted(params, ids, masked, count, config):
        logp = jax.nn.log_softmax(noised_logits(params, ids, masked), axis=-1)
        return loss_with(ids, masked, 2.0 / (block + 1.0), logp, ids)

    def shifted(params, ids, masked, count, config):
        logp = jax.nn.log_softmax(noised_logits(params, ids, masked), axis=-1)
        return loss_with(ids, masked, 1.0 / count, logp,
                         jnp.roll(ids, -1, axis=1))

    lowered = (
        ("reference, the attention's softmax in bf16",
         {"_heads": bf16_softmax}),
        ("reference, logits, log-softmax and loss in bf16",
         {"loss": bf16_loss}),
    )
    faults = (
        ("reference, a noised block sees its own clean copy",
         {"visible": sees_own_clean_copy}),
        ("reference, plain causal attention over the 2 L rows",
         {"visible": causal_rows}),
        ("reference, the loss without its 1 / c", {"loss": unweighted}),
        ("reference, the targets shifted by one", {"loss": shifted}),
    )

    def system(seed, what, w0, tokens, ref_loss, ref_grad):
        model = runner.build_model(cell, seed)
        model.flat.w0 = None  # the caller's is the one vector kept
        (sys_loss, stats), sys_grad = jax.jit(model.value_grad_stats)(
            w0, tokens)
        say(what, seed, sys_loss, sys_grad, ref_loss, ref_grad,
            **{name: [round(float(x), 4) for x in jnp.ravel(value)]
               for name, value in stats.items()})

    for seed in seeds:
        model = runner.build_model(cell, seed)
        w0, unravel = model.flat.w0, model.flat.unravel
        del model
        tokens = jnp.asarray(packed_batch(seed + 1_000_003, 0, 1, seq))
        ref_loss, ref_grad = ref.loss_and_grad_flat(w0, unravel, tokens,
                                                    config)
        system(seed, "system", w0, tokens, ref_loss, ref_grad)
        stated = transformer.ROUTER_PRECISION
        transformer.ROUTER_PRECISION = jax.lax.Precision.DEFAULT
        try:
            system(seed, "system, the router's product at one bf16 pass",
                   w0, tokens, ref_loss, ref_grad)
        finally:
            transformer.ROUTER_PRECISION = stated
        for what, kw in lowered + (() if two else faults):
            bad = wrong(w0, unravel, tokens, **kw)
            say(what, seed, *bad, ref_loss, ref_grad)
            del bad
        ids = tokens[:, :-1]
        masked, count = ref.noise(ids, int(config["noise_seed"]), block)
        low_loss, low_grad = jax.jit(jax.value_and_grad(
            lambda flat, ids, masked, count: ref.loss(
                jax.tree_util.tree_map(lambda p: p.astype(jnp.bfloat16),
                                       unravel(flat)), ids, masked, count,
                config)))(w0, ids, jnp.asarray(masked), jnp.asarray(count))
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
