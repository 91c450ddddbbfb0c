"""How the tolerances of ``joyai_plain`` were set.  Run by hand on the
chip:

    chiprun -- python3 -m chipbench.reference.probe_joyai [seed ...]

At the published widths of ``joyai-l5e8-local``'s configuration, on one
seeded sequence of 8192 a seed: the system's loss and gradient (the
program's own model by the cell's launch config, Mosaic kernels at the
two head widths, float32 in memory, the precisions
``models/transformer.py`` states) against ``joyai_plain`` at full
float32 precision, and beside it what the tolerances have to refuse.
**A product at a lower precision than the file states**: the system with
the router's product at one bf16 pass (``ROUTER_PRECISION`` lowered for
that one build: the choice of the eight flips where two scores are
close), and the reference's own arithmetic with parameters and
activations held in bf16 (the nearest precision below the
configuration's).  **The reference with one thing wrong**: the rotary
pairs taken as halves and not interleaved, the scores divided by the
root of the part without positions (128) and not of the whole key (192),
the rotary key a head of its own for every query head's index (the one
shared head's rotation applied at a shifted position), the shared expert
left out, the selection bias leaking into the weights, the MTP
projection with the hidden state first, the MTP head's target the next
token and not the one after.  One JSON line each, the system's with the
block's counters at the seeded weights (both heads' NLL and the
routing's four, one entry a sparse layer, the MTP module's last).
``--tiny`` rehearses the script on the CPU at the configuration's small
size (no number of it is a device number); ``--two`` keeps the readings
a limit is set between, the system and the two lowered precisions, for
more seeds at a fraction of the time.
"""

from __future__ import annotations

import json
import math
import sys

CELL = "joyai-l5e8-local"


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
    eps = float(config["rms_norm_eps"])

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

    rotate_pairs, heads, layer = ref.rotate_pairs, ref._heads, ref._layer

    def halves(x, angle):
        """Rotary over ``(x_j, x_{j + width/2})``, not ``(2j, 2j + 1)``."""
        half = x.shape[-1] // 2
        a, b = x[..., :half], x[..., half:]
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                               axis=-1)

    def nope_scale(q, k, v, mask):
        """Scores over the root of the part without positions alone."""
        nope = int(config["qk_nope_head_dim"])
        return heads(q * math.sqrt(q.shape[-1] / nope), k, v, mask)

    def late_key(x, angle):
        """The one-head rotary key turned as if one position later."""
        if x.shape[1] == 1:
            angle = angle + angle[1:2]
        return rotate_pairs(x, angle)

    def leaking(h, router, bias, top_k, normalise, scale):
        scores = jax.nn.sigmoid(h @ router) + bias   # bias in the weights
        _, chosen = jax.lax.top_k(scores, top_k)
        gates = jnp.zeros_like(scores).at[
            jnp.arange(scores.shape[0])[:, None], chosen].set(
                jnp.take_along_axis(scores, chosen, axis=-1))
        if normalise:
            gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
        return gates * scale

    def hidden_first(params, x_last, next_tokens, config):
        pair = jnp.concatenate(
            [ref.rms_norm(x_last, params["mtp_hidden_norm"], eps),
             ref.rms_norm(params["embed"][next_tokens],
                          params["mtp_embed_norm"], eps)], axis=-1)
        return layer(pair @ params["mtp_proj"], params["mtp_block"], False,
                     config)

    def next_token_mtp(params, tokens, config):
        """The MTP head scored on ``t_{i+1}``, the main head's target."""
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        x_last = ref.stack(params, inputs, config)
        main = jnp.mean(ref.head_nll(x_last, params["final_norm"],
                                     params["head"], targets, eps))
        z = ref.mtp_hidden(params, x_last, targets, config)
        mtp = jnp.mean(ref.head_nll(z[:, :-1], params["mtp_final_norm"],
                                    params["head"], targets[:, :-1], eps))
        return main, mtp

    variants = (
        ("reference, rotary pairs as halves", {"rotate_pairs": halves}),
        ("reference, scores over sqrt(128)", {"_heads": nope_scale}),
        ("reference, the shared rotary key one position late",
         {"rotate_pairs": late_key}),
        ("reference, no shared expert",
         {"shared_expert": lambda h, p: jnp.zeros_like(h)}),
        ("reference, bias leaks into the weights", {"router_gates": leaking}),
        ("reference, MTP projection with the hidden state first",
         {"mtp_hidden": hidden_first}),
        ("reference, MTP head's target the next token",
         {"losses": next_token_mtp}),
    )

    def system(seed, what, w0, tokens, ref_loss, ref_grad):
        model = runner.build_model(cell, seed)
        model.flat.w0 = None  # the caller's is the one vector kept
        (sys_loss, stats), sys_grad = jax.jit(model.value_grad_stats)(
            w0, tokens)
        say(what, seed, sys_loss, sys_grad, ref_loss, ref_grad,
            **{name: [round(float(x), 4) for x in value]
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
        for what, kw in () if two else variants:
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
