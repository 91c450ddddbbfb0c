"""How the tolerances of ``ouro_plain`` were set.  Run by hand on the
chip:

    chiprun -- python3 -m chipbench.reference.probe_ouro [seed ...]

At the published widths of ``ouro-l6-local``'s configuration, on one
seeded sequence of 4096 a seed: the system's loss and gradient (the
program's own model by the cell's launch config, Mosaic kernels, float32
in memory, one bf16 pass a product) against ``ouro_plain`` at full
float32 precision at the weights the chip's products see (``bf16(W)``:
``ouro_plain.as_the_products_see``), and beside it what the tolerances
have to refuse:
the reference's own arithmetic with parameters and activations held in
bf16 (the nearest precision below the configuration's), and the
reference itself with one thing wrong: no norm on the sublayers'
outputs, the next pass fed the stream before the final norm, one pass
fewer, no entropy term, rotary pairs interleaved, attention one key into
the future.  One JSON line each, the system's with the loop's three
counters at the seeded weights.  ``--tiny`` rehearses the script on the
CPU at the configuration's small size (no number of it is a device
number); ``--two`` keeps the two readings a limit is set between, the
system and the bf16 reference, for more seeds at a fraction of the time;
``--exact`` adds the reference at the float32 weights themselves against
the reference at ``bf16(W)``: the share of an error that is the choice
of the point and not arithmetic.
"""

from __future__ import annotations

import json
import math
import sys

CELL = "ouro-l6-local"


def main(seeds, tiny: bool = False, two: bool = False,
         exact: bool = False) -> None:
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

    pass_end, rms = ref.pass_end, ref.rms

    def no_out_norms(u, p, c):
        eps = float(c["rms_norm_eps"])
        u = u + ref.attention(rms(u, p["attn_norm"], eps), p,
                              int(c["num_attention_heads"]),
                              int(c["head_dim"]), float(c["rope_theta"]))
        b = rms(u, p["mlp_norm"], eps)
        return u + (jax.nn.silu(b @ p["w_gate"]) * (b @ p["w_up"])) \
            @ p["w_down"]

    def unnormed_carry(u, params, targets, eps):
        _h, nll, lam = pass_end(u, params, targets, eps)
        return u, nll, lam

    def interleaved(x, cos, sin):
        half = x.shape[-1] // 2
        c, s = cos[..., :half], sin[..., :half]
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * c - b * s, b * c + a * s],
                         axis=-1).reshape(x.shape)

    @jax.checkpoint
    def sees_the_future(q, k, v, first):
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
        at = first + jnp.arange(q.shape[2]) + 1
        seen = jnp.arange(k.shape[2])[None, :] <= at[:, None]
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(
            jnp.where(seen, scores, -jnp.inf), axis=-1), v)

    variants = (
        ("reference, no norm on the sublayers' outputs",
         {"layer": no_out_norms}),
        ("reference, the next pass fed the stream before the final norm",
         {"pass_end": unnormed_carry}),
        ("reference, one pass fewer",
         {"changed": {"total_ut_steps": int(config["total_ut_steps"]) - 1}}),
        ("reference, no entropy term",
         {"changed": {"exit_entropy_beta": 0.0}}),
        ("reference, rotary pairs interleaved", {"rotate": interleaved}),
        ("reference, attention one key into the future",
         {"_rows": sees_the_future}),
    )

    def system(seed, w0, tokens, ref_loss, ref_grad):
        model = runner.build_model(cell, seed)
        model.flat.w0 = None  # the caller's is the one vector kept
        (sys_loss, stats), sys_grad = jax.jit(model.value_grad_stats)(
            w0, tokens)
        say("system", seed, sys_loss, sys_grad, ref_loss, ref_grad,
            **{name: round(float(value), 5) for name, value in stats.items()})

    for seed in seeds:
        model = runner.build_model(cell, seed)
        w0, unravel = model.flat.w0, model.flat.unravel
        del model
        tokens = jnp.asarray(packed_batch(seed + 1_000_003, 0, 1, seq))
        ref_loss, ref_grad = ref.loss_and_grad_flat(w0, unravel, tokens,
                                                    config)
        system(seed, w0, tokens, ref_loss, ref_grad)
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
        del low_grad
        if exact:
            with jax.default_matmul_precision("highest"):
                at_f32 = jax.jit(jax.value_and_grad(
                    lambda flat, tok: ref.loss(unravel(flat), tok, config))
                )(w0, tokens)
            say("reference, at the float32 weights (no product rounds them)",
                seed, *at_f32, ref_loss, ref_grad)
            del at_f32
        del ref_grad
        stats = jax.devices()[0].memory_stats() or {}
        print(json.dumps({"seed": seed, "peak_bytes_in_use":
                          int(stats.get("peak_bytes_in_use", 0))}), flush=True)


if __name__ == "__main__":
    main([int(a) for a in sys.argv[1:] if not a.startswith("--")] or [1, 2, 3],
         tiny="--tiny" in sys.argv, two="--two" in sys.argv,
         exact="--exact" in sys.argv)
