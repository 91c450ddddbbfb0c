"""How the tolerances of ``lfm2_plain`` were set.  Run by hand on the
chip:

    chiprun -- python3 -m chipbench.reference.probe_lfm2 [seed ...]

At the published widths of ``lfm2-l5e8-local``'s configuration, on one
seeded sequence of 8192 a seed: the system's loss and gradient (the
program's own model by the cell's launch config, Mosaic kernels, float32
in memory, the precisions ``models/transformer.py`` states) against
``lfm2_plain`` at full float32 precision, and beside it what
the tolerances have to refuse, each the reference itself with one thing
wrong: the convolution's taps reversed, the convolution one position
late, the gates ``B`` and ``C`` exchanged, the selection bias leaking
into the weights, the query/key norm over the whole projection in place
of a head, the query heads on the wrong KV head (``g % 8`` for ``g //
4``; fewer KV heads than the file says do not fit the matrices' shapes
at all), and the reference's own
arithmetic with parameters and activations held in bf16 (the nearest
precision below the configuration's).  One JSON line each, with the
routing's three counters at the seeded weights.  ``--tiny`` rehearses
the script on the CPU at the configuration's small size (no number of
it is a device number); ``--two`` keeps the two readings a limit is set
between, the system and the bf16 reference, for more seeds at a fraction
of the time.  (Whether the attention path needs more than one bf16 pass,
as the OLMoE block's does, was read with an earlier state of this script
and the block: it does not, PERF.md section 6, PR 32.)
"""

from __future__ import annotations

import json
import sys

CELL = "lfm2-l5e8-local"


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

    short_conv, rms_norm = ref.short_conv, ref.rms_norm

    def exchanged(h, p):
        d = h.shape[-1]
        bcz = h @ p["conv_in"]
        c_gate, b_gate, z = bcz[..., :d], bcz[..., d:2 * d], bcz[..., 2 * d:]
        return (c_gate * ref.short_conv(b_gate * z, p["conv_taps"])) \
            @ p["conv_out"]

    def leaking(h, router, bias, top_k, normalise, scale):
        scores = jax.nn.sigmoid(h @ router) + bias   # bias in the weights
        _, chosen = jax.lax.top_k(scores, top_k)
        gates = jnp.zeros_like(scores).at[
            jnp.arange(scores.shape[0])[:, None], chosen].set(
                jnp.take_along_axis(scores, chosen, axis=-1))
        if normalise:
            gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-6)
        return gates * scale

    def whole_norm(x, weight, eps):
        """RMSNorm over all heads at once where the weight is a head's."""
        if x.ndim == 4 and weight.shape[0] == x.shape[-1] \
                and weight.shape[0] != config["hidden_size"]:
            mean = jnp.mean(x * x, axis=(1, 3), keepdims=True)
            return x / jnp.sqrt(mean + eps) * weight
        return rms_norm(x, weight, eps)

    def wrong_kv_head(h, p, n_head, n_kv, eps, rope):
        """Query head g on KV head g % n_kv in place of g // group."""
        b, seq, d = h.shape
        head = d // n_head

        def split(x, count):
            return x.reshape(b, seq, count, head).transpose(0, 2, 1, 3)

        q = ref.rms_norm(split(h @ p["wq"], n_head), p["q_norm"], eps)
        k = ref.rms_norm(split(h @ p["wk"], n_kv), p["k_norm"], eps)
        v = split(h @ p["wv"], n_kv)
        cos, sin = ref.rotary_table(seq, head, rope)
        q, k = ref.rotate(q, cos, sin), ref.rotate(k, cos, sin)
        mask = jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None]
        out = jnp.concatenate(
            [ref._heads(q[:, g:g + 1], k[:, g % n_kv], v[:, g % n_kv], mask)
             for g in range(n_head)], axis=1)
        return out.transpose(0, 2, 1, 3).reshape(b, seq, -1) @ p["wo"]

    variants = (
        ("reference, taps reversed",
         {"short_conv": lambda u, taps: short_conv(u, taps[::-1])}),
        ("reference, convolution one position late",
         {"short_conv": lambda u, taps: ref.shifted(short_conv(u, taps), 1)}),
        ("reference, gates B and C exchanged", {"gated_conv": exchanged}),
        ("reference, bias leaks into the weights", {"router_gates": leaking}),
        ("reference, q/k norm over the whole projection",
         {"rms_norm": whole_norm}),
        ("reference, query heads on the wrong KV head",
         {"attention": wrong_kv_head}),
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
