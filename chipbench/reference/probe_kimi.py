"""How the tolerances of ``kimi_plain`` were set.  Run by hand on the
chip:

    chiprun -- python3 -m chipbench.reference.probe_kimi [seed ...]

At the published widths of ``kimi-linear-l5e8-local``'s configuration,
on one seeded sequence of 8192 a seed: the system's loss and gradient
(the program's own model by the cell's launch config, the chunked scan,
Mosaic kernels, float32 in memory, the precisions
``models/transformer.py`` and ``ops/delta_rule.py`` state) against
``kimi_plain`` at full float32 precision, and beside it what the
tolerances have to refuse.  **A product at a lower precision than the
file states**: the system with the chunk's triangular solve at one bf16
pass (``ops/delta_rule.py`` ``SOLVE_PRECISION`` lowered for that one
build), the system with the router's product at one bf16 pass, and the
reference's own arithmetic with parameters and activations held in bf16
(the nearest precision below the configuration's).  **The reference
with one thing wrong**: the delta term left out (``S_t = Diag(alpha)
S_{t-1} + beta k v^T``: a gated linear attention), a scalar decay a head
in place of the channel-wise one (the channels' mean log-decay), the
convolution reversed (the taps in the other order), the latent
attention's scores over the root of 128
and not of 192, the shared expert left out.  One JSON line each, the
system's with the block's counters at the seeded weights (the decay's
mean a KDA layer and the routing's four a sparse layer).  ``--tiny``
rehearses the script on the CPU at the configuration's small size (no
number of it is a device number); ``--two`` keeps the readings a limit
is set between, the system and the lowered precisions, for more seeds
at a fraction of the time.
"""

from __future__ import annotations

import json
import math
import sys

CELL = "kimi-linear-l5e8-local"


def main(seeds, tiny: bool = False, two: bool = False) -> None:
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

    heads, conv, decay = ref._heads, ref.causal_conv, ref.log_decay

    def no_delta(state, q, k, v, g, beta):
        """``S_t = Diag(alpha) S_{t-1} + beta k v^T``: nothing seen is
        taken back."""
        state = jnp.exp(g)[..., None] * state + beta[..., None, None] \
            * jnp.einsum("bhk,bhv->bhkv", k, v)
        return state, jnp.einsum("bhk,bhkv->bhv", q, state)

    def scalar_decay(h, p, n_heads):
        """One decay a head: the channels' mean log-decay on all."""
        g = decay(h, p, n_heads)
        return jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)

    def reversed_conv(u, taps):
        return conv(u, taps[::-1])

    def nope_scale(q, k, v, mask):
        """Scores over the root of the part without positions alone."""
        nope = int(config["qk_nope_head_dim"])
        return heads(q * math.sqrt(q.shape[-1] / nope), k, v, mask)

    variants = (
        ("reference, the delta term left out", {"delta_step": no_delta}),
        ("reference, a scalar decay a head", {"log_decay": scalar_decay}),
        ("reference, the convolution reversed",
         {"causal_conv": reversed_conv}),
        ("reference, scores over sqrt(128)", {"_heads": nope_scale}),
        ("reference, no shared expert",
         {"shared_expert": lambda h, p: jnp.zeros_like(h)}),
    )

    def system(seed, what, w0, tokens, ref_loss, ref_grad):
        model = runner.build_model(cell, seed)
        model.flat.w0 = None  # the caller's is the one vector kept
        (sys_loss, stats), sys_grad = jax.jit(model.value_grad_stats)(
            w0, tokens)
        say(what, seed, sys_loss, sys_grad, ref_loss, ref_grad,
            **{name: [round(float(x), 4) for x in value]
               for name, value in stats.items()})

    lowered = (
        ("system, the chunk's solve at one bf16 pass",
         delta_rule, "SOLVE_PRECISION"),
        ("system, the router's product at one bf16 pass",
         transformer, "ROUTER_PRECISION"),
    )
    for seed in seeds:
        model = runner.build_model(cell, seed)
        w0, unravel = model.flat.w0, model.flat.unravel
        del model
        tokens = jnp.asarray(packed_batch(seed + 1_000_003, 0, 1, seq))
        ref_loss, ref_grad = ref.loss_and_grad_flat(w0, unravel, tokens,
                                                    config)
        system(seed, "system", w0, tokens, ref_loss, ref_grad)
        for what, module, name in lowered:
            stated = getattr(module, name)
            setattr(module, name, jax.lax.Precision.DEFAULT)
            try:
                system(seed, what, w0, tokens, ref_loss, ref_grad)
            finally:
                setattr(module, name, stated)
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
