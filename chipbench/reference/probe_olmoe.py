"""How the tolerances of ``olmoe_plain`` were set.  Run by hand on the
chip:

    chiprun -- python3 -m chipbench.reference.probe_olmoe [seed ...]

At the published widths of ``olmoe-l1-ps1w-su1``'s configuration, on one
seeded sequence a seed: the system's loss and gradient (the program's
own model by the cell's launch config, Mosaic kernels, float32 in
memory, XLA's default product precision) against ``olmoe_plain`` at full
float32 precision, and beside it what the tolerances have to refuse,
each the reference itself with one thing wrong: the router's top-k
renormalised, the router's weights dropped (every chosen expert at
weight 1 / k), a mask that lets a query see one key ahead, the wrong
rotary convention (pairs interleaved, not halves), and the reference's
own arithmetic with parameters and activations held in bf16 (the
nearest precision below the configuration's).  Then the system with its
router's product at the default precision (one bf16 pass), to say
whether the full-precision router is needed.  One JSON line each.
``--tiny`` rehearses the script on the CPU at the configuration's small
size (no number of it is a device number); ``--two`` keeps the two
readings a limit is set between, the system and the bf16 reference, for
more seeds at a fraction of the time; ``--leaves`` adds each leaf's
share of the error and the system with its attention path at full
precision (where the error comes from).
"""

from __future__ import annotations

import json
import sys

CELL = "olmoe-l1-ps1w-su1"


def main(seeds, tiny: bool = False, two: bool = False,
         leaves: bool = False) -> None:
    import jax
    import jax.numpy as jnp

    from chipbench import compare, run as runner, spec as spec_mod
    from chipbench.traffic.packed_bytes import packed_batch
    from mpit_tpu.models import transformer
    from mpit_tpu.parallel import moe

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

    def wrong(name, replacement, w0, unravel, tokens):
        """The reference with one of its functions replaced."""
        kept = getattr(ref, name)
        setattr(ref, name, replacement(kept))
        try:
            return ref.loss_and_grad_flat(w0, unravel, tokens, config)
        finally:
            setattr(ref, name, kept)

    def renormalised(gates_fn):
        def gates(h, router, k):
            g = gates_fn(h, router, k)
            return g / jnp.sum(g, axis=-1, keepdims=True)
        return gates

    def unweighted(gates_fn):
        def gates(h, router, k):
            return jnp.where(gates_fn(h, router, k) > 0, 1.0 / k, 0.0)
        return gates

    def one_ahead(_heads):
        import math

        @jax.checkpoint
        def heads(q, k, v):
            n, head = q.shape[-2], q.shape[-1]
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(head)
            mask = jnp.tril(jnp.ones((n, n), bool), k=1)
            scores = jnp.where(mask, scores, -jnp.inf)
            return jnp.einsum("bhqk,bhkd->bhqd",
                              jax.nn.softmax(scores, axis=-1), v)
        return heads

    def interleaved(_rotate):
        def rotate(x, theta):
            n, head = x.shape[-2], x.shape[-1]
            freq = theta ** (-jnp.arange(0, head, 2, dtype=jnp.float32) / head)
            angle = jnp.arange(n, dtype=jnp.float32)[:, None] * freq[None, :]
            cos, sin = jnp.cos(angle), jnp.sin(angle)
            a, b = x[..., 0::2], x[..., 1::2]
            return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                             axis=-1).reshape(x.shape)
        return rotate

    def low_router(model, w0, tokens, seed, ref_loss, ref_grad):
        kept = transformer.ROUTER_PRECISION
        transformer.ROUTER_PRECISION = None
        try:
            low = jax.jit(lambda w, t: model.value_and_grad(w, t))(w0, tokens)
        finally:
            transformer.ROUTER_PRECISION = kept
        say("system, router's product at the default precision", seed,
            *low, ref_loss, ref_grad)

    def by_leaf(unravel, sys_grad, ref_grad):
        """Each leaf's share of the squared error and of the squared
        norm, in percent (one fused program: no vector of the model's
        size beside the two)."""
        @jax.jit
        def sums(got, want):
            pairs = zip(jax.tree_util.tree_leaves(unravel(got)),
                        jax.tree_util.tree_leaves(unravel(want)))
            return [(jnp.sum(jnp.square(g - r)), jnp.sum(jnp.square(r)))
                    for g, r in pairs]

        rows = [(float(e), float(n)) for e, n in sums(sys_grad, ref_grad)]
        names = [jax.tree_util.keystr(path) for path, _ in
                 jax.tree_util.tree_flatten_with_path(
                     jax.eval_shape(unravel, ref_grad))[0]]
        total_err, total = sum(e for e, _ in rows), sum(n for _, n in rows)
        return {name: [round(100 * e / total_err, 2),
                       round(100 * n / total, 2)]
                for name, (e, n) in zip(names, rows)}

    def precise_attention(model, w0, tokens, seed, ref_loss, ref_grad):
        """The system with its attention path (projections and the
        flash kernel's two products) at full precision: what is left is
        the error of everything after the router's input."""
        import functools

        kept = transformer.ATTN_PRECISION, transformer.flash_attention
        transformer.ATTN_PRECISION = jax.lax.Precision.HIGHEST
        transformer.flash_attention = functools.partial(
            kept[1], precision="highest")
        try:
            out = jax.jit(lambda w, t: model.value_and_grad(w, t))(w0, tokens)
        finally:
            transformer.ATTN_PRECISION, transformer.flash_attention = kept
        say("system, attention path at full precision", seed, *out,
            ref_loss, ref_grad)

    for seed in seeds:
        for kernel in ("auto",) if two else ("auto", "xla"):
            chosen = moe.grouped_dot
            if kernel == "xla":  # the choice of parallel/moe.py, refused
                moe.grouped_dot = jax.lax.ragged_dot
            model = runner.build_model(cell, seed)
            w0, unravel = model.flat.w0, model.flat.unravel
            tokens = jnp.asarray(packed_batch(seed + 1_000_003, 0, 1, seq))
            if kernel == "auto":
                ref_loss, ref_grad = ref.loss_and_grad_flat(
                    w0, unravel, tokens, config)
            sys_loss, sys_grad = jax.jit(model.value_and_grad)(w0, tokens)
            moe.grouped_dot = chosen
            say(f"system, grouped product {kernel}", seed, sys_loss, sys_grad,
                ref_loss, ref_grad,
                **({"by_leaf_err_and_norm_pct":
                    by_leaf(unravel, sys_grad, ref_grad)} if leaves else {}))
            del sys_grad
        if leaves:
            precise_attention(model, w0, tokens, seed, ref_loss, ref_grad)
        if two:
            more = ()
        else:
            more = (
                ("reference, top-k renormalised", "router_gates",
                 renormalised),
                ("reference, router weights dropped", "router_gates",
                 unweighted),
                ("reference, mask one key ahead", "_heads", one_ahead),
                ("reference, rotary pairs interleaved", "rotate",
                 interleaved))
            low_router(model, w0, tokens, seed, ref_loss, ref_grad)
        for what, name, replacement in more:
            bad = wrong(name, replacement, w0, unravel, tokens)
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
         tiny="--tiny" in sys.argv, two="--two" in sys.argv,
         leaves="--leaves" in sys.argv)
