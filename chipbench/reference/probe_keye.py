"""How the tolerances of ``keye_plain`` were set.  Run by hand on the
chip:

    chiprun -- python3 -m chipbench.reference.probe_keye [seed ...]

At the published widths of ``keye-l6e8-local``'s configuration, on one
seeded sequence of 8192 a seed: the system's loss and gradient (the
program's own model by the cell's launch config, the indexer's bits,
Mosaic kernels that mask by them, float32 in memory, the precisions
``models/transformer.py`` states) against ``keye_plain`` at full float32
precision, and beside it what the tolerances have to refuse.  **A
product at a lower precision than the file states**: the system with the
router's and the indexer's products at one bf16 pass
(``ROUTER_PRECISION`` and ``ops/index_select.py`` ``SCORE_PRECISION``
lowered for that one build), and the reference's
own arithmetic with parameters and activations held in bf16 (the nearest
precision below the configuration's).  **The reference with one thing
wrong**: the selection left out (plain causal attention); the 2048 most
recent positions in place of the indexer's choice (a sliding window);
the ReLU left out of the score; the heads' query/key norm left out.  One
JSON line each, the system's with the block's counters at the seeded
weights (the selection's two and the routing's three a layer).

**The rows whose sets differ.**  The two sides choose their sets from
streams that differ by the program's bf16 rounding (layer 0's input is
the same table row on both sides), so near the threshold a row's last
keys can differ.  A line a seed gives, a layer: the share of rows whose
set is not the reference's to the key, and over those rows the mean
number of keys that differ (of ``topk``), from the program's own
``indexer_select`` on the program's own stream (the layers' outputs
captured) against ``keye_plain``'s sets on its stream.  The line's
``empty_tiles_pct`` is the share of the kernels' causal ``512 x 512``
tiles in which the program's set has no pair (what the kernels skip).
The line says ``within_limits``: under :data:`ROWS_LIMIT_FIRST_PCT` of
layer 0's rows, under :data:`ROWS_LIMIT_LATER_PCT` of any later layer's
and under :data:`KEYS_LIMIT` keys a differing row.  Layer 0 reads the
same table rows on both sides and its indexer runs at full precision on
both, so its sets differ only by the order of a sum; a later layer's
stream carries the bf16 rounding of everything before it, a thousandth
of its size, against a spacing of the scores at the threshold of a
three-thousandth of their spread: a row's 2048th and 2049th key change
places in a tenth to a quarter of the rows, more the deeper the layer
(the v5e, seeds 1, 5 and 2147486011: 0.0-0.012% of layer 0's rows, then
10.7-11.5, 15.5-16.1, 18.9-20.5, 23.2-23.7 and 26.2-26.7%, by 1.0-1.10
keys).  What the limits have to refuse is the line after it, **the
program's scores at one bf16 pass** (``SCORE_PRECISION`` lowered for
that one build, the projections left as stated): 68.6-69.4% of every
layer's rows, the first's too, by 2.37-2.54 keys, on the same three
seeds.  Each limit lies between its two readings: 1% of layer 0's rows
(0.012 and 68.6), 40% of a later layer's (26.7 and 68.6: one and a half
times the deepest sound layer, under three fifths of the fault) and 1.5
keys (1.10 and 2.37); a share beyond them says the two sides score
differently, not that they round differently.  The limits are this
configuration's six layers': a deeper stack's last layers read higher.

``--tiny`` rehearses the script on the CPU at the configuration's small
size (no number of it is a device number); ``--two`` keeps the readings
a limit of ``correct`` is set between, ``--sets`` the two lines of the
sets alone, for more seeds at a fraction of the time.
"""

from __future__ import annotations

import json
import sys

CELL = "keye-l6e8-local"
TILE = 512  # the float32 kernels' block (ops/flash_attention.py)
ROWS_LIMIT_FIRST_PCT, ROWS_LIMIT_LATER_PCT, KEYS_LIMIT = 1.0, 40.0, 1.5


def main(seeds, tiny: bool = False, two: bool = False,
         sets: bool = False) -> None:
    import jax
    import jax.numpy as jnp

    from chipbench import compare, run as runner, spec as spec_mod
    from chipbench.traffic.packed_bytes import packed_batch
    from mpit_tpu.models import transformer
    from mpit_tpu.ops import index_select, select_bits
    from mpit_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()  # the seeds share every program
    cell = spec_mod.load_cell(CELL)
    if tiny:
        cell.config.update(cell.config["tiny"])
        cell.traffic["launcher"].update(lm_use_flash=0)
    config, ref = cell.config, cell.reference()
    seq = int(runner.launch_config(cell, 0).lm_seq)
    topk = int(config["sa_config"]["topk"])
    n_layers = int(config["num_hidden_layers"])

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

    def causal(x, p, c):
        t = jnp.arange(x.shape[1])
        return jnp.broadcast_to(t[None, :] <= t[:, None],
                                (x.shape[0], x.shape[1], x.shape[1]))

    def window(x, p, c):
        t = jnp.arange(x.shape[1])
        near = (t[None, :] <= t[:, None]) & (t[:, None] - t[None, :] < topk)
        return jnp.broadcast_to(near, (x.shape[0], *near.shape))

    def no_relu(qi, ki, w):
        return jnp.sum(jnp.einsum("rhd,kd->rhk", qi, ki) * w[:, :, None],
                       axis=1)

    variants = (
        ("reference, the selection left out", {"selection": causal}),
        ("reference, the most recent positions chosen",
         {"selection": window}),
        ("reference, no ReLU in the score", {"index_scores": no_relu}),
        ("reference, no norm on the heads' queries and keys",
         {"head_norm": lambda x, weight, eps: x}),
    )

    def system(seed, what, w0, tokens, ref_loss, ref_grad):
        model = runner.build_model(cell, seed)
        model.flat.w0 = None  # the caller's is the one vector kept
        (sys_loss, stats), sys_grad = jax.jit(model.value_grad_stats)(
            w0, tokens)
        say(what, seed, sys_loss, sys_grad, ref_loss, ref_grad,
            **{name: [round(float(x), 4) for x in value]
               for name, value in stats.items()})
        return model

    def sets_differ(seed, what, model, w0, unravel, tokens):
        """The program's sets, a layer, from its own stream, against the
        reference's on the reference's."""
        inputs = tokens[:, :-1]

        @jax.jit
        def program_sets(w):
            params = unravel(w)
            _, state = model.module.apply(
                {"params": params}, inputs, tokens[:, 1:],
                capture_intermediates=lambda mdl, _: isinstance(
                    mdl, transformer.KeyeBlock))
            streams = [params["embed"][inputs]] + [
                state["intermediates"][f"KeyeBlock_{i}"]["__call__"][0][0]
                for i in range(n_layers - 1)]
            sa = config["sa_config"]
            return [select_bits.unpack(transformer.indexer_select(
                transformer.rms_norm(u, params[f"KeyeBlock_{i}"]["attn_norm"],
                                     float(config["rms_norm_eps"])),
                params[f"KeyeBlock_{i}"],
                index_heads=int(sa["indexer_num_heads"]),
                index_head_dim=int(sa["indexer_head_dim"]), topk=topk,
                theta=float(config["rope_theta"]),
                eps=float(config["rms_norm_eps"]))[0], seq)
                for i, u in enumerate(streams)]

        @jax.jit
        def reference_sets(w):
            sets = []
            with jax.default_matmul_precision("highest"):
                ref.layers(unravel(w), inputs, config, sets)
            return sets

        @jax.jit
        def rows(mine, theirs):
            keys = jnp.sum(mine != theirs, axis=-1) // 2  # swapped pairs
            off = keys > 0
            blocks = -(-seq // TILE)
            pad = blocks * TILE - seq
            tiles = jnp.pad(mine[0], ((0, pad), (0, pad))).reshape(
                blocks, TILE, blocks, TILE).any(axis=(1, 3))
            live = jnp.tril(jnp.ones((blocks, blocks), bool))
            return (jnp.mean(off), jnp.sum(keys) / jnp.maximum(jnp.sum(off), 1),
                    1.0 - jnp.sum(tiles & live) / jnp.sum(live))

        found = [rows(a, b) for a, b in zip(program_sets(w0),
                                            reference_sets(w0))]
        rows_pct = [100 * float(r[0]) for r in found]
        keys = [float(r[1]) for r in found]
        print(json.dumps({
            "what": what, "seed": seed,
            "rows_differing_pct": [round(x, 3) for x in rows_pct],
            "keys_differing_a_differing_row": [round(x, 2) for x in keys],
            "empty_tiles_pct": [round(100 * float(r[2]), 3) for r in found],
            "within_limits": bool(
                rows_pct[0] < ROWS_LIMIT_FIRST_PCT
                and max(rows_pct[1:], default=0.0) < ROWS_LIMIT_LATER_PCT
                and max(keys) < KEYS_LIMIT),
        }), flush=True)

    def sets_sound_and_planted(seed, model, w0, unravel, tokens):
        """The line, and the line the limits have to refuse: the
        program's scores at one bf16 pass."""
        sets_differ(seed, "rows whose sets differ, a layer", model, w0,
                    unravel, tokens)
        stated = index_select.SCORE_PRECISION
        index_select.SCORE_PRECISION = jax.lax.Precision.DEFAULT
        try:
            sets_differ(seed, "rows whose sets differ, the program's scores "
                        "at one bf16 pass", model, w0, unravel, tokens)
        finally:
            index_select.SCORE_PRECISION = stated

    for seed in seeds:
        model = runner.build_model(cell, seed)
        w0, unravel = model.flat.w0, model.flat.unravel
        tokens = jnp.asarray(packed_batch(seed + 1_000_003, 0, 1, seq))
        if sets:
            model.flat.w0 = None
            sets_sound_and_planted(seed, model, w0, unravel, tokens)
            continue
        del model
        ref_loss, ref_grad = ref.loss_and_grad_flat(w0, unravel, tokens,
                                                    config)
        model = system(seed, "system", w0, tokens, ref_loss, ref_grad)
        stated = transformer.ROUTER_PRECISION, index_select.SCORE_PRECISION
        transformer.ROUTER_PRECISION = index_select.SCORE_PRECISION = \
            jax.lax.Precision.DEFAULT
        try:
            system(seed, "system, the router's and the indexer's products at "
                   "one bf16 pass", w0, tokens, ref_loss, ref_grad)
        finally:
            transformer.ROUTER_PRECISION, index_select.SCORE_PRECISION = stated
        if not two:
            sets_sound_and_planted(seed, model, w0, unravel, tokens)
        del model
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
         tiny="--tiny" in sys.argv, two="--two" in sys.argv,
         sets="--sets" in sys.argv)
