"""How the tolerances of ``trinity_plain`` were set, and the rule's own
comparison.  Run by hand on the chip:

    chiprun -- python3 -m chipbench.reference.probe_trinity [seed ...]

At the published widths of ``trinity-l5e8-local``'s configuration, on
one seeded sequence of 8192 a seed: the system's loss and gradient (the
program's own model by the cell's launch config, Mosaic kernels, float32
in memory, the precisions ``models/transformer.py`` states) against
``trinity_plain`` at full float32 precision, and beside it what the
tolerances have to refuse.  **A product at a lower precision than the
file states**: the system with the router's product at one bf16 pass
(``ROUTER_PRECISION`` lowered for that one build: the choice of the
eight flips where two scores are close), and the reference's own
arithmetic with parameters and activations held in bf16 (the nearest
precision below the configuration's).  **The reference with one thing
wrong**: rotary positions on the full layer too, none on the window
layers, the window left out, the input left unscaled, the bias left out
of the selection, the shared expert left out.  One JSON line
each, the system's with the block's counters at the seeded weights.

**The rule's own comparison**, a line a seed (``"what": "rule"``): the
norms of the whole gradient and of the rule's slots (why the 2-norm
cannot see one wrong slot); a sparse layer each, how many of the
reference's rows choose their eighth expert by less than
:data:`NEAR_TIE` over the ninth (rows that may fall either way on two
streams that differ by the program's rounding), how far the system's
counts are from the reference's (``trinity_plain.rule_agrees``), whether
the system's step is the rule's step of its own counts to the bit, and
whether its signs are the reference's wherever a count is further from
the mean than the near ties.

``--tiny`` rehearses the script on the CPU at the configuration's small
size (no number of it is a device number); ``--two`` keeps the readings
a limit is set between, the system and the two lowered precisions, and
the rule's line, for more seeds at a fraction of the time.
"""

from __future__ import annotations

import json
import sys

CELL = "trinity-l5e8-local"
# Of a score in (0, 1) plus a bias of O(0.02).  The system's stream
# reaches a router through products of one bf16 pass, so its scores
# differ from the reference's by more than float32's rounding: at 1e-5
# the probe found 5-15 such rows a layer on four seeds and counts off by
# 3-9, in one layer of sixteen by more than its near ties (9 against 8;
# my chip runs, PR 53), so the margin is set ten times wider.
NEAR_TIE = 1e-4


def main(seeds, tiny: bool = False, two: bool = False) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

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
    rate = float(config["load_balance_coeff"])
    top_k = int(config["num_experts_per_tok"])

    def say(what, seed, sys_loss, sys_grad, ref_loss, ref_grad, **more):
        print(json.dumps({
            "what": what, "seed": seed,
            "device": jax.devices()[0].device_kind,
            **compare.compare(sys_loss, sys_grad, ref_loss, ref_grad, ref),
            **more}), flush=True)

    def wrong(w0, unravel, tokens, replaced=None, **keys):
        """The reference with functions or configuration keys replaced."""
        replaced = replaced or {}
        kept = {name: getattr(ref, name) for name in replaced}
        for name, fn in replaced.items():
            setattr(ref, name, fn)
        try:
            return ref.loss_and_grad_flat(w0, unravel, tokens,
                                          {**config, **keys})
        finally:
            for name, fn in kept.items():
                setattr(ref, name, fn)

    gates = ref.router_gates
    variants = (
        ("reference, rotary positions on the full layer too",
         {"layer_types": ["sliding_attention"] * len(config["layer_types"]),
          "sliding_window": 1 << 30}, None),
        ("reference, no rotary positions on the window layers", {},
         {"rotate": lambda x, theta: x}),
        ("reference, the window left out", {"sliding_window": 1 << 30}, None),
        ("reference, the input left unscaled", {"mup_enabled": False}, None),
        ("reference, the bias left out of the selection", {},
         {"router_gates": lambda h, router, bias, c: gates(
             h, router, 0.0 * bias, c)}),
        ("reference, the shared expert left out",
         {"num_shared_experts": 0}, None),
    )

    # the system's counts: the program hands them to nothing but its
    # rule, so the rule's carrier is watched (the recomputed branch
    # reports the same integers again)
    seen_counts, carry = [], moe.carry_step

    def watched(weights, bias, counts, step_rate):
        jax.debug.callback(lambda c: seen_counts.append(np.asarray(c)),
                           counts)
        return carry(weights, bias, counts, step_rate)

    near_ties = {}  # a layer's bias, summed -> its rows in a near tie

    def tied(h, router, bias, c):
        ranked = jax.nn.sigmoid(h @ router) + bias
        top = jax.lax.top_k(ranked, top_k + 1)[0]
        jax.debug.callback(
            lambda n, key: near_ties.__setitem__(float(key), int(n)),
            jnp.sum(top[:, top_k - 1] - top[:, top_k] < NEAR_TIE),
            jnp.sum(bias))
        return gates(h, router, bias, c)

    def system(seed, what, w0, tokens, ref_loss, ref_grad):
        model = runner.build_model(cell, seed)
        model.flat.w0 = None  # the caller's is the one vector kept
        (sys_loss, stats), sys_grad = jax.jit(model.value_grad_stats)(
            w0, tokens)
        say(what, seed, sys_loss, sys_grad, ref_loss, ref_grad,
            **{name: [round(float(x), 4) for x in value]
               for name, value in stats.items()})
        return model.flat.plain, sys_grad

    def rule_line(seed, plain, sys_grad, ref_grad, counted):
        jax.effects_barrier()
        slots = [np.asarray(sys_grad[a:b]) for a, b in plain]
        layers = []
        for (name, counts_ref), slot in zip(sorted(counted.items()), slots):
            ties = near_ties[name]
            mine = [c for c in seen_counts
                    if np.array_equal(ref.balance_step(c, rate), -slot)]
            counts_sys = mine[0] if mine else np.asarray(counts_ref)
            layers.append({
                "layer": name, "near_tie_rows": ties,
                "own_counts_found": bool(mine),
                **ref.rule_agrees(counts_sys, counts_ref, -slot, rate,
                                  near_ties=max(ties, 1))})
        print(json.dumps({
            "what": "rule", "seed": seed,
            "grad_norm": float(jnp.linalg.norm(ref_grad)),
            "rule_slots_norm": float(np.sqrt(sum(
                float(np.sum(s * s)) for s in slots))),
            "layers": layers}), flush=True)

    for seed in seeds:
        model = runner.build_model(cell, seed)
        w0, unravel = model.flat.w0, model.flat.unravel
        del model
        tokens = jnp.asarray(packed_batch(seed + 1_000_003, 0, 1, seq))
        del seen_counts[:]
        near_ties.clear()
        ref.router_gates = tied
        try:    # forward alone: every layer's router reports once
            with jax.default_matmul_precision("highest"):
                _, counted = jax.jit(lambda flat, tok: ref.loss(
                    unravel(flat), tok, config))(w0, tokens)
            counted = {k: np.asarray(v) for k, v in counted.items()}
            jax.effects_barrier()
        finally:
            ref.router_gates = gates
        sums = {name: float(jnp.sum(leaves["router_bias"]))
                for name, leaves in unravel(w0).items() if name in counted}
        by_sum = dict(near_ties)
        for name, total in sums.items():   # by the layer's own bias
            near_ties[name] = by_sum[min(by_sum,
                                         key=lambda k: abs(k - total))]
        ref_loss, ref_grad = ref.loss_and_grad_flat(w0, unravel, tokens,
                                                    config)
        moe.carry_step = watched
        try:
            plain, sys_grad = system(seed, "system", w0, tokens, ref_loss,
                                     ref_grad)
            rule_line(seed, plain, sys_grad, ref_grad, counted)
        finally:
            moe.carry_step = carry
        del sys_grad
        stated = transformer.ROUTER_PRECISION
        transformer.ROUTER_PRECISION = jax.lax.Precision.DEFAULT
        try:
            system(seed, "system, the router's product at one bf16 pass",
                   w0, tokens, ref_loss, ref_grad)
        finally:
            transformer.ROUTER_PRECISION = stated
        for what, keys, replaced in () if two else variants:
            bad = wrong(w0, unravel, tokens, replaced, **keys)
            say(what, seed, *bad, ref_loss, ref_grad)
            del bad

        def low(flat, tok):
            nll, grads, _ = ref.loss_grads_counts(
                jax.tree_util.tree_map(lambda p: p.astype(jnp.bfloat16),
                                       unravel(flat)), tok, config)
            return nll.astype(jnp.float32), jnp.concatenate(
                [leaf.reshape(-1).astype(jnp.float32)
                 for leaf in jax.tree_util.tree_leaves(grads)])

        low_loss, low_grad = jax.jit(low)(w0, tokens)
        say("reference, parameters and activations in bf16", seed,
            low_loss, low_grad, ref_loss, ref_grad)
        del low_grad, ref_grad
        stats = jax.devices()[0].memory_stats() or {}
        print(json.dumps({"seed": seed, "peak_bytes_in_use":
                          int(stats.get("peak_bytes_in_use", 0))}), flush=True)


if __name__ == "__main__":
    main([int(a) for a in sys.argv[1:] if not a.startswith("--")] or [1, 2, 3],
         tiny="--tiny" in sys.argv, two="--two" in sys.argv)
