"""How the tolerances of ``gpt_plain`` were set.  Run by hand on the
chip:

    chiprun -- python3 -m chipbench.reference.probe_tolerance [cell ...]

For each cell's configuration (one whose file says ``"reference":
"gpt_plain"``), on a few seeded sequences: the system's loss and
gradient (the program's own model by the cell's launch config, with the
Mosaic kernel, float32 in memory, XLA's default product precision)
against the reference at full float32 precision, and beside it a lower
precision that the tolerance has to refuse: the reference's own
arithmetic with parameters and activations held in bf16.  Prints one
JSON line per (cell, seed).  A reference for another block brings a
probe of its own.
"""

from __future__ import annotations

import json
import sys


def probe(cell_name: str, seeds=(1, 2, 3)) -> None:
    import jax
    import jax.numpy as jnp

    from chipbench import compare, run as runner, spec as spec_mod
    from chipbench.reference import gpt_plain
    from chipbench.traffic.packed_bytes import packed_batch

    cell = spec_mod.load_cell(cell_name)
    config = cell.config
    seq = int(runner.launch_config(cell, 0).lm_seq)
    heads, depth = int(config["n_head"]), int(config["n_layer"])
    for seed in seeds:
        model = runner.build_model(cell, seed)
        w0, unravel = model.flat.w0, model.flat.unravel
        tokens = jnp.asarray(packed_batch(seed + 1_000_003, 0, 1, seq))
        sys_loss, sys_grad = jax.jit(model.value_and_grad)(w0, tokens)
        ref_loss, ref_grad = gpt_plain.loss_and_grad_flat(
            w0, unravel, tokens, config)
        low_loss, low_grad = jax.jit(jax.value_and_grad(
            lambda flat, tok: gpt_plain.loss(
                jax.tree_util.tree_map(lambda p: p.astype(jnp.bfloat16),
                                       unravel(flat)), tok, heads, depth)
        ))(w0, tokens)
        print(json.dumps({
            "cell": cell_name, "config": cell.config_name, "seed": seed,
            "device": jax.devices()[0].device_kind,
            "system": compare.compare(sys_loss, sys_grad, ref_loss, ref_grad,
                                      gpt_plain),
            "all_bf16": compare.compare(low_loss.astype(jnp.float32),
                                        low_grad, ref_loss, ref_grad,
                                        gpt_plain),
        }), flush=True)


if __name__ == "__main__":
    from chipbench import spec as spec_mod

    for name in sys.argv[1:] or [w["name"] for w in
                                 spec_mod.load_bench()["workloads"]]:
        probe(name)
