"""How the tolerance of ``gpt_plain.compare`` was set.  Run by hand on
the chip:

    chiprun -- python3 -m chipbench.reference.probe_tolerance [config ...]

For each configuration, on a few seeded sequences: the system's loss and
gradient (the program's step with the Mosaic kernel, float32 in memory,
XLA's default product precision) against the reference at full float32
precision, and beside it a lower precision that the tolerance has to
refuse: the reference's own arithmetic with parameters and activations
held in bf16.  Prints one JSON line per (configuration, seed).
"""

from __future__ import annotations

import json
import sys


def probe(config_name: str, seeds=(1, 2, 3)) -> None:
    import jax
    import jax.numpy as jnp
    from jax.flatten_util import ravel_pytree

    from chipbench import spec as spec_mod
    from chipbench.child import set_vocab
    from chipbench.reference import gpt_plain
    from chipbench.traffic.packed_bytes import packed_batch
    from mpit_tpu.lm import build

    bench = spec_mod.load_bench()
    entry = next(c for c in bench["configs"] if c["name"] == config_name)
    with open(spec_mod.ROOT / entry["file"]) as fh:
        c = json.load(fh)
    set_vocab(c["vocab_size"])
    for seed in seeds:
        model = build(d_model=c["n_embd"], n_heads=c["n_head"],
                      n_layers=c["n_layer"], seq_len=c["n_positions"],
                      seed=seed, use_flash=True)
        w0 = model.flat.w0
        tokens = jnp.asarray(packed_batch(seed + 1_000_003, 0, 1,
                                          c["n_positions"]))
        sys_loss, sys_grad = jax.jit(model.value_and_grad)(w0, tokens)
        params = model.flat.unravel(w0)
        ref_loss, ref_tree = gpt_plain.loss_and_grad(
            params, tokens, c["n_head"], c["n_layer"])
        ref_grad = ravel_pytree(ref_tree)[0]
        low = jax.tree_util.tree_map(lambda p: p.astype(jnp.bfloat16), params)
        low_loss, low_tree = jax.jit(
            jax.value_and_grad(gpt_plain.loss), static_argnums=(2, 3))(
                low, tokens, c["n_head"], c["n_layer"])
        low_grad = ravel_pytree(low_tree)[0].astype(jnp.float32)
        print(json.dumps({
            "config": config_name, "seed": seed,
            "device": jax.devices()[0].device_kind,
            "system": gpt_plain.compare(sys_loss, sys_grad, ref_loss, ref_grad),
            "all_bf16": gpt_plain.compare(low_loss.astype(jnp.float32),
                                          low_grad, ref_loss, ref_grad),
        }), flush=True)


if __name__ == "__main__":
    from chipbench import spec as spec_mod

    for name in sys.argv[1:] or [c["name"] for c in
                                 spec_mod.load_bench()["configs"]]:
        probe(name)
