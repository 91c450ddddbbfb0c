"""Where a mix's ``lr`` and ``token_budget`` lie, by the step alone.  By
hand, on the chip:

    chiprun -- python3 -m chipbench.sweep_budget <cell> <steps> <out.jsonl> \
        --lr <lr> [<lr> ...] --seed <seed> [<seed> ...]

For each learning rate and each seed: the cell's model by the program's
own trainer from the cell's launch config, the donated ``msgd`` step the
window runs, the stream's batches the benchmark's child draws
(``packed_batch(seed, k, ...)``), ``steps`` micro-steps from the seeded
weights, every micro-step's loss (a JSON line a run, appended to the
file named and printed).  No reference check, no window, no trace: a run
is the steps' own time, and a learning rate compiles once (the seeds
after the first read the compile cache).

``--table <out.jsonl>`` reads such lines back (anywhere, no jax) and
prints what the choice needs, by ``loss_at_budget``'s own form
(``chipbench/measure.py``): for each ``lr`` and each step ``k``, the
mean of the four losses that end at ``k`` a seed, and over the seeds its
median and its quartile distance (``statistics.quantiles(n=4)``) as a
share of the median.  It prints no last line of the contract's form and
is not the benchmark's command.
"""

from __future__ import annotations

import json
import statistics
import sys
import time


def run(name: str, steps: int, out: str, lrs, seeds) -> None:
    import gc

    import jax
    import jax.numpy as jnp

    from chipbench import run as runner, spec as spec_mod
    from chipbench.traffic.packed_bytes import packed_batch
    from mpit_tpu.lm import LmTrainer
    from mpit_tpu.train.launch import lm_trainer_cfg
    from mpit_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    cell = spec_mod.load_cell(name)
    batch = int(cell.traffic["batch"])
    for lr in lrs:
        for seed in seeds:
            cfg = runner.launch_config(cell, seed).merged(lr=lr)
            trainer = LmTrainer(lm_trainer_cfg(cfg))
            opt, seq = trainer.optimizer, int(cfg.lm_seq)
            losses, t0 = [], time.monotonic()
            for k in range(steps):
                tokens = jnp.asarray(packed_batch(seed, k, batch, seq))
                trainer.w, loss = opt.step(trainer.w, tokens)
                losses.append(loss)
            losses = [float(x) for x in losses]
            line = json.dumps({
                "cell": name, "lr": lr, "seed": seed,
                "device": jax.devices()[0].device_kind,
                "seconds": round(time.monotonic() - t0, 2),
                "losses": [round(x, 5) for x in losses]})
            print(line, flush=True)
            with open(out, "a") as fh:
                fh.write(line + "\n")
            del trainer, opt
            gc.collect()


def table(path: str) -> None:
    runs = {}
    with open(path) as fh:
        for row in map(json.loads, fh):
            runs.setdefault(row["lr"], []).append(row["losses"])
    for lr, curves in sorted(runs.items()):
        steps = min(map(len, curves))
        print(f"lr {lr}: {len(curves)} seeds, {steps} steps; first loss "
              f"{min(c[0] for c in curves):.3f}-{max(c[0] for c in curves):.3f}")
        rises = [sum(1 for a, b in zip(c, c[1:]) if b > a + 0.05)
                 for c in curves]
        print(f"  steps that rise by over 0.05 nats, a seed: {rises}")
        for k in range(3, steps):
            means = [statistics.fmean(c[k - 3:k + 1]) for c in curves]
            q1, _, q3 = statistics.quantiles(means, n=4)
            median = statistics.median(means)
            print(f"  k {k + 1:3d} (budget {k + 1} micro-steps): median "
                  f"{median:.4f} iqr {100 * (q3 - q1) / median:.3f}% "
                  f"range {min(means):.4f}-{max(means):.4f}")


def main(argv) -> int:
    import argparse

    parser = argparse.ArgumentParser(prog="chipbench.sweep_budget")
    parser.add_argument("--table", help="print the table of such a file")
    parser.add_argument("cell", nargs="?")
    parser.add_argument("steps", nargs="?", type=int)
    parser.add_argument("out", nargs="?")
    parser.add_argument("--lr", nargs="+", type=float, default=[])
    parser.add_argument("--seed", nargs="+", type=int, default=[])
    args = parser.parse_args(argv)
    if args.table:
        table(args.table)
    else:
        run(args.cell, args.steps, args.out, args.lr, args.seed)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
