"""How each mix's ``lr`` and ``token_budget`` were chosen.  By hand, on
the chip:

    chiprun -- python3 -m chipbench.sweep_lr <cell> <seconds> <seed> <lr> [<lr> ...]

Runs the cell through the runner's own code once per learning rate, the
mix's other parameters as its file has them, and prints what the choice
needs: the losses by micro-step (on the runner's earlier lines) and the
end-to-end metrics.  The rule (ISSUE 22): the largest ``lr`` at which
the loss falls from the first step on in every seed tried; the sweep and
the choice are recorded in PERF.md section 6, and the mix's
``min_learning_nats`` is set from the chosen rate's runs.  It prints no last line of the
contract's form and is not the benchmark's command.
"""

from __future__ import annotations

import json
import sys

from chipbench import measure, run as runner, spec as spec_mod


def main(argv) -> int:
    name, seconds, seed = argv[0], float(argv[1]), int(argv[2])
    for lr in (float(x) for x in argv[3:]):
        cell = spec_mod.load_cell(name)
        cell.traffic["lr"] = lr
        cell.traffic["token_budget"] = 4 * cell.traffic["batch"] * int(
            runner.launch_config(cell, seed).lm_seq)  # every run reaches it
        try:
            out = runner.run_cell(cell, seed, seconds, trace=False)
        except measure.RunFailed as exc:
            print(f"sweep_lr: {name} lr {lr}: FAILED: {exc}", flush=True)
            continue
        print(f"sweep_lr: {name} seed {seed} lr {lr}: "
              + json.dumps(out["line"]["metrics"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
