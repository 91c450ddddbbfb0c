"""3-seed accuracy evidence for the round-5 north-star doc.

Round-4 established median+spread over 3 reps as the evidence bar for
throughput; this applies the same discipline to the ACCURACY claims
(round-4 verdict weak #4): the docqa BiCNN top-1 accuracies and the
flagship trainer's final test error, each over 3 seeds, emitted as a
markdown table + one JSON line.

Run (CPU is fine — accuracy is platform-independent; the flagship leg
honors whatever platform jax resolves):

    JAX_PLATFORMS=cpu python tools/accuracy_table.py

Env: MPIT_ACC_SEEDS (csv, default 0,1,2), MPIT_ACC_LEGS (csv of
docqa,flagship; default both), MPIT_ACC_OUT (JSON-lines file).
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from benchmarks._common import emit_json, log as _log

SEEDS = [int(s) for s in os.environ.get("MPIT_ACC_SEEDS", "0,1,2").split(",")]
LEGS = os.environ.get("MPIT_ACC_LEGS", "docqa,flagship").split(",")
OUT = os.environ.get("MPIT_ACC_OUT", "")


def _stats(xs):
    xs = [float(x) for x in xs]
    med = float(np.median(xs))
    spread = (max(xs) - min(xs)) / abs(med) * 100.0 if med else 0.0
    return {"median": round(med, 4), "runs": [round(x, 4) for x in xs],
            "spread_pct": round(spread, 1)}


def leg_docqa() -> dict:
    """The round-4 docqa config (real stdlib-docstring corpus),
    per seed: sgd, 8 epochs, 200 filters."""
    from mpit_tpu.train.bicnn import BICNN_DEFAULTS, BiCNNTrainer

    accs = {"valid": [], "test1": [], "test2": []}
    for seed in SEEDS:
        cfg = BICNN_DEFAULTS.merged(
            docqa=True, optimization="sgd", learning_rate=0.05, momentum=0.9,
            epoch=8, num_filters=200, batch_size=16, maxnegsample=20,
            seed=seed, loss_report_every=10**9,
        )
        t0 = time.monotonic()
        result = BiCNNTrainer(cfg).run()
        _log(f"docqa seed={seed}: {result['accuracy']} "
             f"({time.monotonic() - t0:.0f}s)")
        for k in accs:
            accs[k].append(result["accuracy"][k])
    return {"leg": "docqa_bicnn_top1", "seeds": SEEDS,
            "config": "sgd lr=0.05 mom=0.9 epoch=8 filters=200 mb=16 neg=20",
            "pools": "20-way (5% chance)",
            **{k: _stats(v) for k, v in accs.items()}}


def leg_flagship() -> dict:
    """Flagship mesh-EASGD final test error per seed (the bench.py
    training config at its default epochs, no early stop)."""
    from mpit_tpu.train.mesh_launch import (
        FLAGSHIP_BENCH_KWARGS, MESH_LAUNCH_DEFAULTS, run,
    )

    errs, epochs = [], None
    for seed in SEEDS:
        cfg = MESH_LAUNCH_DEFAULTS.merged(
            **FLAGSHIP_BENCH_KWARGS, epochs=30, seed=seed,
        )
        result = run(cfg)
        errs.append(result["final_test_err"])
        epochs = len(result["history"])
        _log(f"flagship seed={seed}: final_test_err "
             f"{result['final_test_err']:.4f} ({epochs} epochs, "
             f"{result['data_source']})")
    return {"leg": "flagship_final_test_err", "seeds": SEEDS,
            "epochs": epochs,
            "condition": "BASELINE.md measurement condition "
                         "(optdigits-8x8 fixture)",
            "test_err": _stats(errs)}


def main():
    known = {"docqa": leg_docqa, "flagship": leg_flagship}
    recs = []
    for leg in [s.strip() for s in LEGS if s.strip()]:
        recs.append(known[leg]())
        emit_json(recs[-1], OUT)
    # Markdown table for the north-star doc.
    _log("\n| leg | metric | median | runs (seeds " +
         ",".join(map(str, SEEDS)) + ") | spread |")
    _log("|---|---|---|---|---|")
    for r in recs:
        for key in ("valid", "test1", "test2", "test_err"):
            if key in r:
                s = r[key]
                _log(f"| {r['leg']} | {key} | {s['median']} | "
                     f"{s['runs']} | {s['spread_pct']}% |")


if __name__ == "__main__":
    main()
