"""The benchmark's rehearsal on the CPU: ``python3 -m chipbench.selfcheck``.

Run it before any chip call.  It prints no metric and names the CPU in
its output; nothing it measures is a device number.  It checks:

1. ``BENCHMARK.json``'s names and units against the contract's rules;
2. ``chipbench/flops.py`` against hand-worked cases;
3. the copied stream against the program's;
4. the trace reduction against ``chipbench/fixtures/steps4.xplane.pb``
   and the numbers beside it, and ``tokens_per_s`` on a hand-made run
   with a stalled round;
5. every cell at a tiny size (``n_embd`` 64, 2 layers, sequence 128,
   reference attention, ``device_policy=cpu``, a 6 s window) end to end
   through the runner's own code, and the last line's keys, names and
   units; one cell traced, its readers fed the fixture's reduction;
6. three steps of the one-worker PS mix against plain Adam on the plain
   reference;
7. that a further cell, a third configuration, a new mix (four workers
   on two servers, the gang no committed cell runs) and a new per-layer
   metric need only new files and entries (throw-away files under a
   temporary directory).

``--quick`` stops after step 4 (no gang is started).
"""

from __future__ import annotations

import json
import math
import os
import pathlib
import shutil
import sys
import tempfile
from typing import Any, Dict, List

os.environ["JAX_PLATFORMS"] = "cpu"  # before anything imports jax

from chipbench import flops, run as runner, spec as spec_mod

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"
TINY = {"n_embd": 64, "n_head": 4, "n_layer": 2, "n_inner": 256,
        "n_positions": 128, "vocab_size": 320}  # not the stream's 256
TINY_BATCH = 2
TINY_BUDGET = 24 * TINY_BATCH * TINY["n_positions"]  # 24 updates' tokens
TINY_LR = {"adam": 3e-3, "msgd": 0.1}
WINDOW_S = 6.0  # the su 8 cell needs 192 micro-steps to its tiny budget
FAILURES: List[str] = []


def check(ok: bool, what: str) -> None:
    print(f"selfcheck[cpu]: {'ok  ' if ok else 'FAIL'} {what}", flush=True)
    if not ok:
        FAILURES.append(what)


def close(a: float, b: float, rel: float) -> bool:
    return abs(a - b) <= rel * abs(b)


# -- 1..4: no gang -----------------------------------------------------------


def check_names() -> None:
    bench = spec_mod.load_bench()
    bad = spec_mod.check_names(bench)
    check(not bad, f"names and units of BENCHMARK.json {bad}")
    for cell in bench["workloads"]:
        loaded = spec_mod.load_cell(cell["name"])
        missing = [m["name"] for m in loaded.metrics("per_layer")
                   if spec_mod.load_reader(loaded.root, bench, m["name"]) is None]
        check(not missing, f"{cell['name']}: a reader for every per-layer "
              f"metric {missing}")


def check_flops() -> None:
    c111 = spec_mod.load_cell("c111m-local").config
    c13 = spec_mod.load_cell("c1.3b-ps1w-su8").config
    # By hand, cerebras-gpt-111m: a layer's matrices 4 x 768^2 + 2 x 768 x
    # 3072 = 7,077,888 weights, x6 = 42,467,328 FLOPs a token; attention
    # 12 x 768 x 2049 / 2 = 9,441,792; ten layers 519,091,200; the head
    # 6 x 768 x 50257 = 231,584,256; 750,675,456 in all.  At 6 x 2048
    # tokens a micro-step: 9.224 TFLOP.
    check(flops.train_flops_per_token(c111) == 750_675_456,
          "flops per token of cerebras-gpt-111m, by hand 750,675,456")
    check(close(flops.train_flops_per_token(c111) * 12288, 9.224e12, 1e-3),
          "a micro-step of cerebras-gpt-111m at batch 6 is 9.22 TFLOP")
    # Parameters: tables 50257 x 768 + 2048 x 768 = 40,170,240; a layer
    # 3,072 + 2,359,296 + 2,362,368 + 2,360,064 = 7,084,800, ten of them;
    # final LayerNorm 1,536; head 38,597,376: 149,617,152, a 598.5 MB
    # vector.
    check(flops.param_count(c111) == 149_617_152,
          "parameters of cerebras-gpt-111m, by hand 149,617,152")
    check(flops.exchange_bytes_per_round(c111) == 2 * 4 * 149_617_152,
          "bytes per exchange round: the vector out and back")
    # cerebras-gpt-1.3b-d4: tables 102,926,336 + 4,194,304; a layer 8,192
    # + 16,777,216 + 16,785,408 + 16,779,264 = 50,350,080, four of them;
    # final LayerNorm 4,096; head 102,926,336.
    check(flops.param_count(c13) == 411_451_392,
          "parameters of cerebras-gpt-1.3b-d4, by hand 411,451,392")
    # Flash, one layer of 111m at batch 8: pairs 8 x 12 x 2048 x 2049 / 2
    # = 201,424,896; forward 4 x 64 = 256 FLOPs a pair: 51.56 GFLOP;
    # backward 640 a pair: 128.9 GFLOP.  q, k, v, o are 8 x 12 x 2048 x 64
    # x 4 B = 50.33 MB each.
    cost = flops.flash_call_cost(c111, 8)
    check(cost["fwd"][0] == 256 * 201_424_896 and
          cost["bwd"][0] == 640 * 201_424_896,
          "flash FLOPs per call of cerebras-gpt-111m at batch 8")
    check(close(cost["fwd"][1], 4 * 50_331_648 + 786_432, 1e-9),
          "flash forward bytes: q, k, v in, o and the row sums out")
    share, bound = flops.roofline(197e12, 1.0, 2.0, flops.load_peaks("TPU v5 lite"))
    check(close(share, 50.0, 1e-9) and bound == "compute",
          "roofline: 197 TFLOP in 2 s on a v5e is 50%, bound by compute")
    try:
        flops.load_peaks("TPU v9")
        check(False, "an unknown device_kind is an error")
    except KeyError:
        check(True, "an unknown device_kind is an error")


def check_stream() -> None:
    import numpy as np

    from chipbench.traffic.packed_bytes import packed_batch
    from mpit_tpu.lm.data import packed_batch as program_batch

    same = all(np.array_equal(
        packed_batch(s, k, b, n), program_batch(s, k, batch=b, seq_len=n))
        for s, k, b, n in ((1, 0, 8, 2048), (7, 3, 2, 128), (123456, 99, 4, 512)))
    check(same, "the copied stream equals the program's for three (seed, step)")


def check_measure() -> None:
    """``tokens_per_s`` by hand: nine rounds of two micro-steps of 100
    tokens, 2 s a round, one of them stalled by 3 s.  The median round
    gives 100 tokens/s as if nothing had stalled, tokens over the
    window's seconds would give 1800 / 21, and ``round_stall_pct`` says
    that 3 of the 21 s are left out."""
    from chipbench import measure

    rows, t = [], 10.0
    for k in range(18):
        span = 1.0 + (3.0 if k == 7 else 0.0)
        rows.append([k, t, t + span, 5.0, k % 2 == 1])
        t += span
    rates = measure.round_rates(rows, 100, 10.0, t)
    check(len(rates) == 9 and close(rates[0][1], 100.0, 1e-12)
          and close(rates[3][1], 40.0, 1e-12),
          "measure: nine whole rounds, 100 tokens/s each and 40 in the "
          "stalled one")
    check(len(measure.round_rates(rows, 100, 10.5, t - 0.5)) == 7,
          "measure: a round that straddles an end of the window is left out")
    worker = {"tokens_per_step": 100, "first_window_step": 0,
              "step_rows": rows, "error": None, "memory_peak_bytes": 1}
    results = {0: {"role": "local", "chipbench_worker": worker,
                   "chipbench": {"marks": {"window_open": 10.0,
                                           "window_close": t}}}}
    summary = measure.summarise(results, 0.0, 800, 0.0)
    check(close(summary["tokens_per_s"], 100.0, 1e-12)
          and close(summary["tokens_per_s_window_mean"], 1800 / 21, 1e-12)
          and close(summary["round_stall_pct"], 100 * 3 / 21, 1e-12)
          and close(summary["step_ms_p50"], 1000.0, 1e-12),
          "measure: the median round's rate, the window's mean, the stalled "
          "share and the median micro-step, by hand")


def fixture_reduction() -> Dict[str, Any]:
    from chipbench.reduce import reduce_trace

    return reduce_trace(str(FIXTURES / "steps4.xplane.pb"), "jit_loss")


def check_reduction() -> Dict[str, Any]:
    red = fixture_reduction()
    with open(FIXTURES / "steps4.expected.json") as fh:
        want = json.load(fh)
    for key, value in want["numbers"].items():
        got = red.get(key)
        ok = (got == value if not isinstance(value, float)
              else got is not None and close(got, value, 1e-9))
        check(ok, f"reduction of the fixture: {key} = {value} (got {got})")
    check(red["idle_gaps"][0][0] == want["longest_idle_owner"],
          f"reduction: most idle time under {want['longest_idle_owner']}")
    check(red["device_ops"][0][0].startswith(want["top_op_prefix"]),
          f"reduction: top device op {want['top_op_prefix']}")
    return red


# -- 5..7: gangs on the CPU --------------------------------------------------


def tiny(cell: spec_mod.Cell) -> spec_mod.Cell:
    """The cell at rehearsal size: the same files, smaller numbers."""
    cell.config.update(TINY)
    cell.traffic["launcher"].update(device_policy="cpu", lm_use_flash=0)
    cell.traffic.update(batch=TINY_BATCH,
                        token_budget=TINY_BUDGET * int(cell.traffic["su"]),
                        lr=TINY_LR[cell.traffic["launcher"]["opt"]],
                        min_learning_nats=0.2)
    return cell


def check_line(cell: spec_mod.Cell, line: Dict[str, Any], traced: bool) -> None:
    want = set(spec_mod.LAST_LINE_KEYS) | ({"breakdown"} if traced else set())
    check(set(line) == want, f"{cell.name}: last line's keys {sorted(line)}")
    group = "per_layer" if traced else "end_to_end"
    declared = {m["name"]: m["unit"] for m in cell.metrics(group)}
    check(set(line["metrics"]) <= set(declared) and
          (traced or set(line["metrics"]) == set(declared)),
          f"{cell.name}: metrics are the cell's {group} ones "
          f"{sorted(line['metrics'])}")
    check(all(declared[n] == m["unit"] and isinstance(m["value"], float)
              and math.isfinite(m["value"])
              for n, m in line["metrics"].items()),
          f"{cell.name}: every metric a finite number in its unit")
    check(line["device"]["platform"] == "cpu",
          f"{cell.name}: the rehearsal ran on the cpu and says so")
    check(line["correct"] and line["failed"] == 0 and line["attempted"] > 0,
          f"{cell.name}: correct, {line['attempted']} attempted, "
          f"{line['failed']} failed")


def rehearse(cell: spec_mod.Cell, traced: bool = False,
             reduction: Any = None) -> Dict[str, Any]:
    out = runner.run_cell(tiny(cell), seed=3, seconds=WINDOW_S, trace=traced,
                          platform="cpu", stand_in_reduction=reduction)
    check_line(cell, out["line"], traced)
    return out


def check_trajectory(out: Dict[str, Any], cell: spec_mod.Cell) -> None:
    """Three steps of plain Adam on the plain reference against the
    one-worker PS run's first three losses: same seeded weights, same
    batches, the server's rule written out plainly."""
    import jax
    import jax.numpy as jnp

    from chipbench.child import set_vocab
    from chipbench.reference import gpt_plain
    from chipbench.traffic.packed_bytes import packed_batch
    from mpit_tpu.lm import build

    set_vocab(cell.config["vocab_size"])
    worker_rank = out["summary"]["worker_ranks"][0]
    rows = out["gang"]["results"][worker_rank]["chipbench_worker"]["step_rows"]
    c, lr = cell.config, cell.traffic["lr"]
    model = build(d_model=c["n_embd"], n_heads=c["n_head"],
                  n_layers=c["n_layer"], seq_len=c["n_positions"], seed=3,
                  use_flash=False)
    params = model.flat.unravel(model.flat.w0)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    m, v = zeros, zeros
    losses = []
    for t in range(1, 4):
        tokens = jnp.asarray(packed_batch(
            3 + worker_rank, t - 1, TINY_BATCH, c["n_positions"]))
        loss, g = gpt_plain.loss_and_grad(params, tokens, c["n_head"],
                                          c["n_layer"])
        losses.append(float(loss))
        m = jax.tree_util.tree_map(lambda a, b: 0.9 * a + 0.1 * b, m, g)
        v = jax.tree_util.tree_map(lambda a, b: 0.999 * a + 0.001 * b * b, v, g)
        lr_t = lr * math.sqrt(1 - 0.999 ** t) / (1 - 0.9 ** t)
        params = jax.tree_util.tree_map(
            lambda p, a, b: p - lr_t * a / (jnp.sqrt(b) + 1e-8), params, m, v)
    got = [rows[k][3] for k in range(3)]
    check(all(abs(a - b) < 2e-4 for a, b in zip(got, losses)),
          f"three steps of the PS run {got} follow plain Adam on the plain "
          f"reference {losses}")


def check_extension() -> None:
    """A further cell with a third configuration, a new mix (the gang of
    four workers on two servers, which no committed cell runs) and a new
    per-layer metric, from new files and entries alone."""
    root = spec_mod.ROOT
    with tempfile.TemporaryDirectory(dir=root / runner.RUNS_DIR) as tmp:
        tmp_root = pathlib.Path(tmp)
        shutil.copytree(root / "chipbench", tmp_root / "chipbench",
                        ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
        bench = spec_mod.load_bench()
        bench["configs"].append({
            "name": "third", "source": "https://example.org/third",
            "file": "chipbench/configs/third.json", "reduced": [],
            "why": "throw-away"})
        bench["workloads"].append({
            "name": "extra", "config": "third", "traffic": "new-mix",
            "chips": 1, "why": "throw-away"})
        bench["per_layer"].append({
            "name": "rounds_counted", "unit": "rounds", "better": "higher",
            "source": "program_counter", "layer": "L3 shell + client",
            "moves": "tokens_per_s", "workloads": ["extra"]})
        with open(tmp_root / "BENCHMARK.json", "w") as fh:
            json.dump(bench, fh)
        base = spec_mod.load_cell("c111m-ps1w-su1")
        with open(tmp_root / "chipbench/configs/third.json", "w") as fh:
            json.dump({**base.config, **TINY, "n_layer": 1}, fh)
        mix = base.traffic  # four workers on two servers: ranks 0 and 3 serve
        mix["launcher"].update(np=6, master_freq=3)
        mix["warmup_rounds"] = 10
        with open(tmp_root / "chipbench/traffic/new-mix.json", "w") as fh:
            json.dump(mix, fh)
        with open(tmp_root / "chipbench/layers/rounds_counted.py", "w") as fh:
            fh.write("def read(run):\n    return float(sum(run['summary']"
                     "['rounds_in_window'].values()))\n")
        check(not spec_mod.check_names(bench), "the extended BENCHMARK.json's names")
        cell = spec_mod.load_cell("extra", root=tmp_root)
        out = runner.run_cell(tiny(cell), seed=4, seconds=WINDOW_S, trace=True,
                              platform="cpu",
                              stand_in_reduction=fixture_reduction())
        check_line(cell, out["line"], traced=True)
        check("rounds_counted" in out["line"]["metrics"],
              "a further cell, a third configuration, a new mix of four "
              "workers and a new per-layer metric ran from new files and "
              "entries alone")
        check(len(out["summary"]["worker_ranks"]) == 4,
              "the new mix ran four workers on two servers")


def main(argv: List[str]) -> int:
    print("selfcheck[cpu]: a rehearsal on the CPU; it prints no metric and "
          "nothing here is a device number", flush=True)
    check_names()
    check_flops()
    check_stream()
    check_measure()
    reduction = check_reduction()
    if "--quick" not in argv:
        (spec_mod.ROOT / runner.RUNS_DIR).mkdir(exist_ok=True)
        bench = spec_mod.load_bench()
        for entry in bench["workloads"]:
            cell = spec_mod.load_cell(entry["name"])
            out = rehearse(cell)
            if (cell.traffic["launcher"]["np"], cell.traffic["su"]) == (3, 1):
                check_trajectory(out, cell)
                rehearse(spec_mod.load_cell(entry["name"]), traced=True,
                         reduction=reduction)
        check_extension()
    if FAILURES:
        print(f"selfcheck[cpu]: {len(FAILURES)} FAILED:", flush=True)
        for what in FAILURES:
            print(f"  - {what}", flush=True)
        return 1
    print("selfcheck[cpu]: all checks passed", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
