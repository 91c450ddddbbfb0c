"""The benchmark's rehearsal on the CPU: ``python3 -m chipbench.selfcheck``.

Run it before any chip call.  It prints no metric and names the CPU in
its output; nothing it measures is a device number.  It checks:

1. ``BENCHMARK.json``'s names and units against the contract's rules,
   and that every configuration's file names a reference and an
   arithmetic module that load;
2. each configuration's arithmetic module (``chipbench/arithmetic/``)
   against its own hand-worked cases, and ``chipbench/flops.py``'s
   roofline and peaks;
3. the copied stream against the program's;
4. the trace reduction against ``chipbench/fixtures/steps4.xplane.pb``
   and the numbers beside it (its sixteen Mosaic calls carry no scope
   and are booked for no family), against the hand-made scoped trace of
   ``chipbench/fixtures/handmade.py`` (Mosaic calls and seconds under
   each scope, a call under two counted for neither), and
   ``tokens_per_s`` on a hand-made run with a stalled round;
5. every cell at its configuration's own small size (the ``tiny``
   overrides of its file; reference attention, ``device_policy=cpu``, a
   6 s window) end to end through the runner's own code, and the last
   line's keys, names and units; one cell traced, its readers fed the
   hand-made trace's reduction;
6. three steps of the one-worker PS mix against plain Adam on the
   configuration's plain reference, the model built by the program's own
   builder from the cell's launch config;
7. that a further cell needs only new files and entries (throw-away
   files under a temporary directory): a configuration whose file shares
   no key with the committed ones, with a reference module, an
   arithmetic module, a scope more and a kernel family of its own, a
   new mix (four workers on two servers, the gang no committed cell
   runs) and new per-layer metrics, one of them the family's reader.

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
TINY_BATCH = 2
TINY_UPDATES = 24  # the tiny budget, in updates
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
        check(callable(loaded.reference().loss_and_grad_flat)
              and callable(loaded.arithmetic().kernels),
              f"{cell['name']}: its configuration's reference "
              f"{loaded.config['reference']!r} and arithmetic "
              f"{loaded.config['arithmetic']!r} load by name")


def check_flops() -> None:
    """Each arithmetic module a configuration names, on its own
    hand-worked cases; then the committed configurations through it and
    ``chipbench/flops.py``."""
    bench = spec_mod.load_bench()
    seen, worked = set(), set()
    for entry in bench["workloads"]:
        cell = spec_mod.load_cell(entry["name"])
        if cell.config_name in seen:
            continue
        seen.add(cell.config_name)
        arithmetic = cell.arithmetic()
        cases = ([] if cell.config["arithmetic"] in worked
                 else arithmetic.hand_worked())
        worked.add(cell.config["arithmetic"])
        for what, got, want in cases:
            check(got == want, f"arithmetic {cell.config['arithmetic']}: "
                  f"{what}, by hand {want} (got {got})")
        n = arithmetic.param_count(cell.config)
        check(flops.exchange_bytes_per_round(cell) == 2 * 4 * n,
              f"{cell.config_name}: {n} parameters, a {4 * n / 1e6:.1f} MB "
              "vector out and back in an exchange round")
        families = arithmetic.kernels(cell.config, int(cell.traffic["batch"]))
        check(all(k["scope"] in cell.config["scopes"] and k["flops"] > 0
                  and k["bytes"] > 0 and k["least_calls"] > 0
                  for k in families.values()),
              f"{cell.config_name}: every kernel family {sorted(families)} "
              "has a scope of the configuration, FLOPs, bytes and a least "
              "count of calls")
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
    check(red["mosaic_by_scope"] == {} and
          sum(row[1] for row in red["mosaic_no_family"]) == red["mosaic_calls"],
          "reduction of the fixture: its Mosaic calls carry no scope and "
          "are booked for no kernel family")
    return scoped_reduction(check_it=True)


def scoped_reduction(check_it: bool = False) -> Dict[str, Any]:
    """The reduction of the hand-made scoped trace
    (``fixtures/handmade.py``), which is also what the traced rehearsals
    hand the readers in place of a device trace."""
    from chipbench.fixtures import handmade
    from chipbench.reduce import reduce_trace

    with tempfile.TemporaryDirectory() as tmp:
        path = handmade.write_scoped(pathlib.Path(tmp) / "scoped.xplane.pb")
        red = reduce_trace(str(path), "jit_loss", handmade.SCOPES)
    for key, want in handmade.SCOPED_EXPECTED.items() if check_it else ():
        got = red.get(key)
        check(_same(got, want), f"reduction of the hand-made scoped trace: "
              f"{key} = {want} (got {got})")
    return red


def _same(got: Any, want: Any) -> bool:
    if isinstance(want, float):
        return isinstance(got, (int, float)) and close(got, want, 1e-9)
    if isinstance(want, dict):
        return isinstance(got, dict) and set(got) == set(want) and all(
            _same(got[k], v) for k, v in want.items())
    if isinstance(want, list):
        return isinstance(got, list) and len(got) == len(want) and all(
            _same(a, b) for a, b in zip(got, want))
    return got == want


# -- 5..7: gangs on the CPU --------------------------------------------------


def tiny(cell: spec_mod.Cell) -> spec_mod.Cell:
    """The cell at rehearsal size: the same files, the configuration's
    own small sizes (``tiny`` in its file) laid over its keys."""
    cell.config.update(cell.config["tiny"])
    cell.traffic["launcher"].update(device_policy="cpu", lm_use_flash=0)
    seq = int(runner.launch_config(cell, 0).lm_seq)
    cell.traffic.update(batch=TINY_BATCH,
                        token_budget=(TINY_UPDATES * TINY_BATCH * seq
                                      * int(cell.traffic["su"])),
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
    """Three steps of plain Adam on the configuration's plain reference
    against the one-worker PS run's first three losses: same seeded
    weights (the program's own builder, from the cell's launch config),
    same batches, the server's rule written out plainly on the flat
    vector.  ``cell`` is the rehearsed one, at its small size."""
    import jax.numpy as jnp

    from chipbench.traffic.packed_bytes import packed_batch

    worker_rank = out["summary"]["worker_ranks"][0]
    rows = out["gang"]["results"][worker_rank]["chipbench_worker"]["step_rows"]
    lr, reference = cell.traffic["lr"], cell.reference()
    flat = runner.build_model(cell, seed=3).flat
    seq = int(runner.launch_config(cell, 3).lm_seq)
    w = flat.w0
    m = v = jnp.zeros_like(w)
    losses = []
    for t in range(1, 4):
        tokens = jnp.asarray(packed_batch(3 + worker_rank, t - 1,
                                          TINY_BATCH, seq))
        loss, g = reference.loss_and_grad_flat(w, flat.unravel, tokens,
                                               cell.config)
        losses.append(float(loss))
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        lr_t = lr * math.sqrt(1 - 0.999 ** t) / (1 - 0.9 ** t)
        w = w - lr_t * m / (jnp.sqrt(v) + 1e-8)
    got = [rows[k][3] for k in range(3)]
    check(all(abs(a - b) < 2e-4 for a, b in zip(got, losses)),
          f"three steps of the PS run {got} follow plain Adam on the plain "
          f"reference {losses}")


def check_extension() -> None:
    """A further cell from new files and entries alone: the files under
    ``fixtures/extension`` laid into a copy of the benchmark's directory
    (a configuration none of whose size keys the committed ones have,
    its reference and arithmetic modules, two readers), a new mix
    written here (four workers on two servers, which no committed cell
    runs), and the entries that name them.  The kernel family the
    arithmetic adds, ``commit`` under the scope ``update``, is read by
    its own reader from the hand-made trace's reduction: 40 us in two
    micro-steps, 0.02 ms a step."""
    root = spec_mod.ROOT
    with tempfile.TemporaryDirectory(dir=root / runner.RUNS_DIR) as tmp:
        tmp_root = pathlib.Path(tmp)
        shutil.copytree(root / "chipbench", tmp_root / "chipbench",
                        ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
        shutil.copytree(FIXTURES / "extension", tmp_root / "chipbench",
                        dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
        bench = spec_mod.load_bench()
        bench["configs"].append({
            "name": "other", "source": "https://example.org/other",
            "file": "chipbench/configs/other.json", "reduced": [],
            "why": "throw-away"})
        bench["workloads"].append({
            "name": "extra", "config": "other", "traffic": "new-mix",
            "chips": 1, "why": "throw-away"})
        bench["per_layer"] += [{
            "name": "rounds_counted", "unit": "rounds", "better": "higher",
            "source": "program_counter", "layer": "L3 shell + client",
            "moves": "tokens_per_s", "workloads": ["extra"]}, {
            "name": "commit_ms_per_step", "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": "L1 kernels",
            "moves": "tokens_per_s", "workloads": ["extra"]}]
        with open(tmp_root / "BENCHMARK.json", "w") as fh:
            json.dump(bench, fh)
        mix = spec_mod.load_cell("c111m-ps1w-su1").traffic
        mix["launcher"].update(np=6, master_freq=3)  # ranks 0 and 3 serve
        mix["warmup_rounds"] = 10
        with open(tmp_root / "chipbench/traffic/new-mix.json", "w") as fh:
            json.dump(mix, fh)
        check(not spec_mod.check_names(bench), "the extended BENCHMARK.json's names")
        cell = spec_mod.load_cell("extra", root=tmp_root)
        committed = {key for w in spec_mod.load_bench()["workloads"]
                     for key in spec_mod.load_cell(w["name"]).config["tiny"]}
        check(not committed & set(cell.config),
              f"the new configuration has none of the committed ones' size "
              f"keys {sorted(committed)}")
        for what, got, want in cell.arithmetic().hand_worked():
            check(got == want, f"arithmetic other: {what}, by hand {want} "
                  f"(got {got})")
        out = runner.run_cell(tiny(cell), seed=4, seconds=WINDOW_S, trace=True,
                              platform="cpu",
                              stand_in_reduction=scoped_reduction())
        check_line(cell, out["line"], traced=True)
        metrics = out["line"]["metrics"]
        check("rounds_counted" in metrics and
              close(metrics.get("commit_ms_per_step", {}).get("value", 0.0),
                    0.02, 1e-9),
              "a further cell ran from new files and entries alone: a "
              "configuration with its own keys, reference, arithmetic, "
              "scopes, kernel family and small size, a new mix of four "
              "workers, and two new per-layer metrics, one of them the "
              f"family's own reader {sorted(metrics)}")
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
                traced = rehearse(spec_mod.load_cell(entry["name"]),
                                  traced=True, reduction=reduction)
                check("flash_ms_per_step" in traced["line"]["metrics"],
                      f"{cell.name}, traced: the attn family's reader found "
                      "the hand-made trace's calls under its scope")
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
