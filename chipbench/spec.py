"""Reads a cell from data: ``BENCHMARK.json``, the configuration's file,
the traffic mix's file and the per-layer readers, each found by the name
``BENCHMARK.json`` gives it; and what the configuration's file names in
its turn: its plain reference (``reference/<module>.py``), its
arithmetic (``arithmetic/<module>.py``), its model's scopes and its
small size for the CPU rehearsal.  Nothing here names a cell, a block or
one of a model's size keys, so a later PR adds a cell, and a model whose
block the benchmark has never seen, with new files and new entries only.
No jax in this module.

What a configuration's file must hold beside its model's own published
keys (:data:`CONFIG_KEYS`; a missing one is a :class:`SpecError`, never
a default):

- ``reference``: a module under ``reference/`` with
  ``loss_and_grad_flat(w0, unravel, row, config) -> (loss, flat
  gradient)`` in float32 at ``default_matmul_precision("highest")``,
  ``config`` being this file as a dict, and its own ``LOSS_TOL_NATS`` and
  ``GRAD_REL_TOL`` with the reason beside them (``chipbench/compare.py``
  holds every reference by the same comparison);
- ``arithmetic``: a module under ``arithmetic/`` with ``param_count(c)``
  (what is exchanged), ``train_flops_per_token(c)`` (what a token needs),
  ``kernels(c, batch)`` (``{family: {"scope", "flops", "bytes",
  "least_calls"}}``: each Mosaic kernel family of the block, the model
  scope its calls run under, what the algorithm needs per micro-step and
  the fewest ``tpu_custom_call``s the lowered step may hold) and
  ``hand_worked()`` (``[(what, got, want)]``, the self-check's cases);
- ``scopes``: the ``jax.named_scope`` names of the model's layers in the
  program, by which device time and Mosaic calls are booked;
- ``tiny``: overrides of its own keys that make it a CPU-sized model of
  the same block;
- ``launcher`` and ``launcher_from``: the program's switches, and a
  switch for each of its sizes by the size's key.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import re
from typing import Any, Callable, Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parent.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LAST_LINE_KEYS = ("correct", "attempted", "failed", "metrics", "device")
CONFIG_KEYS = ("reference", "arithmetic", "scopes", "tiny", "launcher_from")


class SpecError(ValueError):
    """A benchmark file that does not say what the runner needs."""


class Cell:
    """One entry of ``workloads`` with everything it points at."""

    def __init__(self, bench: Dict[str, Any], entry: Dict[str, Any],
                 root: pathlib.Path):
        self.bench = bench
        self.root = root
        self.name: str = entry["name"]
        self.chips: int = int(entry["chips"])
        self.why: str = entry["why"]
        self.config_name: str = entry["config"]
        self.traffic_name: str = entry["traffic"]
        cfg_entry = next((c for c in bench["configs"]
                          if c["name"] == self.config_name), None)
        if cfg_entry is None:
            raise SpecError(f"cell {self.name}: no configuration "
                            f"{self.config_name!r} in BENCHMARK.json")
        self.config: Dict[str, Any] = _read_json(root / cfg_entry["file"])
        missing = [k for k in CONFIG_KEYS if k not in self.config]
        if missing:
            raise SpecError(f"{cfg_entry['file']}: no {missing} (a "
                            "configuration names its own reference, "
                            "arithmetic, scopes and small size)")
        self.traffic: Dict[str, Any] = _read_json(
            traffic_path(root, bench, self.traffic_name))

    def reference(self) -> Any:
        """The configuration's plain reference, loaded by path."""
        return load_named(bench_dir(self.root, self.bench), "reference",
                          self.config)

    def arithmetic(self) -> Any:
        """The configuration's operations, bytes and kernel families."""
        return load_named(bench_dir(self.root, self.bench), "arithmetic",
                          self.config)

    def metrics(self, group: str) -> List[Dict[str, Any]]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports:
        those with no ``workloads`` key, and those that list it."""
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]


def _read_json(path: pathlib.Path) -> Dict[str, Any]:
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, json.JSONDecodeError) as exc:
        raise SpecError(f"{path}: {exc}") from exc


def bench_dir(root: pathlib.Path, bench: Dict[str, Any]) -> pathlib.Path:
    """The directory that holds ``traffic/`` and ``layers/``: the first
    entry of ``paths``."""
    return root / bench["paths"][0]


def traffic_path(root: pathlib.Path, bench: Dict[str, Any],
                 name: str) -> pathlib.Path:
    """The mix's data file.  The one generator reads JSON; the contract
    allows other endings, which would need a reader added here."""
    path = bench_dir(root, bench) / "traffic" / f"{name}.json"
    if not path.exists():
        raise SpecError(f"no traffic file {path} for mix {name!r}")
    return path


def load_bench(root: pathlib.Path = ROOT) -> Dict[str, Any]:
    return _read_json(root / "BENCHMARK.json")


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = load_bench(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        have = ", ".join(w["name"] for w in bench["workloads"])
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have: {have})")
    return Cell(bench, entry, root)


def _load_by_path(path: pathlib.Path, kind: str, name: str) -> Any:
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(root: pathlib.Path, bench: Dict[str, Any],
                metric: str) -> Optional[Callable[[Any], Optional[float]]]:
    """The reader of one per-layer metric: ``layers/<metric>.py`` with a
    function ``read(run)`` that returns the value, or None where there is
    nothing to read.  Loaded by path, so a reader under a temporary root
    (the self-check's throw-away cell) is found like any other."""
    path = bench_dir(root, bench) / "layers" / f"{metric}.py"
    if not path.exists():
        return None
    return _load_by_path(path, "layer", metric).read


def load_named(directory: pathlib.Path, kind: str,
               config: Dict[str, Any]) -> Any:
    """The module ``<directory>/<kind>/<config[kind]>.py``, loaded by
    path: ``kind`` is ``reference`` or ``arithmetic``, ``directory`` the
    benchmark's own (:func:`bench_dir`; a rank of the gang is told it,
    because the self-check's throw-away configuration lives under a
    temporary root).  A configuration without the key, a name outside
    the contract's characters and a missing file are each a
    :class:`SpecError`: nothing falls back to another block's module."""
    name = config.get(kind)
    if not isinstance(name, str) or not NAME.match(name):
        raise SpecError(f"configuration {config.get('name')!r} names no "
                        f"{kind} module (key {kind!r}: {name!r})")
    path = pathlib.Path(directory) / kind / f"{name}.py"
    if not path.exists():
        raise SpecError(f"no {kind} module {path} for configuration "
                        f"{config.get('name')!r}")
    return _load_by_path(path, kind, name)


def all_scopes(root: pathlib.Path = ROOT) -> List[str]:
    """Every scope any configuration of ``BENCHMARK.json`` lists, in
    order of first mention: what a run that names no cell is read under
    (``layers/spantree.py``)."""
    bench = load_bench(root)
    out: List[str] = []
    for entry in bench["configs"]:
        for scope in _read_json(root / entry["file"]).get("scopes", []):
            if scope not in out:
                out.append(scope)
    return out


def check_names(bench: Dict[str, Any]) -> List[str]:
    """The contract's rules on names and units, as far as they can be
    checked here; returns the breaches (the self-check wants none)."""
    bad: List[str] = []
    names = ([c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]]
             + [w["config"] for w in bench["workloads"]]
             + [w["traffic"] for w in bench["workloads"]]
             + [k for c in bench["configs"] for k in c["reduced"]])
    metrics = bench["end_to_end"] + bench["per_layer"]
    names += [m["name"] for m in metrics]
    bad += [f"name {n!r}" for n in names if not NAME.match(n)]
    bad += [f"unit {m['unit']!r} of {m['name']}" for m in metrics
            if not UNIT.match(m["unit"])]
    bad += [f"better {m['better']!r} of {m['name']}" for m in metrics
            if m["better"] not in ("lower", "higher")]
    for group in ("configs", "workloads"):
        seen = [e["name"] for e in bench[group]]
        bad += [f"duplicate {group} name {n!r}"
                for n in set(seen) if seen.count(n) > 1]
    seen = [m["name"] for m in metrics]
    bad += [f"duplicate metric name {n!r}" for n in set(seen)
            if seen.count(n) > 1]
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    bad += [f"pair {p} twice" for p in set(pairs) if pairs.count(p) > 1]
    e2e = {m["name"] for m in bench["end_to_end"]}
    bad += [f"{m['name']} moves unknown metric {m['moves']!r}"
            for m in bench["per_layer"] if m["moves"] not in e2e]
    return bad
