"""The chip's peaks and the shares taken of them: ``load_peaks``
(``chipbench/peaks.json``), ``roofline``, ``mfu_pct``, and the bytes of
one exchange round.  Operations, parameters and kernel families are not
here: they belong to a block, and each configuration's file names the
module under ``chipbench/arithmetic/`` that counts its own
(``chipbench/spec.py`` has the contract, ``arithmetic/gpt2.py`` the
committed configurations' module with its hand-worked cases).  The
functions here that need such a number take the cell and ask its module.
"""

from __future__ import annotations

import json
import pathlib
from typing import Any, Dict, Optional, Tuple

F32 = 4  # bytes; the exchanged vector is float32


def exchange_bytes_per_round(cell: Any) -> int:
    """Bytes one worker moves in one sync round with codec none: the
    whole gradient vector out and the whole parameter vector back.  Each
    crosses the chip's link once (d2h, h2d) and the wire once."""
    return 2 * cell.arithmetic().param_count(cell.config) * F32


def load_peaks(device_kind: str) -> Dict[str, Any]:
    """The chip's published peaks; an unknown ``device_kind`` is an
    error, never a default."""
    path = pathlib.Path(__file__).resolve().parent / "peaks.json"
    with open(path) as fh:
        table = json.load(fh)["peaks"]
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} is not in {path}; "
                       f"have {sorted(table)}")
    return table[device_kind]


def roofline(flops: float, nbytes: float, seconds: float,
             peaks: Dict[str, Any]) -> Tuple[float, str]:
    """(share in percent, which peak binds): the least time the chip
    could take, the larger of FLOPs over peak FLOP/s and bytes over peak
    bytes/s, over the time taken."""
    t_compute = flops / (peaks["bf16_tflops"] * 1e12)
    t_memory = nbytes / (peaks["hbm_gbps"] * 1e9)
    bound = "compute" if t_compute >= t_memory else "memory"
    return 100.0 * max(t_compute, t_memory) / seconds, bound


def kernel_family(run: Dict[str, Any], family: str
                  ) -> Optional[Tuple[Dict[str, Any], float]]:
    """For the reader of one kernel family's time or roofline share:
    (the family's entry of the configuration's ``kernels``, device
    seconds per traced micro-step of the Mosaic calls the reduction
    booked under its scope); None where the configuration has no such
    family, the trace no such call, or no run of the step's program."""
    cell, red = run["cell"], run["reduction"]
    kernel = cell.arithmetic().kernels(
        cell.config, int(cell.traffic["batch"])).get(family)
    if kernel is None or not red.get("step_module_runs"):
        return None
    calls, seconds = (red.get("mosaic_by_scope") or {}).get(
        kernel["scope"], (0, 0.0))
    if not calls:
        return None
    return kernel, seconds / red["step_module_runs"]


def mfu_pct(cell: Any, tokens_per_s: float, chips: int,
            peaks: Dict[str, Any]) -> float:
    """Forward plus backward FLOPs a token needs by the configuration's
    own arithmetic (for experts: the active ones), times the rate, over
    the chips' published bf16 peak."""
    per_token = cell.arithmetic().train_flops_per_token(cell.config)
    return (100.0 * per_token * tokens_per_s
            / (chips * peaks["bf16_tflops"] * 1e12))
