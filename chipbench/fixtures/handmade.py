"""A device trace made by hand: a small ``.xplane.pb`` whose every
number is known, for what the recorded fixture cannot show.
``steps4.xplane.pb`` was recorded before the program named its layers
(PR 23), so its sixteen Mosaic calls carry no scope; this one has Mosaic
calls under one scope, under another, under none, under two, and one
whose name two stacks claim, and ``chipbench/reduce.py`` must book each
where :data:`SCOPED_EXPECTED` says.  The self-check and the tests under
``chipbench/tests`` reduce it; it is also the stand-in reduction of the
self-check's traced rehearsals, so that the kernel families' readers
find something to read on the CPU.

Only the fields of ``XSpace`` the reduction reads are written
(tsl/profiler/protobuf/xplane.proto): planes with a name, lines of
events (metadata id, offset and duration in picoseconds), event metadata
with a name and string stats, stat metadata.
"""

from __future__ import annotations

import pathlib
from typing import Any, Dict, List, Sequence, Tuple

Event = Tuple[str, int, int]  # name, start ns, duration ns


def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number: int, value: Any) -> bytes:
    """One protobuf field: a varint for an int, else length-delimited."""
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    data = value.encode() if isinstance(value, str) else value
    return varint(number << 3 | 2) + varint(len(data)) + data


def xplane(name: str, lines: Dict[str, Sequence[Event]],
           stacks: Sequence[Tuple[str, str]] = ()) -> bytes:
    """One ``XPlane`` of an ``XSpace``.  ``stacks`` is ``[(event name,
    name stack)]``: each becomes a metadata entry with the stack as its
    ``tf_op`` stat, so a name listed twice has two entries, as the same
    HLO text in two programs has."""
    names = sorted({ev[0] for events in lines.values() for ev in events})
    meta_id = {n: i + 1 for i, n in enumerate(names)}
    body = field(2, name)
    for k, (line, events) in enumerate(lines.items()):
        packed = field(1, k + 1) + field(2, line) + field(3, 0)
        for ev_name, start, dur in events:
            packed += field(4, field(1, meta_id[ev_name])
                            + field(2, start * 1000) + field(3, dur * 1000))
        body += field(3, packed)
    stacked = {n for n, _s in stacks}
    entries = [(n, None) for n in names if n not in stacked] + list(stacks)
    seen, spare = set(), len(names)
    for ev_name, stack in entries:
        if ev_name in seen:  # the name's second entry: an id no event has
            spare += 1
            ident = spare
        else:
            ident = meta_id[ev_name]
            seen.add(ev_name)
        meta = field(1, ident) + field(2, ev_name)
        if stack is not None:
            meta += field(5, field(1, 1) + field(5, stack))
        body += field(4, field(1, ident) + field(2, meta))
    body += field(5, field(1, 1) + field(2, field(1, 1) + field(2, "tf_op")))
    return field(1, body)


def _mosaic(name: str, shape: str) -> str:
    return (f"%{name} = {shape} custom-call(f32[8]{{0}} %p), "
            'custom_call_target="tpu_custom_call"')


# Two runs of the step's program, ``jit_loss``, 400 us each, at 100 us
# and at 600 us; the host's annotations span 0 to 1100 us.  In each run,
# by microsecond from the run's begin:
#   10  flash forward   30 us  under .../layer_0/attn/            -> attn
#   50  a plain fusion 100 us  under .../layer_0/mlp/        (not Mosaic)
#  160  flash backward  50 us  under transpose(jvp(...))/attn/    -> attn
#  220  fused commit    20 us  under update/                      -> update
#  250  a stray kernel   7 us  no name stack                      -> no family
#  260  a nested kernel 11 us  under mlp/.../attn/: two scopes    -> no family
#  280  a shared name    5 us  one name, two programs' stacks     -> no family
# Mosaic: 6 calls and 123 us a run, 12 calls and 246 us in all; attn 4
# calls and 160 us; update 2 calls and 40 us; no family 6 calls, 46 us.
# Busy 223 us a run, 446 us of the 1100 us window: idle 59.4545...%.
SCOPES = ["embed", "attn", "mlp", "head_loss", "update"]
_OPS = [
    (_mosaic("flash_fwd.1", "f32[2,4,128,128]{3,2,1,0}"), 10, 30,
     ["jit(loss)/jvp(Block)/layer_0/attn/vmap(vmap())/pallas_call:"]),
    ("%fusion.9 = f32[2,128,256]{2,1,0} fusion(f32[8]{0} %p), kind=kOutput",
     50, 100, ["jit(loss)/jvp(Block)/layer_0/mlp/dot_general:"]),
    (_mosaic("flash_bwd.1", "f32[2,4,128,64]{3,2,1,0}"), 160, 50,
     ["jit(loss)/transpose(jvp(Block))/layer_0/attn/pallas_call:"]),
    (_mosaic("commit.1", "f32[4096,128]{1,0}"), 220, 20,
     ["jit(loss)/update/pallas_call:"]),
    (_mosaic("stray.1", "f32[16]{0}"), 250, 7, []),
    (_mosaic("nested.1", "f32[32]{0}"), 260, 11,
     ["jit(loss)/layer_0/mlp/remat(attn)/pallas_call:"]),
    (_mosaic("shared.1", "f32[64]{0}"), 280, 5,
     ["jit(loss)/layer_0/attn/pallas_call:",
      "jit(other)/update/pallas_call:"]),
]
SCOPED_EXPECTED: Dict[str, Any] = {
    "chips": 1,
    "window_s": 1100e-6,
    "busy_s": 446e-6,
    "step_module_runs": 2,
    "step_module_ms_p50": 0.4,
    "mosaic_calls": 12,
    "mosaic_s": 246e-6,
    "mosaic_by_scope": {"attn": [4, 160e-6], "update": [2, 40e-6]},
    "mosaic_no_family": [["[mosaic kernel] f32[16]{0}", 2, 14e-6],
                         ["[mosaic kernel] f32[32]{0}", 2, 22e-6],
                         ["[mosaic kernel] f32[64]{0}", 2, 10e-6]],
}


def write_scoped(path: pathlib.Path) -> pathlib.Path:
    """Writes the trace described above to ``path``."""
    ops: List[Event] = []
    for begin in (100, 600):
        ops += [(name, (begin + at) * 1000, dur * 1000)
                for name, at, dur, _stacks in _OPS]
    stacks = [(name, stack) for name, _at, _dur, found in _OPS
              for stack in found]
    space = xplane(
        "/device:TPU:0",
        {"XLA Ops": ops,
         "XLA Modules": [("jit_loss(7)", 100_000, 400_000),
                         ("jit_loss(7)", 600_000, 400_000)]}, stacks)
    space += xplane("/host:CPU", {"python": [
        ("bench.batch", 0, 100_000), ("bench.dispatch", 100_000, 420_000),
        ("bench.fence", 520_000, 30_000), ("bench.batch", 550_000, 50_000),
        ("bench.dispatch", 600_000, 500_000)]})
    path = pathlib.Path(path)
    path.write_bytes(space)
    return path
