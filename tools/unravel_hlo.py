"""What the flat vector costs a cell's compiled step: compiles the
worker's step at the cell's real shapes for the described chip
(``v5e:2x2``, one device; no chip, not a chip run) and prints every
operation of the optimized HLO whose result has at least 0.9 of the
vector's elements, with its count, and the compiler's
``memory_analysis()``:

    JAX_PLATFORMS=cpu python3 tools/unravel_hlo.py [cell ...]

Run by hand like ``chipbench/rehearse_compile.py``, not by the tests (it
loads the TPU's compiler, which one process at a time may do).  The
step is the one the worker runs: the donated ``msgd_step`` in a local
cell (``optim/msgd.py``), ``jit(value_and_grad(loss))`` under a
parameter server.  The model comes from the program's own builder by
the cell's launch config, with the Mosaic-pinned attention in place of
the reference one, and ``jax.default_backend`` is answered ``tpu``
while the step lowers (as ``rehearse_compile.py`` does, and why).  A
whole-vector ``reshape`` to a 2-D shape is the compiler re-laying the
vector for a leaf's trailing width (``models/flat.py``); one
``concatenate`` is the gradient; ``pad`` and ``add`` are a barrier's
transpose.  An operation inside a fusion's body is marked ``(fused)``:
it costs no sweep of its own.
"""

from __future__ import annotations

import collections
import inspect
import math
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# results that are no operation of their own
PLUMBING = {"parameter", "get-tuple-element", "bitcast", "tuple", "constant"}
OPERATION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (.+?) ([\w\-]+)\(")
ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")


def whole_vector_ops(hlo: str, n: int) -> collections.Counter:
    """``{"reshape f32[7594726,64]": 3, ...}`` over the operations of
    ``hlo`` with at least 0.9 ``n`` elements in a result (each such
    array of a tuple's)."""
    found: collections.Counter = collections.Counter()
    fused = False
    for line in hlo.splitlines():
        if line and not line[0].isspace():  # a computation's head, or its end
            fused = line.startswith(("%fused_", "fused_"))
            continue
        hit = OPERATION.match(line)
        if not hit or hit.group(2) in PLUMBING:
            continue
        result, opcode = hit.groups()
        large = [f"{dtype}[{dims}]" for dtype, dims in ARRAY.findall(result)
                 if math.prod(int(d) for d in dims.split(",") if d) >= 0.9 * n]
        if large:
            kind = re.search(r"kind=(\w+)", line) if opcode == "fusion" else None
            found[f"{opcode}{' ' + kind.group(1) if kind else ''} "
                  f"{' '.join(large)}{' (fused)' if fused else ''}"] += 1
    return found


def compile_cell(name: str):
    """``(compiled, n, what)`` of the cell's worker step."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import run as runner, spec as spec_mod
    from mpit_tpu.models.transformer import default_attn
    from mpit_tpu.optim.msgd import MSGDConfig, msgd_step

    cell = spec_mod.load_cell(name)
    cfg = runner.launch_config(cell, 1)
    seq, batch = int(cfg.lm_seq), int(cell.traffic["batch"])
    shapes = runner.build_model(cell, seed=1, lm_use_flash=0)
    module = shapes.module.clone(
        attn_fn=default_attn(causal=True, use_flash=True, interpret=False))
    unravel, n = shapes.flat.unravel, int(shapes.flat.size)
    # a decoder called with the targets closes its own loss (lm/model.py)
    own_loss = "targets" in inspect.signature(type(module).__call__).parameters

    def loss(w, tokens):
        params = {"params": unravel(w)}
        if own_loss:
            return module.apply(params, tokens[:, :-1], tokens[:, 1:])[0]
        logp = module.apply(params, tokens[:, :-1])
        return jnp.mean(-jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))

    vgf = jax.value_and_grad(loss)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    w = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=chip)
    tokens = jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32, sharding=chip)
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"  # see the module's docstring
    try:
        if cfg.opt in ("sgd", "msgd"):
            mcfg = MSGDConfig(lr=float(cfg.lr), mom=float(cfg.mom),
                              mommax=float(cfg.mommax),
                              momdecay=float(cfg.momdecay))
            state = {"k": jax.ShapeDtypeStruct((), jnp.int32, sharding=chip),
                     "vt": w}
            what = "msgd_step, donated"
            lowered = jax.jit(lambda w, s, t: msgd_step(vgf, w, s, mcfg, t),
                              donate_argnums=(0, 1)).lower(w, state, tokens)
        else:
            what = "value_and_grad(loss)"
            lowered = jax.jit(vgf).lower(w, tokens)
        return lowered.compile(), n, f"{what} at batch {batch} x {seq}"
    finally:
        jax.default_backend = real


def main(argv) -> int:
    from chipbench import spec as spec_mod

    for name in argv or [w["name"] for w in spec_mod.load_bench()["workloads"]]:
        t0 = time.monotonic()
        compiled, n, what = compile_cell(name)
        mem = compiled.memory_analysis()
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        print(f"{name}: {what}, {n} elements ({n * 4 / 1e6:.1f} MB), compiled "
              f"in {time.monotonic() - t0:.1f} s (no chip; not a chip run)")
        print(f"  memory_analysis: arguments {mem.argument_size_in_bytes / 1e9:.3f}"
              f" GB, outputs {mem.output_size_in_bytes / 1e9:.3f} GB, temporaries "
              f"{mem.temp_size_in_bytes / 1e9:.3f} GB, aliased "
              f"{mem.alias_size_in_bytes / 1e9:.3f} GB: program total "
              f"{total / 1e9:.3f} GB")
        ops = whole_vector_ops(compiled.as_text(), n)
        print(f"  operations with at least 0.9 of the vector's elements: "
              f"{sum(ops.values())}")
        for op, count in sorted(ops.items()):
            print(f"    {count:4d} x {op}")
        sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
