"""Records the small device trace that ``chipbench/selfcheck.py`` checks
the reduction (``chipbench/reduce.py``) against.  Run once, on the chip:

    chiprun -- python3 -m chipbench.fixtures.record_fixture

It traces four micro-steps of the program's own LM step (``lm.build``
with the Mosaic flash kernel) at a tiny size, under the benchmark's
annotations, with a host sleep between steps so that the trace has idle
gaps of a known owner.  The ``.xplane.pb`` it leaves under
``chiprun_out/fixture/`` is what was copied to
``chipbench/fixtures/steps4.xplane.pb``; ``dump.txt`` beside it lists the
planes, lines and first events, which is how the reduction was written.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import time


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from mpit_tpu.lm import build

    if jax.default_backend() != "tpu":
        print("record_fixture: needs the chip", file=sys.stderr)
        return 2
    out = os.path.join("chiprun_out", "fixture")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    model = build(d_model=256, n_heads=2, n_layers=2, seq_len=512, seed=1,
                  use_flash=True)
    vgf = jax.jit(model.value_and_grad)
    w = model.flat.w0
    tokens = jnp.zeros((2, 513), jnp.int32)
    loss, g = vgf(w, tokens)
    jax.block_until_ready((loss, g))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    for step in range(4):
        with jax.profiler.TraceAnnotation("bench.batch", step=step):
            time.sleep(0.002)
            tokens = jnp.full((2, 513), step, jnp.int32)
        with jax.profiler.TraceAnnotation("bench.dispatch", step=step):
            loss, g = vgf(w, tokens)
        with jax.profiler.TraceAnnotation("bench.fence", step=step):
            jax.block_until_ready((loss, g))
        with jax.profiler.TraceAnnotation("bench.ps_round", step=step):
            time.sleep(0.005)
    jax.profiler.stop_trace()
    path = glob.glob(out + "/plugins/profile/*/*.xplane.pb")[0]
    shutil.copy(path, os.path.join(out, "steps4.xplane.pb"))
    with open(os.path.join(out, "dump.txt"), "w") as fh:
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            lines = list(plane.lines)
            fh.write(f"PLANE {plane.name!r} lines={len(lines)} "
                     f"stats={dict(plane.stats)}\n")
            for line in lines:
                evs = list(line.events)
                fh.write(f"  LINE {line.name!r} events={len(evs)}\n")
                for e in evs[:60]:
                    fh.write(f"    {e.name!r} start={e.start_ns} "
                             f"dur={e.duration_ns} {dict(e.stats)}\n")
    print("size", os.path.getsize(path), "devices", jax.devices())
    print(open(os.path.join(out, "dump.txt")).read()[-20000:])
    return 0


if __name__ == "__main__":
    sys.exit(main())
