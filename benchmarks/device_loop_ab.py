"""A/B: host round-trip PS loop vs the device-resident data plane.

Two modes, selected by ``MPIT_AB_MODE``:

- ``dplane`` (default, ISSUE 10): the same 2-server/2-client lockstep
  PS gang run twice on a forced-8-device CPU mesh
  (``--xla_force_host_platform_device_count``) —

  * **host** leg: the legacy wire path (LocalRouter transport, codec
    none): every round pays grad-mirror copy -> wire frame -> server
    h2d -> jitted apply -> snapshot d2h -> wire frame -> client decode;
  * **device** leg: the dplane exchange (`ExchangeClient.sync_device`):
    grads ride as sharded ``jax.Array``s into the server's donated
    fused apply, pulls return the slot's per-version replicated array
    (an XLA all-gather) — the loop never touches host memory.

  Both legs run the identical grad schedule in lockstep, so the final
  parameter vectors must be **bitwise equal** — the leg is invalid (rc
  1) otherwise.  One JSON line:
  ``{"metric": "dplane_exchange_ab", "host": {...}, "device": {...},
  "speedup": ..., "bitwise_equal": true}``.

- ``flagship``: the PR-8-era host-epoch-loop vs ``lax.while_loop``
  comparison on the mesh_launch flagship config (kept for the
  ``time_to_target_s`` flip decision).

Env (dplane mode): MPIT_AB_MB (payload MB per client, default 64),
MPIT_AB_ROUNDS (default 5), MPIT_AB_REPS (default 3), MPIT_AB_DEVICES
(default 8), MPIT_KBENCH_OUT (append JSON here too).
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _common import emit_json, log  # noqa: E402

MODE = os.environ.get("MPIT_AB_MODE", "dplane")
N_DEV = int(os.environ.get("MPIT_AB_DEVICES", "8"))

if MODE == "dplane":
    # Must precede any jax backend init: the device leg shards over a
    # forced virtual-CPU mesh (+ pool headroom, see utils/platform.py).
    from mpit_tpu.utils.platform import ensure_cpu_device_headroom

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    ensure_cpu_device_headroom(N_DEV)


REPS = int(os.environ.get("MPIT_AB_REPS", "3"))
TARGET = float(os.environ.get("MPIT_AB_TARGET", "0.02"))
EPOCHS = int(os.environ.get("MPIT_AB_EPOCHS", "30"))
MB = float(os.environ.get("MPIT_AB_MB", "64"))
ROUNDS = int(os.environ.get("MPIT_AB_ROUNDS", "5"))
OUT = os.environ.get("MPIT_KBENCH_OUT", "")


# ---------------------------------------------------------------------------
# dplane mode


def _plane_cfg(kind: str):
    from mpit_tpu.dplane import PlaneConfig
    from mpit_tpu.parallel.mesh import make_mesh
    from mpit_tpu.utils.platform import default_devices

    if kind == "host":
        return None
    if kind == "device":
        return PlaneConfig(mesh=None)  # single-backend-device slots
    if kind == "device_mesh":
        return PlaneConfig(mesh=make_mesh(default_devices(), dp=1))
    raise ValueError(kind)


def _gang(cfg, size: int):
    import threading

    import numpy as np

    from mpit_tpu.comm.local import LocalRouter
    from mpit_tpu.dplane import ExchangeClient
    from mpit_tpu.ps import ParamClient, ParamServer

    router = LocalRouter(4)
    sranks, cranks = [0, 1], [2, 3]
    servers = [ParamServer(r, cranks, router.endpoint(r), rule="add",
                           dplane=cfg) for r in sranks]
    threads = [threading.Thread(target=s.start, daemon=True)
               for s in servers]
    for t in threads:
        t.start()
    clients = []
    for r in cranks:
        pc = ParamClient(r, sranks, router.endpoint(r),
                         seed_servers=(r == cranks[0]))
        clients.append(ExchangeClient(pc) if cfg is not None else pc)
    params = [np.zeros(size, np.float32) for _ in cranks]
    starters = [threading.Thread(
        target=c.start, args=(p, np.zeros(size, np.float32)), daemon=True)
        for c, p in zip(clients, params)]
    for t in starters:
        t.start()
    for t in starters:
        t.join(60)
        if t.is_alive():
            raise RuntimeError("client start hung")
    return servers, clients, threads


def _one_dplane(kind: str, size: int, gtab) -> dict:
    """One rep: fresh gang, 1 warmup round (compile), ROUNDS timed
    lockstep rounds; returns MB/s + the final param vector.

    Both legs hoist the constant per-client gradient out of the timed
    loop (mirror write for the host leg, per-shard device slices for
    the device legs), so the loop measures exactly the exchange: the
    host leg's wire round-trip (send copy -> recv staging -> h2d ->
    apply -> d2h snapshot -> reply copy -> param write) vs the device
    legs' submit -> donated apply -> replicated pull, all in device
    memory and sharded-native (parts in, parts out — the form a
    TPU-resident loop holds anyway)."""
    import numpy as np

    import jax.numpy as jnp

    servers, clients, threads = _gang(_plane_cfg(kind), size)
    device = kind != "host"
    if device:
        gparts = [[jnp.asarray(gtab[i][sh.offset:sh.end])
                   for sh in c.pc.shards]
                  for i, c in enumerate(clients)]
    else:
        for i, c in enumerate(clients):
            c.grad[:] = gtab[i]

    def round_step() -> None:
        for i, c in enumerate(clients):
            if device:
                c.sync_device(gparts[i], concat=False)
            else:
                c.async_send_grad()
                c.async_recv_param()
                c.wait()

    round_step()  # warmup: compile the apply/replicate programs
    t0 = time.monotonic()
    for _ in range(ROUNDS):
        round_step()
    elapsed = time.monotonic() - t0
    clients[0].async_recv_param()
    clients[0].wait()
    final = clients[0].param.copy()
    for c in clients:
        c.stop()
    for t in threads:
        t.join(60)
        if t.is_alive():
            raise RuntimeError("server stop hung")
    # ptest's reference formula, per client per round: push + pull.
    mbs = 2 * size * 4 * ROUNDS * len(clients) / elapsed / 2**20
    return {"mbs": mbs, "elapsed_s": elapsed, "final": final}


def _leg_dplane(kind: str, size: int, gtab):
    import numpy as np

    reps = [_one_dplane(kind, size, gtab) for _ in range(REPS)]
    for rep in reps[1:]:
        np.testing.assert_array_equal(reps[0]["final"], rep["final"])
    values = sorted(r["mbs"] for r in reps)
    out = {
        "mbs": round(values[len(values) // 2], 1),
        "value_runs": [round(r["mbs"], 1) for r in reps],
        "elapsed_runs": [round(r["elapsed_s"], 3) for r in reps],
    }
    log(f"[device_loop_ab] {kind}: {out}")
    return out, reps[0]["final"]


def _main_dplane() -> int:
    import numpy as np

    import jax

    size = int(MB * (1 << 20) / 4)
    rng = np.random.default_rng(5)
    gtab = rng.normal(size=(2, size)).astype(np.float32)
    host, host_final = _leg_dplane("host", size, gtab)
    device, device_final = _leg_dplane("device", size, gtab)
    mesh, mesh_final = _leg_dplane("device_mesh", size, gtab)
    bitwise = bool(np.array_equal(host_final, device_final)
                   and np.array_equal(host_final, mesh_final))
    speedup = round(device["mbs"] / host["mbs"], 2) if host["mbs"] else None
    rec = {
        "metric": "dplane_exchange_ab",
        "payload_mb_per_client": MB,
        "rounds": ROUNDS,
        "reps": REPS,
        "clients": 2,
        "servers": 2,
        "devices": len(jax.devices()),
        "mesh_devices": N_DEV,
        "host": host,
        "device": device,
        "device_mesh8": mesh,
        "speedup": speedup,
        "speedup_mesh8": (round(mesh["mbs"] / host["mbs"], 2)
                          if host["mbs"] else None),
        "bitwise_equal": bitwise,
    }
    emit_json(rec, OUT)
    if not bitwise:
        log("[device_loop_ab] FAIL: a device leg diverged from the "
            "host leg")
        return 1
    if device["mbs"] <= host["mbs"]:
        log("[device_loop_ab] FAIL: device-resident loop did not beat "
            "the host round-trip")
        return 1
    return 0


# ---------------------------------------------------------------------------
# flagship mode (the PR-8-era host-loop vs lax.while_loop A/B)


def _one_flagship(device_loop: int) -> dict:
    from mpit_tpu.train.mesh_launch import (
        FLAGSHIP_BENCH_KWARGS, MESH_LAUNCH_DEFAULTS, run,
    )

    cfg = MESH_LAUNCH_DEFAULTS.merged(
        **FLAGSHIP_BENCH_KWARGS, epochs=EPOCHS, target_test_err=TARGET,
        stop_at_target=1, device_loop=device_loop,
    )
    r = run(cfg)
    return {
        "time_to_target": r["time_to_target"],
        "compile_s": r["compile_s"],
        "final_test_err": r["final_test_err"],
        "epochs_run": len(r["history"]),
    }


def _leg_flagship(device_loop: int) -> dict:
    reps = [_one_flagship(device_loop) for _ in range(REPS)]
    ttt = sorted(r["time_to_target"] for r in reps
                 if r["time_to_target"] is not None)
    med = ttt[len(ttt) // 2] if ttt else None
    out = {
        "median_ttt_s": round(med, 3) if med is not None else None,
        "ttt_runs": [round(r["time_to_target"], 3)
                     if r["time_to_target"] is not None else None
                     for r in reps],
        "compile_runs": [round(r["compile_s"], 3) for r in reps],
        "final_err_runs": [round(r["final_test_err"], 4) for r in reps],
        "epochs_runs": [r["epochs_run"] for r in reps],
    }
    log(f"[device_loop_ab] device_loop={device_loop}: {out}")
    return out


def _main_flagship() -> int:
    rec = {
        "metric": "device_loop_ab",
        "target_test_err": TARGET,
        "reps": REPS,
        "host": _leg_flagship(0),
        "device_loop": _leg_flagship(1),
    }
    emit_json(rec, OUT)
    return 0


def main() -> int:
    if MODE == "flagship":
        return _main_flagship()
    if MODE != "dplane":
        raise SystemExit(f"MPIT_AB_MODE must be dplane|flagship, got {MODE!r}")
    return _main_dplane()


if __name__ == "__main__":
    sys.exit(main())
