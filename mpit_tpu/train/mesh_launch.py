"""On-mesh distributed MNIST training CLI — the mlaunch analog on ICI.

Where :mod:`mpit_tpu.train.launch` reproduces the reference's
process-gang shape (pServer/pClient ranks over the host transport,
reference asyncsgd/mlaunch.lua), this entry point runs the same
algorithms as *sharded XLA programs* over a device mesh — the BASELINE
north-star configuration: MNIST EASGD with workers on the ``dp`` axis
and parameter/center shards on the ``shard`` axis, trained to a target
test error using only ICI collectives, with wall-clock-to-target
reported.

Multi-host: pass ``--hostfile`` (the reference's host:slots format,
BiCNN/hostfiles) or ``--coordinator/--num_processes/--process_id``
(or MPIT_* env) and run the same command on every host —
``jax.distributed`` forms the group before any backend use and the mesh
then spans all hosts (DCN for cross-host hops).

Example (single host, all local devices):

    python -m mpit_tpu.train.mesh_launch --opt easgd --su 10 \
        --mva 0.15 --epochs 10
"""

from __future__ import annotations

import json
import pathlib
import sys
import time
from typing import List, Optional

from mpit_tpu.utils.config import Config
from mpit_tpu.utils.logging import get_logger
from mpit_tpu.obs import profiler_trace

MESH_LAUNCH_DEFAULTS = Config(
    model="cnn",  # linear | mlp | cnn
    opt="easgd",  # easgd | syncdp
    lr=1e-2,
    mom=0.99,
    mommax=1.0,
    momdecay=0.0,
    l2wd=0.0,
    mva=0.0,  # 0 -> beta/p with beta=0.9 (mlaunch.lua:42)
    su=10,
    epochs=10,
    batch=128,  # per-worker batch (easgd) / global batch (syncdp)
    seed=1,
    side=32,
    dp=0,  # 0 -> inferred from device count
    shard=0,
    target_test_err=0.01,
    stop_at_target=0,  # 1 -> stop training once target_test_err is reached
    device_stream=0,  # 1 -> stage each epoch's batches on device up front
    epoch_scan=1,  # with device_stream: whole epoch as ONE jitted scan
    device_loop=0,  # 1 -> the WHOLE train-to-target run as one device
    # program (lax.while_loop over epochs: on-device shuffle, epoch scan,
    # test eval, early exit at target).  RTT-proof time-to-target;
    # single-process only, no mid-run checkpoint/resume (_device_loop_train)
    measure_throughput=0,  # 1 -> post-training steady-state samples/s leg
    ckpt_dir="",  # save full trainer state every ckpt_every epochs
    ckpt_every=1,
    resume="",  # path to a mesh_*.npz (or "auto": <ckpt_dir>/mesh_latest.npz)
    dtype="float32",
    profile_dir="",
    compile_cache=1,  # persistent XLA compilation cache (utils.platform)
    precompile=0,  # 1 -> compile+warm the step/eval programs before t0
    # multi-host bootstrap (parallel.distributed.bootstrap)
    hostfile="",
    coordinator="",
    num_processes=0,
    process_id=-1,
)

# The flagship benchmark training config (mlaunch.lua:39-47 analog) —
# ONE definition shared by bench.py (throughput/time-to-target) and
# tools/accuracy_table.py (3-seed test_err), so the accuracy evidence
# always describes the benchmarked trainer.
FLAGSHIP_BENCH_KWARGS = dict(
    opt="easgd", model="cnn", batch=128, side=32,
    su=10, mom=0.99, lr=1e-2, device_stream=1, precompile=1,
)


def _epoch_layout(cfg, n_dp, trainer, mesh, nsteps):
    """Staged-epoch leading shape + sharding — ONE definition shared by
    the host path's ``stage_epoch`` and the device-loop gather, which
    must agree on the batch layout or the two modes silently diverge."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    shape = ((nsteps, n_dp, cfg.batch)
             if cfg.opt == "easgd" else (nsteps, cfg.batch))
    return shape, NamedSharding(mesh, P(None, *trainer.batch_sharding.spec))


def _device_loop_train(*, cfg, trainer, state, eval_params, err_fn, mesh,
                       n_dp, x_train, y_train, x_test, y_test, dtype,
                       steps_per_epoch, per_step, log):
    """Train-to-target as ONE device program: a ``lax.while_loop`` over
    epochs with the on-device shuffle (``jax.random.permutation``), the
    whole-epoch scan, and the test-error eval all inside the loop body,
    early-exiting once the error meets the target (``stop_at_target``).

    Why: the host epoch loop pays >=2 blocking host<->device round trips
    per epoch (loss + error fetches) plus an H2D epoch stage, which
    dominate short epochs.  Here the full run is one AOT-compiled
    dispatch and one result fetch, so time-to-target reflects the
    device, not the host loop.  (The reference's loop is host-driven by
    construction — goot.lua:129-146; a device-resident data-dependent
    training loop is XLA-native ground.)

    bench.py defaults to device_loop=1 for the headline
    time_to_target_s (MPIT_BENCH_DEVICE_LOOP=0 restores the host loop);
    that default came from a July 2026 A/B on a forced-host-device
    CPU whose script and record are gone; the ledger has no
    counterpart.

    Trade-offs (why the host loop remains the general default): the shuffle is
    jax.random rather than the host path's numpy rng (equally random,
    but trajectories are not bit-comparable across modes), per-epoch
    wall timestamps do not exist (only the final ``at`` is real), and
    mid-run checkpoint/profiling hooks cannot fire.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = len(x_train)
    take = steps_per_epoch * per_step
    shape, ep_sharding = _epoch_layout(cfg, n_dp, trainer, mesh,
                                       steps_per_epoch)
    x_all = jnp.asarray(
        np.asarray(x_train, np.float32).reshape(n, -1), dtype)
    y_all = jnp.asarray(np.asarray(y_train))
    epochs = int(cfg.epochs)
    # The early exit happens ON DEVICE: a sentinel no error reaches keeps
    # the loop running every epoch when stop_at_target is off.
    target = jnp.float32(
        cfg.target_test_err if cfg.stop_at_target else -1.0)

    def _body(carry):
        ep, st, key, errs, losses = carry
        key, sub = jax.random.split(key)
        order = jax.random.permutation(sub, n)[:take]
        x_ep = jax.lax.with_sharding_constraint(
            x_all[order].reshape(*shape, -1), ep_sharding)
        y_ep = jax.lax.with_sharding_constraint(
            y_all[order].reshape(shape), ep_sharding)
        st, ep_losses = trainer.run_epoch(st, x_ep, y_ep)
        err = err_fn(eval_params(st), x_test, y_test)
        return (ep + 1, st, key, errs.at[ep].set(err),
                losses.at[ep].set(jnp.mean(ep_losses)))

    def _cond(carry):
        ep, _st, _key, errs, _losses = carry
        hit = jnp.logical_and(
            ep > 0, errs[jnp.maximum(ep - 1, 0)] <= target)
        return jnp.logical_and(ep < epochs, jnp.logical_not(hit))

    def _train(st, key):
        carry = (jnp.asarray(0, jnp.int32), st, key,
                 jnp.full((epochs,), jnp.inf, jnp.float32),
                 jnp.zeros((epochs,), jnp.float32))
        ep, st, _key, errs, losses = jax.lax.while_loop(
            _cond, _body, carry)
        return ep, st, errs, losses

    key0 = jax.random.PRNGKey(cfg.seed)
    t_c = time.perf_counter()
    compiled = jax.jit(_train, donate_argnums=(0,)).lower(
        state, key0).compile()
    compile_s = time.perf_counter() - t_c
    log.info("device-loop compile: %.2fs (whole train-to-target program)",
             compile_s)

    t0 = time.perf_counter()
    ep_d, state, errs_d, losses_d = compiled(state, key0)
    ep = int(ep_d)  # the fetch that fences the whole program
    wall = time.perf_counter() - t0
    errs, losses = np.asarray(errs_d), np.asarray(losses_d)
    # run_epoch's host-side counter advanced once at TRACE time, not once
    # per executed epoch — resynchronize it with the device-resident
    # schedule so any subsequent step()/run_epoch use (e.g. the
    # measure_throughput leg) continues the true global sync phase.
    trainer.set_steps(ep * steps_per_epoch)

    history = [
        {"epoch": i, "avg_loss": float(losses[i]),
         "test_err": float(errs[i]),
         # One program ran every epoch: only the final wall is real.
         "at": round(wall, 3) if i == ep - 1 else None}
        for i in range(ep)
    ]
    for h in history:
        log.info("epoch %d avg_loss %.5f test_err %.4f",
                 h["epoch"], h["avg_loss"], h["test_err"])
    hit_target = bool(ep and errs[ep - 1] <= float(cfg.target_test_err))
    # Contract difference vs the host loop: with stop_at_target=0 the
    # host loop reports time_to_target at whichever epoch first met the
    # target mid-run; inside one device program no per-epoch wall
    # timestamp exists, so a mid-run hit has no honest wall time to
    # report — time_to_target is defined here ONLY when the program
    # early-exits at the target (stop_at_target=1).
    time_to_target = wall if (cfg.stop_at_target and hit_target) else None
    if (not cfg.stop_at_target
            and any(errs[:ep] <= float(cfg.target_test_err))):
        log.warning(
            "device_loop: target %.4f was reached mid-run but "
            "stop_at_target=0 — no per-epoch wall times exist inside the "
            "device program, so time_to_target stays None (use "
            "stop_at_target=1 or the host loop to measure it)",
            float(cfg.target_test_err))
    log.info("device-loop: %d epoch(s) in %.2fs wall (one dispatch)",
             ep, wall)
    return state, history, time_to_target, compile_s, wall, ep * take, t0


def run(cfg: Config) -> dict:
    # Bootstrap BEFORE any jax backend use (multi-host group formation).
    from mpit_tpu.parallel.distributed import bootstrap

    pg = bootstrap(
        coordinator=cfg.coordinator or None,
        num_processes=cfg.num_processes or None,
        process_id=cfg.process_id if cfg.process_id >= 0 else None,
        hostfile=cfg.hostfile or None,
    )

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mpit_tpu.data.mnist import load_mnist
    from mpit_tpu.models import MnistCNN, MnistLinear, MnistMLP, flatten_module
    from mpit_tpu.optim.msgd import MSGDConfig
    from mpit_tpu.parallel import MeshEASGD, SyncDataParallel, make_mesh
    from mpit_tpu.parallel.mesh import put_local
    from mpit_tpu.utils.platform import device_report

    log = get_logger("mesh", pg.process_id)
    log.info("%s", pg.describe())
    if cfg.compile_cache:
        from mpit_tpu.utils.platform import enable_compile_cache

        log.info("compile cache: %s", enable_compile_cache())
    mesh = make_mesh(
        dp=cfg.dp or None, shard=cfg.shard or None
    )
    n_dp = mesh.shape["dp"]
    log.info("mesh: dp=%d shard=%d", n_dp, mesh.shape["shard"])

    (x_train, y_train, x_test, y_test), source = load_mnist(side=cfg.side)
    log.info("data source: %s", source)
    dtype = jnp.dtype(cfg.dtype)
    x_test, y_test = jnp.asarray(x_test, dtype), jnp.asarray(y_test)

    models = {"linear": MnistLinear, "mlp": MnistMLP}
    if cfg.model == "cnn":
        module = MnistCNN(side=cfg.side, num_classes=10)
    elif cfg.model in models:
        module = models[cfg.model](num_classes=10)
    else:
        raise ValueError(f"model must be linear|mlp|cnn, got {cfg.model!r}")
    flat = flatten_module(
        module, jax.random.PRNGKey(cfg.seed), jnp.asarray(x_train[:2], dtype)
    )
    log.info("flat params: %d", flat.size)

    def vgf(w, xb, yb):
        def loss_fn(w):
            logp = flat.apply_flat(w, xb)
            return -jnp.mean(jnp.take_along_axis(logp, yb[:, None], axis=1))

        return jax.value_and_grad(loss_fn)(w)

    msgd = MSGDConfig(
        lr=cfg.lr, mom=cfg.mom, mommax=cfg.mommax, momdecay=cfg.momdecay,
        l2wd=cfg.l2wd,
    )
    mva = cfg.mva or 0.9 / max(n_dp, 1)
    if cfg.opt == "easgd":
        trainer = MeshEASGD(mesh, vgf, msgd, mva=mva, su=cfg.su)
        eval_params = trainer.center_params
    elif cfg.opt == "syncdp":
        trainer = SyncDataParallel(mesh, vgf, msgd)
        eval_params = lambda state: state["w"]
    else:
        raise ValueError(f"opt must be easgd|syncdp, got {cfg.opt!r}")
    state = trainer.init(flat.w0.astype(dtype))

    # Checkpoint backend: single-process uses the portable npz state
    # dict; multi-process uses orbax, which writes each shard from the
    # process holding it (host-local numpy round-trips of globally-
    # sharded state are invalid, and the npz _latest publish would race
    # across hosts).
    use_orbax = pg.num_processes > 1

    def _meta_path():
        return pathlib.Path(cfg.ckpt_dir) / "mesh_meta.json"

    if cfg.device_loop:
        if pg.num_processes > 1:
            raise ValueError(
                "device_loop=1 is single-process: the while_loop body "
                "gathers epoch batches from the replicated dataset, which "
                "multi-host feeding (process-local rows) cannot express"
            )
        if cfg.ckpt_dir or cfg.resume or cfg.profile_dir:
            raise ValueError(
                "device_loop=1 runs every epoch inside one device program "
                "— there are no host epoch boundaries for checkpointing, "
                "resume, or per-epoch profiling; use the host loop for "
                "ckpt_dir/resume/profile_dir"
            )

    start_epoch = 0
    prev_elapsed = 0.0  # cumulative training seconds from resumed runs
    resume_path = cfg.resume
    if resume_path == "auto" and not cfg.ckpt_dir:
        raise ValueError("--resume auto requires --ckpt_dir")
    if resume_path:
        from mpit_tpu.utils.checkpoint import latest_pytree_step

        # Resume backend is detected from what is ON DISK, not from the
        # current topology: a single process can restore orbax step dirs
        # (load_pytree re-places to this run's shardings), while a
        # multi-process group can never round-trip host-local npz.
        disk_step = (latest_pytree_step(cfg.ckpt_dir)
                     if cfg.ckpt_dir and resume_path == "auto" else None)
        if disk_step is not None and not use_orbax:
            # Mixed directory (multi-host steps + later single-process
            # npz saves): prefer the newest artifact.
            npz_latest = pathlib.Path(cfg.ckpt_dir) / "mesh_latest.npz"
            step_dir = pathlib.Path(cfg.ckpt_dir) / f"step_{disk_step}"
            if (npz_latest.exists()
                    and npz_latest.stat().st_mtime > step_dir.stat().st_mtime):
                disk_step = None
        if resume_path == "auto" and disk_step is not None:
            from mpit_tpu.utils.checkpoint import load_pytree

            if not _meta_path().exists():
                raise ValueError(
                    f"step_{disk_step} exists but {_meta_path()} is "
                    "missing — cannot validate opt/seed; the meta is "
                    "written before every step, so this directory is "
                    "corrupt or foreign"
                )
            ck_meta = json.loads(_meta_path().read_text())
            if ck_meta.get("opt", cfg.opt) != cfg.opt:
                raise ValueError(
                    f"checkpoint was trained with --opt {ck_meta['opt']}, "
                    f"not {cfg.opt}"
                )
            state = load_pytree(cfg.ckpt_dir, disk_step, state)
            # The step number, not the (separately written, possibly
            # stale) meta file, defines where training resumes — a crash
            # between the step write and the meta write must not cause
            # silent double-training.
            ck_meta["epoch"] = disk_step
        else:
            if use_orbax:
                raise ValueError(
                    "multi-process resume needs orbax step_* checkpoints "
                    f"under --ckpt_dir (found none in {cfg.ckpt_dir!r}); "
                    "host-local .npz checkpoints cannot restore a "
                    "multi-process mesh"
                )
            from mpit_tpu.utils.checkpoint import load_state_dict

            if resume_path == "auto":
                resume_path = str(
                    pathlib.Path(cfg.ckpt_dir) / "mesh_latest.npz")
            saved, ck_meta = load_state_dict(resume_path)
            if set(saved) != set(state):
                raise ValueError(
                    f"checkpoint keys {sorted(saved)} do not match trainer "
                    f"state {sorted(state)} — wrong --opt or model?"
                )
            # Re-place each array with its mesh sharding (init produced
            # the placement template; shapes must match exactly).
            for key, arr in saved.items():
                if tuple(arr.shape) != tuple(state[key].shape):
                    raise ValueError(
                        f"checkpoint {key} shape {arr.shape} != trainer "
                        f"{tuple(state[key].shape)} (different mesh/model?)"
                    )
                state[key] = jax.device_put(
                    jnp.asarray(arr), state[key].sharding
                )
        if "seed" in ck_meta and int(ck_meta["seed"]) != int(cfg.seed):
            raise ValueError(
                f"checkpoint was trained with --seed {ck_meta['seed']}, "
                f"resuming with --seed {cfg.seed} would silently diverge "
                "the data order — pass the original seed"
            )
        start_epoch = int(ck_meta.get("epoch", -1)) + 1
        prev_elapsed = float(ck_meta.get("elapsed", 0.0))
        log.info("resumed at epoch %d (%.1fs of prior training)",
                 start_epoch, prev_elapsed)

    err_fn = jax.jit(
        lambda w, xb, yb: jnp.mean(
            (jnp.argmax(flat.apply_flat(w, xb), axis=1) != yb).astype(jnp.float32)
        )
    )

    n = len(x_train)
    if cfg.opt == "easgd":
        # Per-worker disjoint streams (each reference client walks its own
        # shuffled copy, goot.lua:129-146).
        per_step = n_dp * cfg.batch
    else:
        per_step = cfg.batch
    if n < per_step:
        raise ValueError(
            f"dataset has {n} samples but one global step needs {per_step} "
            f"({'dp x batch' if cfg.opt == 'easgd' else 'batch'}); lower "
            "--batch or --dp"
        )
    steps_per_epoch = n // per_step

    rng = np.random.default_rng(cfg.seed)
    history: List[dict] = []
    time_to_target: Optional[float] = None
    epoch_train_s: List[float] = []  # step-loop only, per epoch
    samples_trained = 0
    # Multi-process batch feeding: every process builds the same global
    # shuffle (same seed) but hands shard_batch only the leading-axis
    # rows its own devices hold (put_local's contract).
    if pg.num_processes > 1:
        from mpit_tpu.parallel.mesh import process_local_rows

        lead = n_dp if cfg.opt == "easgd" else cfg.batch
        rows = process_local_rows(trainer.batch_sharding, lead)
    else:
        rows = slice(None)

    def stage_epoch(idx, nsteps=None):
        """One HBM placement of a shuffled epoch, step axis in front of
        the batch sharding — per-step slices are already correctly
        sharded and feed the trainer directly (each process contributes
        only its local rows)."""
        nsteps = steps_per_epoch if nsteps is None else nsteps
        shape, ep_sharding = _epoch_layout(cfg, n_dp, trainer, mesh, nsteps)
        x_ep = put_local(
            x_train[idx].reshape(*shape, -1)[:, rows].astype(dtype),
            ep_sharding)
        y_ep = put_local(
            y_train[idx].reshape(shape)[:, rows], ep_sharding)
        return x_ep, y_ep

    compile_s = None
    if cfg.device_loop:
        (state, history, time_to_target, compile_s, dl_wall,
         samples_trained, t0) = _device_loop_train(
            cfg=cfg, trainer=trainer, state=state, eval_params=eval_params,
            err_fn=err_fn, mesh=mesh, n_dp=n_dp, x_train=x_train,
            y_train=y_train, x_test=x_test, y_test=y_test, dtype=dtype,
            steps_per_epoch=steps_per_epoch, per_step=per_step, log=log)
        epoch_train_s = [dl_wall]
    if cfg.precompile and not cfg.device_loop:
        # Compile + warm every program the timed region will run — the
        # step program(s) against the exact training shardings and the
        # eval — so t0 measures training, not XLA.  The north star is
        # still a user-honest wall clock: compile_s is reported
        # separately in the result dict, and with the persistent cache
        # warm this whole block costs well under a second.
        t_c = time.perf_counter()
        if cfg.device_stream and cfg.epoch_scan:
            x_w, y_w = stage_epoch(np.arange(steps_per_epoch * per_step)
                                   % len(x_train))
            trainer.precompile_epoch(state, x_w, y_w)
            del x_w, y_w  # free the warm epoch from HBM before training
            warm_batch = None
        elif cfg.device_stream:
            x_w, y_w = stage_epoch(np.arange(per_step), nsteps=1)
            warm_batch = (x_w[0], y_w[0])
        else:
            xw = np.asarray(x_train[:per_step], np.float32)
            yw = np.asarray(y_train[:per_step])
            if cfg.opt == "easgd":
                xw = xw.reshape(n_dp, cfg.batch, -1)
                yw = yw.reshape(n_dp, cfg.batch)
            warm_batch = trainer.shard_batch(
                jnp.asarray(xw[rows], dtype), jnp.asarray(yw[rows]))
        if warm_batch is not None:
            trainer.precompile(state, *warm_batch)
        float(err_fn(eval_params(state), x_test, y_test))
        compile_s = time.perf_counter() - t_c
        log.info("precompile: %.2fs (step + eval programs warm)", compile_s)

    if not cfg.device_loop:
        t0 = time.perf_counter()  # device_loop sets its own t0

    # Resume reproducibility: burn the skipped epochs' permutations so
    # the data order continues exactly where the checkpointed run left it.
    for _ in range(start_epoch):
        rng.permutation(n)
    with profiler_trace(cfg.profile_dir):
        # device_loop already trained inside its one program: skip.
        for epoch in range(start_epoch,
                           0 if cfg.device_loop else cfg.epochs):
            order = rng.permutation(n)
            losses = []
            t_ep = time.perf_counter()
            if cfg.device_stream:
                # The shuffle is still fresh every epoch — staging
                # changes where batches are assembled, not what is
                # trained (regression-tested against the host path).
                x_ep, y_ep = stage_epoch(order[: steps_per_epoch * per_step])
                if cfg.epoch_scan:
                    # One dispatch per epoch: the whole pass runs as a
                    # jitted lax.scan on device (regression-tested
                    # against the step loop).
                    state, ep_losses = trainer.run_epoch(state, x_ep, y_ep)
                    losses.append(ep_losses)
                else:
                    for step in range(steps_per_epoch):
                        state, loss = trainer.step(
                            state, x_ep[step], y_ep[step])
                        losses.append(loss)
            else:
                for step in range(steps_per_epoch):
                    idx = order[step * per_step:(step + 1) * per_step]
                    xb = np.asarray(x_train[idx], np.float32)
                    yb = np.asarray(y_train[idx])
                    if cfg.opt == "easgd":
                        xb = xb.reshape(n_dp, cfg.batch, -1)
                        yb = yb.reshape(n_dp, cfg.batch)
                    state, loss = trainer.step(state, *trainer.shard_batch(
                        jnp.asarray(xb[rows], dtype), jnp.asarray(yb[rows])
                    ))
                    losses.append(loss)
            avg_loss = float(jnp.mean(jnp.stack(losses)))
            epoch_train_s.append(time.perf_counter() - t_ep)
            samples_trained += steps_per_epoch * per_step
            test_err = float(err_fn(eval_params(state), x_test, y_test))
            # Cumulative across resumes (the reference's prevtime
            # convention, bicnn.lua:259-261) so time_to_target stays the
            # true wall-clock from the ORIGINAL start.
            at = time.perf_counter() - t0 + prev_elapsed
            if time_to_target is None and test_err <= cfg.target_test_err:
                time_to_target = at
            history.append({
                "epoch": epoch, "avg_loss": avg_loss,
                "test_err": test_err, "at": round(at, 3),
            })
            log.info("epoch %d avg_loss %.5f test_err %.4f (%.1fs)",
                     epoch, avg_loss, test_err, at)
            if cfg.ckpt_dir and (epoch + 1) % max(int(cfg.ckpt_every), 1) == 0:
                meta = {"epoch": epoch, "opt": cfg.opt,
                        "test_err": test_err, "seed": cfg.seed,
                        "elapsed": round(at, 3)}
                if use_orbax:
                    from mpit_tpu.utils.checkpoint import save_pytree

                    # Meta BEFORE the step dir: the resume epoch comes
                    # from the step number, so a crash in between leaves
                    # a slightly-ahead meta (harmless) rather than a
                    # step with no seed guard.
                    if pg.process_id == 0:
                        tmp = _meta_path().with_suffix(".tmp")
                        tmp.write_text(json.dumps(meta))
                        tmp.replace(_meta_path())
                    save_pytree(cfg.ckpt_dir, state, step=epoch)
                    path = f"{cfg.ckpt_dir}/step_{epoch}"
                else:
                    from mpit_tpu.utils.checkpoint import save_state_dict

                    path = save_state_dict(
                        cfg.ckpt_dir,
                        {k: np.asarray(v) for k, v in state.items()},
                        meta=meta,
                    )
                log.info("checkpoint: %s", path)
            if cfg.stop_at_target and time_to_target is not None:
                break
    train_time = sum(epoch_train_s)
    # Wall-clock throughput: epoch 0 pays jit compile, drop it when there
    # is anything else to measure.  Includes the one loss fetch per epoch
    # — that round-trip can dominate short epochs, which is why the
    # steady-state leg below exists.
    ss = epoch_train_s[1:] if len(epoch_train_s) > 1 else epoch_train_s
    per_epoch = steps_per_epoch * per_step
    if cfg.device_loop:
        # One wall covers every epoch (single dispatch); compile was AOT,
        # outside the wall.  NOT comparable with the host-loop figure:
        # this wall includes the per-epoch on-device eval + shuffle and
        # the dispatch/fetch RTT, where the host path times training
        # only (eval after the per-epoch timer stops) — the result dict
        # carries train_wall_mode so readers of samples_per_sec know
        # which definition they got; samples_per_sec_steady is the
        # mode-independent rate.
        sps = samples_trained / train_time if train_time > 0 else None
    else:
        sps = len(ss) * per_epoch / sum(ss) if ss and sum(ss) > 0 else None

    sps_steady = None
    if cfg.measure_throughput:
        # Latency-cancelled steady-state throughput
        # (:func:`mpit_tpu.utils.timing.timed_chained`): whole passes
        # over one freshly shuffled epoch staged in HBM — every step
        # sees a different batch, the per-pass fetch round-trip is
        # differenced away, and the jits are the already-compiled
        # training programs.
        from mpit_tpu.utils.timing import timed_chained

        x_ep, y_ep = stage_epoch(
            rng.permutation(n)[: steps_per_epoch * per_step])

        if cfg.device_stream and cfg.epoch_scan:
            def one_pass(st):
                st, _losses = trainer.run_epoch(st, x_ep, y_ep)
                return st
        else:
            def one_pass(st):
                for s in range(steps_per_epoch):
                    st, _loss = trainer.step(st, x_ep[s], y_ep[s])
                return st

        # auto_scale + min_ratio: one scan pass is ~ms-scale — iters
        # grows until the differenced legs clear 8x the observed jitter,
        # bounding the estimator's relative error near 1/8.
        # max_iters=128: one iteration here is a whole epoch — the cap
        # bounds escalation cost, and expensive passes stop on the first
        # round anyway (their delta dwarfs jitter by construction).
        per_pass = timed_chained(
            one_pass, state, iters=4, base_iters=1, repeats=3,
            auto_scale=True, min_ratio=8.0, max_iters=128,
        )
        sps_steady = per_epoch / per_pass
    return {
        "history": history,
        "final_test_err": history[-1]["test_err"] if history else None,
        "time_to_target": time_to_target,
        "elapsed": time.perf_counter() - t0 + prev_elapsed,
        "train_time": round(train_time, 3),
        "samples_trained": samples_trained,
        "samples_per_sec": round(sps, 1) if sps else None,
        "samples_per_sec_steady": round(sps_steady, 1) if sps_steady else None,
        # Which wall fed samples_per_sec: "device_loop" includes eval +
        # shuffle inside the one program's wall; "host_loop" times
        # training only.  steady is mode-independent.
        "train_wall_mode": "device_loop" if cfg.device_loop else "host_loop",
        "compile_s": round(compile_s, 3) if compile_s is not None else None,
        "data_source": source,
        "mesh": {"dp": n_dp, "shard": mesh.shape["shard"]},
        "processes": pg.num_processes,
        **device_report(),
    }


def main(argv: Optional[List[str]] = None) -> None:
    cfg = MESH_LAUNCH_DEFAULTS.parse_args(
        list(sys.argv[1:] if argv is None else argv)
    )
    result = run(cfg)
    print(json.dumps(result, indent=2))


if __name__ == "__main__":
    main()
