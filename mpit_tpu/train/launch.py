"""Launchers — the claunch/glaunch/mlaunch analogs.

Role assignment follows the reference's conventions: with the default
``master_freq=2``, even ranks become parameter servers and odd ranks become
workers (reference mlaunch.lua:25-31); BiCNN generalizes to every
``masterFreq``-th rank a server plus optional dedicated tester ranks
(reference plaunch.lua:123-163) — the same rule implemented here.

Three entry modes:

- ``--np 1``: single-process local training, no comm (claunch.lua analog —
  proves L4 is decoupled from L2/L1, SURVEY.md section 3.2);
- ``--np N``: this process forks N role processes wired over the native
  shm transport — the built-in ``mpirun -np N`` analog;
- library use: :func:`run_rank` with injected transports, so tests run
  whole topologies in threads on the in-process router.

Usage:
    python -m mpit_tpu.train.launch --np 4 --opt downpour --lr 0.01
    python -m mpit_tpu.train.launch --np 12 --opt eamsgd --su 100 \\
        --mom 0.99 --mva 0.15 --epochs 10
"""

from __future__ import annotations

import json
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

from mpit_tpu.lm import archs as _lm_archs  # plain data: loads no jax
from mpit_tpu.optim import rules as rules_mod
from mpit_tpu.ps import ParamClient, ParamServer
from mpit_tpu.train.trainer import TRAINER_DEFAULTS, MnistTrainer
from mpit_tpu.utils.config import Config
from mpit_tpu.utils.logging import get_logger

LAUNCH_DEFAULTS = TRAINER_DEFAULTS.merged(
    np=1,
    master_freq=2,  # every master_freq-th rank is a server (mlaunch parity)
    tester="none",  # none | first | last  (plaunch testerfirst/testerlast)
    tester_rounds=10,
    tester_interval=1.0,
    ckpt_dir="",
    ring_mb=64,
    namespace="",
    # Per-rank device assignment (the reference's AGPU map,
    # mlaunch.lua:56-62): workers_accel (each worker, and the tester,
    # owns one TPU chip; every host role is pinned to the CPU backend —
    # train/gang.py assign_devices) | cpu (every rank on the host).
    device_policy="workers_accel",
    # Gang wire: shm (one host) | tcp (cross-host; tcp_addrs = one
    # host:port per rank, comma-separated — the hostfile analog).
    transport="shm",
    tcp_addrs="",
    gang_barrier=True,  # startup rendezvous before any role traffic
    # Server shard checkpointing + resume (beyond-reference — SURVEY §5:
    # the reference never checkpoints server state).  server_ckpt_dir
    # activates periodic per-server shard+rule-state snapshots; --resume
    # restores them and skips client seeding so Adam/RMSProp moments
    # survive a restart.
    server_ckpt_dir="",
    server_ckpt_interval=30.0,
    resume=False,
    # Wire codec for every client<->server shard transfer (comm/codec.py:
    # none | bf16 | int8).  "" defers to $MPIT_PS_CODEC (default none).
    # When set explicitly the servers are PINNED to it, so a rank whose
    # environment disagrees fails its INIT loudly instead of training on
    # corrupt frames.
    codec="",
    # Fault tolerance (mpit_tpu.ft; 0 = off, the legacy wire).  Heartbeat
    # interval for workers, lease TTL for servers (expired => eviction),
    # per-op deadline for workers (enables retry + FT frame headers), and
    # supervise = restarts allowed per rank (the supervisor respawns dead
    # ranks with a bumped epoch; workers rejoin via INIT v3, servers
    # resume from their stamped shard snapshot — needs server_ckpt_dir).
    ft_heartbeat_s=0.0,
    ft_lease_ttl_s=0.0,
    ft_op_deadline_s=0.0,
    ft_max_retries=8,
    # Gradient-staleness telemetry (obs): frames carry the 24-byte
    # [epoch, seq, version] header so servers measure the basis gap per
    # applied grad (mpit_ps_grad_staleness).  Needs ft_op_deadline_s > 0
    # (rides the framed wire); silently off otherwise.
    ft_staleness=False,
    # Causal-timing telemetry (obs/clock, obs/causal; PROTOCOL.md §6.7):
    # frames carry a send stamp, acks/replies a [t_tx, t_recv, t_ack]
    # tail, and heartbeats are echoed — feeding the per-peer clock
    # offset estimator so `python -m mpit_tpu.obs analyze` can join and
    # decompose the gang's trace.  Needs ft_op_deadline_s > 0.
    ft_timing=False,
    # Pipelined streaming transfers (docs/PROTOCOL.md §12): GRAD /
    # PARAM / PARAM_PUSH bodies ship as ~this-many-byte chunk frames so
    # encode, wire and apply overlap on big shards.  Needs
    # ft_op_deadline_s > 0 (chunk retry/dedup ride the framed
    # machinery) and an element-wise server rule; off under shardctl.
    ft_chunk_bytes=0,
    supervise=0,
    # shardctl (mpit_tpu.shardctl): the LAST rank becomes the shard-map
    # controller (the rest split into servers/clients as usual), clients
    # address shards through a versioned map, and the controller
    # rebalances hot shards / fails over a dead server's shards from its
    # checkpoints.  Requires ft_op_deadline_s > 0 (re-routing rides the
    # retry machinery).  shardctl_ratio tunes the rebalance trigger;
    # shardctl_lease_ttl_s > 0 arms server leases at the controller
    # (expiry => shard failover; pair with server_ckpt_dir).
    shardctl=False,
    shardctl_ratio=3.0,
    shardctl_lease_ttl_s=0.0,
    # Serving tier (mpit_tpu.ps.serve; docs/PROTOCOL.md §8): the LAST
    # serve_readers ranks become READ-ONLY readers — they attach to the
    # servers with the lightweight read-only posture, pull the current
    # params serve_rounds times (pacing serve_interval_s apart), assert
    # the observed snapshot version is monotone, and stop.  Servers run
    # the admission budget (serve_budget_mb in-flight reply bytes;
    # serve_budget_reads optionally bounds the reply count) and answer
    # over-budget reads BUSY-with-retry-hint.  Requires
    # ft_op_deadline_s > 0 (BUSY recovery rides the retry machinery).
    serve_readers=0,
    serve_rounds=10,
    serve_interval_s=0.05,
    serve_budget_mb=64.0,
    serve_budget_reads=0,
    # Elastic gangs (mpit_tpu.ft.elastic; docs/PROTOCOL.md §9): --elastic
    # composes shardctl + the supervisor into dynamic membership.
    # elastic_spares reserves that many joiner-server rank slots beyond
    # --np (membership has a provisioned rank-space ceiling; spares
    # spawn only when the controller asks the supervisor through the
    # scale mailbox — or an operator hits the controller's /scale
    # route).  Servers install a SIGTERM preemption notice
    # (checkpoint-on-notice + a PREEMPT report; elastic_grace_s is the
    # window they announce), and the initial cut makes
    # elastic_shards_per_server shards per launch server so scale
    # events have units to move.  Implies shardctl; requires
    # supervise >= 1, ft_op_deadline_s > 0 and server_ckpt_dir; forces
    # the startup barrier off when spares > 0 (spare ranks are not
    # running at launch).
    elastic=False,
    elastic_spares=1,
    elastic_grace_s=5.0,
    elastic_shards_per_server=2,
    # Closed-loop autoscaling (mpit_tpu.shardctl.autoscale;
    # docs/OPERATIONS.md): --autoscale implies --elastic and attaches
    # an SLO-driven policy engine to the controller, which samples the
    # gang through every rank's statusd endpoint (requires
    # MPIT_OBS_HTTP — the same read path `mpit top` uses) and drives
    # the §9 scale verbs automatically; the operator /scale route keeps
    # precedence.  Targets: 0 disables a signal.  The policy's
    # hysteresis/cooldown/flap knobs take the AutoscaleConfig defaults
    # unless overridden here.
    autoscale=False,
    autoscale_p99_ms=0.0,
    autoscale_busy_ratio=0.0,
    autoscale_staleness=0.0,
    autoscale_sendq=0.0,
    autoscale_window_s=2.0,
    autoscale_cooldown_s=20.0,
    autoscale_flap_budget=3,
    autoscale_min_servers=1,
    autoscale_max_servers=0,  # 0 = every provisioned server slot
    # Hierarchical aggregation (mpit_tpu.agg; docs/PROTOCOL.md §13):
    # --agg off|prereduce|tree.  prereduce folds colocated client
    # groups on-device behind a representative; tree additionally
    # reduces representatives through a deterministic REDUCE tree so
    # the servers see ONE gradient per round for the whole gang.
    # agg_groups declares colocation ("4,5;6,7" — ranks sharing a
    # process/backend; empty = every client its own representative),
    # verified against the dplane fingerprint at start.  Requires
    # ft_op_deadline_s > 0 (REDUCE hops ride the framed retry/dedup
    # machinery); off under shardctl and --dplane (the exchange client
    # wraps the same seam).  agg_deadline_s is the straggler wall
    # deadline (§13.4); agg_chunk_bytes cuts the REDUCE hops (0 =
    # ft_chunk_bytes, then 1 MiB).
    agg="off",
    agg_groups="",
    agg_fanin=2,
    agg_tree_seed=0,
    agg_deadline_s=5.0,
    agg_chunk_bytes=0,
    # Flagship LM workload (mpit_tpu.lm; docs/WORKLOADS.md): --lm 1
    # swaps the MNIST trainer for the sharded transformer-LM loop.  The
    # shared optimizer knobs (--opt/--lr/--mom/--mva/--su/--batch/
    # --seed/--dtype) carry over; the lm_* knobs size the model and the
    # step loop.  Unless shardctl owns placement, every client AND
    # reader announces the same weighted aligned-cut layout
    # (mpit_tpu.lm.plan over the params+optimizer pytree) instead of
    # the equal split — lm_weights skews it ("3,1" = server 0 aims at
    # 3/4 of the vector), empty = balanced cut on parameter boundaries.
    lm=0,
    # the block (lm/archs.py BLOCKS) and every block's sizes, one switch
    # each: the table there has each one's name, default and meaning,
    # and which block takes which
    lm_arch="gpt2",
    **{switch: _lm_archs.DEFAULTS[name]
       for name, switch in _lm_archs.SWITCHES.items()},
    lm_steps=200,
    lm_eval_every=50,
    # -1 auto (flash on TPU) | 0 jnp reference | 1 the Pallas kernel
    # compiled by Mosaic or an error (never interpreted — chip runs pin 1)
    lm_use_flash=-1,
    lm_weights="",
    # Device-resident data plane (mpit_tpu.dplane; docs/DEVICE.md):
    # servers hold shard + optimizer state as (mesh-sharded) HBM arrays
    # with donated jitted applies and publish an in-process device
    # exchange; workers route through an ExchangeClient that takes the
    # device path to same-backend servers and falls back to the wire
    # (codecs/retry/dedup intact) everywhere else — in the process-mode
    # gang every pair crosses a process boundary, so the win there is
    # the server-side slot (no per-apply reallocation, shared snapshot
    # caches); the np=1 path and in-process harnesses get the full
    # device exchange.
    dplane=0,
)


def parse_agg_groups(spec: str) -> "Tuple[Tuple[int, ...], ...]":
    """--agg_groups "4,5;6,7" -> ((4, 5), (6, 7)): semicolon-separated
    colocation groups of comma-separated client ranks (PROTOCOL.md
    §13.0).  Empty spec = no declared colocation (every client its own
    representative)."""
    return tuple(
        tuple(int(x) for x in part.split(",") if x.strip() != "")
        for part in spec.split(";") if part.strip())


def ft_from_cfg(cfg: Config):
    """FTConfig for one rank: env base (the supervisor's restart env —
    MPIT_FT_EPOCH/MPIT_FT_REJOIN — rides there) with the launch config's
    non-zero knobs layered on top."""
    from mpit_tpu.ft import FTConfig

    overrides = {}
    for ck, fk, cast in (
        ("ft_heartbeat_s", "heartbeat_s", float),
        ("ft_lease_ttl_s", "lease_ttl_s", float),
        ("ft_op_deadline_s", "op_deadline_s", float),
    ):
        value = cast(cfg.get(ck, 0) or 0)
        if value:
            overrides[fk] = value
    if overrides.get("op_deadline_s"):
        overrides["max_retries"] = int(cfg.get("ft_max_retries", 8))
    if overrides.get("lease_ttl_s") or int(cfg.get("supervise", 0)):
        overrides["rejoin"] = True
    if bool(cfg.get("ft_staleness", False)):
        overrides["staleness"] = True
    if bool(cfg.get("ft_timing", False)):
        overrides["timing"] = True
    chunk = int(cfg.get("ft_chunk_bytes", 0) or 0)
    if chunk:
        overrides["chunk_bytes"] = chunk
    return FTConfig.from_env(**overrides)


def assign_roles(
    size: int, master_freq: int = 2, tester: str = "none"
) -> Tuple[List[int], List[int], Optional[int]]:
    """Returns (server_ranks, client_ranks, tester_rank)."""
    ranks = list(range(size))
    tester_rank: Optional[int] = None
    if tester == "first":
        tester_rank = 0
        ranks = ranks[1:]
    elif tester == "last":
        tester_rank = size - 1
        ranks = ranks[:-1]
    sranks = [r for r in ranks if r % master_freq == 0]
    cranks = [r for r in ranks if r % master_freq != 0]
    if not sranks or not cranks:
        raise ValueError(
            f"role split produced {len(sranks)} servers / {len(cranks)} "
            f"clients from size={size}, master_freq={master_freq}"
        )
    return sranks, cranks, tester_rank


def _dplane_cfg(cfg: Config):
    """PlaneConfig for --dplane servers: mesh over the default devices
    when more than one exists, single-device HBM placement otherwise."""
    from mpit_tpu.dplane import PlaneConfig

    return PlaneConfig.auto(namespace=str(cfg.get("namespace", "") or ""))


def server_rule_for(cfg: Config) -> Any:
    """The server-side shard rule matching the client optimizer
    (reference BiCNN/pserver.lua:123-197 dispatch)."""
    name = cfg.opt
    if name in ("rmsprop", "adam", "adamax", "adagrad", "adadelta"):
        return rules_mod.make(name, lr=cfg.lr)
    return rules_mod.make("add")  # downpour/easgd/eamsgd ship pre-scaled deltas


def serve_cfg_for(cfg: Config):
    """The serving tier's admission budget from the launch config."""
    from mpit_tpu.ps import ServeConfig

    return ServeConfig.from_env(
        budget_bytes=int(float(cfg.get("serve_budget_mb", 64.0)) * (1 << 20)),
        budget_reads=int(cfg.get("serve_budget_reads", 0) or 0),
    )


def lm_trainer_cfg(cfg: Config) -> Config:
    """The :data:`mpit_tpu.lm.trainer.LM_DEFAULTS`-shaped config for one
    launch config: shared optimizer/loop knobs carried over verbatim,
    lm_* knobs mapped onto the trainer's names, each in the type of its
    launch default and at that default where ``cfg`` has none."""
    def knob(key: str) -> Any:
        default = LAUNCH_DEFAULTS[key]
        return type(default)(cfg.get(key, default))

    return Config(
        **{name: knob(switch)
           for name, switch in _lm_archs.SWITCHES.items()},
        arch=knob("lm_arch"), steps=knob("lm_steps"),
        eval_every=knob("lm_eval_every"), use_flash=knob("lm_use_flash"),
        opt=cfg.opt, lr=cfg.lr, lrd=cfg.lrd, lrp=cfg.lrp, mom=cfg.mom,
        mommax=cfg.mommax, momdecay=cfg.momdecay, l2wd=cfg.l2wd,
        mva=cfg.mva, su=cfg.su, batch=cfg.batch, seed=cfg.seed,
        dtype=cfg.dtype, profile_dir=cfg.get("profile_dir", ""),
    )


def _lm_shapes(cfg: Config):
    """The LM as a worker builds it, for its parameters' shapes: they do
    not depend on the attention implementation, so a host role's never
    touches the accelerator kernels."""
    from mpit_tpu.lm.model import build, build_kw

    return build(use_flash=False, **build_kw(lm_trainer_cfg(cfg)))


def lm_layout(cfg: Config, n_servers: int):
    """The gang's static weighted aligned-cut layout (one Shard per
    server) under --lm: the deterministic cut every client and reader
    must announce identically.  ``lm_weights`` ("3,1") skews the
    targets; empty keeps balanced targets (still boundary-aligned, so
    it differs from the raw equal split)."""
    from mpit_tpu.lm import plan

    model = _lm_shapes(cfg)
    params = model.flat.unravel(model.flat.w0)
    spec = str(cfg.get("lm_weights", "") or "")
    weights = ([float(x) for x in spec.split(",") if x.strip() != ""]
               if spec else None)
    if weights is not None and len(weights) != n_servers:
        raise ValueError(
            f"--lm_weights names {len(weights)} servers but the role "
            f"split made {n_servers}")
    rule = cfg.opt if cfg.opt in rules_mod.names() else "add"
    return plan(params, n_servers, rule=rule, server_weights=weights).layout


def _serve_vec_len(cfg: Config, rank: int) -> int:
    """The flat parameter-vector length a reader must mirror — derived
    exactly the way the trainer derives it (same model ctor + flatten),
    so the reader's shard announcement matches the writers' cut."""
    import jax
    import jax.numpy as jnp

    from mpit_tpu.data.mnist import load_mnist
    from mpit_tpu.models import MnistCNN, flatten_module
    from mpit_tpu.train.trainer import MODELS

    full = TRAINER_DEFAULTS.merged(cfg.to_dict())
    if int(cfg.get("lm", 0)):
        return int(_lm_shapes(cfg).flat.size)
    x_train = load_mnist(side=full.side)[0][0]
    if full.model == "cnn":
        module = MnistCNN(num_classes=10, side=full.side)
    else:
        module = MODELS[full.model](num_classes=10)
    rng = jax.random.PRNGKey(full.seed + rank)
    sample = jnp.asarray(x_train[:2], jnp.dtype(full.dtype))
    return int(flatten_module(module, rng, sample).w0.size)


def run_reader(rank: int, sranks: List[int], cfg: Config,
               transport: Any) -> Dict[str, Any]:
    """One READ-ONLY reader rank (serve mode): attach, pull the current
    params ``serve_rounds`` times at ``serve_interval_s`` pacing, check
    version monotonicity, stop."""
    import numpy as np

    from mpit_tpu.ps import ReaderClient

    log = get_logger("serve", rank)
    rc = ReaderClient(
        rank, sranks, transport,
        codec=str(cfg.get("codec", "") or "") or None,
        ft=ft_from_cfg(cfg),
        # --lm readers must announce the identical weighted cut the
        # writers announced (servers reject a disagreeing attach).
        layout=(lm_layout(cfg, len(sranks)) if int(cfg.get("lm", 0))
                else None),
    )
    mirror = np.zeros(_serve_vec_len(cfg, rank),
                      np.dtype(str(cfg.get("dtype", "float32"))))
    rc.start(mirror)
    rounds = int(cfg.get("serve_rounds", 10))
    interval = float(cfg.get("serve_interval_s", 0.05))
    for _ in range(rounds):
        rc.read_params()
        if interval > 0:
            time.sleep(interval)
    rc.stop()
    log.info("reader done: %d reads, monotone=%s, busy honored %d",
             rc.reads_done, rc.monotone, rc.busy_honored)
    return {
        "role": "reader",
        "reads": rc.reads_done,
        "monotone": bool(rc.monotone),
        "busy_honored": rc.busy_honored,
        "retries": rc.retries,
        "versions": {str(k): v for k, v in rc.versions.items()},
    }


def _autoscaler_for(cfg: Config, ctl, size: int):
    """The controller rank's Autoscaler under --autoscale: SLO targets
    from the launch knobs, telemetry pooled over every rank's statusd
    endpoint (HttpSampler — launch_processes validated MPIT_OBS_HTTP)."""
    from mpit_tpu.obs.statusd import base_port
    from mpit_tpu.shardctl.autoscale import (
        AutoscaleConfig,
        Autoscaler,
        HttpSampler,
        SLOConfig,
    )

    slo = SLOConfig(
        p99_ms=float(cfg.get("autoscale_p99_ms", 0) or 0),
        busy_ratio=float(cfg.get("autoscale_busy_ratio", 0) or 0),
        staleness=float(cfg.get("autoscale_staleness", 0) or 0),
        send_queue=float(cfg.get("autoscale_sendq", 0) or 0),
    )
    max_servers = int(cfg.get("autoscale_max_servers", 0) or 0)
    if max_servers <= 0:
        max_servers = len(ctl.sranks) + len(ctl.spares)
    acfg = AutoscaleConfig(
        slo=slo,
        window_s=float(cfg.get("autoscale_window_s", 2.0)),
        cooldown_s=float(cfg.get("autoscale_cooldown_s", 20.0)),
        flap_budget=int(cfg.get("autoscale_flap_budget", 3)),
        min_servers=int(cfg.get("autoscale_min_servers", 1)),
        max_servers=max_servers,
    )
    sampler = HttpSampler(base_port(), nranks=size)
    return Autoscaler(ctl, acfg, sampler=sampler)


def _maybe_preemption(cfg: Config):
    """A server's SIGTERM preemption notice under --elastic (installed
    in the child's main thread — run_rank runs there); None otherwise.
    The handler only sets a flag (mtlint MT-P204); checkpoint-on-notice
    and the PREEMPT report run from the serving loop (§9.3)."""
    if not bool(cfg.get("elastic", False)):
        return None
    from mpit_tpu.ft.elastic import PreemptionNotice

    return PreemptionNotice.from_env(
        default_grace_s=float(cfg.get("elastic_grace_s", 5.0))).install()


def run_joiner_server(rank: int, cranks: List[int], cfg: Config,
                      transport: Any, ctl_rank: Optional[int]
                      ) -> Dict[str, Any]:
    """One controller-spawned joiner server (--elastic spare slot)."""
    log = get_logger("launch", rank)
    ckpt_dir = str(cfg.get("server_ckpt_dir", "") or "")
    server = ParamServer(
        rank, cranks, transport, rule=server_rule_for(cfg),
        dtype=cfg.get("dtype", "float32"),
        ckpt_dir=ckpt_dir or None,
        ckpt_interval=float(cfg.get("server_ckpt_interval", 30.0)),
        codec=str(cfg.get("codec", "") or "") or None,
        ft=ft_from_cfg(cfg),
        controller_rank=ctl_rank,
        shardctl=True,
        preempt=_maybe_preemption(cfg),
    )
    log.info("joiner server for clients %s (controller %s)", cranks, ctl_rank)
    server.start()
    return {
        "role": "server",
        "joiner": True,
        "retired": server.retired,
        "grads_applied": server.grads_applied,
        "params_served": server.params_served,
        "ckpts_written": server.ckpts_written,
    }


def run_rank(
    rank: int,
    size: int,
    cfg: Config,
    transport: Any,
    data: Any = None,
) -> Dict[str, Any]:
    """Run one rank's role to completion; returns its result dict, with
    the transport's word on which way the bytes it received went
    (``rx_direct_bytes`` / ``rx_assembled_bytes`` on the shm wire) and on
    how its rings were used (``tx_chunks``, ``tx_ring_full``, ``rx_chunks``,
    ``rx_overlap_chunks``, ``tx_early_bytes``, ``tx_split_bytes``,
    ``rx_split_bytes``)."""
    result = _run_role(rank, size, cfg, transport, data)
    if transport is not None:
        result = {**result, **transport.wire_counts()}
    return result


def _run_role(rank: int, size: int, cfg: Config, transport: Any,
              data: Any) -> Dict[str, Any]:
    log = get_logger("launch", rank)
    if size == 1:
        if bool(cfg.get("resume", False)):
            # Server-shard resume needs servers; silently restarting from
            # scratch would look like a successful resume.
            raise ValueError(
                "--resume restores parameter-server shards and needs "
                "--np > 1 (single-process runs have no servers)"
            )
        if int(cfg.get("lm", 0)):
            from mpit_tpu.lm import LmTrainer

            return {"role": "local",
                    **LmTrainer(lm_trainer_cfg(cfg), rank=rank).run()}
        trainer = MnistTrainer(cfg, pclient=None, data=data, rank=rank)
        return {"role": "local", **trainer.run()}

    elastic_on = bool(cfg.get("elastic", False))
    sc_on = bool(cfg.get("shardctl", False)) or elastic_on
    lm_on = int(cfg.get("lm", 0))
    if lm_on:
        if str(cfg.get("tester", "none")) != "none":
            raise ValueError("--lm and a tester rank are mutually "
                             "exclusive (the tester is MNIST-only)")
    # Under --elastic the transport spans the provisioned ceiling
    # (np0 + spares); roles split over the initial membership np0 and
    # ranks beyond it are joiner-server slots the controller may spawn.
    np0 = int(cfg.get("elastic_np0", 0) or 0) if elastic_on else size
    if elastic_on and not np0:
        np0 = size
    ctl_rank: Optional[int] = None
    role_size = size
    n_readers = int(cfg.get("serve_readers", 0) or 0)
    reader_ranks: List[int] = []
    if n_readers:
        if sc_on:
            raise ValueError("serve_readers and shardctl are mutually "
                             "exclusive for now")
        if str(cfg.get("tester", "none")) != "none":
            raise ValueError("serve_readers and a tester rank are mutually "
                             "exclusive for now (both claim edge ranks)")
        if float(cfg.get("ft_op_deadline_s", 0) or 0) <= 0:
            raise ValueError("serve_readers needs --ft_op_deadline_s > 0: "
                             "BUSY recovery rides the FT retry machinery")
        if size - n_readers < 2:
            raise ValueError(
                f"serve_readers={n_readers} leave "
                f"{size - n_readers} role ranks; need >= 1 "
                "server + >= 1 worker")
        role_size = size - n_readers
        reader_ranks = list(range(role_size, size))
    if sc_on:
        if str(cfg.get("tester", "none")) != "none":
            raise ValueError("shardctl and a tester rank are mutually "
                             "exclusive for now (both claim an edge rank)")
        if np0 < 3:
            raise ValueError("shardctl needs np >= 3 "
                             "(>=1 server + >=1 worker + the controller)")
        if float(cfg.get("ft_op_deadline_s", 0) or 0) <= 0:
            raise ValueError("shardctl needs --ft_op_deadline_s > 0: map "
                             "re-routing rides the FT retry machinery")
        ctl_rank = np0 - 1
        role_size = np0 - 1
    sranks, cranks, tester_rank = assign_roles(
        role_size, cfg.get("master_freq", 2), cfg.get("tester", "none")
    )
    single_mode = str(cfg.opt).endswith("-single")
    if rank in reader_ranks:
        return run_reader(rank, sranks, cfg, transport)
    if elastic_on and rank >= np0:
        # A spare slot the controller asked the supervisor to spawn:
        # a joiner server — no INIT rendezvous, shards arrive by
        # ACQUIRE, clients greet lazily (docs/PROTOCOL.md §9.1).
        return run_joiner_server(rank, cranks, cfg, transport, ctl_rank)
    if sc_on and rank == ctl_rank:
        from mpit_tpu.shardctl import RebalancePolicy, ShardController

        spawner = None
        spares: List[int] = []
        if elastic_on:
            from mpit_tpu.ft.elastic import ElasticDirectory

            spares = list(range(np0, size))
            mailbox = ElasticDirectory.from_env()
            if mailbox is not None:
                def spawner(r):
                    # Stamp the spawn request with the live set so the
                    # joiner's TCP rendezvous dials only reachable
                    # peers (train/gang.py child_transport).
                    live = sorted(
                        set(ctl._live_servers())
                        | {c for c in ctl.cranks if c not in ctl._stopped}
                        | {ctl.rank})
                    mailbox.request_spawn(r, {
                        "MPIT_ELASTIC_DIAL":
                            ",".join(str(x) for x in live if x < r)})

                retire_mark = mailbox.mark_retired
            else:
                retire_mark = None
        ctl = ShardController(
            rank, transport, sranks, cranks,
            policy=RebalancePolicy(ratio=float(cfg.get("shardctl_ratio", 3.0))),
            lease_ttl_s=float(cfg.get("shardctl_lease_ttl_s", 0) or 0),
            spawner=spawner,
            spare_ranks=spares,
        )
        if elastic_on and retire_mark is not None:
            # The supervisor must learn a retirement before the rank's
            # exit reaches its budget check — wrap scale_down to mark
            # the mailbox first.
            _scale_down = ctl.scale_down

            def scale_down_marked(r):
                retire_mark(r)
                return _scale_down(r)

            ctl.scale_down = scale_down_marked
        if bool(cfg.get("autoscale", False)):
            ctl.attach_autoscaler(_autoscaler_for(cfg, ctl, size))
        ctl.serve()
        if ctl.autoscaler is not None:
            return {
                "role": "controller",
                "map_version": getattr(ctl.smap, "version", None),
                "rebalances": int(ctl._m_rebal.value),
                "failovers": int(ctl._m_fail.value),
                "membership_epoch": ctl.membership_epoch,
                "elastic_events": {
                    "up": int(ctl._m_up.value),
                    "down": int(ctl._m_down.value),
                    "preempt": int(ctl._m_pre.value),
                },
                "autoscale": ctl.autoscaler.status_section(),
            }
        return {
            "role": "controller",
            "map_version": getattr(ctl.smap, "version", None),
            "rebalances": int(ctl._m_rebal.value),
            "failovers": int(ctl._m_fail.value),
            "membership_epoch": ctl.membership_epoch,
            "elastic_events": {
                "up": int(ctl._m_up.value),
                "down": int(ctl._m_down.value),
                "preempt": int(ctl._m_pre.value),
            },
        }
    if rank == tester_rank:
        from mpit_tpu.train.tester import run_tester

        return {"role": "tester", **run_tester(rank, sranks, cfg, transport, data)}
    import os as _os

    rejoining = _os.environ.get("MPIT_FT_REJOIN", "0") not in ("0", "")
    ft = ft_from_cfg(cfg)
    if elastic_on and rank in sranks and rejoining:
        # A supervisor-restarted server in an elastic gang rejoins as a
        # joiner: its shards already failed over to survivors (or are
        # about to), and shard-oriented checkpoints have no
        # server<rank>_latest alias to resume from.  The controller
        # rebalances onto it once its beats arm (§9.1).
        return run_joiner_server(rank, cranks, cfg, transport, ctl_rank)
    if rank in sranks:
        # The tester counts as a (pull-only) client: it announces shards and
        # participates in the stop protocol like any worker.
        all_clients = cranks + ([tester_rank] if tester_rank is not None else [])
        ckpt_dir = str(cfg.get("server_ckpt_dir", "") or "")
        server = ParamServer(
            rank, all_clients, transport, rule=server_rule_for(cfg),
            single_mode=single_mode, dtype=cfg.get("dtype", "float32"),
            ckpt_dir=ckpt_dir or None,
            ckpt_interval=float(cfg.get("server_ckpt_interval", 30.0)),
            codec=str(cfg.get("codec", "") or "") or None,
            ft=ft,
            controller_rank=ctl_rank,
            reader_ranks=reader_ranks or None,
            serve=serve_cfg_for(cfg) if reader_ranks else None,
            preempt=_maybe_preemption(cfg),
            dplane=(_dplane_cfg(cfg) if int(cfg.get("dplane", 0)) else None),
        )
        if bool(cfg.get("resume", False)):
            import pathlib

            path = pathlib.Path(ckpt_dir) / f"server{rank}_latest.npz"
            if not ckpt_dir or not path.exists():
                raise FileNotFoundError(
                    f"--resume needs --server_ckpt_dir with a "
                    f"server{rank}_latest.npz (looked at {path})"
                )
            server.restore_state(path)
            log.info("restored shard from %s", path)
        log.info("server for clients %s", cranks)
        server.start()
        return {
            "role": "server",
            "grads_applied": server.grads_applied,
            "apply_inplace": server.apply_inplace,
            "params_served": server.params_served,
            "ckpts_written": server.ckpts_written,
        }
    # On resume the restored servers are authoritative for params — no
    # client re-seeds (ps/server.py restore_state contract).  Same for a
    # supervisor-restarted worker rejoining mid-run (MPIT_FT_REJOIN): the
    # live servers hold the current center, and a re-seed would rewind it.
    pclient = ParamClient(
        rank, sranks, transport,
        seed_servers=(rank == cranks[0])
        and not bool(cfg.get("resume", False)) and not rejoining,
        codec=str(cfg.get("codec", "") or "") or None,
        ft=ft,
        shardctl=sc_on,
        controller_rank=ctl_rank,
        sc_shards_per_server=(
            int(cfg.get("elastic_shards_per_server", 2) or 1)
            if elastic_on else 1),
        # --lm: the weighted aligned-cut layout replaces the equal
        # split on the static path (shardctl owns placement otherwise).
        layout=(lm_layout(cfg, len(sranks)) if lm_on and not sc_on
                else None),
    )
    if int(cfg.get("dplane", 0)):
        from mpit_tpu.dplane import ExchangeClient

        pclient = ExchangeClient(pclient)
    agg_mode = str(cfg.get("agg", "off") or "off")
    if agg_mode != "off":
        from mpit_tpu.agg import AggClient, AggConfig

        if sc_on:
            raise ValueError("--agg composes with the static shard map "
                             "only (run without --shardctl/--elastic)")
        if int(cfg.get("dplane", 0)):
            raise ValueError("--agg and --dplane both wrap the client "
                             "data path; pick one")
        if float(cfg.get("ft_op_deadline_s", 0) or 0) <= 0:
            raise ValueError("--agg needs --ft_op_deadline_s > 0: REDUCE "
                             "hops ride the framed retry machinery")
        groups = parse_agg_groups(str(cfg.get("agg_groups", "") or ""))
        pclient = AggClient(
            pclient, cranks,
            AggConfig(mode=agg_mode, groups=groups,
                      fanin=int(cfg.get("agg_fanin", 2)),
                      tree_seed=int(cfg.get("agg_tree_seed", 0)),
                      deadline_s=float(cfg.get("agg_deadline_s", 5.0)),
                      chunk_bytes=int(cfg.get("agg_chunk_bytes", 0))),
            namespace=str(cfg.get("namespace", "") or ""))
    if lm_on:
        from mpit_tpu.lm import LmTrainer

        trainer = LmTrainer(lm_trainer_cfg(cfg), pclient=pclient, rank=rank)
    else:
        trainer = MnistTrainer(cfg, pclient=pclient, data=data, rank=rank)
    log.info("worker with servers %s", sranks)
    return {"role": "worker", **trainer.run()}


# -- process-mode launcher (the mpirun analog) -------------------------------


def expected_role(rank: int, size: int, cfg: Config) -> str:
    """The role this rank will run, derived the same way run_rank does —
    for labeling introspection endpoints/flight dumps *before* the role
    objects exist.  Best-effort: '' when the split is invalid (run_rank
    raises the real error)."""
    if size == 1:
        return "local"
    elastic_on = bool(cfg.get("elastic", False))
    sc_on = bool(cfg.get("shardctl", False)) or elastic_on
    np0 = (int(cfg.get("elastic_np0", 0) or 0) or size) if elastic_on \
        else size
    if elastic_on and rank >= np0:
        return "server"  # spare joiner slot
    if sc_on and rank == np0 - 1:
        return "controller"
    n_readers = int(cfg.get("serve_readers", 0) or 0)
    if n_readers and rank >= size - n_readers:
        return "reader"
    try:
        sranks, _cranks, tester_rank = assign_roles(
            np0 - 1 if sc_on else size - n_readers,
            int(cfg.get("master_freq", 2)),
            str(cfg.get("tester", "none")))
    except ValueError:
        return ""
    if rank == tester_rank:
        return "tester"
    return "server" if rank in sranks else "worker"


def _child_main() -> None:
    from mpit_tpu.train.gang import child_env, child_transport, write_result

    rank, size, cfg = child_env()
    # Live introspection (obs/statusd; no-op unless MPIT_OBS_HTTP is
    # set): serve /metrics, /status and /trace on base_port + rank for
    # the whole life of this rank.  Flight dumps inherit the identity.
    from mpit_tpu.obs import get_flight, maybe_start_statusd

    role = expected_role(rank, size, cfg)
    maybe_start_statusd(rank, role=role)
    get_flight().set_identity(rank=rank, role=role)
    transport = child_transport(cfg, rank, size)
    result = run_rank(rank, size, cfg, transport)
    transport.close()
    # Per-rank Chrome-trace part (MPIT_OBS_TRACE; no-op when unset) —
    # the gang parent merges the parts into one timeline at exit.
    from mpit_tpu.obs import maybe_write_rank_trace

    maybe_write_rank_trace(rank, role=str(result.get("role", "")))
    write_result(result)


def device_env_overrides(cfg: Config, size: int) -> Dict[int, Dict[str, str]]:
    """Per-rank device environment from cfg.device_policy."""
    policy = cfg.get("device_policy", "workers_accel")
    if policy == "cpu":
        return {r: {"JAX_PLATFORMS": "cpu"} for r in range(size)}
    if policy != "workers_accel":
        raise ValueError(
            f"device_policy must be workers_accel|cpu, got {policy!r}")
    from mpit_tpu.train.gang import assign_devices

    # The chip owners are the ranks that train or test.  Under shardctl
    # the last rank is the controller; under --elastic the split runs
    # over the initial membership (spare joiner slots are servers);
    # readers sit past the role ranks — host roles all.
    role_size = int(cfg.get("elastic_np0", 0) or 0) or size
    if bool(cfg.get("shardctl", False)) or bool(cfg.get("elastic", False)):
        role_size -= 1
    role_size -= int(cfg.get("serve_readers", 0) or 0)
    _sranks, cranks, tester = assign_roles(
        role_size, int(cfg.get("master_freq", 2)),
        str(cfg.get("tester", "none")))
    return assign_devices(
        size, cranks + ([tester] if tester is not None else []))


def launch_processes(cfg: Config, timeout: float = 3600.0) -> Dict[int, Dict[str, Any]]:
    # Fail fast in the parent: a bad optimizer name discovered only inside a
    # worker child would strand the server children in their stop protocol.
    if int(cfg.get("lm", 0)):
        from mpit_tpu.lm import LmTrainer

        if cfg.opt not in LmTrainer.KNOWN_OPTS:
            raise ValueError(
                f"unknown LM optimizer {cfg.opt!r}; have "
                f"{LmTrainer.KNOWN_OPTS}"
            )
    elif cfg.opt not in MnistTrainer.KNOWN_OPTS:
        raise ValueError(
            f"unknown optimizer {cfg.opt!r}; have {MnistTrainer.KNOWN_OPTS}"
        )
    restarts = int(cfg.get("supervise", 0))
    if bool(cfg.get("autoscale", False)):
        # --autoscale = --elastic + the closed loop on the controller.
        # The loop's telemetry rides the statusd endpoints, so the gang
        # must be serving them; failing here beats a controller that
        # silently samples nothing and never scales.
        from mpit_tpu.obs.statusd import base_port as _obs_base_port

        if _obs_base_port() is None:
            raise ValueError(
                "--autoscale needs MPIT_OBS_HTTP=<base_port>: the "
                "autoscaler samples the gang through the statusd "
                "endpoints (the same read path `mpit top` uses)")
        if not any(float(cfg.get(k, 0) or 0) > 0 for k in
                   ("autoscale_p99_ms", "autoscale_busy_ratio",
                    "autoscale_staleness", "autoscale_sendq")):
            raise ValueError(
                "--autoscale needs at least one SLO target "
                "(--autoscale_p99_ms / _busy_ratio / _staleness / "
                "_sendq)")
        cfg = cfg.merged(elastic=True)
    if bool(cfg.get("elastic", False)):
        # --elastic (docs/PROTOCOL.md §9): shardctl + supervisor + the
        # scale mailbox, over a provisioned rank-space ceiling of
        # np + elastic_spares.  Spare slots spawn only on controller
        # request; membership changes never restart the gang.
        import os
        import tempfile as _tempfile

        from mpit_tpu.ft.elastic import ENV_DIR, ENV_GRACE_S, ElasticDirectory
        from mpit_tpu.ft.supervisor import RestartPolicy, supervise_gang

        if restarts <= 0:
            raise ValueError("--elastic needs --supervise >= 1: the "
                             "supervisor is what spawns and retires ranks")
        if not str(cfg.get("server_ckpt_dir", "") or ""):
            raise ValueError("--elastic needs --server_ckpt_dir: "
                             "checkpoint-on-notice and shard failover "
                             "write there")
        if float(cfg.get("ft_op_deadline_s", 0) or 0) <= 0:
            raise ValueError("--elastic needs --ft_op_deadline_s > 0: "
                             "membership changes ride the retry machinery")
        np0 = int(cfg.np)
        spares = max(int(cfg.get("elastic_spares", 1) or 0), 0)
        total = np0 + spares
        cfg = cfg.merged(np=total, elastic_np0=np0, shardctl=True)
        if spares > 0:
            cfg = cfg.merged(gang_barrier=False)
        mailbox = ElasticDirectory(
            _tempfile.mkdtemp(prefix="mpit_elastic_"))
        env_overrides = device_env_overrides(cfg, total)
        for r in range(total):
            env_overrides.setdefault(r, {})
            env_overrides[r][ENV_DIR] = str(mailbox.root)
            env_overrides[r][ENV_GRACE_S] = str(
                float(cfg.get("elastic_grace_s", 5.0)))
            if str(cfg.get("transport", "shm")) == "tcp":
                # Spare slots join (and rejoiners re-join) through the
                # event loop's persistent accept service — every rank
                # must agree on reconnect mode (it is part of the mesh
                # handshake digest).
                env_overrides[r].setdefault(
                    "MPIT_TCP_RECONNECT_S",
                    os.environ.get("MPIT_TCP_RECONNECT_S", "60"))
        sranks, _cranks, _tester = assign_roles(
            np0 - 1, int(cfg.get("master_freq", 2)), "none")
        return supervise_gang(
            "mpit_tpu.train.launch", cfg, timeout,
            policy=RestartPolicy(max_restarts=restarts),
            env_overrides=env_overrides,
            server_ranks=sranks + list(range(np0, total)),
            initial_ranks=range(np0),
            elastic_dir=mailbox,
        )
    if restarts > 0:
        from mpit_tpu.ft.supervisor import RestartPolicy, supervise_gang

        sranks, _cranks, _tester = assign_roles(
            int(cfg.np), int(cfg.get("master_freq", 2)),
            str(cfg.get("tester", "none")),
        )
        return supervise_gang(
            "mpit_tpu.train.launch", cfg, timeout,
            policy=RestartPolicy(max_restarts=restarts),
            env_overrides=device_env_overrides(cfg, int(cfg.np)),
            server_ranks=sranks,
        )
    from mpit_tpu.train.gang import launch_gang

    return launch_gang(
        "mpit_tpu.train.launch", cfg, timeout,
        env_overrides=device_env_overrides(cfg, int(cfg.np)),
    )


def main(argv: Optional[List[str]] = None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--child" in argv:
        _child_main()
        return
    cfg = LAUNCH_DEFAULTS.parse_args(argv)
    t0 = time.monotonic()
    if int(cfg.np) == 1:
        from mpit_tpu.obs import maybe_start_statusd
        from mpit_tpu.utils.platform import device_report, enable_compile_cache

        enable_compile_cache()
        maybe_start_statusd(0, role="local")
        result = {**run_rank(0, 1, cfg, transport=None), **device_report()}
        from mpit_tpu.obs import maybe_merge_rank_traces, maybe_write_rank_trace

        maybe_write_rank_trace(0, role=str(result.get("role", "")))
        maybe_merge_rank_traces()
        print(json.dumps({"rank0": _summarize(result)}, indent=2))
    else:
        results = launch_processes(cfg)
        print(
            json.dumps(
                {str(r): _summarize(res) for r, res in sorted(results.items())},
                indent=2,
            )
        )
    print(f"total wall time: {time.monotonic() - t0:.1f}s")


def _summarize(result: Dict[str, Any]) -> Dict[str, Any]:
    keep = {"role", "final_test_err", "time_to_target", "elapsed",
            "grads_applied", "apply_inplace", "params_served",
            "best_test_err",
            "reads", "monotone", "busy_honored",
            "final_loss", "final_eval_loss", "tokens_per_s", "tokens_total",
            "steps", "rx_direct_bytes",
            "rx_assembled_bytes", "tx_chunks", "tx_ring_full", "rx_chunks",
            "rx_overlap_chunks", "tx_early_bytes", "tx_split_bytes",
            "rx_split_bytes", "train_seconds",
            "first_step_seconds", "mosaic_calls",
            "moe_load_max_over_mean",
            "platform", "device_kind", "device_count", "device_ids",
            "chip_nodes"}
    return {k: v for k, v in result.items() if k in keep}


if __name__ == "__main__":
    main()
