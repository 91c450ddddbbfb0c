"""BiCNN launcher — the plaunch.lua analog.

Reproduces the reference's start-point semantics (BiCNN/plaunch.lua):
the ~50-flag config surface (:7-69, here BICNN_DEFAULTS), ``maxrank``
parking of excess ranks (:90-96), per-rank seeding (:113-115), and the
role table (:123-163):

- ``testerfirst``: rank 0 is the dedicated tester ('pe'); among ranks
  1..size-1 every ``master_freq``-th is a server ('ps'), the rest are
  training clients ('pt');
- ``testerlast``: among ranks 0..size-2 every rank with
  ``(i+1) % master_freq == 0`` is a server; rank size-1 is the tester;
- ``valid_mode='lastClient'`` marks the last client to ALSO run test3
  in-train every commperiod (plaunch.lua:166-167, bicnn.lua:625-633);
  ``'additionalTester'`` requires testerfirst or testerlast
  (plaunch.lua:169-177).

Parked ranks return immediately with role='parked' instead of the
reference's infinite sleep loop (plaunch.lua:92-95) so gangs always
terminate.

Usage:
    python -m mpit_tpu.train.bicnn_launch --np 4 --optimization downpour \\
        --valid_mode none
    python -m mpit_tpu.train.bicnn_launch --np 6 --optimization eamsgd \\
        --testerfirst true --valid_mode additionalTester

(The default ``valid_mode='additionalTester'`` needs ``testerfirst`` or
``testerlast``, exactly like the reference errors on its defaults,
plaunch.lua:169-177; the parent validates the combination before
spawning so a bad config never strands a gang.)
"""

from __future__ import annotations

import json
import sys
import time
from typing import Any, Dict, List, Optional, Set, Tuple

from mpit_tpu.ps import ParamClient, ParamServer
from mpit_tpu.train.bicnn import BICNN_DEFAULTS, BiCNNTrainer, server_rule_for
from mpit_tpu.utils.config import Config
from mpit_tpu.utils.logging import get_logger

BICNN_LAUNCH_DEFAULTS = BICNN_DEFAULTS.merged(
    np=1,
    ring_mb=64,
    namespace="",
    # Canonical tester surface shared with train.launch: none|first|last.
    # The reference-parity booleans (testerfirst/testerlast,
    # plaunch.lua:10-12) remain as aliases; setting both surfaces
    # inconsistently is an error.
    tester="",
    gang_barrier=True,  # startup rendezvous before any role traffic
)


def resolve_tester_flags(cfg: Config) -> tuple[bool, bool]:
    """Unify the two tester dialects into (testerfirst, testerlast).

    ``tester=none|first|last`` (the :mod:`mpit_tpu.train.launch` surface)
    wins when set; the plaunch-parity booleans are aliases.  A conflict
    between the two surfaces raises rather than silently preferring one.
    """
    t = str(cfg.get("tester", "") or "").strip().lower()
    tf, tl = bool(cfg.get("testerfirst", False)), bool(cfg.get("testerlast", False))
    if not t:
        return tf, tl
    if t not in ("none", "first", "last"):
        raise ValueError(f"tester must be none|first|last, got {t!r}")
    want = (t == "first", t == "last")
    if (tf or tl) and (tf, tl) != want:
        raise ValueError(
            f"conflicting tester config: tester={t!r} vs "
            f"testerfirst={tf} testerlast={tl}"
        )
    return want


def assign_roles(
    size: int,
    master_freq: int = 2,
    testerfirst: bool = False,
    testerlast: bool = False,
    valid_mode: str = "additionalTester",
) -> Tuple[List[int], List[int], Optional[int], Set[int]]:
    """(server_ranks, client_ranks, tester_rank, tranks) per
    plaunch.lua:123-177.  ``client_ranks`` includes the tester — it joins
    the PS protocol as a pull-only client, exactly like conf.cranks there.
    ``tranks`` marks ranks that run test3 (the conf.tranks table)."""
    if testerfirst and testerlast:
        raise ValueError("testerfirst and testerlast are mutually exclusive")
    sranks: List[int] = []
    cranks: List[int] = []
    tester_rank: Optional[int] = None
    if testerfirst:
        tester_rank = 0
        cranks.append(0)
        for i in range(1, size):
            (cranks if i % master_freq != 0 else sranks).append(i)
    elif testerlast:
        for i in range(size - 1):
            (cranks if (i + 1) % master_freq != 0 else sranks).append(i)
        tester_rank = size - 1
        cranks.append(tester_rank)
    else:
        # No dedicated tester: the asyncsgd parity split (mlaunch.lua:25-31).
        for i in range(size):
            (sranks if i % master_freq == 0 else cranks).append(i)
    training_clients = [c for c in cranks if c != tester_rank]
    if not sranks or not training_clients:
        raise ValueError(
            f"role split produced {len(sranks)} servers and no training "
            f"clients from size={size}, master_freq={master_freq}"
        )
    tranks: Set[int] = set()
    if valid_mode == "lastClient":
        # The highest-ranked *training client* (plaunch.lua:166-167 adds
        # size-1, which there is always a client; here the last rank may
        # be a server, so pick the last rank that actually trains).
        tranks.add(training_clients[-1])
    elif valid_mode == "additionalTester":
        if tester_rank is None:
            # plaunch.lua:169-177 errors on this combination too.
            raise ValueError(
                "valid_mode='additionalTester' requires testerfirst or testerlast"
            )
        tranks.add(tester_rank)
    elif valid_mode != "none":
        raise ValueError(f"unknown valid_mode {valid_mode!r}")
    return sranks, cranks, tester_rank, tranks


def run_rank(
    rank: int,
    size: int,
    cfg: Config,
    transport: Any,
    data: Any = None,
) -> Dict[str, Any]:
    """One rank's role to completion; returns its result dict."""
    log = get_logger("plaunch", rank)
    # maxrank parking (plaunch.lua:90-96): the effective world is
    # min(size, maxrank+1); excess ranks do nothing.
    effective = min(size, int(cfg.maxrank) + 1)
    if rank >= effective:
        log.info("rank %d > maxrank %d: parked", rank, cfg.maxrank)
        return {"role": "parked"}
    if effective == 1:
        # Single-process = the claunch analog: only local optimizers make
        # sense (SURVEY.md section 3.2); refusing beats silently training
        # with a different rule than the one configured.
        if cfg.optimization != "sgd":
            raise ValueError(
                f"single-process runs support optimization='sgd' only "
                f"(got {cfg.optimization!r}); distributed optimizers need "
                f"--np > 1"
            )
        trainer = BiCNNTrainer(cfg, None, data, rank)
        return {"role": "local", **trainer.run()}
    testerfirst, testerlast = resolve_tester_flags(cfg)
    sranks, cranks, tester_rank, tranks = assign_roles(
        effective, int(cfg.master_freq), testerfirst, testerlast,
        str(cfg.valid_mode),
    )
    if rank in sranks:
        server = ParamServer(
            rank, cranks, transport,
            rule=server_rule_for(cfg),
            single_mode=bool(cfg.singlemode)
            or cfg.optimization.endswith("single"),
            dtype=cfg.get("dtype", "float32"),
        )
        log.info("server for clients %s", cranks)
        server.start()
        return {
            "role": "server",
            "grads_applied": server.grads_applied,
            "params_served": server.params_served,
        }
    # The FIRST entry of cranks seeds the initial params (reference
    # pclient.lua:125-128 — with testerfirst that is the tester itself,
    # whose freshly-built model provides the init, bicnn.lua:268-271).
    pclient = ParamClient(
        rank, sranks, transport, seed_servers=(rank == cranks[0])
    )
    trainer = BiCNNTrainer(cfg, pclient=pclient, data=data, rank=rank)
    if rank == tester_rank:
        log.info("tester with servers %s", sranks)
        return {"role": "tester", **trainer.run_tester()}
    log.info("worker with servers %s", sranks)
    return {"role": "worker", **trainer.run(is_last_client=rank in tranks)}


def _child_main() -> None:
    from mpit_tpu.train.gang import child_env, child_transport, write_result

    rank, size, cfg = child_env()
    # Live introspection endpoint (obs/statusd; no-op unless
    # MPIT_OBS_HTTP is set) — same hook as train/launch.py children.
    from mpit_tpu.obs import maybe_start_statusd

    maybe_start_statusd(rank)
    transport = child_transport(cfg, rank, size)
    result = {**run_rank(rank, size, cfg, transport),
              **transport.wire_counts()}
    transport.close()
    write_result(result)


def main(argv: Optional[List[str]] = None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--child" in argv:
        _child_main()
        return
    cfg = BICNN_LAUNCH_DEFAULTS.parse_args(argv)
    # Fail fast in the parent: a bad optimizer name or role split discovered
    # only inside a child would strand its gang peers in the stop protocol.
    if cfg.optimization not in BiCNNTrainer.KNOWN_OPTS:
        raise ValueError(
            f"unknown optimization {cfg.optimization!r}; "
            f"have {BiCNNTrainer.KNOWN_OPTS}"
        )
    from mpit_tpu.train.bicnn import explicit_qa_files

    if cfg.get("docqa", False) and not explicit_qa_files(cfg):
        # Explicit --*_file flags take precedence over the fixture (the
        # trainer's _load_data order), so only the fixture-needing case
        # is validated here — in the parent, so a gang is never spawned
        # to fail rank by rank.
        from mpit_tpu.data.qa import docqa_paths

        if docqa_paths() is None:
            raise FileNotFoundError(
                "--docqa 1 but data/fixtures/docqa is absent — run "
                "tools/make_docqa.py or pass explicit --*_file flags"
            )
    effective = min(int(cfg.np), int(cfg.maxrank) + 1)
    tester_flags = resolve_tester_flags(cfg)  # validate even for np=1
    trainers = [0]  # maxrank=0 parks all but a local rank 0
    if effective > 1:
        _sranks, trainers, _tester, _tranks = assign_roles(
            effective, int(cfg.master_freq), *tester_flags,
            str(cfg.valid_mode),
        )
    t0 = time.monotonic()
    if int(cfg.np) == 1:
        from mpit_tpu.utils.platform import device_report, enable_compile_cache

        enable_compile_cache()
        result = {**run_rank(0, 1, cfg, transport=None), **device_report()}
        print(json.dumps({"rank0": _summarize(result)}, indent=2))
    else:
        from mpit_tpu.train.gang import assign_devices, launch_gang

        # A chip for every client (the tester is one); servers and
        # parked ranks stay on the host.
        results = launch_gang(
            "mpit_tpu.train.bicnn_launch", cfg,
            env_overrides=assign_devices(int(cfg.np), trainers))
        print(json.dumps(
            {str(r): _summarize(res) for r, res in sorted(results.items())},
            indent=2,
        ))
    print(f"total {time.monotonic() - t0:.1f}s")


def _summarize(result: Dict[str, Any]) -> Dict[str, Any]:
    out = {k: v for k, v in result.items() if k != "history"}
    history = result.get("history")
    if history:
        out["last"] = history[-1]
    return out


if __name__ == "__main__":
    main()
