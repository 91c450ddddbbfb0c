"""PS push/pull bandwidth benchmark — the asyncsgd/ptest.lua analog.

The reference measures bi-directional parameter-server bandwidth: half
the ranks serve shards of a big flat vector, the rest run T rounds of
{pull params, push grads, wait} and print ``2*T*ssize*4/elapsed`` MB/s
(reference asyncsgd/ptest.lua:3,58-67; BASELINE.md config 4).  This
script measures both rebuild transports:

- **ici** — the on-mesh path: one jitted round = reduce-scatter(grad) +
  shard apply + all-gather(param) over the ``shard`` axis
  (:func:`mpit_tpu.parallel.collective.ps_pushpull`), i.e. the traffic
  pattern the reference drives through MPI, riding ICI instead.
- **shm** — the host path: ParamClient/ParamServer over the native C++
  shared-memory transport, **one OS process per rank** (the reference's
  ``mpirun -np N`` shape; train/gang.py is the trainer's analog of the
  same spawner).  ``MPIT_BENCH_GANG=threads`` keeps the old
  all-ranks-in-one-process mode, but that shares a single GIL across
  every rank's scheduler and codec work: the convoy effect slows the
  tiled int8 encoder ~10x under three busy sibling threads (measured on
  the 1-core bench host), so thread-mode numbers understate every codec
  and flatten A/B ratios — use it only for debugging.

Env knobs: MPIT_BENCH_MB (payload size, default 64), MPIT_BENCH_ROUNDS
(default 20), MPIT_BENCH_MODE (ici|shm|both, default both),
MPIT_BENCH_SERVERS / MPIT_BENCH_CLIENTS for the shm topology (default
2/2, the reference's np=4 split), MPIT_BENCH_GANG (procs|threads,
default procs), MPIT_PS_CODEC (wire codec for the shm leg —
comm/codec.py), and MPIT_BENCH_CODECS (comma list, e.g.
"none,bf16,int8": run the shm leg once per codec — the codec A/B sweep,
docs/PROTOCOL.md §5).  MPIT_BENCH_REPS (default 1 here) repeats each
shm leg and reports the median + per-run values.  MPIT_BENCH_DECOMP=1
adds a causally-traced leg whose row carries per-phase p50/p99 latency
from `obs analyze` (docs/OBSERVABILITY.md, *Causal op tracing*).
MPIT_BENCH_PROFILE=1 adds the CPU/utilization attribution columns from
`obs profile` (per-rank core use, pool overlap efficiency, the
encode-while-wire fraction) to a gate-exempt codec=none overhead leg,
the chunked stream legs and the agg legs (docs/OBSERVABILITY.md,
*CPU/utilization attribution*).

Prints one JSON line per mode (and per codec in a sweep): MB/s
bi-directional, plus per-chip for the ici mode.  MB/s counts *logical*
payload bytes (2 * size * 4 per round per client) — with a quantizing
codec the wire moves fewer bytes, which is exactly the effect being
measured.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _common import join_checked, log as _log, shm_gang  # noqa: E402

MB = float(os.environ.get("MPIT_BENCH_MB", "64"))
ROUNDS = int(os.environ.get("MPIT_BENCH_ROUNDS", "20"))
MODE = os.environ.get("MPIT_BENCH_MODE", "both")
NSERVERS = int(os.environ.get("MPIT_BENCH_SERVERS", "2"))
NCLIENTS = int(os.environ.get("MPIT_BENCH_CLIENTS", "2"))
CODECS = [c for c in os.environ.get("MPIT_BENCH_CODECS", "").split(",") if c]
REPS = max(int(os.environ.get("MPIT_BENCH_REPS", "1")), 1)
GANG = os.environ.get("MPIT_BENCH_GANG", "procs")
# MPIT_BENCH_HEARTBEAT=1: run each shm leg twice — heartbeats (and the
# server lease registry) off, then on — and record the column, so the
# liveness tax on the PS hot path is a measured number, not a guess.
# Heartbeats only; FT frame headers (op deadlines) are a different mode
# with a known staging-copy cost and are not part of this sweep.
HEARTBEAT_SWEEP = os.environ.get("MPIT_BENCH_HEARTBEAT", "") not in ("", "0")
# MPIT_BENCH_OBS=1: run each shm leg twice — observability (registry
# counters + op spans, MPIT_OBS) off, then on — mirroring the heartbeat
# sweep, so the instrumentation tax on the PS hot path is a measured
# number.  The trace *exporter* is not part of the sweep (it runs at
# exit, off the timed window); what this measures is the per-op span
# and per-message counter cost.
OBS_SWEEP = os.environ.get("MPIT_BENCH_OBS", "") not in ("", "0")
# MPIT_BENCH_STATUS=1: run one extra codec=none shm leg with the live
# introspection endpoints up (MPIT_OBS_HTTP: obs registry + statusd
# thread in every gang child) and a parent-side poller hitting rank 0's
# /metrics throughout the timed window — live serving under load, as a
# measured column.  The leg joins the codec=none baseline gate, so
# serving scrapes while moving bytes must hold the captured record.
STATUS_SWEEP = os.environ.get("MPIT_BENCH_STATUS", "") not in ("", "0")
STATUS_PORT = int(os.environ.get("MPIT_BENCH_STATUS_PORT", "8390"))
# MPIT_BENCH_SKEW=1: run the shm leg twice more under an injected
# straggler — one server's replies are delay-injected (ft/faults.py,
# MPIT_BENCH_SKEW_POLLS test()-polls per reply) — first with the
# shardctl rebalance policy off (static map), then on.  The on-leg's
# controller migrates the slow server's shard away once its busy-report
# dominates, so the column pair measures what the rebalancer is worth
# under skew (docs/PROTOCOL.md §7.6; ISSUE 5 bar: on >= 1.2x off).
SKEW_SWEEP = os.environ.get("MPIT_BENCH_SKEW", "") not in ("", "0")
# MPIT_BENCH_DECOMP=1: run one extra codec=none leg with the causal
# tracing surface fully on — obs + Chrome-trace parts in every child,
# the framed wire with FLAG_TIMING (clock-offset tails, PROTOCOL.md
# §6.7) — then merge the per-rank parts and run the causal analyzer
# (obs/causal.py) on the gang's own trace: per-phase p50/p99 latency
# (encode/send-queue/wire/server-queue/apply/ack-wire/...) lands in the
# BENCH json next to MB/s.  The leg runs the *framed* wire (a protocol
# mode with a known staging-copy cost, like the skew legs), so it is
# excluded from the codec=none baseline gate; the plain codec=none leg
# in the same sweep still must clear it.
DECOMP_SWEEP = os.environ.get("MPIT_BENCH_DECOMP", "") not in ("", "0")
DECOMP_DEADLINE = float(os.environ.get("MPIT_BENCH_DECOMP_DEADLINE", "120"))
# 600 polls per reply ~ hundreds of ms of straggle per ack at bench
# scale — enough to dominate a round (40 was invisible next to a
# multi-MB shard transfer, measured off==on within noise).
SKEW_POLLS = int(os.environ.get("MPIT_BENCH_SKEW_POLLS", "600"))
SKEW_DEADLINE = float(os.environ.get("MPIT_BENCH_SKEW_DEADLINE", "30"))
# MPIT_BENCH_READERS="2,64,512": the many-client serving sweep (ISSUE 8,
# ROADMAP item 1).  Per count N, a TCP gang — MPIT_BENCH_SERVERS servers
# + 1 writer + N READ-ONLY readers (mpit_tpu.ps.serve) spread over a few
# reader-host processes — runs paced whole-vector reads against the
# epoll event-loop transport: every reader pulls the current params
# MPIT_BENCH_READER_ROUNDS times, one read per
# MPIT_BENCH_READER_INTERVAL_S (start-staggered), while the writer bumps
# the param version once per interval.  The row records pooled
# per-client PARAM p50/p99 latency, aggregate MB/s, BUSY admission
# counts, and the snapshot-cache counters — the acceptance bar is p50
# flat within 2x from 64 -> 512 readers while snapshot_copies stays at
# one per committed version (the N-readers=1-copy invariant at
# hundreds of connections).  Separate knobs from the shm legs: the
# serving sweep measures read-latency-under-fanout, not bulk bandwidth.
READERS_SWEEP = [int(x) for x in
                 os.environ.get("MPIT_BENCH_READERS", "").split(",") if x]
READER_MB = float(os.environ.get("MPIT_BENCH_READER_MB", "0.25"))
READER_ROUNDS = int(os.environ.get("MPIT_BENCH_READER_ROUNDS", "6"))
READER_INTERVAL = float(os.environ.get("MPIT_BENCH_READER_INTERVAL_S", "1.0"))
READER_BUDGET_MB = float(os.environ.get("MPIT_BENCH_READER_BUDGET_MB", "8"))
# MPIT_BENCH_CELLS="1,2,3": the multi-cell serving-fabric sweep (ISSUE
# 12, PROTOCOL.md §11).  Per cell count N, a TCP gang — 1 training
# server + 1 writer + N replica cells + MPIT_BENCH_CELL_READERS
# fabric-routed readers — runs paced whole-vector reads while the
# writer commits a version per interval and samples its own GRAD
# latency.  Every serving member (the cells; the server itself in the
# N=0 direct-serving control that always runs first) models a fixed
# per-member reply capacity of MPIT_BENCH_CELL_MBS (the BENCH_r11
# member-throttle rationale: an unthrottled 1-core host measures
# time-slicing, not fan-out), so aggregate read throughput scaling in
# N is the capacity the fabric actually adds.  The sweep asserts reads
# stay bitwise-correct and monotone; the kill leg
# (MPIT_BENCH_CELL_KILL=1, default on, needs >= 2 cells) SIGKILLs one
# cell mid-run and asserts every reader completes with zero
# RetryExhausted and >= 1 failover.  Rows are serving-metric rows and
# never join the codec=none baseline gate.
CELLS_SWEEP = [int(x) for x in
               os.environ.get("MPIT_BENCH_CELLS", "").split(",") if x]
CELL_READERS = int(os.environ.get("MPIT_BENCH_CELL_READERS", "96"))
CELL_MB = float(os.environ.get("MPIT_BENCH_CELL_MB", "0.25"))
CELL_ROUNDS = int(os.environ.get("MPIT_BENCH_CELL_ROUNDS", "6"))
CELL_INTERVAL = float(os.environ.get("MPIT_BENCH_CELL_INTERVAL_S", "0.15"))
CELL_MBS = float(os.environ.get("MPIT_BENCH_CELL_MBS", "60"))
CELL_MAX_LAG = int(os.environ.get("MPIT_BENCH_CELL_MAX_LAG", "8"))
CELL_KILL = os.environ.get("MPIT_BENCH_CELL_KILL", "1") not in ("", "0")
# Reader-host driver processes: one thread stepping ~100 ReaderClients
# keeps up; past that the O(in-flight) poll scan becomes the measured
# ceiling instead of the serving members (the PR 8 driver lesson) —
# spread bigger populations over 2+ hosts.
CELL_HOSTS = max(int(os.environ.get("MPIT_BENCH_CELL_HOSTS", "2")), 1)
# MPIT_BENCH_ELASTIC=1: the shrink/grow sweep (ISSUE 9, PROTOCOL.md
# §9) — three codec=none shm legs at 1 -> 2 -> 1 servers, capturing the
# steady-state capacity the gang gains (and gives back) with each
# membership size.  The *transitions* are covered by the elastic tests
# and smoke (bitwise + bounded); the bench answers "what is a member
# worth", which is what an autoscaler trades against preemption risk.
# Rows are tagged metric=..._elastic and never join the codec=none
# baseline gate (a 1-server leg is half the serving hardware).  Each
# server member applies at MPIT_BENCH_ELASTIC_MBS (default 300 MB/s, 0
# = unthrottled): the **member-capacity model** — on a time-shared
# 1-core bench host, N server processes cannot add real compute, so an
# unthrottled sweep measures host contention, not membership; the
# throttle makes each member a fixed-capacity resource, which is
# exactly the quantity an autoscaler trades against preemption risk.
ELASTIC_SWEEP = os.environ.get("MPIT_BENCH_ELASTIC", "") not in ("", "0")
ELASTIC_MBS = float(os.environ.get("MPIT_BENCH_ELASTIC_MBS", "300"))
# MPIT_BENCH_AUTOSCALE=1: the closed-loop A/B (ISSUE 11,
# docs/OPERATIONS.md §3) — the 'bench' scenario's bursty leg (shaped
# reader load + gradient bursts, mpit_tpu.ft.traffic) runs twice on the
# in-process elastic gang under the BENCH_r11 member-capacity throttle:
# once as a static gang (launch membership, no loop), once with the
# SLO-driven autoscaler attached and nobody calling /scale.  Rows
# record completed logical MB/s over the scenario plus the decision
# counts, tagged metric=ps_autoscale_closed_loop — they measure what
# the loop is worth under shaped load, never the wire record, so they
# are excluded from the codec=none baseline gate like the skew and
# elastic rows.  Both legs must end bitwise-identical (asserted
# in-bench: the loop must not cost correctness to buy throughput).
AUTOSCALE_SWEEP = os.environ.get("MPIT_BENCH_AUTOSCALE", "") not in ("", "0")
# MPIT_BENCH_STREAM=1: the pipelined-streaming A/B (ISSUE 13,
# docs/PROTOCOL.md §12) — per codec, a 1-server/1-client framed gang
# over a MODELED serial link (ft/faults.py PacedTransport at
# MPIT_BENCH_STREAM_LINK_MBS) runs the 640 MB round loop twice:
# whole-frame transfers (the unchunked control), then FLAG_CHUNKED
# streaming at MPIT_BENCH_STREAM_CHUNK_MB chunks.  Each GRAD and PARAM
# op is individually timed; the rows carry per-op p50 next to the
# aggregate, and the chunked row records its GRAD speedup over the
# control (bar: >= 1.5x on the 640 MB leg).  The link model exists for
# the same reason the elastic sweep's member-capacity throttle does:
# on a time-shared 1-core bench host, loopback "wire" time IS host CPU
# time, so an unmodeled A/B measures scheduling, not transfer
# pipelining — with the link modeled, overlap buys exactly the time a
# real network would hide.  Rows are tagged metric=ps_stream_pipeline
# and never join the codec=none baseline gate (a modeled link is not
# the record's wire).
STREAM_SWEEP = os.environ.get("MPIT_BENCH_STREAM", "") not in ("", "0")
STREAM_LINK_MBS = float(os.environ.get("MPIT_BENCH_STREAM_LINK_MBS", "800"))
STREAM_CHUNK_MB = float(os.environ.get("MPIT_BENCH_STREAM_CHUNK_MB", "8"))
STREAM_DEADLINE = float(os.environ.get("MPIT_BENCH_STREAM_DEADLINE", "600"))
# MPIT_BENCH_AGG=1: the hierarchical-aggregation A/B (ISSUE 14,
# docs/PROTOCOL.md §13.6) — a 1-server gang with MPIT_BENCH_AGG_CLIENTS
# clients (threads in this process: the group plane needs a shared
# backend, exactly the deployment it models) over per-endpoint modeled
# serial links (MPIT_BENCH_AGG_LINK_MBS), run three times: flat pushes
# (every client ships its grad upstream), prereduce (one colocated
# group, the representative ships ONE fold), and tree (singleton reps
# reducing through the REDUCE tree, the root ships one fold).  The
# aggregate column is LOGICAL gradient bytes delivered per wall second
# (nclients x payload x rounds / window): flat pays nclients upstream
# transits of the server link per round, the hierarchical modes pay
# one — fewer bytes upstream, not better overlap, is the lever, so
# the hierarchical rows must beat flat by >= 1.3x (the ISSUE 14 bar).
# Rows are tagged metric=ps_agg_hierarchy and never join the
# codec=none baseline gate (a modeled link is not the record's wire).
AGG_SWEEP = os.environ.get("MPIT_BENCH_AGG", "") not in ("", "0")
AGG_CLIENTS = int(os.environ.get("MPIT_BENCH_AGG_CLIENTS", "4"))
AGG_MB = float(os.environ.get("MPIT_BENCH_AGG_MB", "64"))
AGG_LINK_MBS = float(os.environ.get("MPIT_BENCH_AGG_LINK_MBS", "300"))
AGG_ROUNDS = int(os.environ.get("MPIT_BENCH_AGG_ROUNDS", "5"))
AGG_CHUNK_MB = float(os.environ.get("MPIT_BENCH_AGG_CHUNK_MB", "4"))
AGG_DEADLINE = float(os.environ.get("MPIT_BENCH_AGG_DEADLINE", "600"))
# MPIT_BENCH_LM=1: the flagship LM workload (mpit_tpu.lm) measured in
# tokens/second — an in-process thread gang training the transformer LM
# through the FULL static PS composition at once: the weighted
# aligned-cut layout spreads params + per-element optimizer slots over
# >= 2 servers (each server's footprint is priced and must be under the
# whole model's, i.e. the state genuinely spans servers), FLAG_CHUNKED
# streaming, the int8 error-feedback codec, and the §13 aggregation
# tree.  Two legs, both gated in-bench: the headline leg asserts the
# loss envelope (final avg window < first — the gang is *training*,
# not just moving bytes), the determinism leg runs the identical
# 1-worker gang twice and asserts the servers' final params are
# bitwise equal.  Rows are tagged metric=lm_* and never join the
# codec=none baseline gate.
LM_SWEEP = os.environ.get("MPIT_BENCH_LM", "") not in ("", "0")
LM_STEPS = int(os.environ.get("MPIT_BENCH_LM_STEPS", "40"))
LM_DMODEL = int(os.environ.get("MPIT_BENCH_LM_DMODEL", "64"))
LM_LAYERS = int(os.environ.get("MPIT_BENCH_LM_LAYERS", "2"))
LM_SEQ = int(os.environ.get("MPIT_BENCH_LM_SEQ", "128"))
LM_BATCH = int(os.environ.get("MPIT_BENCH_LM_BATCH", "8"))
LM_WORKERS = int(os.environ.get("MPIT_BENCH_LM_WORKERS", "2"))
LM_SERVERS = int(os.environ.get("MPIT_BENCH_LM_SERVERS", "2"))
# rmsprop: server-stateful AND chunk-splittable (adam's scalar step
# counter is rejected under FLAG_CHUNKED — per-chunk apply would not
# be bitwise; docs/PROTOCOL.md §12.5), with 3 optimizer slots per
# element beside each shard — params+state is 4x the param bytes.
LM_OPT = os.environ.get("MPIT_BENCH_LM_OPT", "rmsprop")
LM_CHUNK_KB = float(os.environ.get("MPIT_BENCH_LM_CHUNK_KB", "64"))
# MPIT_BENCH_POOL=1: run the stream and agg sweeps once per worker-pool
# configuration (ISSUE 17, comm/pool.py) — first MPIT_POOL_THREADS=0
# (the serial data plane, today's control) then once per entry of
# MPIT_BENCH_POOL_THREADS (default "2") — and tag every row
# pool_threads=N.  The knob must pin BOTH sides explicitly: the pool
# defaults to min(4, cores-1), which is 0 (serial) on the 1-core bench
# container, so an untagged run would silently A/A.  Chunked stream
# rows record pool_grad_speedup (this leg's GRAD p50 vs the pool=0
# leg's, same codec) and agg tree rows record pool_speedup the same
# way — the cross-leg column that shows what pooling itself bought,
# next to the within-leg chunked-vs-control / tree-vs-flat bars.
# Pool rows ride the modeled-wire sweeps and never join the codec=none
# baseline gate.
POOL_SWEEP = os.environ.get("MPIT_BENCH_POOL", "") not in ("", "0")
POOL_THREADS = [int(x) for x in
                os.environ.get("MPIT_BENCH_POOL_THREADS", "2").split(",")
                if x.strip()]
# MPIT_BENCH_PROFILE=1: the CPU/utilization attribution columns
# (ISSUE 19, obs/profile.py).  Three touchpoints: (1) one extra
# codec=none shm leg with MPIT_OBS_PROFILE=1 + trace export in every
# child, analyzed by `obs profile` so the row carries per-rank core
# use and counter-sample counts — the overhead column.  The row is
# EXCLUDED from the codec=none baseline gate like the skew/decomp
# legs: per-step thread-clock reads on a time-shared 1-core host are
# a measured ~2x tax (BENCH_r17), which is exactly what the column
# records — the plain codec=none leg in the same run still gates;
# (2) the
# chunked stream legs run profiled, recording pool overlap efficiency
# and the encode-while-wire fraction next to their latencies; (3) the
# agg legs profile in-process (scheduler-attributed CPU + pool busy
# over the leg's wall) so tree rows carry utilization.  Captured
# columns: BENCH_r17.json.
PROFILE_SWEEP = os.environ.get("MPIT_BENCH_PROFILE", "") not in ("", "0")
# MPIT_BENCH_BASELINE=<MB/s>: fail the run if any codec=none shm leg
# (heartbeats/obs on or off) lands below 97% of this reference — the
# regression gate for the captured record (PR 2: 252.7 at 640 MB).
# Skew legs are excluded: a deliberately-injected straggler is not a
# regression.
BASELINE = float(os.environ.get("MPIT_BENCH_BASELINE", "0") or 0)
# MPIT_BENCH_HOST_MBS=<MB/s>: healthy warm-copy reference for the
# host_probe control that runs beside the baseline gate.  0 (default)
# derives the threshold as 8x BASELINE — the shm path costs several
# host copies per delivered byte, so a host that cannot even memcpy at
# 8x the record cannot reproduce it regardless of any code change.
HOST_MBS = float(os.environ.get("MPIT_BENCH_HOST_MBS", "0") or 0)


def host_probe(mb: float = 0.0) -> dict:
    """Warm-copy host-bandwidth control for the baseline gate.

    One cold ``np.copyto`` pass (page faults + first touch of fresh
    buffers) then three warm passes over the same pages; reports both so
    a gate miss can be attributed.  A healthy host that misses the
    record is a code regression; a host whose warm memcpy is slow
    (noisy neighbor, cgroup throttle) OR whose cold first-touch is slow
    (lazily-faulted VM memory — the BENCH_r17 failure mode: warm pages
    at 6.8 GB/s while fresh pages fault at ~117 MB/s) is an
    environmental miss — the bench allocates fresh vectors per rep, so
    it cannot outrun the host's page-fault path.
    """
    import numpy as np

    mb = mb or min(MB, 256.0)
    n = max(int(mb * 2**20) // 8, 1)
    src = np.ones(n, np.float64)
    dst = np.empty_like(src)
    t0 = time.perf_counter()
    np.copyto(dst, src)
    cold_s = time.perf_counter() - t0
    warm = []
    for _ in range(3):
        t0 = time.perf_counter()
        np.copyto(dst, src)
        warm.append(time.perf_counter() - t0)
    probe_mb = n * 8 / 2**20
    return {
        "mb": round(probe_mb, 1),
        "cold_mbs": round(probe_mb / max(cold_s, 1e-9), 1),
        "warm_mbs": round(probe_mb / max(min(warm), 1e-9), 1),
    }


def bench_ici() -> dict:
    from mpit_tpu.parallel.collective import measure_ps_pushpull

    r = measure_ps_pushpull(MB, rounds=ROUNDS)
    _log(f"[ici] {r['devices']} devices, payload {r['payload_mb']:.1f} MB: "
         f"{r['ms_per_round']:.2f} ms/round -> {r['mbs']:.1f} MB/s "
         f"({r['per_chip']:.1f} MB/s/chip)")
    return {
        "metric": "ps_pushpull_bandwidth_ici",
        "value": round(r["mbs"], 1),
        "unit": "MB/s",
        "per_chip": round(r["per_chip"], 1),
        "devices": r["devices"],
    }


def bench_shm(codec: str = "", heartbeat: bool = False,
              obs: bool = False, skew_rebalance=None,
              status: bool = False, decomp: bool = False,
              throttle_mbs: float = 0.0, profile: bool = False) -> dict:
    """One shm PS push/pull measurement; ``codec`` overrides
    MPIT_PS_CODEC for the gang (read at client/server construction);
    ``heartbeat`` arms client beacons + the server lease registry;
    ``obs`` enables the observability registry + op spans (MPIT_OBS)
    inside every gang child; ``status`` additionally serves the statusd
    introspection endpoints (MPIT_OBS_HTTP) in every child while a
    parent poller scrapes rank 0's /metrics throughout the run;
    ``skew_rebalance`` (None = no skew) delay-injects the last server's
    replies and runs the gang in shardctl mode with the rebalance policy
    off (False) or on (True); ``decomp`` arms the causal-tracing column:
    framed FLAG_TIMING wire + per-rank trace parts, merged and fed
    through ``obs analyze`` so the row carries per-phase p50/p99;
    ``profile`` arms the CPU-attribution column: MPIT_OBS_PROFILE +
    trace export in every child, merged and fed through ``obs
    profile`` so the row carries per-rank core use (gate-exempt like
    decomp: the per-step clock tax is the measured column, not a wire
    regression)."""
    import numpy as np

    from mpit_tpu.comm import codec as codec_mod

    if codec:
        os.environ["MPIT_PS_CODEC"] = codec
    codec_name = codec_mod.get(codec or None).name
    size = int(MB * (1 << 20) / 4)
    _log(f"[shm] {NSERVERS} servers + {NCLIENTS} clients, codec "
         f"{codec_name}, heartbeat {'on' if heartbeat else 'off'}, "
         f"obs {'on' if obs else 'off'}, "
         f"status {'on' if status else 'off'}, "
         + (f"skew rebalance={'on' if skew_rebalance else 'off'}, "
            if skew_rebalance is not None else "")
         + f"payload {size * 4 / 2**20:.1f} MB x {REPS} rep(s)")

    if (heartbeat or obs or status or decomp or profile) and GANG != "procs":
        raise RuntimeError(
            "MPIT_BENCH_HEARTBEAT/MPIT_BENCH_OBS/MPIT_BENCH_STATUS/"
            "MPIT_BENCH_DECOMP/MPIT_BENCH_PROFILE need MPIT_BENCH_GANG=procs")
    if skew_rebalance is not None and GANG != "procs":
        raise RuntimeError("MPIT_BENCH_SKEW needs MPIT_BENCH_GANG=procs")
    polls = [0]
    decomp_out: dict = {}
    profile_out: dict = {}
    if GANG == "procs":
        runs = [_shm_run_procs(size, heartbeat=heartbeat, obs=obs,
                               skew_rebalance=skew_rebalance,
                               status_port=STATUS_PORT if status else None,
                               status_polls=polls,
                               decomp_out=decomp_out if decomp else None,
                               profile_out=profile_out if profile else None,
                               throttle_mbs=throttle_mbs)
                for _ in range(REPS)]
    else:
        runs = [_shm_run_threads(size, heartbeat=heartbeat)
                for _ in range(REPS)]
    mbs = float(np.median(np.asarray(runs)))
    _log(f"[shm] codec {codec_name} hb={int(heartbeat)} obs={int(obs)} "
         f"status={int(status)} skew={skew_rebalance}: "
         f"median {mbs:.1f} MB/s over {runs}")
    row = {
        "metric": "ps_pushpull_bandwidth_shm",
        "value": round(mbs, 1),
        "unit": "MB/s",
        "codec": codec_name,
        "heartbeat": int(heartbeat),
        "obs": int(obs),
        "gang": GANG,
        "reps": REPS,
        "value_runs": [round(v, 1) for v in runs],
        "clients": NCLIENTS,
        "servers": NSERVERS,
    }
    if status:
        row["status"] = 1
        row["status_polls"] = polls[0]
    if decomp:
        # Per-phase latency decomposition from the last rep's analyzed
        # trace (ms; obs/causal.py) — the "where does an op's time go"
        # column next to the MB/s it cost to measure it.
        row["decomp"] = 1
        row.update(decomp_out)
    if profile:
        # CPU/utilization attribution from the last rep's analyzed
        # trace (obs/profile.py) — per-rank core use next to the MB/s
        # it cost to measure it.
        row["profile"] = 1
        row.update(profile_out)
    if skew_rebalance is not None:
        row["skew"] = 1
        row["rebalance"] = int(bool(skew_rebalance))
        row["skew_polls"] = SKEW_POLLS
    return row


def bench_elastic() -> list:
    """The 1 -> 2 -> 1 server sweep (MPIT_BENCH_ELASTIC): one
    codec=none leg per membership phase, same clients/payload/rounds
    throughout, so the three rows read as "throughput tracking gang
    size".  Runs by retargeting the module's server-count knob — the
    legs are steady-state gangs at each size (what capacity each
    membership is worth); scale-*transition* correctness and
    boundedness are the elastic test suite's job."""
    global NSERVERS
    saved = NSERVERS
    rows = []
    try:
        for phase, n in (("start", 1), ("grown", 2), ("shrunk", 1)):
            NSERVERS = n
            row = bench_shm("none", throttle_mbs=ELASTIC_MBS)
            row["metric"] = "ps_pushpull_bandwidth_elastic"
            row["elastic"] = 1
            row["phase"] = phase
            if ELASTIC_MBS > 0:
                row["member_capacity_mbs"] = ELASTIC_MBS
            rows.append(row)
    finally:
        NSERVERS = saved
    by_phase = {r["phase"]: r["value"] for r in rows}
    _log(f"[elastic] 1->2->1 sweep: {by_phase} MB/s")
    if by_phase["grown"] <= max(by_phase["start"], by_phase["shrunk"]):
        _log("[elastic] WARNING: the grown (2-server) leg did not beat "
             "the 1-server legs — server CPU was not the bottleneck at "
             "this payload/host; prefer MPIT_BENCH_MB large enough that "
             "apply+encode dominates")
    return rows


def bench_autoscale() -> list:
    """The closed-loop A/B (MPIT_BENCH_AUTOSCALE): static vs
    autoscaler-on under the 'bench' scenario's bursty leg, both on the
    member-capacity throttle.  Reuses the soak harness's gang driver
    (tools/autoscale_soak.py) so the bench and the CI smoke measure
    the same machinery."""
    import importlib.util

    import numpy as np

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools", "autoscale_soak.py")
    spec = importlib.util.spec_from_file_location("autoscale_soak", path)
    soak = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(soak)

    import tempfile

    from mpit_tpu.ft.traffic import Scenario
    from mpit_tpu.obs import configure

    scenario = Scenario.builtin("bench")
    os.environ.setdefault("MPIT_OBS_FLIGHT", tempfile.mkdtemp(
        prefix="mpit_bench_autoscale_"))
    rows, finals = [], {}
    try:
        for label, on in (("static", False), ("autoscaled", True)):
            configure(enabled=True, reset=True)
            with tempfile.TemporaryDirectory() as ckpt:
                res = soak.run_scenario(scenario, autoscale=on,
                                        chaos=True, ckpt_dir=ckpt)
            if res["errors"]:
                raise RuntimeError(f"autoscale {label} leg: {res['errors']}")
            finals[label] = res["final"]
            ops = res["grad_rounds"] + res["reads_done"]
            mbs = ops * res["size"] * 4 / res["elapsed"] / 2 ** 20
            scaler = res["scaler"]
            row = {
                "metric": "ps_autoscale_closed_loop",
                "value": round(mbs, 1),
                "unit": "MB/s",
                "phase": label,
                "autoscale": int(on),
                "grad_rounds": res["grad_rounds"],
                "reads_done": res["reads_done"],
                "elapsed_s": round(res["elapsed"], 2),
                "member_capacity_mbs": soak.MEMBER_MBS,
                "p99_target_ms": soak.P99_TARGET_MS,
            }
            if scaler is not None:
                row["scale_ups"] = scaler.ups
                row["scale_downs"] = scaler.downs
                row["operator_calls"] = scaler.operator_calls
            rows.append(row)
            _log(f"[autoscale] {label}: {mbs:.1f} MB/s logical "
                 f"({res['grad_rounds']} rounds + {res['reads_done']} "
                 f"reads in {res['elapsed']:.1f}s)")
    finally:
        configure(enabled=None, reset=True)
    # The loop must not cost correctness to buy throughput.
    np.testing.assert_array_equal(finals["static"], finals["autoscaled"])
    by = {r["phase"]: r["value"] for r in rows}
    ratio = by["autoscaled"] / max(by["static"], 1e-9)
    _log(f"[autoscale] closed loop vs static: {by['autoscaled']:.1f} vs "
         f"{by['static']:.1f} MB/s ({ratio:.2f}x), bitwise-equal finals")
    if ratio <= 1.0:
        _log("[autoscale] WARNING: the closed loop did not beat the "
             "static gang — the burst never saturated the launch "
             "membership on this host (capacity model mistuned?)")
    return rows


def bench_stream() -> list:
    """The pipelined-streaming A/B (MPIT_BENCH_STREAM, §12.7): per
    codec, the unchunked control then the FLAG_CHUNKED leg, both as a
    1-server/1-client framed gang over the modeled serial link.  The
    chunked row records its GRAD p50 speedup over the control — the
    ISSUE 13 bar is >= 1.5x at 640 MB."""
    import numpy as np

    global NSERVERS, NCLIENTS
    saved = (NSERVERS, NCLIENTS)
    saved_pool = os.environ.get("MPIT_POOL_THREADS")
    NSERVERS = NCLIENTS = 1
    size = int(MB * (1 << 20) / 4)
    chunk_bytes = int(STREAM_CHUNK_MB * (1 << 20))
    rows = []
    # None = inherit the caller's pool config (sweep off, today's rows
    # keep their shape); with MPIT_BENCH_POOL, the explicit 0 control
    # first, then each pooled thread count.  Children pick the value up
    # from MPIT_POOL_THREADS in their env.
    pool_legs = ([0] + [n for n in POOL_THREADS if n > 0]
                 if POOL_SWEEP else [None])
    serial_grad = {}  # codec -> pool=0 chunked GRAD p50
    try:
        for pool_n in pool_legs:
            if pool_n is not None:
                os.environ["MPIT_POOL_THREADS"] = str(pool_n)
            for codec in (CODECS or ["none"]):
                os.environ["MPIT_PS_CODEC"] = codec or "none"
                pair = {}
                for chunked in (0, 1):
                    spec = {"chunk_bytes": chunk_bytes if chunked else 0,
                            "link_mbs": STREAM_LINK_MBS,
                            "deadline_s": STREAM_DEADLINE}
                    out: dict = {}
                    # Profiled chunked legs (MPIT_BENCH_PROFILE): the
                    # attribution plane rides the leg, so pool overlap
                    # efficiency and the encode-while-wire fraction
                    # land next to the latencies they explain.
                    prof_out = {} if (PROFILE_SWEEP and chunked) else None
                    _log(f"[stream] codec {codec or 'none'} "
                         f"{'chunked' if chunked else 'control'}: 1s/1c, "
                         f"link {STREAM_LINK_MBS:.0f} MB/s, payload "
                         f"{size * 4 / 2**20:.0f} MB"
                         + (f", {STREAM_CHUNK_MB:.0f} MB chunks"
                            if chunked else "")
                         + (f", pool {pool_n}t" if pool_n is not None
                            else ""))
                    mbs = _shm_run_procs(size, stream=spec, stream_out=out,
                                         profile_out=prof_out)
                    gp50 = float(np.percentile(out["lat_grad"], 50)) * 1e3
                    pp50 = float(np.percentile(out["lat_param"], 50)) * 1e3
                    row = {
                        "metric": "ps_stream_pipeline",
                        "unit": "ms",
                        "value": round(gp50, 1),
                        "codec": codec or "none",
                        "stream": chunked,
                        "grad_p50_ms": round(gp50, 1),
                        "param_p50_ms": round(pp50, 1),
                        "aggregate_mbs": round(mbs, 1),
                        "link_mbs": STREAM_LINK_MBS,
                        "chunk_mb": STREAM_CHUNK_MB if chunked else 0,
                        "payload_mb": round(size * 4 / 2**20, 1),
                        "rounds": ROUNDS,
                        "retries": out.get("retries", 0),
                    }
                    if pool_n is not None:
                        row["pool_threads"] = pool_n
                    if prof_out:
                        row["profile"] = 1
                        row.update(prof_out)
                    rows.append(row)
                    pair[chunked] = row
                speedup = (pair[0]["grad_p50_ms"]
                           / max(pair[1]["grad_p50_ms"], 1e-9))
                pair[1]["grad_speedup"] = round(speedup, 2)
                pair[1]["param_speedup"] = round(
                    pair[0]["param_p50_ms"]
                    / max(pair[1]["param_p50_ms"], 1e-9), 2)
                if pool_n == 0:
                    serial_grad[codec] = pair[1]["grad_p50_ms"]
                elif pool_n and serial_grad.get(codec):
                    pair[1]["pool_grad_speedup"] = round(
                        serial_grad[codec]
                        / max(pair[1]["grad_p50_ms"], 1e-9), 2)
                _log(f"[stream] codec {codec or 'none'}"
                     + (f" pool {pool_n}t" if pool_n is not None else "")
                     + f": GRAD p50 "
                     f"{pair[0]['grad_p50_ms']:.0f} -> "
                     f"{pair[1]['grad_p50_ms']:.0f} ms ({speedup:.2f}x), "
                     f"PARAM p50 {pair[0]['param_p50_ms']:.0f} -> "
                     f"{pair[1]['param_p50_ms']:.0f} ms"
                     + (f", pooled GRAD {pair[1]['pool_grad_speedup']:.2f}x"
                        f" vs serial"
                        if "pool_grad_speedup" in pair[1] else ""))
    finally:
        NSERVERS, NCLIENTS = saved
        if saved_pool is None:
            os.environ.pop("MPIT_POOL_THREADS", None)
        else:
            os.environ["MPIT_POOL_THREADS"] = saved_pool
    return rows


def _agg_gang_run(mode: str, size: int, codec: str = "none") -> dict:
    """One timed aggregation leg (§13.6): 1 server + AGG_CLIENTS client
    threads over per-endpoint PacedTransport links, AGG_ROUNDS lockstep
    GRAD rounds.  Returns the window and per-round latencies."""
    import numpy as np

    from mpit_tpu.agg import AggClient, AggConfig
    from mpit_tpu.comm.local import LocalRouter
    from mpit_tpu.ft import FTConfig, LinkClock, PacedTransport

    # In-process profiling (MPIT_BENCH_PROFILE): the agg gang is
    # threads, so the attribution plane is enabled programmatically
    # BEFORE roles construct (capture-at-construction) and the leg
    # reads the shared profiler + the native pool's busy clock
    # directly instead of a child trace.
    prof = None
    if PROFILE_SWEEP:
        from mpit_tpu import obs as obs_pkg
        from mpit_tpu.obs import profile as obs_profile

        obs_pkg.configure(enabled=True, reset=True)
        obs_profile.configure(enabled=True)
        prof = obs_profile.get_profiler()
    busy0 = 0.0
    if prof is not None:
        from mpit_tpu.comm import pool as comm_pool

        pool = comm_pool.current_pool()
        if pool is not None and not pool.serial:
            pool.sample_obs()
            busy0 = pool.busy_seconds()
    nclients = AGG_CLIENTS
    router = LocalRouter(1 + nclients)
    cranks = list(range(1, 1 + nclients))
    # Chunked wire in EVERY leg (flat included — the §12 pipeline is
    # the established baseline): the tree leg additionally streams the
    # root's push gated on fold progress (§13.3).
    ft = FTConfig(op_deadline_s=AGG_DEADLINE, max_retries=2,
                  chunk_bytes=int(AGG_CHUNK_MB * (1 << 20)))
    # ONE LinkClock across the gang: every rank's inbound NIC is one
    # serial link shared by all its senders — the flat fan-in pays
    # nclients transits of the server's link per round, hierarchical
    # modes pay one (plus pipelined REDUCE hops on the clients' links).
    link = LinkClock()
    server_ep = PacedTransport(router.endpoint(0), AGG_LINK_MBS,
                               min_bytes=1 << 14, link=link)
    from mpit_tpu.ps import ParamClient, ParamServer

    server = ParamServer(0, cranks, server_ep, rule="add")
    sth = threading.Thread(target=server.start, daemon=True)
    sth.start()
    groups = ()
    if mode == "prereduce":
        groups = (tuple(cranks),)
    cfg = AggConfig(mode=("off" if mode == "flat" else
                          "tree" if mode == "tree" else "prereduce"),
                    groups=groups, fanin=2, tree_seed=0,
                    deadline_s=AGG_DEADLINE)
    _GANG_SEQ[0] += 1
    ns = f"aggbench{_GANG_SEQ[0]}"
    clients, params = [], []
    for i, r in enumerate(cranks):
        ep = PacedTransport(router.endpoint(r), AGG_LINK_MBS,
                            min_bytes=1 << 14, link=link)
        inner = ParamClient(r, [0], ep, seed_servers=(i == 0), ft=ft,
                            codec=codec or "none")
        clients.append(AggClient(inner, cranks, cfg, namespace=ns))
        params.append((np.zeros(size, np.float32),
                       np.full(size, 1e-6, np.float32)))
    barrier = threading.Barrier(nclients + 1)
    lat = []

    def drive(i, c):
        c.start(*params[i])
        barrier.wait()
        for _ in range(AGG_ROUNDS):
            s = time.monotonic()
            c.async_send_grad()
            c.wait()
            if i == 0:
                lat.append(time.monotonic() - s)
            barrier.wait()

    ths = [threading.Thread(target=drive, args=(i, c), daemon=True)
           for i, c in enumerate(clients)]
    for t in ths:
        t.start()
    barrier.wait()  # all started + seeded
    t0 = time.time()
    for _ in range(AGG_ROUNDS):
        barrier.wait()  # end of each round
    t1 = time.time()
    for t in ths:
        t.join(AGG_DEADLINE)
        assert not t.is_alive(), f"agg bench driver hung (mode {mode})"
    for c in clients:
        c.stop()
    sth.join(60)
    assert not sth.is_alive(), "agg bench server never stopped"
    out = {"dt": t1 - t0, "lat": lat,
           "applied": server.grads_applied}
    if prof is not None:
        from mpit_tpu import obs as obs_pkg
        from mpit_tpu.comm import pool as comm_pool

        wall = max(t1 - t0, 1e-9)
        res = {"sched_cpu_s": round(prof.cpu_seconds, 3),
               "cpu_util": round(prof.cpu_seconds / wall, 3)}
        pool = comm_pool.current_pool()
        if pool is not None and not pool.serial:
            pool.sample_obs()
            res["pool_util"] = round(
                max(pool.busy_seconds() - busy0, 0.0)
                / (wall * max(pool.threads, 1)), 3)
        obs_pkg.configure(enabled=None, reset=True)
        out["profile"] = res
    return out


def bench_agg() -> list:
    """The hierarchical-aggregation A/B (MPIT_BENCH_AGG, §13.6): flat
    vs prereduce vs tree on one modeled-link gang; aggregate = logical
    gradient bytes delivered per wall second.  The ISSUE 14 bar is the
    hierarchical rows >= 1.3x the flat row."""
    import numpy as np

    from mpit_tpu.comm import pool as comm_pool

    size = int(AGG_MB * (1 << 20) / 4)
    rows = []
    # The agg gang is in-process (threads share the group plane), so
    # the pool legs reconfigure the process-wide pool directly instead
    # of relying on child env.  None = inherit (sweep off).
    pool_legs = ([0] + [n for n in POOL_THREADS if n > 0]
                 if POOL_SWEEP else [None])
    serial_tree = {}  # codec -> pool=0 tree aggregate MB/s
    saved_pool = os.environ.get("MPIT_POOL_THREADS")
    try:
        for pool_n in pool_legs:
            if pool_n is not None:
                os.environ["MPIT_POOL_THREADS"] = str(pool_n)
                comm_pool.configure(pool_n)
            for codec in (CODECS or ["none", "int8"]):
                flat_mbs = None
                for mode in ("flat", "prereduce", "tree"):
                    _log(f"[agg] {mode} codec {codec}: 1s/{AGG_CLIENTS}c "
                         f"threads, link {AGG_LINK_MBS:.0f} MB/s, payload "
                         f"{AGG_MB:.0f} MB x {AGG_ROUNDS} rounds"
                         + (f", pool {pool_n}t" if pool_n is not None
                            else ""))
                    r = _agg_gang_run(mode, size, codec=codec)
                    mbs = (AGG_CLIENTS * AGG_ROUNDS * size * 4
                           / r["dt"] / 2**20)
                    row = {
                        "metric": "ps_agg_hierarchy",
                        "unit": "MB/s",
                        "value": round(mbs, 1),
                        "mode": mode,
                        "codec": codec,
                        "aggregate_mbs": round(mbs, 1),
                        "round_p50_ms": round(
                            float(np.percentile(r["lat"], 50)) * 1e3, 1),
                        "grads_applied": r["applied"],
                        "clients": AGG_CLIENTS,
                        "link_mbs": AGG_LINK_MBS,
                        "payload_mb": round(AGG_MB, 1),
                        "rounds": AGG_ROUNDS,
                    }
                    if pool_n is not None:
                        row["pool_threads"] = pool_n
                    if r.get("profile"):
                        # In-process utilization (MPIT_BENCH_PROFILE):
                        # scheduler-attributed CPU + pool busy over the
                        # leg's wall window.
                        row["profile"] = 1
                        row.update(r["profile"])
                    if mode == "flat":
                        flat_mbs = mbs
                    else:
                        row["speedup_vs_flat"] = round(
                            mbs / max(flat_mbs, 1e-9), 2)
                    if mode == "tree":
                        if pool_n == 0:
                            serial_tree[codec] = mbs
                        elif pool_n and serial_tree.get(codec):
                            row["pool_speedup"] = round(
                                mbs / max(serial_tree[codec], 1e-9), 2)
                    rows.append(row)
                    _log(f"[agg] {mode} codec {codec}"
                         + (f" pool {pool_n}t" if pool_n is not None
                            else "")
                         + f": {mbs:.1f} MB/s "
                         f"aggregate, round p50 {row['round_p50_ms']:.0f}"
                         f" ms, applied {r['applied']}"
                         + (f", {row['speedup_vs_flat']:.2f}x vs flat"
                            if mode != "flat" else "")
                         + (f", {row['pool_speedup']:.2f}x vs serial tree"
                            if "pool_speedup" in row else ""))
    finally:
        if POOL_SWEEP:
            if saved_pool is None:
                os.environ.pop("MPIT_POOL_THREADS", None)
            else:
                os.environ["MPIT_POOL_THREADS"] = saved_pool
            comm_pool.configure(None)
    return rows


def _lm_gang_run(nservers: int, nworkers: int, *, steps: int,
                 weights=None, codec: str = "int8", agg: bool = True,
                 seed: int = 1) -> dict:
    """One in-process LM training gang: ``nservers`` PS threads holding
    the weighted aligned-cut layout (server rule = the trainer's opt,
    so per-element optimizer slots live beside each shard), ``nworkers``
    LmTrainer threads over chunked FT transports with codec ``codec``,
    optionally through the §13 aggregation tree.  Returns per-worker
    trainer results, the plan summary, and the servers' final params."""
    import numpy as np

    from mpit_tpu.agg import AggClient, AggConfig
    from mpit_tpu.comm.local import LocalRouter
    from mpit_tpu.ft import FTConfig
    from mpit_tpu.lm import LmTrainer, build, plan
    from mpit_tpu.optim import rules as rules_mod
    from mpit_tpu.ps import ParamClient, ParamServer
    from mpit_tpu.utils.config import Config

    tcfg = Config(d_model=LM_DMODEL, n_heads=4, n_layers=LM_LAYERS,
                  seq_len=LM_SEQ, batch=LM_BATCH, opt=LM_OPT, lr=0.1,
                  steps=steps, eval_every=max(steps // 4, 1),
                  eval_batches=1, seed=seed, use_flash=0)
    model = build(d_model=tcfg.d_model, n_heads=tcfg.n_heads,
                  n_layers=tcfg.n_layers, seq_len=tcfg.seq_len,
                  seed=tcfg.seed, use_flash=False)
    rule = LM_OPT if LM_OPT in rules_mod.names() else "add"
    lm_plan = plan(model.flat.unravel(model.flat.w0), nservers,
                   rule=rule, server_weights=weights)
    ft = FTConfig(op_deadline_s=120.0, max_retries=4,
                  backoff_base_s=0.01, backoff_cap_s=0.1,
                  chunk_bytes=int(LM_CHUNK_KB * 1024))
    n = nservers + nworkers
    router = LocalRouter(n)
    cranks = list(range(nservers, n))
    servers = [ParamServer(r, cranks, router.endpoint(r), rule=rule,
                           ft=ft)
               for r in range(nservers)]
    sths = [threading.Thread(target=s.start, daemon=True)
            for s in servers]
    for t in sths:
        t.start()
    _GANG_SEQ[0] += 1
    ns = f"lmbench{_GANG_SEQ[0]}"
    acfg = AggConfig(mode="tree", groups=(), fanin=2, tree_seed=0,
                     deadline_s=600.0)
    trainers = []
    for i, r in enumerate(cranks):
        inner = ParamClient(r, list(range(nservers)), router.endpoint(r),
                            seed_servers=(i == 0), ft=ft,
                            codec=codec or "none", layout=lm_plan.layout)
        pc = (AggClient(inner, cranks, acfg, namespace=ns)
              if agg else inner)
        trainers.append(LmTrainer(tcfg, pclient=pc, rank=r))
    results: list = [None] * nworkers

    def drive(i):
        results[i] = trainers[i].run()

    t0 = time.monotonic()
    ths = [threading.Thread(target=drive, args=(i,), daemon=True)
           for i in range(nworkers)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(1800)
        assert not t.is_alive(), "lm bench worker hung"
    wall = time.monotonic() - t0
    for s in servers:
        s.live.stop()
    for t in sths:
        t.join(60)
        assert not t.is_alive(), "lm bench server never stopped"
    finals = [np.asarray(s.param).copy() for s in servers]
    return {"results": results, "plan": lm_plan, "wall": wall,
            "final_params": np.concatenate(finals),
            "grads_applied": [s.grads_applied for s in servers]}


def bench_lm() -> list:
    """The flagship LM workload legs (MPIT_BENCH_LM, ISSUE 20).

    Headline: LM_WORKERS trainer threads x LM_SERVERS weighted-layout
    servers, chunked + int8 EF + agg tree all negotiated at once;
    the row carries the tokens/sec trajectory and is gated in-bench on
    the loss envelope.  Determinism: the identical 1-worker gang twice;
    gated on bitwise-equal final server params."""
    import numpy as np

    rows = []
    weights = ([3.0, 2.0] + [1.0] * (LM_SERVERS - 2)
               if LM_SERVERS >= 2 else None)
    _log(f"[lm] headline: {LM_SERVERS}s/{LM_WORKERS}w threads, "
         f"d_model {LM_DMODEL} x {LM_LAYERS}L seq {LM_SEQ} batch "
         f"{LM_BATCH}, opt {LM_OPT}, {LM_STEPS} steps, weighted cut "
         f"{weights}, chunk {LM_CHUNK_KB:.0f} KB, codec int8, agg tree")
    r = _lm_gang_run(LM_SERVERS, LM_WORKERS, steps=LM_STEPS,
                     weights=weights, codec="int8", agg=True)
    summary = r["plan"].summary()
    # the sharding is real: no single server holds the whole
    # params+optimizer state it would need without the cut
    foot = summary["footprint_mb"]
    assert max(foot) < summary["total_footprint_mb"] * 0.75, summary
    tokens = sum(res["tokens_total"] for res in r["results"])
    losses0 = [res["history"][0]["avg_loss"] for res in r["results"]]
    losses1 = [res["history"][-1]["avg_loss"] for res in r["results"]]
    # the loss envelope gate: every worker's avg window descended
    assert all(b < a for a, b in zip(losses0, losses1)), \
        (losses0, losses1)
    agg_tps = tokens / max(r["wall"], 1e-9)
    rows.append({
        "metric": "lm_tokens_per_s",
        "value": round(agg_tps, 1),
        "unit": "tokens/s",
        "servers": LM_SERVERS,
        "workers": LM_WORKERS,
        "codec": "int8",
        "chunk_kb": LM_CHUNK_KB,
        "agg": "tree",
        "opt": LM_OPT,
        "steps": LM_STEPS,
        "d_model": LM_DMODEL,
        "n_layers": LM_LAYERS,
        "seq_len": LM_SEQ,
        "batch": LM_BATCH,
        "tokens_total": tokens,
        "wall_s": round(r["wall"], 2),
        "per_worker_tps": [round(res["tokens_per_s"], 1)
                           for res in r["results"]],
        "loss_first": [round(x, 4) for x in losses0],
        "loss_final": [round(x, 4) for x in losses1],
        "trajectory": [
            {"step": h["step"],
             "avg_loss": round(h["avg_loss"], 4),
             "eval_loss": round(h["eval_loss"], 4),
             "tokens_per_s": round(h["tokens_per_s"], 1)}
            for h in r["results"][0]["history"]],
        "plan": summary,
        "grads_applied": r["grads_applied"],
    })
    _log(f"[lm] headline: {agg_tps:.1f} tokens/s aggregate, loss "
         f"{losses0} -> {losses1}, shards {summary['shard_elems']} "
         f"({summary['footprint_mb']} MB incl. "
         f"{summary['slots']} opt slots/elem)")
    det_steps = max(LM_STEPS // 2, 4)
    _log(f"[lm] determinism: identical 1-worker gang twice, "
         f"{det_steps} steps, same stack")
    a = _lm_gang_run(LM_SERVERS, 1, steps=det_steps, weights=weights,
                     codec="int8", agg=True, seed=7)
    b = _lm_gang_run(LM_SERVERS, 1, steps=det_steps, weights=weights,
                     codec="int8", agg=True, seed=7)
    bitwise = bool(np.array_equal(a["final_params"], b["final_params"]))
    assert bitwise, "1-worker LM gang is not bitwise reproducible"
    rows.append({
        "metric": "lm_bitwise_determinism",
        "value": 1,
        "unit": "bool",
        "servers": LM_SERVERS,
        "workers": 1,
        "codec": "int8",
        "agg": "tree",
        "steps": det_steps,
        "param_elems": int(a["final_params"].size),
    })
    _log("[lm] determinism: final server params bitwise equal")
    return rows


_GANG_SEQ = [0]  # unique shm namespace per gang within this process


def _ring_bytes(size: int) -> int:
    # Ring sized for the rank's aggregate inbound traffic: every peer on
    # the other side may have a full shard in flight into this rank's
    # one inbox ring (2 clients -> 1 server ring, and vice versa), so a
    # per-shard ring is perpetually full and each transfer degrades into
    # ring-granularity handoff cycles — each paying a scheduling quantum
    # on a shared core (a whole OS timeslice in the process gang).
    shard_bytes = size * 4 // max(NSERVERS, 1)
    peers = max(NSERVERS, NCLIENTS)
    return max(64 << 20, 2 * peers * shard_bytes + (16 << 20))


def _status_poller(port: int, stop, polls) -> None:
    """Scrape one rank's /metrics until told to stop, counting the
    successful polls — the 'live serving under load' half of the
    MPIT_BENCH_STATUS column."""
    import urllib.request

    while not stop.is_set():
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=1) as resp:
                if resp.status == 200 and resp.read():
                    polls[0] += 1
        except OSError:
            pass  # child still importing jax / already exited
        stop.wait(0.2)


def _shm_run_procs(size: int, heartbeat: bool = False,
                   obs: bool = False, skew_rebalance=None,
                   status_port=None, status_polls=None,
                   decomp_out=None, throttle_mbs: float = 0.0,
                   stream=None, stream_out=None,
                   profile_out=None) -> float:
    """One timed gang, one OS process per rank: servers run the PS serve
    loop, clients run T rounds of {pull, push, wait} and report their
    round-loop window; aggregate MB/s uses the union of the client
    windows, so child startup (jax import, seeding) is excluded.  Skew
    mode adds one controller rank and delay-injects the last server.
    ``status_port`` arms statusd endpoints in every child (base+rank)
    plus the parent-side /metrics poller."""
    import subprocess
    import tempfile

    nranks = NSERVERS + NCLIENTS + (1 if skew_rebalance is not None else 0)
    _GANG_SEQ[0] += 1
    ns = f"ptest_{os.getpid()}_{_GANG_SEQ[0]}"
    spec = {
        "ns": ns, "nservers": NSERVERS, "nclients": NCLIENTS,
        "size": size, "ring": _ring_bytes(size), "rounds": ROUNDS,
        "heartbeat": int(heartbeat),
    }
    if throttle_mbs > 0:
        spec["throttle_mbs"] = throttle_mbs
    if stream is not None:
        spec["stream"] = stream
    if decomp_out is not None:
        # Causal-tracing leg: the framed FLAG_TIMING wire (generous
        # deadline — a spurious retry at bench scale would corrupt the
        # measured column) + a per-rank trace part from every child.
        spec["decomp"] = {"deadline_s": DECOMP_DEADLINE}
    if skew_rebalance is not None:
        spec["skew"] = {"slow_server": NSERVERS - 1,
                        "delay_polls": SKEW_POLLS,
                        "rebalance": int(bool(skew_rebalance)),
                        "deadline_s": SKEW_DEADLINE}
    tmpdir = tempfile.mkdtemp(prefix=f"{ns}_")
    procs, result_files = [], []
    for rank in range(nranks):
        result_path = os.path.join(tmpdir, f"rank{rank}.json")
        result_files.append(result_path)
        log_path = os.path.join(tmpdir, f"rank{rank}.log")
        env = dict(
            os.environ, JAX_PLATFORMS="cpu", PTEST_GANG=json.dumps(spec),
            PTEST_RANK=str(rank), PTEST_RESULT=result_path,
            # Explicit either way: the A/B must measure the obs
            # machinery, not whatever MPIT_OBS the caller env carries.
            MPIT_OBS="1" if obs else "0",
        )
        env.pop("MPIT_OBS_TRACE", None)  # tracing implies obs; keep A/B clean
        env.pop("MPIT_OBS_PROFILE", None)  # profiling implies obs too
        if decomp_out is not None:
            env["MPIT_OBS"] = "1"
            env["MPIT_OBS_TRACE"] = os.path.join(tmpdir, "decomp_trace.json")
        if profile_out is not None:
            # CPU-attribution leg (MPIT_BENCH_PROFILE): profiling +
            # trace export in every child; the parent merges and runs
            # `obs profile` over the result.
            env["MPIT_OBS"] = "1"
            env["MPIT_OBS_PROFILE"] = "1"
            env["MPIT_OBS_TRACE"] = os.path.join(tmpdir,
                                                 "profile_trace.json")
        if status_port is not None:
            env["MPIT_OBS_HTTP"] = str(status_port)
        else:
            env.pop("MPIT_OBS_HTTP", None)  # endpoints imply obs; A/B clean
        with open(log_path, "w") as fh:
            procs.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--gang-child"],
                env=env, stdout=fh, stderr=subprocess.STDOUT, text=True,
            ))
    poll_stop, poller = None, None
    if status_port is not None:
        poll_stop = threading.Event()
        local = [0]
        poller = threading.Thread(
            target=_status_poller, args=(status_port, poll_stop, local),
            daemon=True)
        poller.start()
    deadline = time.monotonic() + float(
        os.environ.get("MPIT_BENCH_GANG_TIMEOUT", "900"))
    try:
        while any(p.poll() is None for p in procs):
            bad = next((r for r, p in enumerate(procs)
                        if p.poll() not in (None, 0)), None)
            if bad is not None or time.monotonic() > deadline:
                for p in procs:
                    if p.poll() is None:
                        p.terminate()
                for r, path in enumerate(result_files):
                    with open(path.replace(".json", ".log")) as fh:
                        sys.stderr.write(fh.read())
                raise RuntimeError(
                    f"gang rank {bad} failed (logs: {tmpdir})"
                    if bad is not None else
                    f"gang timed out (logs: {tmpdir})"
                )
            time.sleep(0.1)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        if poll_stop is not None:
            poll_stop.set()
            poller.join(timeout=5)
    if status_port is not None:
        if local[0] == 0:
            raise RuntimeError(
                "MPIT_BENCH_STATUS leg completed but the parent poller "
                "never got a 200 from rank 0's /metrics — the endpoint "
                "was not live during the run (fake column)")
        if status_polls is not None:
            status_polls[0] += local[0]
        _log(f"[shm] status poller: {local[0]} successful /metrics "
             f"scrape(s) during the gang")
    windows = []
    for rank in range(NSERVERS, NSERVERS + NCLIENTS):
        with open(result_files[rank]) as fh:
            rec = json.load(fh)
        windows.append((rec["t0"], rec["t1"]))
        if stream_out is not None:
            stream_out.setdefault("lat_grad", []).extend(
                rec.get("lat_grad", []))
            stream_out.setdefault("lat_param", []).extend(
                rec.get("lat_param", []))
            stream_out["retries"] = stream_out.get("retries", 0) + int(
                rec.get("retries", 0))
    dt = max(w[1] for w in windows) - min(w[0] for w in windows)
    if decomp_out is not None:
        decomp_out.clear()
        decomp_out.update(_analyze_gang_trace(
            os.path.join(tmpdir, "decomp_trace.json")))
    if profile_out is not None:
        profile_out.clear()
        profile_out.update(_profile_gang_trace(
            os.path.join(tmpdir, "profile_trace.json")))
    import shutil

    shutil.rmtree(tmpdir, ignore_errors=True)
    mbs = 2 * ROUNDS * NCLIENTS * size * 4 / dt / 2**20
    _log(f"[shm] {ROUNDS} rounds x {NCLIENTS} client procs in {dt:.3f}s "
         f"-> {mbs:.1f} MB/s aggregate")
    return mbs


def _analyze_gang_trace(base: str) -> dict:
    """Merge the gang's per-rank trace parts and run the causal
    analyzer: per-(op, phase) p50/p99 in ms plus the join rate — the
    MPIT_BENCH_DECOMP column's payload.  Fails loudly when the parts
    are missing or the analyzer finds violations (a broken decomposition
    must not be captured as a bench column)."""
    import glob

    from mpit_tpu.obs import causal as obs_causal
    from mpit_tpu.obs import trace as obs_trace

    parts = sorted(glob.glob(f"{base}.rank*.json"))
    if not parts:
        raise RuntimeError(
            "MPIT_BENCH_DECOMP leg completed but no trace parts were "
            "written — the children never exported (fake column)")
    obs_trace.merge_traces(base, parts)
    report = obs_causal.analyze(base)
    if report["violations"]:
        raise RuntimeError(
            f"MPIT_BENCH_DECOMP analyzer found {len(report['violations'])} "
            f"negative-phase violation(s): {report['violations'][:3]}")
    phases = {}
    for op, st in report["phase_stats"].items():
        phases[op] = {
            phase: {"p50_ms": round(p["p50_us"] / 1000.0, 3),
                    "p99_ms": round(p["p99_us"] / 1000.0, 3)}
            for phase, p in st["phases"].items() if p["total_us"] > 0
        }
    return {
        "phases": phases,
        "join_rate": round(report["ops"]["join_rate"], 4),
        "joined_ops": report["ops"]["joined"],
    }


def _profile_gang_trace(base: str) -> dict:
    """Merge the gang's per-rank trace parts and run the CPU/utilization
    attribution (obs/profile.py): per-rank core use, pool overlap
    efficiency and the encode-while-wire fraction — the
    MPIT_BENCH_PROFILE column's payload.  Fails loudly when the parts
    or the counter tracks are missing (a fake utilization column must
    not be captured)."""
    import glob

    from mpit_tpu.obs import profile as obs_profile
    from mpit_tpu.obs import trace as obs_trace

    parts = sorted(glob.glob(f"{base}.rank*.json"))
    if not parts:
        raise RuntimeError(
            "MPIT_BENCH_PROFILE leg completed but no trace parts were "
            "written — the children never exported (fake column)")
    obs_trace.merge_traces(base, parts)
    report = obs_profile.analyze_trace(base)
    if not report["counter_events"]:
        raise RuntimeError(
            "MPIT_BENCH_PROFILE leg produced no counter-track samples — "
            "profiling was not live in the children (fake column)")
    out = {
        "counter_events": report["counter_events"],
        "cpu_util": {rank: round(row["cpu_util"], 3)
                     for rank, row in report["ranks"].items()},
    }
    eff = report.get("pool_overlap_efficiency")
    if eff is not None:
        out["pool_overlap_efficiency"] = round(eff, 3)
    s = report.get("streaming")
    if s:
        out["encode_while_wire"] = round(s["fraction"], 3)
    return out


def _throttle_applies(server, mbs: float) -> None:
    """The elastic sweep's member-capacity model: every grad apply
    blocks this serving rank for shard_bytes/rate wall-seconds — each
    member is a fixed-capacity resource, so aggregate throughput is a
    function of *membership*, not of how the bench host time-slices N
    processes over its cores.  The blocking sleep is deliberate: it
    serializes this rank's service the way a truly compute-bound apply
    would."""
    inner = server._apply_for

    def apply_for(codec):
        fn = inner(codec)

        def throttled(param, grad, state):
            time.sleep(server.size * 4 / (mbs * 2**20))
            return fn(param, grad, state)

        return throttled

    server._apply_for = apply_for


def _gang_child() -> None:
    """One rank of the process gang (--gang-child): a server runs the
    serve loop to completion; a client times its round loop and writes
    the window to PTEST_RESULT; in skew mode the extra last rank runs
    the shard controller and the last *server* rank's replies are
    delay-injected (the straggler under test)."""
    import numpy as np

    from mpit_tpu.comm.collectives import HostCollectives
    from mpit_tpu.comm.shm import ShmTransport
    from mpit_tpu.ft import FaultPlan, FaultyTransport, FTConfig
    from mpit_tpu.ps import ParamClient, ParamServer, tags

    spec = json.loads(os.environ["PTEST_GANG"])
    rank = int(os.environ["PTEST_RANK"])
    skew = spec.get("skew")
    stream = spec.get("stream")
    nranks = spec["nservers"] + spec["nclients"] + (1 if skew else 0)
    sranks = list(range(spec["nservers"]))
    cranks = list(range(spec["nservers"],
                        spec["nservers"] + spec["nclients"]))
    ctl_rank = nranks - 1 if skew else None
    size = spec["size"]
    heartbeat = bool(spec.get("heartbeat"))
    # Live introspection endpoint (no-op unless MPIT_OBS_HTTP rode in
    # from the parent — the MPIT_BENCH_STATUS column).
    from mpit_tpu.obs import maybe_start_statusd

    maybe_start_statusd(
        rank, role=("controller" if rank == ctl_rank
                    else "server" if rank in sranks else "client"))
    # Explicit FTConfig either way: the A/B must measure the heartbeat
    # machinery, not whatever MPIT_FT_* happens to be in the caller env.
    # Very generous TTL: the sweep measures liveness *cost*, not
    # eviction, and an oversubscribed bench host can starve a rank hard
    # enough (observed: beats at 1/4 nominal rate at 640 MB) that a
    # production-tight TTL evicts a live client mid-leg and wedges it.
    client_ft = FTConfig(heartbeat_s=0.05) if heartbeat else FTConfig()
    server_ft = FTConfig(lease_ttl_s=120.0) if heartbeat else FTConfig()
    decomp = spec.get("decomp")
    if decomp:
        # Causal-tracing leg: framed wire + FLAG_TIMING tails.  The
        # deadline is deliberately huge — this column measures where an
        # op's time goes, not the retry machinery.
        client_ft = FTConfig(op_deadline_s=float(decomp["deadline_s"]),
                             timing=True)
    if skew:
        # Shardctl mode: framed ops with a deadline sized for the leg's
        # delayed straggler replies, beats for the controller's window.
        client_ft = FTConfig(op_deadline_s=float(skew["deadline_s"]),
                             max_retries=8)
        server_ft = FTConfig(heartbeat_s=0.05)
    if stream:
        # Streaming A/B (§12.7): framed wire, chunked or not per the
        # leg; a generous deadline — this column measures pipelining,
        # not the retry machinery.
        client_ft = FTConfig(op_deadline_s=float(stream["deadline_s"]),
                             max_retries=2,
                             chunk_bytes=int(stream["chunk_bytes"]))
    transport = ShmTransport(spec["ns"], rank, nranks,
                             ring_bytes=spec["ring"])
    if stream and float(stream.get("link_mbs", 0)) > 0:
        # The modeled serial link, both directions (see the
        # MPIT_BENCH_STREAM comment at the top of this file): big
        # frames transit at link_mbs; control traffic passes.
        from mpit_tpu.ft import PacedTransport

        transport = PacedTransport(transport, float(stream["link_mbs"]),
                                   min_bytes=1 << 14)
    # Startup barrier: no PS traffic until every ring is mapped (the
    # mpirun-gives-you-this guarantee, same as train/gang.py).
    HostCollectives(transport).barrier()
    if skew and rank == ctl_rank:
        from mpit_tpu.shardctl import RebalancePolicy, ShardController

        ctl = ShardController(
            rank, transport, sranks, cranks,
            policy=RebalancePolicy(ratio=2.0, min_busy_s=0.01,
                                   cooldown_s=0.5,
                                   enabled=bool(skew["rebalance"])),
        )
        ctl.serve()
        result = {"role": "controller",
                  "rebalances": int(ctl._m_rebal.value),
                  "map_version": getattr(ctl.smap, "version", None)}
    elif rank in sranks:
        ep = transport
        if skew and rank == skew["slow_server"]:
            # The straggler: every reply crawls out delay_polls
            # test()-polls late (send-side injection, message-atomic).
            ep = FaultyTransport(ep, FaultPlan(
                delay_every=1, delay_polls=int(skew["delay_polls"]),
                tags=frozenset({tags.GRAD_ACK, tags.PARAM,
                                tags.PARAM_PUSH_ACK})))
        server = ParamServer(rank, cranks, ep, rule="add",
                             ft=server_ft, controller_rank=ctl_rank)
        if spec.get("throttle_mbs"):
            _throttle_applies(server, float(spec["throttle_mbs"]))
        server.start()
        result = {
            "role": "server", "grads_applied": server.grads_applied,
            "snapshot_copies": server.snapshot_copies,
            "snapshot_hits": server.snapshot_hits,
            "heartbeats_seen": server.heartbeats_seen,
        }
    else:
        client = ParamClient(rank, sranks, transport,
                             seed_servers=(rank == cranks[0]),
                             ft=client_ft, shardctl=bool(skew),
                             controller_rank=ctl_rank)
        param = np.zeros(size, np.float32)
        grad = np.full(size, 1e-6, np.float32)
        client.start(param, grad)
        # Align client windows before timing: a non-seeding client's
        # start() returns while the seeder is still pushing the whole
        # vector, and an unaligned window would fold that seeding time
        # into the measured aggregate.  One warmup pull per client (so
        # every server has served once), then a client-only barrier on a
        # tag outside the PS/collectives ranges.
        client.async_recv_param()
        client.wait()
        # The barrier spins pump client.ping(): with heartbeats on, a
        # client parked here while a peer finishes its (multi-second at
        # 640 MB) warmup pull must keep beating, or the lease registry
        # evicts it mid-barrier and wedges the leg.
        _SYNC_TAG = 59999
        if rank == cranks[0]:
            for peer in cranks[1:]:
                while not transport.iprobe(peer, _SYNC_TAG):
                    client.ping()
                transport.recv(peer, _SYNC_TAG)
            for peer in cranks[1:]:
                transport.send(b"go", peer, _SYNC_TAG)
        else:
            transport.send(b"rdy", cranks[0], _SYNC_TAG)
            while not transport.iprobe(cranks[0], _SYNC_TAG):
                client.ping()
            transport.recv(cranks[0], _SYNC_TAG)
        t0 = time.time()
        if stream:
            # Per-op timing (the §12.7 A/B's payload): each GRAD and
            # each PARAM read individually, serial — the pipelining
            # under test is WITHIN one op, and concurrent ops would
            # fold cross-op scheduling into the measured latency.
            lat_grad, lat_param = [], []
            for _ in range(spec["rounds"]):
                s = time.monotonic()
                client.async_send_grad()
                client.wait()
                lat_grad.append(time.monotonic() - s)
                s = time.monotonic()
                client.async_recv_param()
                client.wait()
                lat_param.append(time.monotonic() - s)
            t1 = time.time()
            client.stop()
            result = {"role": "client", "t0": t0, "t1": t1,
                      "lat_grad": lat_grad, "lat_param": lat_param,
                      "retries": client.retries}
        else:
            for _ in range(spec["rounds"]):
                client.async_recv_param()
                client.async_send_grad()
                client.wait()
            t1 = time.time()
            client.stop()
            result = {"role": "client", "t0": t0, "t1": t1}
    # Per-rank Chrome-trace part (no-op unless MPIT_OBS_TRACE rode in —
    # the MPIT_BENCH_DECOMP column); the parent merges + analyzes.
    from mpit_tpu.obs import maybe_write_rank_trace

    maybe_write_rank_trace(rank, role=str(result.get("role", "")))
    transport.close()
    with open(os.environ["PTEST_RESULT"], "w") as fh:
        json.dump(result, fh)


def bench_readers(nreaders: int) -> dict:
    """One serving-tier leg: servers + 1 writer + ``nreaders`` paced
    readers over the TCP event-loop transport, one OS process per
    server/writer and a few reader-host processes driving many readers
    each (one transport + one ReaderClient per reader; the *server*
    side holds all N connections on its single I/O thread)."""
    import subprocess
    import tempfile

    import numpy as np

    from mpit_tpu.comm.tcp import allocate_local_addresses

    size = int(READER_MB * (1 << 20) / 4)
    # One reader-host process by default: on the shared-core bench box,
    # extra driver processes just contend with the servers (measured:
    # 4 hosts nearly doubled 512-reader p50 vs 1); the *server* side is
    # what holds all N connections either way.
    hosts = max(int(os.environ.get("MPIT_BENCH_READER_HOSTS", "1")), 1)
    batches = [list(range(NSERVERS + 1 + i, NSERVERS + 1 + nreaders, hosts))
               for i in range(hosts)]
    core = NSERVERS + 1
    nranks = core + nreaders
    addrs, socks = allocate_local_addresses(core)
    for s in socks:
        s.close()  # children rebind these ports
    addrs = addrs + ["127.0.0.1:0"] * nreaders  # readers never listen
    _log(f"[serve] {NSERVERS} servers + 1 writer + {nreaders} readers "
         f"({hosts} host proc(s)), vector {size * 4 / 2**20:.2f} MB, "
         f"{READER_ROUNDS} reads/reader at {READER_INTERVAL:.2f}s pacing")
    spec = {
        "addrs": addrs, "nservers": NSERVERS, "nreaders": nreaders,
        "size": size, "rounds": READER_ROUNDS, "interval": READER_INTERVAL,
        "budget_mb": READER_BUDGET_MB,
    }
    tmpdir = tempfile.mkdtemp(prefix=f"ptest_serve_{os.getpid()}_")
    jobs = ([("server", r, None) for r in range(NSERVERS)]
            + [("writer", NSERVERS, None)]
            + [("readers", core + i, batch)
               for i, batch in enumerate(batches) if batch])
    procs, result_files = [], {}
    for role, label, batch in jobs:
        result_path = os.path.join(tmpdir, f"{role}{label}.json")
        result_files[(role, label)] = result_path
        env = dict(
            os.environ, JAX_PLATFORMS="cpu",
            PTEST_SERVE=json.dumps({**spec, "role": role, "rank": label,
                                    "batch": batch or []}),
            PTEST_RESULT=result_path,
        )
        log_path = result_path.replace(".json", ".log")
        with open(log_path, "w") as fh:
            procs.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--serve-child"],
                env=env, stdout=fh, stderr=subprocess.STDOUT, text=True,
            ))
    deadline = time.monotonic() + float(
        os.environ.get("MPIT_BENCH_GANG_TIMEOUT", "900"))
    try:
        while any(p.poll() is None for p in procs):
            bad = next((i for i, p in enumerate(procs)
                        if p.poll() not in (None, 0)), None)
            if bad is not None or time.monotonic() > deadline:
                for p in procs:
                    if p.poll() is None:
                        p.terminate()
                for path in result_files.values():
                    logp = path.replace(".json", ".log")
                    if os.path.exists(logp):
                        with open(logp) as fh:
                            sys.stderr.write(fh.read())
                raise RuntimeError(
                    f"serve gang job {jobs[bad][:2]} failed (logs: {tmpdir})"
                    if bad is not None else
                    f"serve gang timed out (logs: {tmpdir})")
            time.sleep(0.1)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    samples, busy_honored, windows, reads = [], 0, [], 0
    for (role, label), path in result_files.items():
        with open(path) as fh:
            rec = json.load(fh)
        if role == "readers":
            samples.extend(rec["samples"])
            busy_honored += rec["busy_honored"]
            windows.append((rec["t0"], rec["t1"]))
            reads += rec["reads"]
    srv = [json.load(open(result_files[("server", r)]))
           for r in range(NSERVERS)]
    dt = max(w[1] for w in windows) - min(w[0] for w in windows)
    arr = np.asarray(samples)
    p50 = float(np.percentile(arr, 50)) * 1e3
    p99 = float(np.percentile(arr, 99)) * 1e3
    mbs = reads * size * 4 / dt / 2**20
    copies = sum(s["snapshot_copies"] for s in srv)
    versions = sum(s["snap_version"] for s in srv)
    if copies > versions + NSERVERS:
        raise RuntimeError(
            f"snapshot cache broke under fan-out: {copies} copies for "
            f"{versions} committed versions (the N-readers=1-copy "
            "invariant must hold at every reader count)")
    import shutil

    shutil.rmtree(tmpdir, ignore_errors=True)
    _log(f"[serve] {nreaders} readers: p50 {p50:.1f} ms, p99 {p99:.1f} ms, "
         f"{mbs:.1f} MB/s aggregate, busy={sum(s['busy_replies'] for s in srv)}"
         f"/{busy_honored} (issued/honored), copies={copies} for "
         f"{versions} versions")
    return {
        "metric": "ps_serve_read_latency",
        "unit": "ms",
        "value": round(p50, 2),
        "p99_ms": round(p99, 2),
        "readers": nreaders,
        "reads": reads,
        "mbs": round(mbs, 1),
        "vector_mb": round(size * 4 / 2**20, 3),
        "interval_s": READER_INTERVAL,
        "busy_replies": sum(s["busy_replies"] for s in srv),
        "busy_honored": busy_honored,
        "snapshot_copies": copies,
        "snap_versions": versions,
        "snapshot_hits": sum(s["snapshot_hits"] for s in srv),
    }


def _serve_child() -> None:
    """One process of the serving-tier gang (--serve-child): a server
    or the writer for its single rank, or a reader host driving a batch
    of readers (one transport + ReaderClient per reader, all stepped by
    one thread — the server side is what holds N connections)."""
    import numpy as np

    from mpit_tpu.comm.tcp import TcpTransport
    from mpit_tpu.ft import FTConfig
    from mpit_tpu.ps import ParamClient, ParamServer, ReaderClient, ServeConfig

    spec = json.loads(os.environ["PTEST_SERVE"])
    addrs = spec["addrs"]
    nranks = len(addrs)
    sranks = list(range(spec["nservers"]))
    wrank = spec["nservers"]
    readers = list(range(wrank + 1, nranks))
    size = spec["size"]
    rounds, interval = spec["rounds"], spec["interval"]
    role = spec["role"]
    ft = FTConfig(op_deadline_s=120.0)
    if role == "server":
        rank = spec["rank"]
        transport = TcpTransport(rank, nranks, addrs, reconnect=120.0,
                                 dial_peers=list(range(rank)),
                                 connect_timeout=120.0)
        server = ParamServer(
            rank, [wrank], transport, rule="add", reader_ranks=readers,
            serve=ServeConfig(budget_bytes=int(spec["budget_mb"] * (1 << 20))))
        server.start()
        result = {
            "role": "server",
            "busy_replies": server.busy_replies,
            "snapshot_copies": server.snapshot_copies,
            "snapshot_hits": server.snapshot_hits,
            "snap_version": server._snap_version,
            "params_served": server.params_served,
            "grads_applied": server.grads_applied,
        }
        transport.close()
    elif role == "writer":
        transport = TcpTransport(wrank, nranks, addrs, reconnect=120.0,
                                 dial_peers=sranks, connect_timeout=120.0)
        client = ParamClient(wrank, sranks, transport, seed_servers=True,
                             ft=ft)
        param = np.arange(size, dtype=np.float32)
        grad = np.full(size, 1e-6, np.float32)
        client.start(param, grad)
        # One committed version per pacing interval for the whole read
        # window (+1 slack): readers must observe versions moving.
        for _ in range(rounds + 1):
            client.async_send_grad()
            client.wait()
            time.sleep(interval)
        client.stop()
        result = {"role": "writer", "grads": rounds + 1}
        transport.close()
    else:  # reader host
        batch = spec["batch"]
        transports, clients = {}, {}
        for r in batch:
            transports[r] = TcpTransport(r, nranks, addrs, reconnect=120.0,
                                         dial_peers=sranks, listen=False,
                                         connect_timeout=120.0)
            clients[r] = ReaderClient(r, sranks, transports[r], ft=ft)
            clients[r].start(np.zeros(size, np.float32))
        for r in batch:  # one warmup read (first-touch, codec caches)
            clients[r].read_params()
        # Paced async driver: start-staggered reads, one thread stepping
        # every in-flight reader round-robin; per-read latency sampled
        # from async-start to drain.
        t_start = time.time()
        base = time.monotonic()
        state = {r: {"next": base + (i / max(len(batch), 1)) * interval,
                     "t0": None, "reads": 0}
                 for i, r in enumerate(batch)}
        samples = []
        import heapq

        inflight: set = set()
        due = [(state[r]["next"], r) for r in batch]
        heapq.heapify(due)
        pending = len(batch)
        while pending or inflight:
            now = time.monotonic()
            while due and due[0][0] <= now:  # O(newly due), not O(batch)
                _t, r = heapq.heappop(due)
                clients[r].async_read_params()
                state[r]["t0"] = time.monotonic()
                inflight.add(r)
            for r in list(inflight):  # hot path: only in-flight readers
                if not clients[r].poll():
                    st = state[r]
                    samples.append(time.monotonic() - st["t0"])
                    st["reads"] += 1
                    st["next"] = st["t0"] + interval
                    st["t0"] = None
                    inflight.discard(r)
                    if st["reads"] >= rounds:
                        pending -= 1
                    else:
                        heapq.heappush(due, (st["next"], r))
            # Yield the core between passes (a driver spinning poll()
            # flat-out steals the cycles the colocated 1-core servers
            # need to produce the replies being waited for — the
            # IDLE_USEC lesson), but keep the in-flight cadence tight:
            # a paced read's latency floor is this sleep times the
            # number of protocol hops.
            time.sleep(0.0002 if inflight else 0.001)
        t_end = time.time()
        for r in batch:
            assert clients[r].monotone, f"reader {r} saw a version go back"
            clients[r].stop()
            transports[r].close()
        result = {
            "role": "readers", "samples": samples,
            "reads": sum(st["reads"] for st in state.values()),
            "busy_honored": sum(c.busy_honored for c in clients.values()),
            "t0": t_start, "t1": t_end,
        }
    with open(os.environ["PTEST_RESULT"], "w") as fh:
        json.dump(result, fh)


def bench_cells(ncells: int, kill: bool = False) -> dict:
    """One serving-fabric leg (MPIT_BENCH_CELLS): 1 training server + 1
    writer + ``ncells`` replica cells + CELL_READERS fabric-routed
    readers, every serving member throttled to CELL_MBS of modeled
    reply capacity.  ``ncells=0`` is the direct-serving control (the
    readers hit the training server, §8 style) — its GRAD p50 is the
    no-fabric baseline the cells legs must stay flat against.  With
    ``kill``, one cell is SIGKILLed mid-window and the leg additionally
    asserts zero RetryExhausted and >= 1 reader failover."""
    import signal as _signal
    import subprocess
    import tempfile

    import numpy as np

    from mpit_tpu.comm.tcp import allocate_local_addresses

    size = int(CELL_MB * (1 << 20) / 4)
    core = 2 + ncells  # server, writer, cells
    nranks = core + CELL_READERS
    cell_ranks = list(range(2, 2 + ncells))
    # The listening children INHERIT the parent's bound sockets
    # (pass_fds) instead of close-and-rebind: on loopback the kernel's
    # ephemeral-port hand loves a just-freed port, so a sibling's
    # outbound connect can squat a rebinding listener's port for the
    # whole leg — the silent-child flake this layout removes.
    addrs, socks = allocate_local_addresses(core)
    addrs = addrs + ["127.0.0.1:0"] * CELL_READERS
    _log(f"[cells] 1 server + 1 writer + {ncells} cells + {CELL_READERS} "
         f"readers{' (kill leg)' if kill else ''}, vector "
         f"{size * 4 / 2**20:.2f} MB, member capacity {CELL_MBS:.0f} MB/s, "
         f"{CELL_ROUNDS} reads/reader at {CELL_INTERVAL:.2f}s pacing")
    spec = {
        "addrs": addrs, "ncells": ncells, "cell_ranks": cell_ranks,
        "size": size, "rounds": CELL_ROUNDS, "interval": CELL_INTERVAL,
        "member_mbs": CELL_MBS, "max_lag": CELL_MAX_LAG, "kill": kill,
    }
    tmpdir = tempfile.mkdtemp(prefix=f"ptest_cells_{os.getpid()}_")
    batches = [list(range(core + i, nranks, CELL_HOSTS))
               for i in range(CELL_HOSTS)]
    jobs = ([("server", 0, None), ("writer", 1, None)]
            + [("cell", c, None) for c in cell_ranks]
            + [("readers", core + i, batch)
               for i, batch in enumerate(batches) if batch])
    procs, result_files, by_job = [], {}, {}
    for role, label, batch in jobs:
        result_path = os.path.join(tmpdir, f"{role}{label}.json")
        result_files[(role, label)] = result_path
        env = dict(
            os.environ, JAX_PLATFORMS="cpu",
            PTEST_CELLS=json.dumps({**spec, "role": role, "rank": label,
                                    "batch": batch or []}),
            PTEST_RESULT=result_path,
        )
        pass_fds = ()
        if role in ("server", "writer", "cell"):
            fd = socks[label].fileno()
            env["PTEST_LISTEN_FD"] = str(fd)
            pass_fds = (fd,)
        log_path = result_path.replace(".json", ".log")
        with open(log_path, "w") as fh:
            p = subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--cells-child"],
                env=env, stdout=fh, stderr=subprocess.STDOUT, text=True,
                pass_fds=pass_fds,
            )
        procs.append(p)
        by_job[(role, label)] = p
    for s in socks:
        s.close()  # the children own their inherited copies now
    victim = cell_ranks[0] if (kill and ncells >= 2) else None
    # The kill anchors to the READ WINDOW, not the spawn: the reader
    # host drops a .started marker once every reader finished its
    # warmup read, and the victim dies 40% into the paced window — a
    # kill during gang formation would tear reader *construction*
    # dials, which is a different (uninteresting) failure.
    started_markers = [path + ".started"
                       for (role, _l), path in result_files.items()
                       if role == "readers"]
    kill_at: "float | None" = None
    deadline = time.monotonic() + float(
        os.environ.get("MPIT_BENCH_GANG_TIMEOUT", "900"))
    killed = False
    try:
        while any(p.poll() is None for p in procs):
            if victim is not None and not killed and kill_at is None \
                    and all(os.path.exists(m) for m in started_markers):
                kill_at = time.monotonic() + (CELL_ROUNDS
                                              * CELL_INTERVAL) * 0.4
            if victim is not None and not killed and kill_at is not None \
                    and time.monotonic() >= kill_at:
                by_job[("cell", victim)].send_signal(_signal.SIGKILL)
                killed = True
                _log(f"[cells] SIGKILLed cell {victim} mid-window")
            bad = next(
                (i for i, p in enumerate(procs)
                 if p.poll() not in (None, 0)
                 and not (killed and p is by_job[("cell", victim)])),
                None)
            if bad is not None or time.monotonic() > deadline:
                for p in procs:
                    if p.poll() is None:
                        p.terminate()
                for path in result_files.values():
                    logp = path.replace(".json", ".log")
                    if os.path.exists(logp):
                        with open(logp) as fh:
                            sys.stderr.write(fh.read())
                raise RuntimeError(
                    f"cells gang job {jobs[bad][:2]} failed (logs: {tmpdir})"
                    if bad is not None else
                    f"cells gang timed out (logs: {tmpdir})")
            time.sleep(0.1)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    host_recs = [json.load(open(path))
                 for (role, _l), path in result_files.items()
                 if role == "readers"]
    reader_rec = {
        "samples": [s for r in host_recs for s in r["samples"]],
        "reads": sum(r["reads"] for r in host_recs),
        "failovers": sum(r["failovers"] for r in host_recs),
        "busy_honored": sum(r["busy_honored"] for r in host_recs),
        "max_lag_seen": max(r["max_lag_seen"] for r in host_recs),
        "errors": [e for r in host_recs for e in r["errors"]],
        "t0": min(r["t0"] for r in host_recs),
        "t1": max(r["t1"] for r in host_recs),
    }
    writer_rec = json.load(open(result_files[("writer", 1)]))
    cells_rec = []
    for c in cell_ranks:
        if c == victim:
            continue  # SIGKILLed: no result file, by design
        cells_rec.append(json.load(open(result_files[("cell", c)])))
    samples = np.asarray(reader_rec["samples"])
    dt = reader_rec["t1"] - reader_rec["t0"]
    reads = reader_rec["reads"]
    mbs = reads * size * 4 / dt / 2**20
    p50 = float(np.percentile(samples, 50)) * 1e3
    p99 = float(np.percentile(samples, 99)) * 1e3
    if kill:
        if reader_rec["failovers"] < 1:
            raise RuntimeError(
                "kill leg: no reader ever failed over — the victim "
                "served nobody?")
        if reader_rec["errors"]:
            raise RuntimeError(
                f"kill leg drew RetryExhausted: {reader_rec['errors']}")
    import shutil

    shutil.rmtree(tmpdir, ignore_errors=True)
    _log(f"[cells] n={ncells}{'+kill' if kill else ''}: {mbs:.1f} MB/s "
         f"aggregate reads (p50 {p50:.1f} ms), GRAD p50 "
         f"{writer_rec['grad_p50_ms']:.1f} ms, failovers="
         f"{reader_rec['failovers']}, max observed lag "
         f"{reader_rec['max_lag_seen']}")
    return {
        "metric": "ps_cells_serving",
        "unit": "MB/s",
        "value": round(mbs, 1),
        "cells": ncells,
        "kill": bool(kill),
        "readers": CELL_READERS,
        "reads": reads,
        "read_p50_ms": round(p50, 2),
        "read_p99_ms": round(p99, 2),
        "grad_p50_ms": round(writer_rec["grad_p50_ms"], 2),
        "grad_p99_ms": round(writer_rec["grad_p99_ms"], 2),
        "member_mbs": CELL_MBS,
        "vector_mb": round(size * 4 / 2**20, 3),
        "interval_s": CELL_INTERVAL,
        "failovers": reader_rec["failovers"],
        "busy_honored": reader_rec["busy_honored"],
        "max_lag_seen": reader_rec["max_lag_seen"],
        "max_lag_bound": CELL_MAX_LAG,
        "diffs_installed": sum(c["diffs_installed"] for c in cells_rec),
        "resyncs": sum(c["resyncs"] for c in cells_rec),
    }


def _cells_child() -> None:
    """One process of the serving-fabric gang (--cells-child): the
    training server (diff producer; direct reader serving in the N=0
    control), the writer (samples its own GRAD latency — the flatness
    claim), one replica cell, or the reader host driving the
    fabric-routed reader population."""
    import numpy as np

    from mpit_tpu.comm.tcp import TcpTransport
    from mpit_tpu.ft import FTConfig, RetryExhausted
    from mpit_tpu.ps import ParamClient, ParamServer, ReaderClient, ServeConfig

    spec = json.loads(os.environ["PTEST_CELLS"])
    addrs = spec["addrs"]
    nranks = len(addrs)
    cell_ranks = spec["cell_ranks"]
    ncells = spec["ncells"]
    core = 2 + ncells
    readers = list(range(core, nranks))
    size = spec["size"]
    rounds, interval = spec["rounds"], spec["interval"]
    member_mbs = spec["member_mbs"]
    role = spec["role"]
    listener = None
    if "PTEST_LISTEN_FD" in os.environ:
        import socket as _socket

        listener = _socket.socket(_socket.AF_INET, _socket.SOCK_STREAM,
                                  fileno=int(os.environ["PTEST_LISTEN_FD"]))

    def throttle(member) -> None:
        """Model a fixed per-member reply capacity: every granted read
        spends frame_bytes/member_mbs of the member's (single-threaded)
        time, exactly the BENCH_r11 throttle shape."""
        inner = member._snapshot_wire
        cost = size * 4 / (member_mbs * (1 << 20))

        def wrapped(codec):
            time.sleep(cost)
            return inner(codec)

        member._snapshot_wire = wrapped

    ft = FTConfig(op_deadline_s=60.0)
    if role == "server":
        transport = TcpTransport(0, nranks, addrs, listener=listener,
                                 reconnect=120.0, dial_peers=[],
                                 connect_timeout=120.0)
        server = ParamServer(
            0, [1], transport, rule="add",
            reader_ranks=(readers if ncells == 0 else None),
            cell_ranks=(cell_ranks or None),
            serve=ServeConfig(budget_bytes=1 << 30),
            ft=FTConfig(lease_ttl_s=5.0))
        if ncells == 0:
            throttle(server)  # the control serves reads itself
        server.start()
        result = {
            "role": "server",
            "snap_version": server._snap_version,
            "params_served": server.params_served,
            "grads_applied": server.grads_applied,
            "diffs_sent": int(server._m_diff_full.value)
            + int(server._m_diff_delta.value),
        }
        transport.close()
    elif role == "writer":
        transport = TcpTransport(1, nranks, addrs, listener=listener,
                                 reconnect=120.0, dial_peers=[0],
                                 connect_timeout=120.0)
        client = ParamClient(1, [0], transport, seed_servers=True, ft=ft)
        param = np.arange(size, dtype=np.float32)
        grad = np.full(size, 1e-6, np.float32)
        client.start(param, grad)
        lat = []
        # One committed version per pacing interval across the whole
        # read window (+2 slack), each grad individually timed: this
        # distribution's p50 is the "training stays flat" claim.
        for _ in range(rounds + 2):
            t0 = time.monotonic()
            client.async_send_grad()
            client.wait()
            lat.append(time.monotonic() - t0)
            time.sleep(interval)
        client.stop()
        result = {
            "role": "writer", "grads": rounds + 2,
            "grad_p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "grad_p99_ms": float(np.percentile(lat, 99)) * 1e3,
        }
        transport.close()
    elif role == "cell":
        from mpit_tpu.cells.cell import ServingCell

        rank = spec["rank"]
        transport = TcpTransport(rank, nranks, addrs, listener=listener,
                                 reconnect=120.0, dial_peers=[0],
                                 connect_timeout=120.0)
        cell = ServingCell(
            rank, 0, transport, readers, size=size,
            max_lag=spec["max_lag"],
            serve=ServeConfig(budget_bytes=1 << 30),
            ft=FTConfig(heartbeat_s=0.2, op_deadline_s=60.0))
        throttle(cell)
        cell.start()
        result = {
            "role": "cell",
            "version": cell.version,
            "params_served": cell.params_served,
            "diffs_installed": cell.diffs_installed,
            "resyncs": cell.resyncs,
            "lag_sheds": cell.lag_sheds,
        }
        transport.close()
    else:  # reader host: the paced fabric-routed population
        batch = spec["batch"]
        serving = cell_ranks if ncells else [0]
        transports, clients = {}, {}
        reader_ft = FTConfig(op_deadline_s=(2.0 if spec["kill"] else 60.0),
                             max_retries=8)
        for r in batch:
            transports[r] = TcpTransport(r, nranks, addrs, reconnect=120.0,
                                         dial_peers=serving, listen=False,
                                         connect_timeout=120.0)
            clients[r] = ReaderClient(
                r, [0], transports[r], ft=reader_ft,
                cells=({0: cell_ranks} if ncells else None))
            clients[r].start(np.zeros(size, np.float32))
        for r in batch:  # warmup (first-touch, codec caches)
            clients[r].read_params()
        # The paced window starts now — the kill leg's parent waits
        # for this marker before arming the SIGKILL.
        open(os.environ["PTEST_RESULT"] + ".started", "w").close()
        t_start = time.time()
        base = time.monotonic()
        state = {r: {"next": base + (i / max(len(batch), 1)) * interval,
                     "t0": None, "reads": 0}
                 for i, r in enumerate(batch)}
        samples, errors = [], []
        max_lag_seen = 0
        import heapq

        inflight: set = set()
        due = [(state[r]["next"], r) for r in batch]
        heapq.heapify(due)
        pending = len(batch)
        while pending or inflight:
            now = time.monotonic()
            while due and due[0][0] <= now:
                _t, r = heapq.heappop(due)
                clients[r].async_read_params()
                state[r]["t0"] = time.monotonic()
                inflight.add(r)
            for r in list(inflight):
                try:
                    busy = clients[r].poll()
                except RetryExhausted as exc:
                    errors.append(f"reader {r}: {exc!r}")
                    inflight.discard(r)
                    pending -= 1
                    continue
                if not busy:
                    st = state[r]
                    samples.append(time.monotonic() - st["t0"])
                    st["reads"] += 1
                    max_lag_seen = max(max_lag_seen,
                                       clients[r].lags.get(0, 0))
                    st["next"] = st["t0"] + interval
                    st["t0"] = None
                    inflight.discard(r)
                    if st["reads"] >= rounds:
                        pending -= 1
                    else:
                        heapq.heappush(due, (st["next"], r))
            time.sleep(0.0002 if inflight else 0.001)
        t_end = time.time()
        for r in batch:
            assert clients[r].monotone, f"reader {r} saw a version go back"
            clients[r].stop()
            transports[r].close()
        result = {
            "role": "readers", "samples": samples,
            "reads": sum(st["reads"] for st in state.values()),
            "busy_honored": sum(c.busy_honored for c in clients.values()),
            "failovers": sum(c.failovers for c in clients.values()),
            "max_lag_seen": max_lag_seen,
            "errors": errors,
            "t0": t_start, "t1": t_end,
        }
        if errors and not spec["kill"]:
            raise SystemExit(f"readers drew RetryExhausted: {errors}")
    with open(os.environ["PTEST_RESULT"], "w") as fh:
        json.dump(result, fh)


def _shm_run_threads(size: int, heartbeat: bool = False) -> float:
    """One timed gang: T rounds of {pull, push, wait} per client, all
    ranks as threads of this process (debug mode — see module docstring
    for why this understates codec throughput)."""
    ring = _ring_bytes(size)
    _GANG_SEQ[0] += 1
    ns = f"ptest_{os.getpid()}_{_GANG_SEQ[0]}"
    with shm_gang(ns, NSERVERS, NCLIENTS, size, ring_bytes=ring) as (
        clients, _params, _grads
    ):
        def client_rounds(i):
            c = clients[i]
            for _ in range(ROUNDS):
                c.async_recv_param()
                c.async_send_grad()
                c.wait()

        workers = [
            threading.Thread(target=client_rounds, args=(i,), daemon=True)
            for i in range(NCLIENTS)
        ]
        t0 = time.perf_counter()
        for t in workers:
            t.start()
        join_checked(workers, 600, "[shm] client rounds")
        dt = time.perf_counter() - t0

    # Bi-directional bytes moved per client per round = 2 * size * 4.
    mbs = 2 * ROUNDS * NCLIENTS * size * 4 / dt / 2**20
    _log(f"[shm] {ROUNDS} rounds x {NCLIENTS} clients in {dt:.3f}s "
         f"-> {mbs:.1f} MB/s aggregate")
    return mbs


def _bench_shm_subprocess(codec: str = "") -> dict:
    """Run the shm leg in a child with JAX_PLATFORMS=cpu: the PS servers
    are host roles (ps/server.py device='cpu'), and this parent may
    already hold the accelerator for the ici leg."""
    import subprocess

    env = dict(os.environ, MPIT_BENCH_MODE="shm", JAX_PLATFORMS="cpu",
               MPIT_BENCH_GANG="threads")
    env.pop("MPIT_BENCH_CODECS", None)  # parent drives the sweep
    if codec:
        env["MPIT_PS_CODEC"] = codec
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__)], env=env,
            capture_output=True, text=True, timeout=900,
        )
    except subprocess.TimeoutExpired as e:
        # Echo whatever the child logged before the stall — it is the
        # only evidence of where it hung.
        for stream in (e.stdout, e.stderr):
            if stream:
                sys.stderr.write(stream if isinstance(stream, str)
                                 else stream.decode(errors="replace"))
        raise
    sys.stderr.write(out.stderr)
    if out.returncode != 0:
        raise RuntimeError(f"shm child failed rc={out.returncode}")
    lines = out.stdout.strip().splitlines()
    if not lines:
        raise RuntimeError("shm child exited 0 but produced no JSON output")
    return json.loads(lines[-1])


def main():
    results = []
    sweep = CODECS or [""]
    hb_modes = [False, True] if HEARTBEAT_SWEEP else [False]
    obs_modes = [False, True] if OBS_SWEEP else [False]
    if MODE in ("ici", "both"):
        results.append(bench_ici())
    if MODE == "shm":
        results.extend(bench_shm(c, hb, ob) for c in sweep
                       for hb in hb_modes for ob in obs_modes)
    elif MODE == "both":
        if GANG == "procs":
            # Every rank is its own child process with JAX_PLATFORMS=cpu;
            # this parent keeps the accelerator for the ici leg and never
            # touches jax on the shm path.
            results.extend(bench_shm(c, hb, ob) for c in sweep
                           for hb in hb_modes for ob in obs_modes)
        else:
            results.extend(_bench_shm_subprocess(c) for c in sweep)
    if STATUS_SWEEP and MODE in ("shm", "both"):
        # Live-serving leg: obs on + statusd endpoints in every child +
        # a parent poller scraping /metrics throughout.  codec=none, so
        # the row joins the baseline gate — serving scrapes must not
        # cost the record.
        results.append(bench_shm("none", obs=True, status=True))
    if PROFILE_SWEEP and MODE in ("shm", "both"):
        # CPU-attribution leg: codec=none with the profiling plane live
        # in every child (MPIT_OBS_PROFILE + trace export), analyzed by
        # `obs profile`.  Gate-exempt like the decomp leg: the
        # per-step thread-clock reads are a measured ~2x tax on a
        # time-shared 1-core host — the overhead IS the column
        # (BENCH_r17); the plain codec=none leg above still gates.
        results.append(bench_shm("none", obs=True, profile=True))
    if DECOMP_SWEEP and MODE in ("shm", "both"):
        # Causal-decomposition leg: traced FLAG_TIMING gang, analyzed;
        # per-phase p50/p99 lands in the row.  Framed wire => excluded
        # from the codec=none gate (a different protocol mode, like
        # skew); the plain codec=none leg above still holds the record.
        results.append(bench_shm("none", decomp=True))
    if READERS_SWEEP and MODE in ("shm", "both"):
        # Many-client serving sweep (TCP event-loop transport): one leg
        # per reader count; rows are latency-metric, not bandwidth, and
        # never join the codec=none baseline gate.
        results.extend(bench_readers(n) for n in READERS_SWEEP)
    if CELLS_SWEEP and MODE in ("shm", "both"):
        # Multi-cell serving fabric (TCP gangs, per-member capacity
        # model): the N=0 direct-serving control first, then one leg
        # per cell count, then the kill-a-cell leg at the largest
        # count >= 2.  Serving-metric rows: never join the codec=none
        # baseline gate.
        results.append(bench_cells(0))
        results.extend(bench_cells(n) for n in CELLS_SWEEP if n > 0)
        killable = [n for n in CELLS_SWEEP if n >= 2]
        if CELL_KILL and killable:
            results.append(bench_cells(max(killable), kill=True))
    if STREAM_SWEEP and MODE in ("shm", "both"):
        # The pipelined-streaming A/B: per codec, unchunked control vs
        # FLAG_CHUNKED over the modeled serial link.  Latency-metric
        # rows on a modeled wire: never join the codec=none gate.
        results.extend(bench_stream())
    if AGG_SWEEP and MODE in ("shm", "both"):
        # The hierarchical-aggregation A/B (§13.6): flat vs prereduce
        # vs tree over the modeled link.  Modeled-wire rows: never join
        # the codec=none gate.
        results.extend(bench_agg())
    if LM_SWEEP and MODE in ("shm", "both"):
        # The flagship LM workload (mpit_tpu.lm): tokens/sec through
        # the full static composition (weighted layout + chunked +
        # int8 EF + agg tree), loss-envelope and bitwise gated
        # in-bench.  lm_* rows: never join the codec=none gate.
        results.extend(bench_lm())
    if SKEW_SWEEP and MODE in ("shm", "both"):
        # The straggler A/B runs at codec=none (the skew is in the
        # *reply latency*, not the byte volume): rebalance off, then on.
        results.append(bench_shm("none", skew_rebalance=False))
        results.append(bench_shm("none", skew_rebalance=True))
    if ELASTIC_SWEEP and MODE in ("shm", "both"):
        # The shrink/grow sweep: capacity at each size of a 1 -> 2 -> 1
        # membership walk; rows never join the codec=none gate.
        results.extend(bench_elastic())
    if AUTOSCALE_SWEEP and MODE in ("shm", "both"):
        # The closed-loop A/B: static vs autoscaled under the bursty
        # scenario leg (in-process gang, member-capacity throttle);
        # rows never join the codec=none gate.  Runs LAST: it flips
        # the parent's obs registry on and off around itself.
        results.extend(bench_autoscale())
    low: list = []
    if BASELINE > 0:
        gated = [
            r for r in results
            if r.get("codec") == "none" and r["metric"].endswith("_shm")
            and not r.get("skew") and not r.get("decomp")
            and not r.get("profile")
        ]
        if gated:
            # Warm-copy control beside the gate legs: every gated row
            # carries the probe so the captured record shows what the
            # host could copy when the number was taken.
            probe = host_probe()
            warm_ref = HOST_MBS or 8.0 * BASELINE
            # fresh-page faulting slower than 2x the record cannot feed
            # the per-rep buffer allocations at the record
            cold_ref = 2.0 * BASELINE
            low = [r for r in gated if r["value"] < 0.97 * BASELINE]
            degraded = (probe["warm_mbs"] < warm_ref
                        or probe["cold_mbs"] < cold_ref)
            miss = "environmental" if degraded else "regression"
            for r in gated:
                r["host_probe"] = probe
                if r in low:
                    r["baseline_miss"] = miss
            _log(f"[gate] host_probe warm {probe['warm_mbs']} MB/s "
                 f"(>= {warm_ref:.0f}?), cold {probe['cold_mbs']} MB/s "
                 f"(>= {cold_ref:.0f}?); {len(low)}/{len(gated)} gated "
                 f"leg(s) below {0.97 * BASELINE:.1f} MB/s")
    for r in results:
        print(json.dumps(r))
    if low:
        if all(r["baseline_miss"] == "environmental" for r in low):
            # The host itself is degraded: the miss is annotated in the
            # captured rows, not raised as a code regression.
            _log(f"[gate] miss annotated environmental: host warm-copy "
                 f"below the healthy reference; rows carry host_probe")
        else:
            raise SystemExit(
                f"codec=none throughput regression: {[r['value'] for r in low]}"
                f" MB/s (heartbeat={[r.get('heartbeat') for r in low]}) below"
                f" 97% of the {BASELINE} MB/s baseline (host_probe healthy)"
            )


if __name__ == "__main__":
    if "--gang-child" in sys.argv:
        _gang_child()
    elif "--serve-child" in sys.argv:
        _serve_child()
    elif "--cells-child" in sys.argv:
        _cells_child()
    else:
        main()
