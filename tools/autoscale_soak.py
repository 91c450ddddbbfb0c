#!/usr/bin/env python3
"""Closed-loop autoscale soak — the harness behind `autoscale-smoke`
(ci.yml) and the ISSUE 11 acceptance bar.

Runs a scenario (mpit_tpu.ft.traffic) against an elastic shardctl gang
on the in-process router, twice:

1. **static envelope** — fixed launch membership, no chaos, no
   autoscaler, the scenario's serialized training rounds only.  This is
   the fault-free reference the chaos run must match **bitwise**.
2. **chaos + closed loop** — the same serialized training rounds,
   plus the scenario's shaped concurrent reader load (diurnal curves,
   bursts), preemption waves (notice flag — the SIGTERM handler's one
   act), slow-joiner churn (late reader admission) and straggler
   injection (one member's capacity throttled harder), with an
   :class:`~mpit_tpu.shardctl.autoscale.Autoscaler` attached to the
   controller and **nobody calling /scale**.

Every serving member runs under the **member-capacity throttle**
(a fixed reply capacity a member): each shard op blocks its rank for
``shard_bytes / member_mbs`` wall-seconds, so a member is a
fixed-capacity resource, reader pressure shows up as queueing in the
pooled ``mpit_ps_op_seconds`` p99, and adding/draining members moves
that p99 the way real capacity would — which is exactly the signal the
policy engine watches.

Asserts (soak mode; `--smoke` is the short CI form):

- the traffic shape changed >= 5 times (smoke: >= 2) and the gang
  resized itself: >= 1 *automatic* scale-up AND >= 1 automatic
  scale-down, with **zero** operator /scale calls;
- SLOs were met within each phase's declared duty cycle, measured over
  the phase's decision windows after a bounded settle window;
- the autoscaler never flapped beyond its budget;
- zero RetryExhausted (no client op ever died);
- final params **bitwise equal** to the static envelope run;
- the decision audit log, the replayable traffic trace, the obs trace
  and every autoscale flight dump validate.

Artifacts land in ``--outdir``: ``autoscale_audit.json`` (every
decision with its telemetry window), ``traffic_trace.json`` (the
seeded, replayable event schedule), ``mpit_autoscale_trace.json``
(validated Chrome trace), ``mpit_flight_*.json`` (autoscale
postmortems).  Usage::

    python tools/autoscale_soak.py [--smoke] [--outdir DIR]
    python tools/autoscale_soak.py --scenario 'seed=7;name=...;...'
"""

import argparse
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

# -- tunables: the member-capacity model and the SLO that rides it ----------

SIZE = 32768            # flat vector (floats) — 128 KiB
SHARDS_PER_SERVER = 3   # launch cut: 2 servers x 3 = 6 migratable units
MEMBER_MBS = 4.0        # each member applies/serves at 4 MB/s
TICK_S = 0.25           # scenario tick pacing (wall)
P99_TARGET_MS = 24.0    # the headline SLO over mpit_ps_op_seconds


def default_autoscale_cfg():
    from mpit_tpu.shardctl import AutoscaleConfig, SLOConfig

    return AutoscaleConfig(
        slo=SLOConfig(p99_ms=P99_TARGET_MS),
        window_s=0.5,
        high_frac=1.0,
        # Band edges are bucket-aware: the op histogram's log2 buckets
        # quantize p99 to {3.9, 7.8, 15.6, 31.2, ...} ms, so with a
        # 24 ms target the breach edge (24) admits only the >= 31.2
        # buckets (true saturation) and the idle edge (0.7 x 24 = 16.8)
        # covers everything a healthy throttled member produces (up to
        # the 15.6 bucket) — the band between absorbs nothing but
        # measurement noise, which is the point of hysteresis.
        low_frac=0.7,
        breach_windows=2,
        idle_windows=4,
        # Cooldown must outlive a drain's transition stall (a scale-down
        # migrates every shard off the victim; in-flight ops park on
        # frozen slots and complete seconds later — measured ~1-2s at
        # this shard size) so the post-action turbulence never feeds the
        # next verdict.
        cooldown_s=4.0,
        settle_s=2.5,
        flap_budget=3,
        flap_window_s=60.0,
        # Operating floor of 2: a 1-server gang has nowhere to migrate
        # and a preemption wave against it has no survivor to drain to —
        # the floor is what makes "absorb a spot reclaim" a promise.
        min_servers=2,
        max_servers=3,
    )


FT_KW = dict(op_deadline_s=10.0, max_retries=10,
             backoff_base_s=0.01, backoff_cap_s=0.05)


def _throttle_member(server, rank, mbs, factors):
    """The member-capacity model at the per-shard-op seam: the
    slot busy-timer wraps dedup->apply->ack (GRAD) and snapshot->send
    (PARAM), so one blocking sleep per op serializes this rank's
    service exactly the way a fixed-capacity member would.  ``factors``
    is the live straggle multiplier table the driver mutates."""
    inner = server._sc_busy_timer

    def busy_timer(sid):
        cm = inner(sid)
        slot = server._slots.get(sid)
        nbytes = slot.size * 4 if slot is not None else 0
        delay = nbytes * factors.get(rank, 1.0) / (mbs * 2 ** 20)

        class _Throttled:
            def __enter__(self):
                if delay > 0:
                    time.sleep(delay)
                return cm.__enter__()

            def __exit__(self, *exc):
                return cm.__exit__(*exc)

        return _Throttled()

    server._sc_busy_timer = busy_timer


class _Reader:
    """One pull-only client on its own thread, fed read permits by the
    driver — reads float concurrently (they never mutate state, so
    their concurrency is pure load), errors surface at the end."""

    def __init__(self, client):
        self.client = client
        self._sem = threading.Semaphore(0)
        self._stop = False
        self.reads_done = 0
        self.errors = []
        self.thread = threading.Thread(target=self._run, daemon=True)

    def start_pulling(self):
        self.thread.start()

    def dispatch(self, n):
        for _ in range(n):
            self._sem.release()

    def _run(self):
        while True:
            self._sem.acquire()
            if self._stop:
                return
            try:
                self.client.async_recv_param()
                self.client.wait()
                self.reads_done += 1
            except Exception as exc:  # noqa: BLE001 — surfaced by the driver
                self.errors.append(repr(exc))
                return

    def finish(self, timeout=60):
        self._stop = True
        self._sem.release()
        self.thread.join(timeout)
        if self.thread.is_alive():
            self.errors.append("reader thread hung")


def run_scenario(scenario, *, autoscale, chaos, ckpt_dir,
                 nservers=2, nspares=2, acfg=None,
                 tick_s=TICK_S, member_mbs=MEMBER_MBS, size=SIZE,
                 shards_per_server=SHARDS_PER_SERVER, pace=True):
    """One gang, one scenario pass.  ``chaos=False`` executes only the
    serialized training rounds (the static envelope); ``pace=False``
    drops the tick pacing (the envelope run needs order, not timing).
    Returns the result record the asserts and the bench consume."""
    from mpit_tpu.comm.local import LocalRouter
    from mpit_tpu.ft import FTConfig, PreemptionNotice
    from mpit_tpu.ft.traffic import (
        GRAD,
        JOIN,
        PREEMPT,
        READ,
        STRAGGLE_OFF,
        STRAGGLE_ON,
        iter_ticks,
    )
    from mpit_tpu.ps import ParamClient, ParamServer
    from mpit_tpu.shardctl import Autoscaler, RegistrySampler, ShardController

    acfg = acfg or default_autoscale_cfg()
    ft = FTConfig(**FT_KW)
    nwriters = scenario.writers
    has_join = chaos and any(ev.kind == JOIN for ev in scenario.schedule())
    # Rank space: servers | writers | attached readers | late reader |
    # spares | controller.  The late reader's slot exists either way
    # (rank-space ceiling), but only joins the client set when the
    # scenario actually joins it.
    nreaders = scenario.readers if chaos else 0
    attached_readers = nreaders - 1 if has_join else nreaders
    sranks = list(range(nservers))
    wranks = list(range(nservers, nservers + nwriters))
    rranks = list(range(nservers + nwriters,
                        nservers + nwriters + attached_readers))
    late_rank = nservers + nwriters + attached_readers if has_join else None
    spare0 = nservers + nwriters + attached_readers + (1 if has_join else 0)
    spares = list(range(spare0, spare0 + nspares))
    ctl_rank = spare0 + nspares
    router = LocalRouter(ctl_rank + 1)
    cranks = wranks + rranks + ([late_rank] if has_join else [])

    factors = {}  # rank -> straggle multiplier (1.0 = nominal)
    servers, threads, notices = {}, {}, {}

    def make_server(r, joiner):
        notices[r] = PreemptionNotice(grace_s=10.0)
        # Launch members know only the launch-time clients; the late
        # joiner arrives through the admission listener (§9.6).  A
        # joiner server spawns after any admission, so it treats the
        # whole provisioned client space as members.
        members = list(cranks) if joiner else wranks + rranks
        servers[r] = ParamServer(
            r, members, router.endpoint(r), rule="add", ft=ft,
            controller_rank=ctl_rank, ckpt_dir=ckpt_dir,
            ckpt_interval=1e9, shardctl=joiner, preempt=notices[r],
            admit_ranks=([late_rank] if has_join and not joiner else None))
        _throttle_member(servers[r], r, member_mbs, factors)
        threads[r] = threading.Thread(target=servers[r].start, daemon=True)
        threads[r].start()

    for r in sranks:
        make_server(r, joiner=False)
    ctl = ShardController(
        ctl_rank, router.endpoint(ctl_rank), sranks, list(cranks),
        spawner=lambda r: make_server(r, joiner=True), spare_ranks=spares)
    scaler = None
    if autoscale:
        scaler = Autoscaler(ctl, acfg, sampler=RegistrySampler())
        ctl.attach_autoscaler(scaler)

    writers = [ParamClient(r, sranks, router.endpoint(r),
                           seed_servers=(r == wranks[0]), ft=ft,
                           shardctl=True, controller_rank=ctl_rank,
                           sc_shards_per_server=shards_per_server)
               for r in wranks]
    readers = [_Reader(ParamClient(r, sranks, router.endpoint(r), ft=ft,
                                   shardctl=True, controller_rank=ctl_rank,
                                   sc_shards_per_server=shards_per_server))
               for r in rranks]

    rng = np.random.default_rng(scenario.seed)
    w0 = rng.normal(size=size).astype(np.float32)
    rounds = [sum(ev.count for ev in scenario.schedule()
                  if ev.kind == GRAD and ev.target == w)
              for w in range(nwriters)]
    gtab = rng.normal(size=(nwriters, max(rounds) if rounds else 0,
                            size)).astype(np.float32)

    starters = []
    for i, c in enumerate(writers):
        p = w0.copy() if i == 0 else np.zeros(size, np.float32)
        starters.append(threading.Thread(
            target=c.start, args=(p, np.zeros(size, np.float32)),
            daemon=True))
        starters[-1].start()
    if chaos:
        for rd in readers:
            starters.append(threading.Thread(
                target=rd.client.start,
                args=(np.zeros(size, np.float32),
                      np.zeros(size, np.float32)),
                daemon=True))
            starters[-1].start()
    for t in starters:
        t.join(60)
        assert not t.is_alive(), "client start hung"
    if chaos:
        for rd in readers:
            rd.start_pulling()
    # The controller runs its own serve loop: the sampling cadence must
    # not depend on how long the driver blocks in a serialized training
    # round (a saturated tick would starve the policy of windows).
    # serve() is the single pump consumer; the driver only reads.
    ctl_thread = threading.Thread(target=ctl.serve,
                                  kwargs={"poll_s": 0.02}, daemon=True)
    ctl_thread.start()
    t_wait = time.monotonic() + 60
    while ctl.smap is None:
        assert time.monotonic() < t_wait, \
            "controller never learned the map"
        time.sleep(0.01)

    round_idx = [0] * nwriters
    late_reader = None
    preempt_rr = 0
    phase_spans = []  # (phase, t_start, t_end)
    errors = []
    t_run0 = time.monotonic()
    cur_phase, cur_t0 = None, t_run0
    for tick, phase, events in iter_ticks(scenario):
        now = time.monotonic()
        if phase.name != cur_phase:
            if cur_phase is not None:
                phase_spans.append((cur_phase, cur_t0, now))
            cur_phase, cur_t0 = phase.name, now
        t_tick_end = now + tick_s
        for ev in events:
            if ev.kind == GRAD:
                c = writers[ev.target]
                for _ in range(ev.count):
                    c.grad[:] = gtab[ev.target, round_idx[ev.target]]
                    round_idx[ev.target] += 1
                    c.async_send_grad()
                    c.wait()
            elif not chaos:
                continue
            elif ev.kind == READ:
                targets = list(readers)
                if late_reader is not None:
                    targets.append(late_reader)
                if ev.target < len(targets):
                    targets[ev.target].dispatch(ev.count)
            elif ev.kind == JOIN and late_reader is None:
                late = ParamClient(
                    late_rank, sranks, router.endpoint(late_rank), ft=ft,
                    shardctl=True, controller_rank=ctl_rank,
                    sc_shards_per_server=shards_per_server)
                t = threading.Thread(
                    target=late.start,
                    args=(np.zeros(size, np.float32),
                          np.zeros(size, np.float32)), daemon=True)
                t.start()
                t.join(60)
                assert not t.is_alive(), "late joiner start hung"
                late_reader = _Reader(late)
                late_reader.start_pulling()
            elif ev.kind == PREEMPT:
                victims = [s for s in sranks
                           if s in ctl._live_servers()]
                if victims:
                    victim = victims[preempt_rr % len(victims)]
                    preempt_rr += 1
                    notices[victim]._notified = True  # the handler's act
            elif ev.kind == STRAGGLE_ON:
                live = ctl._live_servers()
                if live:
                    factors[live[0]] = float(ev.count)
            elif ev.kind == STRAGGLE_OFF:
                factors.clear()
        # pace the tick out (the controller thread keeps sampling)
        while pace and time.monotonic() < t_tick_end:
            time.sleep(0.02)
    phase_spans.append((cur_phase, cur_t0, time.monotonic()))
    elapsed = time.monotonic() - t_run0

    writers[0].async_recv_param()
    writers[0].wait()
    final = writers[0].param.copy()
    for rd in readers + ([late_reader] if late_reader else []):
        rd.finish()
        errors.extend(rd.errors)
    for c in writers + [rd.client for rd in readers] \
            + ([late_reader.client] if late_reader else []):
        c.stop()
    for r, t in threads.items():
        t.join(60)
        if t.is_alive():
            errors.append(f"server {r} stop-protocol hung")
    ctl_thread.join(60)
    assert not ctl_thread.is_alive() and ctl.done, \
        "controller missed client STOPs"
    reads_done = sum(rd.reads_done for rd in readers) \
        + (late_reader.reads_done if late_reader else 0)
    return {
        "final": final,
        "ctl": ctl,
        "scaler": scaler,
        "errors": errors,
        "elapsed": elapsed,
        "phase_spans": phase_spans,
        "grad_rounds": sum(round_idx),
        "reads_done": reads_done,
        "size": size,
    }


# ---------------------------------------------------------------------------
# acceptance checks


def check_duty(result, scenario, acfg, log=print):
    """Per-phase SLO duty: over each phase's decision windows — skipping
    a settle window after the phase starts and after every executed
    scale action — the in-SLO fraction must reach the phase's declared
    duty."""
    audit = result["scaler"].audit_log()
    actions = [d["t"] for d in audit if d.get("executed")]
    spans = {name: (t0, t1) for name, t0, t1 in result["phase_spans"]}
    failures = []
    for phase in scenario.phases:
        t0, t1 = spans[phase.name]
        windows = [
            d for d in audit
            if t0 + acfg.settle_s <= d["t"] < t1
            and d.get("reason") != "cooldown"  # transition turbulence
            and not any(a <= d["t"] < a + acfg.settle_s for a in actions)
        ]
        if not windows:
            log(f"  duty[{phase.name}]: no post-settle windows (phase "
                "shorter than settle) — skipped")
            continue
        ok = sum(1 for d in windows if not d.get("breaches"))
        duty = ok / len(windows)
        log(f"  duty[{phase.name}]: {ok}/{len(windows)} in-SLO windows "
            f"= {duty:.2f} (declared {phase.duty:.2f})")
        if duty < phase.duty:
            failures.append((phase.name, duty, phase.duty))
    assert not failures, f"phase SLO duty not met: {failures}"


def check_flap(result, acfg):
    """The executed-action stream never spends more direction reversals
    than the budget inside any flap window."""
    acts = [(d["t"], d["action"]) for d in result["scaler"].audit_log()
            if d.get("executed")]
    worst = 0
    for i in range(len(acts)):
        reversals = 0
        for j in range(i + 1, len(acts)):
            if acts[j][0] - acts[i][0] > acfg.flap_window_s:
                break
            if acts[j][1] != acts[j - 1][1]:
                reversals += 1
        worst = max(worst, reversals)
    assert worst <= acfg.flap_budget, \
        f"flap budget exceeded: {worst} reversals > {acfg.flap_budget}"
    return worst


def _no_retry_exhausted(outdir):
    bad = [f for f in os.listdir(outdir) if "retry_exhausted" in f]
    assert not bad, f"RetryExhausted flight dumps found: {bad}"


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--smoke", action="store_true",
                        help="short CI form (scenario 'smoke')")
    parser.add_argument("--scenario", default="",
                        help="explicit scenario spec "
                             "(docs/OPERATIONS.md grammar)")
    parser.add_argument("--seed", type=int, default=11)
    parser.add_argument("--outdir", default="/tmp/mpit_autoscale")
    parser.add_argument("--tick-s", type=float, default=TICK_S)
    args = parser.parse_args(argv)

    os.makedirs(args.outdir, exist_ok=True)
    os.environ["MPIT_OBS_FLIGHT"] = args.outdir
    trace_base = os.path.join(args.outdir, "mpit_autoscale_trace.json")
    os.environ["MPIT_OBS_TRACE"] = trace_base

    from mpit_tpu.ft.traffic import Scenario
    from mpit_tpu.obs import configure, validate_dump
    from mpit_tpu.obs.trace import validate_trace

    if args.scenario:
        scenario = Scenario.parse(args.scenario)
    else:
        scenario = Scenario.builtin("smoke" if args.smoke else "soak",
                                    seed=args.seed)
    min_changes = 2 if (args.smoke or args.scenario) else 5
    assert scenario.shape_changes >= min_changes, \
        f"scenario has {scenario.shape_changes} shape changes, " \
        f"need >= {min_changes}"
    acfg = default_autoscale_cfg()

    with open(os.path.join(args.outdir, "traffic_trace.json"), "w") as fh:
        fh.write(scenario.events_json())

    print(f"[soak] scenario: {len(scenario.phases)} phases, "
          f"{scenario.total_ticks} ticks, {scenario.shape_changes} "
          f"shape changes, seed {scenario.seed}")

    # 1. the static fault-free envelope (serialized rounds only)
    configure(enabled=True, reset=True)
    with tempfile.TemporaryDirectory() as ckpt:
        static = run_scenario(scenario, autoscale=False, chaos=False,
                              ckpt_dir=ckpt, pace=False,
                              tick_s=args.tick_s)
    assert not static["errors"], static["errors"]
    print(f"[soak] static envelope: {static['grad_rounds']} rounds in "
          f"{static['elapsed']:.1f}s")

    # 2. chaos + the closed loop (nobody calls /scale)
    configure(enabled=True, reset=True)
    with tempfile.TemporaryDirectory() as ckpt:
        chaos = run_scenario(scenario, autoscale=True, chaos=True,
                             ckpt_dir=ckpt, tick_s=args.tick_s)
    assert not chaos["errors"], chaos["errors"]
    ctl, scaler = chaos["ctl"], chaos["scaler"]
    print(f"[soak] chaos run: {chaos['grad_rounds']} rounds + "
          f"{chaos['reads_done']} reads in {chaos['elapsed']:.1f}s; "
          f"autoscale up={scaler.ups} down={scaler.downs} "
          f"holds={int(scaler._m_hold.value)} "
          f"preempts={int(ctl._m_pre.value)} epoch={ctl.membership_epoch}")

    # decision audit log — the postmortem artifact
    audit = scaler.audit_log()
    with open(os.path.join(args.outdir, "autoscale_audit.json"), "w") as fh:
        json.dump({"config": {"slo": dict(acfg.slo.targets()),
                              "window_s": acfg.window_s,
                              "cooldown_s": acfg.cooldown_s,
                              "flap_budget": acfg.flap_budget},
                   "decisions": audit}, fh, indent=1)

    # the gang operated itself
    assert scaler.operator_calls == 0, "an operator /scale call leaked in"
    assert not ctl._scale_requests, "unexecuted operator requests queued"
    assert scaler.ups >= 1, \
        f"no automatic scale-up fired (audit: {len(audit)} decisions)"
    assert scaler.downs >= 1, \
        f"no automatic scale-down fired (audit: {len(audit)} decisions)"
    assert int(ctl._m_pre.value) >= 1, "the preemption wave never landed"
    print(f"[soak] gang resized itself: {scaler.ups} up / {scaler.downs} "
          "down, zero operator calls")

    # SLO duty per phase + flap budget
    check_duty(chaos, scenario, acfg)
    worst = check_flap(chaos, acfg)
    print(f"[soak] duty met in every phase; worst flap-window reversals "
          f"{worst} <= budget {acfg.flap_budget}")

    # bitwise inside the fault-free envelope; no RetryExhausted
    np.testing.assert_array_equal(static["final"], chaos["final"])
    _no_retry_exhausted(args.outdir)
    print("[soak] final params BITWISE equal to the static envelope; "
          "zero RetryExhausted")

    # every autoscale flight dump validates
    dumps = sorted(f for f in os.listdir(args.outdir)
                   if f.startswith("mpit_flight_"))
    auto_dumps = [f for f in dumps if "autoscale" in f or "slo_breach" in f]
    assert auto_dumps, "no autoscale flight dump was written"
    for f in dumps:
        validate_dump(os.path.join(args.outdir, f))
    print(f"[soak] {len(auto_dumps)} autoscale flight dump(s) validate "
          f"({len(dumps)} total)")

    # obs trace artifact
    from mpit_tpu.obs import maybe_merge_rank_traces, maybe_write_rank_trace

    maybe_write_rank_trace(0, role="soak")
    merged = maybe_merge_rank_traces()
    assert merged, "trace export produced no file"
    stats = validate_trace(merged)
    print(f"[soak] trace OK: {stats}")
    print("[soak] PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
