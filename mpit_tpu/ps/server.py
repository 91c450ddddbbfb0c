"""ParamServer — one process/role per shard, service loops per client.

Rebuild of reference asyncsgd/pserver.lua (plus the BiCNN variant's
server-side optimizer state, BiCNN/pserver.lua:50-83) with TPU-native
mechanics:

- The shard and its optimizer state are JAX arrays; every incoming
  gradient triggers one jitted ``rule.apply`` XLA program (the analog of
  the in-place ``p:add(g)`` / server-side Adam etc., reference
  pserver.lua:83, BiCNN/pserver.lua:123-197).  By default they live on
  the **host CPU backend** — the server is a host role and the
  reference's servers are CPU torch; a process without a CPU backend
  cannot host one.  Pass ``device="default"`` to keep shards on the
  platform default (e.g. a local accelerator whose HBM you want).
- Service loops are generator tasks on the cooperative scheduler — the
  direct analog of the reference's per-client coroutines
  (pserver.lua:131-157): ``recv_init``, one-shot ``recv_param`` from the
  seeding client, perpetual ``send_param`` / ``recv_grad`` loops, and the
  stop counter (pserver.lua:115-129).
- The reference's deliberate lock-free read ("expect inconsistent read",
  pserver.lua:74) maps to serve-latest-committed: ``send_param`` snapshots
  the current immutable device array — writers are never quiesced, and no
  torn read is possible.

Wire codecs (beyond-reference): each client negotiates a codec in its
INIT v2 announcement (mpit_tpu/comm/codec.py; the 16-byte legacy INIT
means 'none').  Gradient frames are decoded *inside* the jitted shard
update — ``decode(wire) -> rule.apply`` is one XLA program, so the
quantized path keeps today's one-call-per-grad shape.  Parameter reads
are served from a **version-counted encoded snapshot cache**: the
version bumps on every apply/seed, and N clients pulling the same
committed version cost one device->host copy plus one encode, not N
(``snapshot_copies`` / ``snapshot_hits`` count the win).

Fault tolerance (mpit_tpu.ft): the server's pre-FT failure mode was to
block forever on a dead client — every per-client service loop recv'd
unboundedly and the stop protocol counted STOPs from all clients.  Now:

- a :class:`LeaseRegistry` tracks per-client liveness from HEARTBEAT
  beacons (INIT v3 announces them); an expired lease **evicts** the
  client: its service loops unblock via their ``abort`` predicate, its
  staging is released, and the stop condition becomes "every client
  STOPPED or EVICTED" — the gang survives the loss;
- framed clients' GRAD / PARAM_PUSH frames carry [epoch, seq] headers,
  admitted through a :class:`DedupTable` so a retried op is applied at
  most once and its ack re-sent (the client's retry makes delivery
  at-least-once; dedup makes the apply exactly-once);
- when rejoin is enabled, a per-client INIT listener accepts a new
  incarnation mid-run (epoch+1), tears down the old generation's
  services, and respawns them against the new epoch;
- checkpoints carry the dedup table and each client's negotiated state,
  so a *restarted server* resumes serving retried ops without fresh
  INITs (clients never learn the server died — their deadlines cover
  the gap).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from mpit_tpu.aio import (
    EXEC,
    DeadlineExceeded,
    LiveFlag,
    Scheduler,
    aio_recv,
    aio_send,
    aio_sleep,
    deadline_at,
)
from mpit_tpu.comm import codec as codec_mod
from mpit_tpu.comm import pool as comm_pool
from mpit_tpu.comm.transport import Transport
from mpit_tpu.ft import (
    ACK_TIMING_WORDS,
    CHUNK_ACK_TIMING_WORDS,
    CHUNK_ACK_WORDS,
    DUP,
    FLAG_CHUNKED,
    FLAG_FRAMED,
    FLAG_HEARTBEAT,
    FLAG_READONLY,
    FLAG_STALENESS,
    FLAG_TIMING,
    HDR_BYTES,
    STALE,
    TIMING_TAIL_BYTES,
    DedupTable,
    FTConfig,
    LeaseRegistry,
    chunk_hdr_bytes,
    chunk_reply_hdr_bytes,
    chunk_spans,
    chunk_stride,
    hdr_bytes,
    pack_chunk_reply,
    pack_reply_stamps,
    pack_version,
    reply_hdr_bytes,
    split_plain_tail,
    unpack_chunk_header,
    unpack_header,
    unpack_tx_stamp,
    unpack_version,
)
from mpit_tpu.dplane import exchange as _dpexchange
from mpit_tpu.dplane import hbm as _dphbm
from mpit_tpu.obs import (
    NULL_SPAN,
    get_flight,
    get_recorder,
    obs_enabled,
    register_status_provider,
    registry_or_local,
)
from mpit_tpu.obs import clock as obs_clock
from mpit_tpu.optim.rules import (
    ShardRule, apply_at, make as make_rule, with_plain,
)
from mpit_tpu.optim.rules import streams as rule_streams
from mpit_tpu.ps import serve as _psserve
from mpit_tpu.ps import tags
from mpit_tpu.shardctl import migrate as _scmigrate
from mpit_tpu.shardctl import wire as _scwire
from mpit_tpu.shardctl.migrate import ShardSlot
from mpit_tpu.shardctl.shardmap import ShardMap
from mpit_tpu.utils.logging import get_logger


#: Bit 5 of the INIT v3/v5 flags word: retired, refused from every rank
#: (``ft/wire.py``).
_FLAG_BIT5_RETIRED = 32

#: XLA:CPU takes host memory under ``jnp.asarray`` as it stands only on
#: this boundary; anything else it copies into a buffer of its own.
_XLA_CPU_ALIGN = 64

_shard_head = jax.jit(lambda param: param[:1])


class _GradFrames:
    """One client's GRAD receive staging on the unchunked path: two
    frames, received into in turn.  The host apply reads a frame's
    payload where it landed — the payload starts on the boundary
    XLA:CPU aliases, so :meth:`lend` moves nothing — and the
    `ps-grad-apply-owned` contract (jax never reads memory the wire is
    writing) holds by rotation instead of by copy: the frame lent to an
    apply goes out of use (:meth:`lent_until`) until that apply has run
    (:meth:`writable`).  The GRAD ack does not serialize this: the
    apply is only dispatched when the ack goes out."""

    def __init__(self, hdr: int, nbytes: int,
                 views_of: Callable[[np.ndarray], Any]):
        self._frames, self._views = [], []
        for _ in range(2):
            raw = np.zeros(hdr + nbytes + _XLA_CPU_ALIGN, np.uint8)
            start = -(raw.ctypes.data + hdr) % _XLA_CPU_ALIGN
            frame = raw[start:start + hdr + nbytes]
            self._frames.append(frame)
            self._views.append(views_of(frame[hdr:]))
        self._readers: List[Any] = [None, None]  # the apply reading each
        self._cur = 0

    @property
    def frame(self) -> np.ndarray:
        """The frame the next GRAD is received into."""
        return self._frames[self._cur]

    @property
    def views(self) -> Any:
        """Its payload as the codec cuts it: one typed view, or the
        wire parts."""
        return self._views[self._cur]

    def writable(self) -> bool:
        """Whether the apply that last read :attr:`frame` has run."""
        reader = self._readers[self._cur]
        if reader is not None and not reader.is_ready():
            return False
        self._readers[self._cur] = None
        return True

    def lend(self) -> Any:
        """The payload as the jitted apply's operand (call it where the
        arrays shall live)."""
        return jax.tree.map(jnp.asarray, self.views)

    def lent_until(self, token) -> None:
        """``token`` is ready when the apply that got :meth:`lend`'s
        operand has run; receive into the other frame meanwhile."""
        self._readers[self._cur] = token
        self._cur ^= 1


class ParamServer:
    def __init__(
        self,
        rank: int,
        client_ranks: list[int],
        transport: Transport,
        rule: ShardRule | str = "add",
        scheduler: Optional[Scheduler] = None,
        dtype=np.float32,
        single_mode: bool = False,
        ckpt_dir: Optional[str] = None,
        ckpt_interval: float = 30.0,
        device: str = "cpu",  # "cpu" (host role, reference-faithful) | "default"
        codec: Optional[str] = None,  # None: adopt each client's announcement;
        #                               a name pins it — mismatches fail loudly
        ft: Optional[FTConfig] = None,
        controller_rank: Optional[int] = None,  # shardctl control plane
        reader_ranks: Optional[list] = None,  # serving tier (§8): READ-ONLY
        #                                       attachers, not protocol clients
        serve: Optional["_psserve.ServeConfig"] = None,
        shardctl: bool = False,  # joiner mode (§9.1): a controller-spawned
        #                          server enters an sc gang mid-run — no
        #                          phase-1 INIT wait; clients greet lazily
        #                          and shards arrive via ACQUIRE
        admit_ranks: Optional[list] = None,  # late-join candidates (§9.6):
        #                                      client ranks that may INIT
        #                                      mid-run without being part of
        #                                      the launch-time set
        preempt: "Optional[Any]" = None,  # ft.elastic.PreemptionNotice —
        #                                   checkpoint-on-notice + PREEMPT
        #                                   report when it fires (§9.3)
        dplane: "Optional[_dphbm.PlaneConfig]" = None,  # device-resident
        #                          data plane (mpit_tpu.dplane): shard +
        #                          rule state live as (mesh-sharded) HBM
        #                          arrays with donated jitted applies;
        #                          publish=True additionally offers the
        #                          in-process device exchange.  Wins over
        #                          the `device` placement knob.
    ):
        self.rank = rank
        self.cranks = list(client_ranks)
        # Serving tier (docs/PROTOCOL.md §8): expected reader ranks.
        # Readers are outside the client phases (no seeding, no grad
        # services) — each gets a lazy attach listener, a read service
        # behind the admission budget, and a stop/lease slot, so the
        # gang ends when every writer AND every expected reader is
        # terminal.
        self.readers = list(reader_ranks or [])
        self._reader_set = set(self.readers)
        if self._reader_set & set(self.cranks):
            raise ValueError(
                f"reader_ranks {sorted(self._reader_set & set(self.cranks))}"
                " overlap client_ranks — a rank is a writer or a reader,"
                " not both")
        self.serve_cfg = (serve if serve is not None
                          else _psserve.ServeConfig.from_env())
        self.transport = transport
        self.rule = make_rule(rule) if isinstance(rule, str) else rule
        self.sched = scheduler or Scheduler()
        from mpit_tpu.utils.serialize import resolve_dtype

        self.dtype = resolve_dtype(dtype)
        self.single_mode = single_mode  # perpetual param-push service
        self.live = LiveFlag()
        self.log = get_logger("pserver", rank)

        self.offset = -1
        self.size = -1
        self.param: Optional[jnp.ndarray] = None  # device-resident shard
        self.rule_state = None
        self.grad_bufs: Dict[int, _GradFrames] = {}  # host recv staging, per client
        # Codec negotiation state (INIT v2).  codec=None adopts whatever
        # each client announces (per-pair negotiation — mixed-codec
        # gangs are legal); an explicit name validates every
        # announcement against it and raises on mismatch rather than
        # decoding frames with the wrong codec.
        if codec:  # fail at construction, not first INIT
            codec_mod.get(codec)
        self._codec_pin = codec or None
        self._codecs: Dict[int, codec_mod.Codec] = {}
        self._push_bufs: Dict[int, np.ndarray] = {}
        self._push_host: Dict[int, np.ndarray] = {}
        self._apply_cache: Dict[str, Callable] = {}
        # FT state (mpit_tpu.ft): lease per client, dedup on
        # (client, epoch, seq), per-client service generation (bumped on
        # rejoin/eviction so stale loops abort), framed/heartbeat flags
        # from INIT v3, and the reply staging the framed paths need.
        self.ft = ft if ft is not None else FTConfig.from_env()
        self.leases = LeaseRegistry(self.cranks + self.readers,
                                    ttl_s=self.ft.lease_ttl_s)
        self.dedup = DedupTable()
        self._framed: Dict[int, bool] = {}
        self._hb: Dict[int, bool] = {}
        # READ-ONLY postures (FLAG_READONLY, §8) + the admission
        # budget's live in-flight accounting: reply bytes/count queued
        # to the transport but not yet accepted, across all readers.
        self._readonly: Dict[int, bool] = {}
        self._serve_inflight_bytes = 0
        self._serve_inflight_reads = 0
        # Staleness telemetry (FLAG_STALENESS, negotiated per pair like
        # framing): frames from these clients carry the 24-byte
        # [epoch, seq, version] header; PARAM replies are stamped with
        # the served snapshot version and each applied GRAD's basis gap
        # feeds the mpit_ps_grad_staleness histogram.
        self._stale_track: Dict[int, bool] = {}
        self._stale_hists: Dict[int, Any] = {}
        # Causal-timing posture (FLAG_TIMING, §6.7): frames from these
        # clients carry a trailing send stamp; their acks/replies grow
        # the [t_tx_echo, t_recv, t_ack] tail the client's clock-offset
        # estimator consumes, and their heartbeats are echoed back on
        # HEARTBEAT_ECHO so the estimate refreshes between ops.
        self._timing: Dict[int, bool] = {}
        # Pipelined streaming posture (FLAG_CHUNKED, §12): elements per
        # chunk announced in INIT v5 (0/absent = whole-frame transfers),
        # the per-client fixed-size chunk receive staging (separate
        # buffers for the concurrent GRAD and PARAM_PUSH services), the
        # PARAM_PUSH assembly frames, and the per-(codec, chunk-size)
        # jitted chunk-apply cache.
        self._chunk: Dict[int, int] = {}
        self._chunk_rx: Dict[int, np.ndarray] = {}
        self._chunk_rx_push: Dict[int, np.ndarray] = {}
        self._chunk_asm: Dict[int, np.ndarray] = {}
        self._chunk_apply_cache: Dict[Tuple[str, int], Callable] = {}
        _members = self.cranks + self.readers
        self._gen: Dict[int, int] = {c: 0 for c in _members}
        self._svc_live: Dict[int, int] = {c: 0 for c in _members}
        self._param_send: Dict[int, np.ndarray] = {}
        self._ack_send: Dict[int, np.ndarray] = {}
        self._req_buf: Dict[int, np.ndarray] = {}
        self._hb_buf: Dict[int, np.ndarray] = {}
        self._restored_clients: set = set()
        # shardctl (mpit_tpu.shardctl): a versioned map replaces the
        # single (offset, size) registration; owned shards live in
        # per-shard slots (param + rule state + shard-scoped dedup +
        # snapshot cache) that migrate as a unit.  Activated by the
        # first INIT v4 announcement; mixing v4 and pre-v4 clients on
        # one server is rejected loudly.
        self.controller_rank = controller_rank
        self.smap: Optional[ShardMap] = None
        self._slots: Dict[int, ShardSlot] = {}
        self._sc = bool(shardctl)
        self._sc_join = bool(shardctl)  # spawned mid-run: no INIT phase
        # Elastic membership (§9): late-join candidates, the preemption
        # notice to poll, retirement posture (a clean goodbye, observable
        # as `retired` after start() returns), and the serving-tier
        # successor announced to readers once retiring.
        self.admit_ranks = list(admit_ranks or [])
        if set(self.admit_ranks) & set(self.cranks):
            raise ValueError(
                f"admit_ranks {sorted(set(self.admit_ranks) & set(self.cranks))}"
                " overlap client_ranks — launch-time members need no admission")
        self._preempt = preempt
        self._preempt_handled = False
        self.retired = False
        self._serve_successor: Optional[int] = None
        self._sc_apply_cache: Dict[Tuple[str, int], Callable] = {}
        self._sc_last_report: Dict[int, Tuple[int, float]] = {}
        self._sc_beat_seq = 0
        # Observability (mpit_tpu.obs): every protocol counter lives in
        # a real registry (the global one when obs is enabled, a private
        # one otherwise — they are load-bearing results either way) and
        # the attribute names below stay readable as properties.  Op
        # processing records spans through the recorder (the null
        # recorder when obs is off: no clock reads).
        self.metrics = registry_or_local()
        self._spans = get_recorder()
        # While recording: what this server's thread did on the wire and
        # asleep, noted on each GRAD and PARAM op span as it closes
        # (obs/spans.py ``WireMeter``), and the ``apply_exec`` span last
        # handed to the recorder's waiter (the null span while obs is off,
        # when nothing reads it).
        self._wire_meter = self._spans.wire_meter(self.transport, self.sched)
        self._exec_pending: Any = None
        _m, _r = self.metrics, rank
        self._m_grads = _m.counter("mpit_ps_grads_applied_total", rank=_r)
        self._m_inplace = _m.counter("mpit_ps_apply_inplace_total", rank=_r)
        self._m_served = _m.counter("mpit_ps_params_served_total", rank=_r)
        self._m_dups = _m.counter("mpit_ps_dup_ops_total", rank=_r)
        self._m_stale = _m.counter("mpit_ps_stale_drops_total", rank=_r)
        self._m_hb_seen = _m.counter("mpit_ps_heartbeats_seen_total", rank=_r)
        self._m_rejoins = _m.counter("mpit_ps_rejoins_total", rank=_r)
        self._m_snap_copies = _m.counter(
            "mpit_ps_snapshot_copies_total", rank=_r)
        self._m_snap_hits = _m.counter("mpit_ps_snapshot_hits_total", rank=_r)
        self._m_ckpts = _m.counter("mpit_ps_ckpts_written_total", rank=_r)
        self._m_busy = _m.counter("mpit_ps_busy_replies_total", rank=_r)
        self._m_readers = _m.gauge("mpit_ps_readers", rank=_r)
        self._m_evictions = _m.counter("mpit_ft_evictions_total", rank=_r)
        self._m_sc_nacks = _m.counter("mpit_shardctl_nacks_sent_total",
                                      rank=_r)
        self._m_sc_busy = _m.counter("mpit_shardctl_busy_replies_total",
                                     rank=_r)
        self._m_sc_out = _m.counter("mpit_shardctl_migrations_total",
                                    rank=_r, direction="out")
        self._m_sc_in = _m.counter("mpit_shardctl_migrations_total",
                                   rank=_r, direction="in")
        self._m_sc_adopt = _m.counter("mpit_shardctl_adoptions_total",
                                      rank=_r)
        self._m_admits = _m.counter("mpit_ps_admits_total", rank=_r)
        self._m_preempt = _m.counter("mpit_ft_preempt_notices_total",
                                     rank=_r)
        self._m_sc_ver = _m.gauge("mpit_shardctl_map_version", rank=_r)
        self._m_sc_owned = _m.gauge("mpit_shardctl_owned_shards", rank=_r)
        # Flight recorder + live introspection (obs/flight, obs/statusd):
        # evictions dump the recent-event ring (the gang just lost a
        # member) and the status provider feeds /status when an endpoint
        # is serving.  Null objects when obs is disabled.
        self._flight = get_flight()
        if obs_enabled():
            register_status_provider(f"server{rank}", self._status_section)
        # Version-counted snapshot cache: _snap_version bumps on every
        # committed write (grad apply / seed / restore); _snap_host is
        # the one device->host copy for that version and _snap_wire the
        # per-codec encoded frame.  Serving allocates a fresh frame per
        # version — an in-flight zero-copy send of the previous version
        # must never see its buffer rewritten.
        self._snap_version = 0
        self._snap_host: Optional[Tuple[int, np.ndarray]] = None
        self._snap_wire: Dict[str, Tuple[int, np.ndarray]] = {}
        # Device-resident data plane (mpit_tpu.dplane): the shard lives
        # in an HbmSlot (donated jitted applies, per-version snapshot +
        # pull caches) and, when published, an in-process DevicePlane
        # serves same-backend clients without touching the wire.
        self._dp_cfg = dplane
        self._hbm: "Optional[_dphbm.HbmSlot]" = None
        self._plane: "Optional[_dpexchange.DevicePlane]" = None
        self._m_dp_ops: Dict[str, Any] = {}
        if device not in ("cpu", "default"):
            raise ValueError(f"device must be 'cpu' or 'default', got {device!r}")
        self._device = None
        if dplane is not None:
            pass  # plane placement wins: slots live on the default backend
        elif device == "cpu":
            self._device = jax.local_devices(backend="cpu")[0]
        # Placement discipline: every jnp array this server creates is
        # built inside _dev_ctx(), so shard + optimizer state live (and
        # the jitted apply runs) on the configured backend.
        self._restored = False
        # Periodic shard checkpointing (the resume flow's producer side).
        self._ckpt_dir = str(ckpt_dir) if ckpt_dir else None
        self._ckpt_interval = float(ckpt_interval)

    # -- live introspection (obs/statusd) ------------------------------------

    def _status_section(self) -> Dict[str, Any]:
        """This server's /status section: shard + snapshot state, the
        per-client lease/negotiation table, shardctl placement, and the
        live task table.  Runs on the statusd thread — plain-attribute
        reads only, never the scheduler."""
        try:
            tasks = [t.name for t in list(self.sched.queue)]
        except RuntimeError:  # deque mutated mid-snapshot; next poll wins
            tasks = ["<scheduler busy>"]
        return {
            "role": "server",
            "rank": self.rank,
            "shard": {"offset": self.offset, "size": self.size},
            "snap_version": self._snap_version,
            "map_version": getattr(self.smap, "version", None),
            "owned_shards": sorted(self._slots),
            "readers": int(self._m_readers.value),
            "busy_replies": int(self._m_busy.value),
            "retired": self.retired,
            "retiring_to": self._serve_successor,
            "dplane": (self._hbm.describe()
                       if self._hbm is not None else None),
            "serve_inflight_bytes": self._serve_inflight_bytes,
            "clients": {
                str(c): {
                    "state": self.leases.state(c),
                    "epoch": self.leases.epoch(c),
                    "framed": self._framed.get(c, False),
                    "stale": self._stale_track.get(c, False),
                    "timing": self._timing.get(c, False),
                    "chunk": self._chunk.get(c, 0),
                    "codec": getattr(self._codecs.get(c), "name", None),
                }
                for c in self.cranks
            },
            "tasks": tasks,
        }

    # -- registry-backed counter reads (the pre-obs attribute surface) -------

    @property
    def grads_applied(self) -> int:
        return int(self._m_grads.value)

    @grads_applied.setter
    def grads_applied(self, v: int) -> None:
        self._m_grads.value = int(v)  # checkpoint restore continuity

    @property
    def apply_inplace(self) -> int:
        """Host applies whose donation engaged: the new shard stands
        where the old one stood.  The rest fell back to fresh outputs
        because something still held a view of the shard."""
        return int(self._m_inplace.value)

    @property
    def params_served(self) -> int:
        return int(self._m_served.value)

    @property
    def dup_ops(self) -> int:
        return int(self._m_dups.value)

    @property
    def stale_drops(self) -> int:
        return int(self._m_stale.value)

    @property
    def heartbeats_seen(self) -> int:
        return int(self._m_hb_seen.value)

    @property
    def rejoins(self) -> int:
        return int(self._m_rejoins.value)

    @property
    def snapshot_copies(self) -> int:
        return int(self._m_snap_copies.value)

    @property
    def snapshot_hits(self) -> int:
        return int(self._m_snap_hits.value)

    @property
    def ckpts_written(self) -> int:
        return int(self._m_ckpts.value)

    @property
    def busy_replies(self) -> int:
        """Admission-control rejections issued (serving tier, §8)."""
        return int(self._m_busy.value)

    # -- shardctl reads (tests / observability) ------------------------------

    @property
    def owned_shards(self) -> "List[int]":
        """Shard ids this server currently holds (shardctl mode)."""
        return sorted(self._slots)

    def shard_param(self, sid: int):
        return self._slots[sid].param

    def _dev_ctx(self):
        """Context placing jnp array creation + jit execution on the
        configured backend (no-op for device='default')."""
        if self._device is None:
            import contextlib

            return contextlib.nullcontext()
        return jax.default_device(self._device)

    def _exec_span(self, crank: int, grad_span):
        """The ``apply_exec`` span of the GRAD op ``grad_span``: begun
        at the dispatch of the jitted apply and handed to the recorder's
        waiter, which ends it when the result is ready (obs/spans.py);
        its phases are ``queued`` (behind the apply dispatched before
        it) and ``exec``.  ``grad_n`` is the GRAD span's ordinal: a dup
        or stale frame opens a GRAD span and no apply, so the two
        ordinals may part.  ``bytes_moved`` is what the sweep reads and
        writes: the shard's bytes times the rule's streams
        (``optim/rules.py`` ``streams``), which makes ``exec`` a pass
        ``apply`` over the host's memory (``obs/copies.py``)."""
        if not self._spans.enabled:
            return NULL_SPAN
        state = (self._hbm.rule_state if self._hbm is not None
                 else self.rule_state)
        span = self._spans.op(
            "apply_exec", peer=crank, side="server", rank=self.rank,
            grad_n=grad_span.args["n"],
            bytes_moved=self.param.nbytes * rule_streams(
                state or {}, self.param.size))
        span.mark("queued")
        return span

    def _apply_token(self):
        """One element of the shard as an array of its own: ready when
        the apply that produced ``self.param`` has run, and an output
        no apply donates, so it outlives the shard it was cut from — a
        later donated apply deletes that.  What waits for an apply
        (the recorder's waiter, a lent receive frame) waits on this."""
        with self._dev_ctx():
            return _shard_head(self.param)

    def _await_apply(self, span) -> None:
        """While recording: wait for a pending apply under a phase of
        its own (``wait_apply``) before the PARAM span's ``snapshot``,
        which would otherwise hide that wait inside its host copy.  The
        snapshot blocks on the same result a moment later, so nothing
        is served later for it.  The wait's end is also the end of that
        apply's ``apply_exec`` span, stamped on time: the waiter's own
        stamp waits for the interpreter lock this thread holds."""
        if self._spans.enabled:
            span.mark("wait_apply")
            jax.block_until_ready(self.param)
            if self._exec_pending is not None:
                self._spans.seen_ready(self._exec_pending)
                self._exec_pending = None

    # -- codec + FT negotiation ---------------------------------------------

    def _negotiate(self, crank: int, payload: bytes) -> "codec_mod.Codec":
        """Parse the INIT announcement (v1/v2/v3) into (offset, size) on
        self, the negotiated codec, and the client's FT posture (epoch +
        framed/heartbeat flags).  Every failure here is loud — a codec
        disagreement must never reach the frame decoders, where it would
        corrupt parameters silently."""
        raw, plain = split_plain_tail(np.frombuffer(payload, dtype=np.int64))
        self._adopt_plain(crank, plain)
        epoch, flags = 0, 0
        if raw.size >= 8 and int(raw[0]) == -1:  # INIT v4 (shardctl)
            return self._negotiate_v4(crank, raw)
        if self._sc:
            raise ValueError(
                f"client {crank} announced a legacy INIT on a shardctl "
                "server — a gang is shardctl everywhere or nowhere"
            )
        chunk_elems = 0
        if raw.size == 2:  # legacy 16-byte v1 announcement
            offset, size, wire_id = int(raw[0]), int(raw[1]), 0
        elif raw.size == 3:
            offset, size, wire_id = (int(x) for x in raw)
        elif raw.size == 5:  # INIT v3: [offset, size, codec_id, epoch, flags]
            offset, size, wire_id, epoch, flags = (int(x) for x in raw)
        elif raw.size == 6:  # INIT v5: v3 + [chunk_elems] (FLAG_CHUNKED)
            offset, size, wire_id, epoch, flags, chunk_elems = (
                int(x) for x in raw)
        else:
            raise ValueError(
                f"client {crank} INIT announcement is {len(payload)} bytes; "
                "expected 16 (legacy [offset, size]), 24 "
                "([offset, size, codec_id]), 40 (v3 + [epoch, flags]) or "
                "48 (v5 + [chunk_elems])"
            )
        chunked = bool(flags & FLAG_CHUNKED)
        if chunked != (raw.size == 6):
            raise ValueError(
                f"client {crank} INIT is malformed: FLAG_CHUNKED and the "
                "48-byte v5 announcement (which carries the chunk cut) "
                "must travel together (docs/PROTOCOL.md §12.1)")
        # READ-ONLY attach (serving tier, §8): the posture is a property
        # of the *rank role*, so a reader announcing as a writer (or
        # vice versa) is a misconfiguration, caught here loudly.
        if flags & _FLAG_BIT5_RETIRED:
            raise ValueError(
                f"rank {crank} announced flag bit 5 (32), which no rank "
                "may: it went with the multi-cell fabric (§11)")
        ro = bool(flags & FLAG_READONLY)
        if ro and crank not in self._reader_set:
            raise ValueError(
                f"rank {crank} announced FLAG_READONLY but is not in this "
                f"server's reader_ranks {sorted(self._reader_set)}")
        if crank in self._reader_set and not ro:
            raise ValueError(
                f"rank {crank} is a reader rank but announced without "
                "FLAG_READONLY — readers attach with the read-only posture")
        if ro and not (flags & FLAG_FRAMED):
            raise ValueError(
                f"reader {crank} announced FLAG_READONLY without "
                "FLAG_FRAMED — status-framed replies echo the request "
                "identity")
        self._readonly[crank] = ro
        codec = codec_mod.by_wire_id(wire_id)
        if self._codec_pin is not None and codec.name != self._codec_pin:
            raise ValueError(
                f"codec negotiation mismatch: client {crank} announced "
                f"{codec.name!r} but server {self.rank} is pinned to "
                f"{self._codec_pin!r} — align MPIT_PS_CODEC (or the codec "
                "config) across the gang"
            )
        if not codec.identity and np.dtype(self.dtype) != np.float32:
            raise ValueError(
                f"codec {codec.name!r} quantizes float32 shards; server "
                f"{self.rank} holds dtype {np.dtype(self.dtype).name} "
                "(use codec='none' for other dtypes)"
            )
        if self.offset == -1:
            self.offset, self.size = offset, size
            if self._dp_cfg is not None:
                self._hbm = _dphbm.HbmSlot(size, self.rule, self.dtype,
                                           config=self._dp_cfg,
                                           rank=self.rank)
                self.param = self._hbm.param
                self.rule_state = self._hbm.rule_state
            else:
                with self._dev_ctx():
                    self.param = jnp.zeros((size,), dtype=self.dtype)
                    self.rule_state = self._init_state(self.param)
        else:
            # All clients must agree on this server's shard (reference :87-88).
            assert (self.offset, self.size) == (offset, size), (
                f"client {crank} announced shard ({offset},{size}) but server "
                f"{self.rank} already holds ({self.offset},{self.size})"
            )
        self._framed[crank] = bool(flags & FLAG_FRAMED)
        self._hb[crank] = bool(flags & FLAG_HEARTBEAT)
        # Pipelined streaming (§12): a framed posture of the writer path.
        if chunked:
            if ro:
                raise ValueError(
                    f"rank {crank} announced FLAG_CHUNKED with the "
                    "READONLY posture — reads are served by the §8 "
                    "dispatcher; chunked streaming is the writer path "
                    "(§12.1)")
            if not self._framed[crank]:
                raise ValueError(
                    f"client {crank} announced FLAG_CHUNKED without "
                    "FLAG_FRAMED — chunk retry/dedup rides the framed "
                    "identity (§12.1)")
            if chunk_elems <= 0 or chunk_elems % codec_mod.BLOCK:
                raise ValueError(
                    f"client {crank} announced chunk_elems={chunk_elems}; "
                    f"must be a positive multiple of {codec_mod.BLOCK} "
                    "(the codec block boundary, §12.2)")
            self._require_splittable_rule(crank)
        self._chunk[crank] = chunk_elems if chunked else 0
        # Staleness telemetry only rides the framed wire: the version
        # word extends the [epoch, seq] header, so a FLAG_STALENESS
        # without FLAG_FRAMED negotiates off (nothing to extend).
        # Readers negotiate both extensions off: their replies use the
        # §8 status header, which carries the version in its own word.
        # Chunked pairs negotiate it off too — the chunked PARAM reply
        # header carries the version in its own word (§12.3).
        self._stale_track[crank] = (self._framed[crank] and not ro
                                    and not chunked
                                    and bool(flags & FLAG_STALENESS))
        # Same rule for the timing extension: no frame, no stamp slot.
        self._timing[crank] = (self._framed[crank] and not ro
                               and bool(flags & FLAG_TIMING))
        self.leases.arm(crank, epoch, heartbeats=self._hb[crank])
        return codec

    def _adopt_plain(self, crank: int, plain) -> None:
        """The vector's plain ranges as ``crank``'s INIT announced them
        (``ft/wire.py`` ``with_plain_tail``): the rule takes them
        (``optim/rules.py`` ``with_plain``; plain add keeps none, its
        clients ship the step itself), and every apply made from here on
        is ``apply_at`` the shard's offset.  A second announcement must
        agree: the ranges are the model's, not a client's."""
        if not plain:
            return
        if self._sc or self._dp_cfg is not None:
            raise ValueError(
                f"client {crank} announced plain ranges; the static "
                "host-resident shard is the only placement whose applies "
                "know them so far")
        was, self.rule = self.rule, with_plain(self.rule, plain)
        if was.plain and was.plain != self.rule.plain:
            raise ValueError(
                f"client {crank} announced plain ranges "
                f"{self.rule.plain} but server {self.rank} already holds "
                f"{was.plain}")
        if was.plain != self.rule.plain:
            self._apply_cache.clear()
            self._chunk_apply_cache.clear()

    def _require_splittable_rule(self, crank: int) -> None:
        """Chunked streaming applies chunk *k* before chunk *k+1* has
        arrived, which is only bitwise-equal to the whole-shard apply
        when the rule is element-wise over (param, grad, state) — i.e.
        every state leaf is param-shaped (or the state is empty).  A
        scalar leaf (Adam's step counter ``t``) would advance once per
        chunk instead of once per op; refuse loudly at negotiation
        rather than corrupt the math quietly (§12.5)."""
        state = (self._hbm.rule_state if self._hbm is not None
                 else self.rule_state)
        bad = sorted(k for k, v in (state or {}).items()
                     if tuple(np.shape(v)) != (self.size,))
        if bad:
            raise ValueError(
                f"client {crank} announced FLAG_CHUNKED but this "
                f"server's rule carries non-element-wise state leaves "
                f"{bad} (e.g. a scalar step counter) — per-chunk apply "
                "would not be bitwise-equal to the whole-shard apply. "
                "Use a splittable rule (add/rmsprop/adadelta) or turn "
                "chunking off (docs/PROTOCOL.md §12.5)")

    def _negotiate_v4(self, crank: int, raw: np.ndarray) -> "codec_mod.Codec":
        """INIT v4: codec + FT posture + the versioned shard map.  The
        map replaces the per-pair (offset, size); owned shards become
        slots.  Shardctl implies framing — re-routable ops need the
        retry/dedup identity under them."""
        if self.readers:
            raise ValueError(
                "the serving tier (reader_ranks) and shardctl are "
                "mutually exclusive for now — readers address a static "
                "shard cut")
        codec_id, epoch, flags, smap = _scwire.parse_init_v4(raw)
        if not (flags & FLAG_FRAMED):
            raise ValueError(
                f"client {crank} announced shardctl without FLAG_FRAMED — "
                "shardctl ops ride the framed retry machinery"
            )
        if self.offset != -1:
            raise ValueError(
                f"client {crank} announced shardctl but server {self.rank} "
                "already holds a legacy (offset, size) registration"
            )
        codec = codec_mod.by_wire_id(codec_id)
        if self._codec_pin is not None and codec.name != self._codec_pin:
            raise ValueError(
                f"codec negotiation mismatch: client {crank} announced "
                f"{codec.name!r} but server {self.rank} is pinned to "
                f"{self._codec_pin!r} — align MPIT_PS_CODEC (or the codec "
                "config) across the gang"
            )
        if not codec.identity and np.dtype(self.dtype) != np.float32:
            raise ValueError(
                f"codec {codec.name!r} quantizes float32 shards; server "
                f"{self.rank} holds dtype {np.dtype(self.dtype).name} "
                "(use codec='none' for other dtypes)"
            )
        self._sc = True
        self._sc_install_map(smap)
        # Slot creation is a *boot-time* act (the version-0 cut, filled
        # by the seeder's pushes).  Any later map — a late client's
        # stale v0 announce after migrations, a greeting that carries a
        # newer map, anything a joiner sees — must never conjure a
        # zeroed slot: mid-run slots only ever arrive through
        # ACQUIRE/ADOPT with their real state (§9.1).
        if (not self._sc_join and self.smap is not None
                and self.smap.version == 0):
            for e in smap.shards_of(self.rank):
                if e.shard_id not in self._slots:
                    self._sc_make_slot(e.shard_id, e.shard)
        self._framed[crank] = True
        self._hb[crank] = bool(flags & FLAG_HEARTBEAT)
        # The 32-byte shard-addressed header has no version slot; the
        # staleness and timing extensions negotiate off under shardctl
        # (§6.6, §6.7).
        self._stale_track[crank] = False
        self._timing[crank] = False
        self.leases.arm(crank, epoch, heartbeats=self._hb[crank])
        return codec

    def _sc_install_map(self, smap: ShardMap) -> None:
        if self.smap is None or smap.version > self.smap.version:
            self.smap = smap
            self._m_sc_ver.set(smap.version)

    def _sc_make_slot(self, sid: int, shard) -> ShardSlot:
        slot = ShardSlot(sid, shard.offset, shard.size)
        slot.param = self._place_param(np.zeros(shard.size, self.dtype))
        slot.rule_state = self._init_state(slot.param)
        self._slots[sid] = slot
        self._m_sc_owned.set(len(self._slots))
        return slot

    def _place_param(self, arr):
        """Place one flat param vector on this server's backend: the
        dplane placement (mesh-sharded HBM) when configured, else the
        legacy device context.  Rule state built from the result
        inherits the placement (zeros_like preserves sharding).
        Always re-owned on device (dplane.hbm.device_copy): slot
        params feed donated applies under dplane, and a numpy-aliased
        buffer there is a use-after-free."""
        if self._dp_cfg is not None:
            return _dphbm.device_copy(_dphbm.place_flat(arr, self._dp_cfg))
        with self._dev_ctx():
            return _dphbm.device_copy(jnp.asarray(arr))

    def _place_state(self, state):
        """Place a restored rule-state dict next to its param."""
        if self._dp_cfg is not None:
            return _dphbm.place_state(state, self._dp_cfg)
        with self._dev_ctx():
            return {k: jnp.asarray(v) for k, v in state.items()}

    def _init_state(self, param):
        """Fresh rule state for ``param``.  The applies donate it, so
        the one zeros_like array some rules hand to several leaves
        (adam's m and v) is broken apart — donating one buffer twice
        is an XLA error."""
        return _dphbm.dedupe_state(self.rule.init(param))

    def _hdr_for(self, crank: int) -> int:
        """Header size of this client's data frames (GRAD/PARAM_PUSH)."""
        if not self._framed.get(crank):
            return 0
        return hdr_bytes(self._stale_track.get(crank, False),
                         self._timing.get(crank, False))

    def _reply_hdr_for(self, crank: int) -> int:
        """Header size of PARAM replies to this client (the timing tail
        makes replies wider than data frames)."""
        if not self._framed.get(crank):
            return 0
        return reply_hdr_bytes(self._stale_track.get(crank, False),
                               self._timing.get(crank, False))

    def _stale_hist(self, crank: int):
        """The per-client staleness histogram, cached (one get-or-create
        per client lifetime, plain attribute updates per observe)."""
        hist = self._stale_hists.get(crank)
        if hist is None:
            hist = self.metrics.histogram(
                "mpit_ps_grad_staleness", rank=self.rank, client=crank)
            self._stale_hists[crank] = hist
        return hist

    def _alloc_client(self, crank: int, codec: "codec_mod.Codec") -> None:
        """(Re)allocate every per-client staging buffer for the client's
        negotiated codec + framing — initial INIT and rejoin both land
        here, so a rejoining incarnation may change codec freely."""
        if self._readonly.get(crank):
            # Readers cost a request header, not a shard: no gradient
            # or push staging, no ack buffers — the read replies are
            # fresh 32-byte headers plus zero-copy views of the shared
            # snapshot cache.
            self._codecs[crank] = codec
            self._req_buf[crank] = np.zeros(2, np.int64)
            if self._hb.get(crank):
                self._hb_buf[crank] = np.zeros(2, np.int64)
            return
        if self._sc:
            # Shardctl frames are shard-addressed and variable-size per
            # shard, so the data paths receive by allocation — the only
            # fixed-size staging is the 32-byte PARAM_REQ header.
            self._codecs[crank] = codec
            self._req_buf[crank] = np.zeros(4, np.int64)
            if self._hb.get(crank):
                self._hb_buf[crank] = np.zeros(2, np.int64)
            return
        if self._chunk.get(crank):
            # Streamed pairs receive fixed-size chunk frames into
            # per-service staging (GRAD and PARAM_PUSH run concurrently
            # — one buffer each); assembly/serve staging is lazy.
            timing = self._timing.get(crank, False)
            stride = self._chunk_stride_for(crank, codec)
            self._codecs[crank] = codec
            for store in (self.grad_bufs, self._push_bufs,
                          self._push_host, self._param_send,
                          self._chunk_asm):
                store.pop(crank, None)
            self._chunk_rx[crank] = np.zeros(stride, np.uint8)
            self._chunk_rx_push[crank] = np.zeros(stride, np.uint8)
            self._ack_send[crank] = np.zeros(
                CHUNK_ACK_TIMING_WORDS if timing else CHUNK_ACK_WORDS,
                np.int64)
            self._req_buf[crank] = np.zeros(3 if timing else 2, np.int64)
            if self._hb.get(crank):
                self._hb_buf[crank] = np.zeros(3 if timing else 2, np.int64)
            return
        hdr = self._hdr_for(crank)
        self._codecs[crank] = codec
        self._push_bufs.pop(crank, None)
        self._push_host.pop(crank, None)
        self._param_send.pop(crank, None)
        self._chunk_rx.pop(crank, None)
        self._chunk_rx_push.pop(crank, None)
        self._chunk_asm.pop(crank, None)
        if codec.identity:
            self.grad_bufs[crank] = _GradFrames(
                hdr, self.size * np.dtype(self.dtype).itemsize,
                lambda payload: payload.view(self.dtype))
        else:
            self.grad_bufs[crank] = _GradFrames(
                hdr, codec.wire_nbytes(self.size),
                lambda payload: codec.split_wire(payload, self.size))
        timing = self._timing.get(crank, False)
        if hdr:
            self._ack_send[crank] = np.zeros(
                ACK_TIMING_WORDS if timing else 2, np.int64)
            self._req_buf[crank] = np.zeros(3 if timing else 2, np.int64)
        if self._hb.get(crank):
            self._hb_buf[crank] = np.zeros(3 if timing else 2, np.int64)

    def _release_client(self, crank: int) -> None:
        """Drop an evicted client's staging (its shard registration's
        per-client footprint); the shard itself is shared state."""
        for store in (self.grad_bufs, self._push_bufs, self._push_host,
                      self._param_send, self._codecs, self._ack_send,
                      self._req_buf, self._hb_buf, self._chunk_rx,
                      self._chunk_rx_push, self._chunk_asm):
            store.pop(crank, None)

    def _apply_for(self, codec: "codec_mod.Codec") -> Callable:
        """The jitted shard update for one codec: frame decode fused with
        ``rule.apply`` into a single XLA program (one call per grad, same
        as the fp32 path).  Param and rule state are DONATED (never the
        gradient): the update then sweeps the shard where it stands
        instead of writing every output into a fresh whole-shard
        allocation.  Best-effort and numerics-neutral, as in
        :meth:`_chunk_apply_for`: while a zero-copy view of the shard is
        alive (a pull in flight, a checkpoint) jax declines by itself
        and allocates, and the view keeps its bytes —
        ``mpit_ps_apply_inplace_total`` says how often it engaged."""
        fn = self._apply_cache.get(codec.name)
        if fn is None:
            # the rule itself unless it has plain ranges (rules.apply_at)
            rule_apply = apply_at(self.rule, self.offset)
            if codec.identity:
                fn = jax.jit(rule_apply, donate_argnums=(0, 2))
            else:
                size = self.size

                def _decode_apply(param, parts, state):
                    return rule_apply(param, codec.decode_parts(parts, size), state)

                fn = jax.jit(_decode_apply, donate_argnums=(0, 2))
            self._apply_cache[codec.name] = fn
        return fn

    def _sc_apply_for(self, codec: "codec_mod.Codec", size: int) -> Callable:
        """The jitted decode+apply for one (codec, shard size) — the
        per-slot analog of :meth:`_apply_for` (frame layouts are a pure
        function of (codec, n), so the cache key carries both)."""
        key = (codec.name, size)
        fn = self._sc_apply_cache.get(key)
        if fn is None:
            rule_apply = self.rule.apply
            # Device-resident slots (dplane) donate param + rule state:
            # the update consumes its HBM footprint in place instead of
            # reallocating it (the MT-J303 contract, load-bearing here).
            donate = ((0, 2) if self._dp_cfg is not None
                      and self._dp_cfg.donate else ())
            if codec.identity:
                fn = jax.jit(rule_apply, donate_argnums=donate)
            else:
                def _decode_apply(param, parts, state):
                    return rule_apply(param, codec.decode_parts(parts, size),
                                      state)

                fn = jax.jit(_decode_apply, donate_argnums=donate)
            self._sc_apply_cache[key] = fn
        return fn

    def _push_staging(self, crank: int) -> np.ndarray:
        """Lazily-allocated PARAM_PUSH recv staging for one client, sized
        to its codec's wire format plus the FT header when framed (cold
        path: seeding / single mode)."""
        buf = self._push_bufs.get(crank)
        if buf is None:
            codec = self._codecs[crank]
            hdr = self._hdr_for(crank)
            if codec.identity and not hdr:
                buf = np.zeros((self.size,), dtype=self.dtype)
            elif codec.identity:
                buf = np.zeros(hdr + self.size * np.dtype(self.dtype).itemsize,
                               np.uint8)
            else:
                buf = np.zeros(hdr + codec.wire_nbytes(self.size), np.uint8)
                self._push_host[crank] = np.zeros((self.size,), np.float32)
            self._push_bufs[crank] = buf
        return buf

    def _committed(self) -> None:
        """A new shard version exists (grad applied / params seeded).
        With a device-resident slot the slot's counter is authoritative
        (device-exchange applies bump it too); mirror it here so the
        wire snapshot cache keys on the same stream."""
        if self._hbm is not None:
            self._snap_version = self._hbm.version
        else:
            self._snap_version += 1

    def _release_snapshot(self) -> None:
        """Let go of the cached host view and frames of the version the
        next apply replaces: the identity codec serves a zero-copy view
        of the shard, and a view still held when the donated apply is
        dispatched makes jax decline the donation.  Nothing is lost — a
        version that is about to be stale never hits again.  A view in
        flight elsewhere (a reply task) keeps
        pinning its buffer; that apply allocates, as every apply did."""
        self._snap_host = None
        self._snap_wire.clear()

    def _snapshot_wire(self, codec: "codec_mod.Codec") -> np.ndarray:
        """The current version's PARAM frame for ``codec``, cached: N
        clients reading one committed version share one device->host
        copy and one encode.  Runs between scheduler yields, so version
        read + copy + encode are atomic w.r.t. grad applies."""
        version = self._snap_version
        cached = self._snap_wire.get(codec.name)
        if cached is not None and cached[0] == version:
            self._m_snap_hits.inc()
            return cached[1]
        if self._snap_host is None or self._snap_host[0] != version:
            # Serve-latest-committed: np.asarray snapshots the current
            # immutable device array (the one device->host copy).  A
            # device-resident slot shares its own per-version d2h cache
            # here, so wire reads, checkpoints and the device exchange
            # all draw from the same single copy.
            host = (self._hbm.snapshot_host() if self._hbm is not None
                    else np.asarray(self.param))
            if self._hbm is None and not host.flags.owndata \
                    and any(self._chunk.values()):
                # Chunked clients (§12): their donated per-chunk
                # applies update the param in place, which jax rightly
                # declines while a zero-copy snapshot view pins the
                # buffer — and a declined donation re-copies the WHOLE
                # shard on the next chunk.  Materialize the snapshot
                # instead: one extra sweep per committed version buys
                # in-place applies for every chunk after it.
                host = np.array(host)
            self._snap_host = (version, host)
            self._m_snap_copies.inc()
        host = self._snap_host[1]
        if codec.identity:
            wire = host
        else:
            # Through the pool seam's synchronous entry: this helper is
            # part of the declared 'ps-read-path-helpers' no-yield
            # window, so the encode runs inline (never queued — a pool
            # wait here would block the scheduler mid-atomic-section,
            # lint rule MT-C204).  The kernel itself is the GIL-free
            # native one when available.
            wire = np.empty(codec.wire_nbytes(self.size), np.uint8)
            comm_pool.get_pool().encode_sync(codec, host, wire)
        self._snap_wire[codec.name] = (version, wire)
        return wire

    # -- FT service plumbing -------------------------------------------------

    def _svc_abort(self, crank: int, gen: int) -> Callable[[], bool]:
        """Abort predicate for one service generation: fire when the
        client left (evicted/stopped) or a newer incarnation's services
        superseded this generation."""
        return lambda: self.leases.gone(crank) or self._gen[crank] != gen

    def _svc(self, crank: int, gen: int, fn: Callable, *args, **kw):
        """Run one service generator while tracking per-client service
        liveness, so a rejoin can wait for the old generation to clear
        before respawning (two generations recv'ing one channel would
        scramble the seq stream)."""
        self._svc_live[crank] += 1
        try:
            yield from fn(crank, *args, gen=gen, **kw)
        finally:
            self._svc_live[crank] -= 1

    def _send_ack(self, crank: int, tag: int, epoch: int, seq: int, gen: int,
                  t_tx: int = 0, t_recv: int = 0):
        buf = self._ack_send[crank]
        buf[0], buf[1] = epoch, seq
        if self._timing.get(crank):
            # FLAG_TIMING tail: the echoed client send stamp, this
            # frame's receive stamp, and the ack-send stamp taken now —
            # one complete NTP exchange per ack (§6.7).
            buf[2], buf[3], buf[4] = t_tx, t_recv, obs_clock.wall_us()
        yield from aio_send(self.transport, buf, crank, tag, live=self.live,
                            abort=self._svc_abort(crank, gen))

    # -- pipelined streaming services (FLAG_CHUNKED, PROTOCOL.md §12) --------

    def _chunk_body_for(self, codec: "codec_mod.Codec", elems: int) -> int:
        """Logical body bytes of a chunk covering ``elems`` elements."""
        if codec.identity:
            return elems * np.dtype(self.dtype).itemsize
        return codec.wire_nbytes(elems)

    def _chunk_stride_for(self, crank: int,
                          codec: "Optional[codec_mod.Codec]" = None) -> int:
        """The uniform chunk data-frame size for one client (§12.2)."""
        codec = codec if codec is not None else self._codecs[crank]
        full = min(self._chunk[crank], self.size)
        return chunk_stride(chunk_hdr_bytes(self._timing.get(crank, False)),
                            self._chunk_body_for(codec, full))

    def _send_chunk_ack(self, crank: int, tag: int, epoch: int, seq: int,
                        idx: int, gen: int, t_tx: int = 0, t_recv: int = 0):
        """One per-chunk ack: [epoch, seq, chunk_idx] (+ the timing
        tail) — the unit the client's resend-missing-chunks loop keys
        on."""
        buf = self._ack_send[crank]
        buf[0], buf[1], buf[2] = epoch, seq, idx
        if self._timing.get(crank):
            buf[3], buf[4], buf[5] = t_tx, t_recv, obs_clock.wall_us()
        yield from aio_send(self.transport, buf, crank, tag, live=self.live,
                            abort=self._svc_abort(crank, gen))

    def _chunk_apply_for(self, codec: "Optional[codec_mod.Codec]",
                         csize: int) -> Callable:
        """The jitted per-chunk decode+apply for the host-resident
        shard — element-wise slice math, one XLA call per chunk,
        cached per (codec, chunk size) with ``lo`` traced.  Param and
        state are DONATED: XLA then updates the slice in place (38x
        measured over the reallocating program at 64 MB/16 chunks —
        without donation every chunk apply copies the WHOLE shard, so
        a K-chunk op costs O(K·size) instead of O(size)).  Donation
        on the host backend is best-effort and numerics-neutral: jax
        declines it while a snapshot view pins the buffer, which is
        exactly the safety the version-keyed snapshot cache needs."""
        key = (codec.name if codec is not None else None, csize)
        fn = self._chunk_apply_cache.get(key)
        if fn is None:
            rule, offset = self.rule, self.offset

            def _chunk_apply(param, payload, state, lo):
                g = (payload if codec is None or codec.identity
                     else codec.decode_parts(payload, csize))
                psl = jax.lax.dynamic_slice(param, (lo,), (csize,))
                ssl = {k: jax.lax.dynamic_slice(v, (lo,), (csize,))
                       for k, v in state.items()}
                # the rule itself unless it has plain ranges, which are
                # cut by the chunk where it lies (rules.apply_at)
                pn, sn = apply_at(rule, offset + lo)(psl, g, ssl)
                return (jax.lax.dynamic_update_slice(param, pn, (lo,)),
                        {k: jax.lax.dynamic_update_slice(state[k], sn[k],
                                                         (lo,))
                         for k in state})

            fn = jax.jit(_chunk_apply, donate_argnums=(0, 2))
            self._chunk_apply_cache[key] = fn
        return fn

    def _chunk_fused_ok(self) -> bool:
        """Whether the per-chunk apply may fuse the codec decode into
        the same XLA call as the rule (§12.5).  XLA contracts a decode
        multiply feeding the apply into an fma — a single rounding —
        but only when the decode is one piece; the whole-shard program
        concatenates (and double-rounds) whenever the shard has a
        partial trailing block.  Bitwise equality to the unchunked
        apply therefore requires matching its rounding: fuse when the
        full-shard decode is concat-free, otherwise decode the chunk
        host-side (bit-identical to the host oracle) and apply the
        materialized f32 — exactly the two-rounding sequence the
        concatenated program produces."""
        return self.size % codec_mod.BLOCK == 0 \
            or self.size <= codec_mod.BLOCK

    def _chunk_decoded(self, crank: int, codec: "codec_mod.Codec",
                       body: np.ndarray, csize: int) -> np.ndarray:
        """Host-decode one chunk into a FRESH f32 buffer (the non-fused
        rounding path of :meth:`_chunk_fused_ok`).  Fresh per chunk on
        purpose — see :meth:`_chunk_owned`: jax aliases aligned host
        arrays, so a reused scratch would race the async apply."""
        out = np.empty(csize, np.float32)
        codec.decode_into(body, out)
        return out

    @staticmethod
    def _chunk_owned(view: np.ndarray) -> np.ndarray:
        """An *owned* copy of a chunk-receive view for handing to jax.
        Chunk frames arrive back-to-back into one reused staging buffer
        — unlike whole-frame ops, there is no ack round trip between a
        chunk's dispatch and the next chunk's receive, so jax's own
        (asynchronous) host transfer can still be reading the staging
        when the next chunk lands.  Copying synchronously here and
        letting jax zero-copy-alias the owned result costs the same
        one sweep the internal transfer would have, with no race."""
        return np.array(view)

    def _apply_chunk(self, crank: int, codec: "codec_mod.Codec",
                     body: np.ndarray, lo: int, hi: int,
                     commit: bool, span=NULL_SPAN) -> None:
        """Decode+apply one GRAD chunk — fused into one XLA call when
        that matches the unchunked rounding (:meth:`_chunk_fused_ok`);
        the version commits once per op (on the final chunk), so the
        snapshot cache keeps op-granular versions.
        ``span`` is the op's GRAD span: ``copy`` covers the owned copy
        (or host decode) of the chunk, ``dispatch`` the jitted call."""
        csize = hi - lo
        fused = codec.identity or self._chunk_fused_ok()
        span.mark("copy")
        if self._hbm is not None:
            if codec.identity:
                payload: Any = self._chunk_owned(body.view(self.dtype))
            elif fused:
                payload = [self._chunk_owned(v)
                           for v in codec.split_wire(body, csize)]
            else:
                payload = self._chunk_decoded(crank, codec, body, csize)
            span.mark("dispatch")
            self._hbm.apply_wire_chunk(codec if fused else None, payload,
                                       lo, csize, commit=commit)
            self.param = self._hbm.param
            self.rule_state = self._hbm.rule_state
            return
        with self._dev_ctx():
            if codec.identity:
                grad_in: Any = jnp.asarray(
                    self._chunk_owned(body.view(self.dtype)))
            elif fused:
                grad_in = [jnp.asarray(self._chunk_owned(v))
                           for v in codec.split_wire(body, csize)]
            else:
                grad_in = jnp.asarray(
                    self._chunk_decoded(crank, codec, body, csize))
            apply_fn = self._chunk_apply_for(codec if fused else None, csize)
            span.mark("dispatch")
            self.param, self.rule_state = apply_fn(
                self.param, grad_in, self.rule_state, np.int32(lo))

    def _recv_grad_chunked(self, crank: int, gen: int = 0):
        """The streamed GRAD service: each chunk frame is admitted per
        (op, chunk), applied the moment it lands — while later chunks
        are still on the wire — and acked individually.  The op commits
        (version bump, counters) on the admission that completed it;
        duplicate chunks re-ack without a second apply, so the client's
        encode-once staging keeps int8 error feedback exact under any
        retry pattern."""
        codec = self._codecs.get(crank)
        if codec is None:
            return
        timing = self._timing.get(crank, False)
        chdr = chunk_hdr_bytes(timing)
        rxbuf = self._chunk_rx[crank]
        spans_ = chunk_spans(self.size, self._chunk[crank])
        cur: "Optional[Tuple[int, int]]" = None
        span = None
        exec_span = NULL_SPAN
        while self.live.on:
            got = yield from aio_recv(
                self.transport, crank, tags.GRAD, live=self.live,
                out=rxbuf, abort=self._svc_abort(crank, gen),
            )
            if got is None:
                if span is not None:
                    span.end("aborted")
                    exec_span.end("aborted")
                return
            epoch, seq, idx, cnt = unpack_chunk_header(rxbuf)
            t_tx = t_recv = 0
            if timing:
                t_recv = obs_clock.wall_us()
                t_tx = unpack_tx_stamp(rxbuf, chdr)
            self.leases.renew(crank, epoch)
            if not (0 <= idx < len(spans_)) or cnt != len(spans_):
                raise ValueError(
                    f"chunked GRAD from client {crank} addresses chunk "
                    f"{idx}/{cnt} but this shard cuts into "
                    f"{len(spans_)} chunks — chunk layouts diverged "
                    "(INIT v5 carries the cut; §12.2)")
            verdict, done = self.dedup.admit_chunk(
                crank, tags.GRAD, epoch, seq, idx, cnt)
            if verdict == STALE:
                self._m_stale.inc()
                continue
            if verdict == DUP:
                self._m_dups.inc()
                yield from self._send_chunk_ack(
                    crank, tags.GRAD_ACK, epoch, seq, idx, gen,
                    t_tx=t_tx, t_recv=t_recv)
                continue
            if cur != (epoch, seq):
                if span is not None:
                    # The client abandoned an op mid-stream (teardown
                    # races only — the pump never overlaps ops).
                    span.end("aborted")
                    exec_span.end("aborted")
                cur = (epoch, seq)
                span = self._spans.op("GRAD", peer=crank, side="server",
                                      rank=self.rank)
                span.note(epoch=epoch, seq=seq, chunks=cnt)
                # one apply_exec per op: first chunk's dispatch to the
                # last chunk's result
                exec_span = self._exec_span(crank, span)
            lo, hi = spans_[idx]
            body = rxbuf[chdr: chdr + self._chunk_body_for(codec, hi - lo)]
            self._apply_chunk(crank, codec, body, lo, hi, commit=done,
                              span=span)
            if done:
                if self._spans.enabled:
                    self._spans.end_when_ready(exec_span,
                                               self._apply_token())
                    self._exec_pending = exec_span
                self._m_grads.inc()
                self._committed()
            if not self.live.on:
                span.end("aborted")
                if not done:
                    exec_span.end("aborted")
                span, cur = None, None
                continue
            span.mark("ack")
            yield from self._send_chunk_ack(
                crank, tags.GRAD_ACK, epoch, seq, idx, gen,
                t_tx=t_tx, t_recv=t_recv)
            if done:
                span.end("applied")
                span, cur = None, None

    def _serve_param_chunks(self, crank: int, codec: "codec_mod.Codec",
                            epoch: int, seq: int, req, t_recv: int,
                            gen: int, span):
        """Answer one chunked PARAM read: cut the shared snapshot
        cache's full frame into K independent chunk frames — every one
        stamped with the snapshot version — and post each without
        waiting, so the gather of chunk k+1 overlaps the wire time of
        chunk k.  The staging is per-client; the sends are awaited
        before returning so the next request cannot rewrite frames
        still in flight."""
        timing = self._timing.get(crank, False)
        chdr = chunk_reply_hdr_bytes(timing)
        spans_ = chunk_spans(self.size, self._chunk[crank])
        full = min(self._chunk[crank], self.size)
        stride = chunk_stride(chdr, self._chunk_body_for(codec, full))
        self._await_apply(span)
        span.mark("snapshot")
        wire = self._snapshot_wire(codec)
        wire_u8 = wire.view(np.uint8) if wire.dtype != np.uint8 else wire
        version = self._snap_version
        staging = self._param_send.get(crank)
        if staging is None or len(staging) != stride * len(spans_):
            staging = np.zeros(stride * len(spans_), np.uint8)
            self._param_send[crank] = staging
        itemsize = np.dtype(self.dtype).itemsize
        handles = []
        span.mark("send")
        # Gather jobs are pure: the snapshot wire is immutable for its
        # version (a new version allocates a fresh frame, never rewrites
        # this one — the Job pins it) and each chunk's staging slot is
        # disjoint.  With workers, the gather of chunk k+1 runs on the
        # pool while chunk k is on the wire; serial keeps today's order.
        pool = comm_pool.get_pool()
        jobs: Dict[int, object] = {}
        lookahead = 0 if pool.serial else 1
        for k, (lo, hi) in enumerate(spans_):
            for j in range(k, min(k + 1 + lookahead, len(spans_))):
                if j not in jobs:
                    jlo, jhi = spans_[j]
                    jframe = staging[j * stride: (j + 1) * stride]
                    jobs[j] = pool.submit_gather(
                        codec, wire_u8, self.size, jlo, jhi,
                        jframe[chdr:], itemsize=itemsize)
            frame = staging[k * stride: (k + 1) * stride]
            pack_chunk_reply(frame, epoch, seq, k, len(spans_), version)
            if timing:
                pack_reply_stamps(frame, chdr - TIMING_TAIL_BYTES,
                                  int(req[2]), t_recv, obs_clock.wall_us())
            if not jobs[k].done():
                span.mark("pool_collect")
                while not jobs[k].done():
                    yield EXEC
            if k:
                span.mark("chunk")
            handles.append(self.transport.isend(frame, crank, tags.PARAM))
            yield EXEC
        for handle in handles:
            while not self.transport.test(handle):
                if not self.live.io or self._svc_abort(crank, gen)():
                    self.transport.cancel(handle)
                    span.end("aborted")
                    return
                yield EXEC
        self._m_served.inc()
        span.end("served")

    def _recv_param_chunked(self, crank: int, once: bool = True,
                            warn_unexpected: bool = False, gen: int = 0):
        """The streamed PARAM_PUSH service: chunk frames scatter into a
        full-frame assembly buffer and the shard seeds exactly once,
        when the last chunk lands.  Chunks ack on admission (like GRAD
        — a commit-only ack would deadlock against periodic drop plans,
        which hit the same chunk index on every full resend), but the
        admissions are NOT checkpoint-persisted: the assembly bytes die
        with the process, so a server restarted mid-push answers the
        retried remainder with a fresh partial that can never complete
        and the push fails loudly (RetryExhausted) instead of seeding a
        torn vector (§12.6)."""
        codec = self._codecs.get(crank)
        if codec is None:
            return
        timing = self._timing.get(crank, False)
        chdr = chunk_hdr_bytes(timing)
        rxbuf = self._chunk_rx_push[crank]
        spans_ = chunk_spans(self.size, self._chunk[crank])
        itemsize = np.dtype(self.dtype).itemsize
        pool = comm_pool.get_pool()
        jobs: Dict[int, object] = {}
        while self.live.on:
            got = yield from aio_recv(
                self.transport, crank, tags.PARAM_PUSH, live=self.live,
                out=rxbuf, abort=self._svc_abort(crank, gen),
            )
            if got is None:
                return
            epoch, seq, idx, cnt = unpack_chunk_header(rxbuf)
            t_tx = t_recv = 0
            if timing:
                t_recv = obs_clock.wall_us()
                t_tx = unpack_tx_stamp(rxbuf, chdr)
            self.leases.renew(crank, epoch)
            if not (0 <= idx < len(spans_)) or cnt != len(spans_):
                raise ValueError(
                    f"chunked PARAM_PUSH from client {crank} addresses "
                    f"chunk {idx}/{cnt} but this shard cuts into "
                    f"{len(spans_)} chunks (§12.2)")
            verdict, done = self.dedup.admit_chunk(
                crank, tags.PARAM_PUSH, epoch, seq, idx, cnt)
            if verdict == STALE:
                self._m_stale.inc()
                continue
            if verdict == DUP:
                self._m_dups.inc()
                yield from self._send_chunk_ack(
                    crank, tags.PARAM_PUSH_ACK, epoch, seq, idx, gen,
                    t_tx=t_tx, t_recv=t_recv)
                continue
            asm = self._chunk_asm.get(crank)
            need = self._chunk_body_for(codec, self.size)
            if asm is None or len(asm) != need:
                asm = np.zeros(need, np.uint8)
                self._chunk_asm[crank] = asm
            lo, hi = spans_[idx]
            body = rxbuf[chdr: chdr + self._chunk_body_for(codec, hi - lo)]
            if pool.serial:
                codec_mod.scatter_chunk(codec, asm, self.size, lo, hi, body,
                                        itemsize=itemsize)
            else:
                # ``rxbuf`` is the reused push rx buffer: the next recv
                # overwrites it while a worker reads, so the job's input
                # must be an owned snapshot (discipline
                # 'pool-server-scatter-owned').  A resent chunk under a
                # new (epoch, seq) reuses the same assembly region, so
                # any prior job on this index must land first.
                prior = jobs.pop(idx, None)
                if prior is not None:
                    while not prior.done():
                        yield EXEC
                jobs[idx] = pool.submit_scatter(
                    codec, asm, self.size, lo, hi, np.array(body),
                    itemsize=itemsize)
            if not done:
                yield from self._send_chunk_ack(
                    crank, tags.PARAM_PUSH_ACK, epoch, seq, idx, gen,
                    t_tx=t_tx, t_recv=t_recv)
                continue
            span = self._spans.op("PARAM_PUSH", peer=crank, side="server",
                                  rank=self.rank)
            span.note(epoch=epoch, seq=seq, chunks=cnt)
            if warn_unexpected:
                self.log.warning(
                    "client %d seeded a RESTORED server: checkpointed "
                    "params overwritten (optimizer state kept) — start "
                    "resume clients with seed_servers=False", crank,
                )
            span.mark("apply")
            # Every scatter must have landed before the assembly buffer
            # is read (jobs write disjoint regions; collection order is
            # irrelevant to the bytes).
            for job in jobs.values():
                while not job.done():
                    yield EXEC
            jobs.clear()
            if codec.identity:
                # Owned copy: the assembly buffer is reused by the next
                # push while jax may still alias this seed's bytes
                # (see _chunk_owned).
                host: Any = self._chunk_owned(asm.view(self.dtype))
            else:
                host = np.empty(self.size, np.float32)
                codec.decode_into(asm, host)
            if self._hbm is not None:
                self._hbm.seed(host)
                self.param = self._hbm.param
            else:
                with self._dev_ctx():
                    # device_copy: a numpy-aliased param entering the
                    # donated chunk applies would hand XLA memory it
                    # does not own (dplane.hbm.device_copy docstring).
                    self.param = _dphbm.device_copy(jnp.asarray(host))
            self._committed()
            span.mark("ack")
            yield from self._send_chunk_ack(
                crank, tags.PARAM_PUSH_ACK, epoch, seq, idx, gen,
                t_tx=t_tx, t_recv=t_recv)
            span.end("applied")
            if once:
                return

    # -- service generators (reference pserver.lua coroutines) --------------

    def _recv_init(self, crank: int, gen: int = 0):
        """Receive [offset, size(, codec_id(, epoch, flags))]; negotiate
        codec + FT posture and allocate shard + staging state
        (reference :33-57)."""
        payload = yield from aio_recv(self.transport, crank, tags.INIT,
                                      live=self.live)
        if payload is None:
            return
        codec = self._negotiate(crank, payload)
        self._alloc_client(crank, codec)

    def _init_listener(self, crank: int):
        """Perpetual rejoin listener (phase 3, FT only): a restarted
        incarnation re-announces on INIT; accept it, supersede the old
        generation's services, and respawn against the new epoch.  The
        INIT v3 handshake is the whole rejoin protocol — the client then
        simply pulls current params and resumes."""
        while self.live.on:
            payload = yield from aio_recv(self.transport, crank, tags.INIT,
                                          live=self.live)
            if payload is None:
                return
            codec = self._negotiate(crank, payload)
            self._gen[crank] += 1
            gen = self._gen[crank]
            self.leases.rejoin(crank, self.leases.epoch(crank))
            self.leases.arm(crank, self.leases.epoch(crank),
                            heartbeats=self._hb.get(crank, False))
            self._alloc_client(crank, codec)
            self._m_rejoins.inc()
            # Two generations must never recv one channel concurrently —
            # wait for the superseded loops to abort out.
            while self._svc_live[crank] > 0:
                yield EXEC
            self._spawn_services(crank)
            self.log.info(
                "client %d rejoined (epoch %d, gen %d)",
                crank, self.leases.epoch(crank), gen,
            )

    def _recv_param(self, crank: int, once: bool = True,
                    warn_unexpected: bool = False, gen: int = 0):
        """Whole-shard write from a client: one-shot seeding from the first
        client (reference :92-102) or perpetual in single mode (the
        BiCNN recvparam_always service, BiCNN/pserver.lua:220-232).
        Framed pushes are dedup-admitted: a retried seed is applied once
        and re-acked."""
        if self._chunk.get(crank):
            yield from self._recv_param_chunked(
                crank, once=once, warn_unexpected=warn_unexpected, gen=gen)
            return
        codec = self._codecs.get(crank)
        if codec is None:  # init never completed (stopped before announce)
            return
        framed = self._framed.get(crank, False)
        timing = self._timing.get(crank, False)
        hdr = self._hdr_for(crank)
        staging = self._push_staging(crank)
        while self.live.on:
            got = yield from aio_recv(
                self.transport, crank, tags.PARAM_PUSH,
                live=self.live, out=staging, abort=self._svc_abort(crank, gen),
            )
            if got is None:
                return
            epoch = seq = t_tx = t_recv = 0
            if timing:
                t_recv = obs_clock.wall_us()
                t_tx = unpack_tx_stamp(staging, hdr)
            span = self._spans.op("PARAM_PUSH", peer=crank, side="server",
                                  rank=self.rank)
            if framed:
                epoch, seq = unpack_header(staging)
                span.note(epoch=epoch, seq=seq)
                self.leases.renew(crank, epoch)
                verdict = self.dedup.admit(crank, tags.PARAM_PUSH, epoch, seq)
                if verdict == STALE:
                    self._m_stale.inc()
                    span.end("stale")
                    continue
                if verdict == DUP:
                    self._m_dups.inc()
                    span.mark("ack")
                    yield from self._send_ack(
                        crank, tags.PARAM_PUSH_ACK, epoch, seq, gen,
                        t_tx=t_tx, t_recv=t_recv)
                    span.end("dup")
                    continue
            if warn_unexpected:
                self.log.warning(
                    "client %d seeded a RESTORED server: checkpointed "
                    "params overwritten (optimizer state kept) — start "
                    "resume clients with seed_servers=False", crank,
                )
            span.mark("apply")
            if codec.identity and not hdr:
                host = staging
            elif codec.identity:
                host = staging[hdr:].view(self.dtype)
            else:  # cold path: host decode, then one h2d
                host = self._push_host[crank]
                codec.decode_into(staging[hdr:], host)
            if self._hbm is not None:
                self._hbm.seed(host)
                self.param = self._hbm.param
            else:
                with self._dev_ctx():
                    # device_copy: a chunked sibling client's donated
                    # chunk applies may consume this param — it must
                    # be device-owned, not a staging alias (cold path;
                    # dplane.hbm.device_copy).
                    self.param = _dphbm.device_copy(jnp.asarray(host))
            self._committed()
            span.mark("ack")
            if framed:
                yield from self._send_ack(
                    crank, tags.PARAM_PUSH_ACK, epoch, seq, gen,
                    t_tx=t_tx, t_recv=t_recv)
            else:
                yield from aio_send(
                    self.transport, tags.EMPTY, crank, tags.PARAM_PUSH_ACK,
                    live=self.live, abort=self._svc_abort(crank, gen),
                )
            span.end("applied")
            if once:
                # The one-shot seed is in ``self.param`` (a copy of its
                # own): the whole-shard staging is never received into
                # again, and kept it is a dead shard of host memory for
                # the rest of the run (1.25 GB a server at OLMoE's one
                # layer: PERF.md section 6, PR 26).
                self._push_bufs.pop(crank, None)
                self._push_host.pop(crank, None)
                return

    def _send_param(self, crank: int, gen: int = 0):
        """Loop: await the read request, send the current version's
        encoded snapshot (reference :59-72).  Framed requests carry
        [epoch, seq]; the reply echoes it so the client can discard
        snapshots answering an earlier (retried) request.  Reads are
        idempotent — duplicates are served, never dedup'd."""
        codec = self._codecs.get(crank)
        if codec is None:  # init never completed (stopped before announce)
            return
        framed = self._framed.get(crank, False)
        timing = self._timing.get(crank, False)
        while self.live.on:
            req = self._req_buf.get(crank) if framed else None
            got = yield from aio_recv(
                self.transport, crank, tags.PARAM_REQ, live=self.live,
                out=req, abort=self._svc_abort(crank, gen),
            )
            if got is None:
                return
            if not self.live.io:
                continue
            t_recv = obs_clock.wall_us() if timing else 0
            span = self._spans.op("PARAM", peer=crank, side="server",
                                  rank=self.rank)
            if not framed:
                self._await_apply(span)
                span.mark("snapshot")
                snapshot = self._snapshot_wire(codec)
                span.note(bytes=snapshot.nbytes)
                span.mark("send")
                yield from aio_send(
                    self.transport, snapshot, crank, tags.PARAM,
                    live=self.live, abort=self._svc_abort(crank, gen),
                )
                # This loop now waits for the next request; a view of
                # the shard left bound here would pin it through the
                # next apply (:meth:`_release_snapshot`).
                del snapshot
                self._m_served.inc()
                self._wire_meter.note(span)
                span.end("served")
                continue
            epoch, seq = int(req[0]), int(req[1])
            span.note(epoch=epoch, seq=seq)
            if epoch < self.leases.epoch(crank):
                self._m_stale.inc()  # dead incarnation's request
                span.end("stale")
                continue
            self.leases.renew(crank, epoch)
            if self._chunk.get(crank):
                span.note(chunks=len(chunk_spans(self.size,
                                                 self._chunk[crank])))
                yield from self._serve_param_chunks(
                    crank, codec, epoch, seq, req, t_recv, gen, span)
                continue
            self._await_apply(span)
            span.mark("snapshot")
            hdr = self._reply_hdr_for(crank)
            wire = self._snapshot_wire(codec)
            span.note(bytes=wire.nbytes)
            wire_u8 = wire.view(np.uint8) if wire.dtype != np.uint8 else wire
            reply = self._param_send.get(crank)
            if reply is None or len(reply) != hdr + len(wire_u8):
                reply = np.zeros(hdr + len(wire_u8), np.uint8)
                self._param_send[crank] = reply
            reply[:HDR_BYTES].view(np.int64)[:] = (epoch, seq)
            if self._stale_track.get(crank):
                # Stamp the served snapshot's version — the basis the
                # client's next gradient will echo (staleness telemetry).
                pack_version(reply, self._snap_version)
            reply[hdr:] = wire_u8
            del wire, wire_u8  # copied: do not pin the shard (as above)
            span.mark("send")
            if timing:
                # The reply's timing tail (§6.7): echoed request stamp,
                # the request's receive stamp, and the send stamp now.
                pack_reply_stamps(reply, hdr - TIMING_TAIL_BYTES,
                                  int(req[2]), t_recv, obs_clock.wall_us())
            yield from aio_send(
                self.transport, reply, crank, tags.PARAM, live=self.live,
                abort=self._svc_abort(crank, gen),
            )
            self._m_served.inc()
            self._wire_meter.note(span)
            span.end("served")

    # -- serving tier: READ-ONLY readers + admission control (§8) ------------

    def _update_reader_gauge(self) -> None:
        live = sum(1 for r in self.readers
                   if r in self._codecs and not self.leases.gone(r))
        self._m_readers.set(live)

    def retire_serving(self, successor: int) -> None:
        """Serving-tier retirement (§9.4): from now on every reader
        request is answered ``GOODBYE`` carrying ``successor`` — the
        reader re-attaches there instead of burning its retry budget
        against a disappearing rank.  The redirected reader is marked
        STOPPED here (it will never send this rank another frame), so
        the stop protocol completes without it."""
        if successor == self.rank:
            raise ValueError("a retiring server cannot be its own successor")
        self._serve_successor = int(successor)
        self.log.info("serving tier retiring: readers redirected to %d",
                      successor)

    def _dispatch_recv(self, crank: int, tag: int, out=None):
        """Receive a message the dispatcher's probe already saw (fully
        assembled, so this completes without waiting on the peer)."""
        handle = self.transport.irecv(crank, tag, out=out)
        while not self.transport.test(handle):
            yield EXEC
        return self.transport.payload(handle)

    def _reader_dispatcher(self):
        """ONE task serves every reader (serving tier, §8).  A
        per-reader service trio would put O(attached readers) perpetual
        tasks on the cooperative scheduler — at 512 readers every
        scheduler pass walks ~1500 parked generators, and per-op
        latency scales with attachment, not load.  Instead this single
        task probes each reader's channels nonblockingly per scan
        (attach/re-attach INIT, STOP, HEARTBEAT, read requests) and
        spawns one bounded *reply task* per granted read: the scheduler
        holds O(in-flight replies) tasks — and in-flight is exactly
        what the admission budget bounds, so admission control is also
        what keeps the scheduler flat under fan-out."""
        reply_live: Dict[int, bool] = {r: False for r in self.readers}
        self._reader_reply_live = reply_live  # introspection/tests
        scan = 0
        while self.live.on:
            progressed = False
            # Rare-event probes (re-attach, STOP, beats) are staggered
            # over 8 scans so a steady-state scan costs ~one probe per
            # reader — the hot path is PARAM_REQ, everything else can
            # tolerate a few scans of latency.
            slot = scan & 7
            for crank in self.readers:
                if reply_live[crank]:
                    # FIFO per reader: one reply (or re-attach gate) at
                    # a time — two in-flight replies to one reader
                    # could interleave their header/body pairs.
                    continue
                attached = crank in self._codecs
                slow_turn = (crank & 7) == slot
                try:
                    if ((not attached or slow_turn)
                            and self.transport.iprobe(crank, tags.INIT)):
                        payload = yield from self._dispatch_recv(
                            crank, tags.INIT)
                        codec = self._negotiate(crank, payload)
                        self._gen[crank] += 1
                        self.leases.rejoin(crank, self.leases.epoch(crank))
                        self.leases.arm(crank, self.leases.epoch(crank),
                                        heartbeats=self._hb.get(crank, False))
                        self._alloc_client(crank, codec)
                        self._update_reader_gauge()
                        attached = True
                        progressed = True
                        self.log.info(
                            "reader %d attached (epoch %d, gen %d)",
                            crank, self.leases.epoch(crank),
                            self._gen[crank])
                    if not attached or self.leases.gone(crank):
                        continue
                    if slow_turn and self.transport.iprobe(crank, tags.STOP):
                        yield from self._dispatch_recv(crank, tags.STOP)
                        self.leases.stop(crank)
                        self._update_reader_gauge()
                        progressed = True
                        if self.leases.all_done():
                            self.live.stop()
                        continue
                    if slow_turn and self._hb.get(crank):
                        while self.transport.iprobe(crank, tags.HEARTBEAT):
                            beat = yield from self._dispatch_recv(
                                crank, tags.HEARTBEAT, out=self._hb_buf[crank])
                            if beat is None:
                                break
                            self._m_hb_seen.inc()
                            self.leases.renew(crank, int(beat[0]))
                    if self.transport.iprobe(crank, tags.PARAM_REQ):
                        yield from self._dispatch_read(crank, reply_live)
                        progressed = True
                except RuntimeError:
                    # Torn connection (the transport's fail-loud probe):
                    # the reader is gone without a STOP — its lease (when
                    # armed) evicts it; a replacement attaches through a
                    # fresh INIT on a revived channel.
                    continue
            scan += 1
            if progressed:
                yield EXEC  # hot: scan again next step
            else:
                # Idle scan: pace the next one — two servers
                # busy-scanning N channels would eat the very core the
                # gang's replies are produced on (the IDLE_USEC lesson).
                if not (yield from aio_sleep(0.002, live=self.live)):
                    return

    def _dispatch_read(self, crank: int, reply_live: Dict[int, bool]):
        """Admit one read request: grant it a reply task, or answer
        BUSY-with-retry-hint when the in-flight budget is spent."""
        codec = self._codecs[crank]
        cfg = self.serve_cfg
        req = yield from self._dispatch_recv(crank, tags.PARAM_REQ,
                                             out=self._req_buf[crank])
        if req is None:
            return
        epoch, seq = int(req[0]), int(req[1])
        span = self._spans.op("PARAM", peer=crank, side="server",
                              rank=self.rank)
        span.note(epoch=epoch, seq=seq, reader=1)
        if epoch < self.leases.epoch(crank):
            self._m_stale.inc()  # dead incarnation's request
            span.end("stale")
            return
        self.leases.renew(crank, epoch)
        gen = self._gen[crank]
        if self._serve_successor is not None:
            # Retiring (§9.4): a goodbye-with-successor, not a grant —
            # and not a silent vanish that costs the reader its budget.
            succ = self._serve_successor
            span.note(successor=succ)
            span.mark("send")
            header = _psserve.serve_reply(epoch, seq, _scwire.GOODBYE, succ)
            reply_live[crank] = True
            self.sched.spawn(
                self._serve_reply(crank, gen, span, header, None, 0,
                                  reply_live),
                name=f"serve_goodbye:{crank}")
            self.leases.stop(crank)
            self._update_reader_gauge()
            return
        nbytes = (self.size * np.dtype(self.dtype).itemsize
                  if codec.identity else codec.wire_nbytes(self.size))
        # An idle rank always grants (a frame larger than the whole
        # budget must not be rejectable forever); past that, the budget
        # bounds what may queue behind in-flight replies.
        if self._serve_inflight_reads > 0 and (
                self._serve_inflight_bytes + nbytes > cfg.budget_bytes
                or (cfg.budget_reads > 0
                    and self._serve_inflight_reads >= cfg.budget_reads)):
            self._m_busy.inc()
            hint = cfg.hint_us(self._serve_inflight_bytes)
            span.note(hint_us=hint)
            span.mark("send")
            header = _psserve.serve_reply(epoch, seq, _scwire.BUSY, hint)
            reply_live[crank] = True
            self.sched.spawn(
                self._serve_reply(crank, gen, span, header, None, 0,
                                  reply_live),
                name=f"serve_busy:{crank}")
            return
        # Declared atomic section `ps-read-snapshot-window` (MT-Y801):
        # no scheduler yield between taking the frame and stamping the
        # reply header — the version in the OK header is the frame's
        # only because nothing can park the task inside this window.
        span.mark("snapshot")
        wire = self._snapshot_wire(codec)
        header = _psserve.serve_reply(epoch, seq, _scwire.OK,
                                      self._snap_version)
        self._serve_inflight_bytes += nbytes
        self._serve_inflight_reads += 1
        reply_live[crank] = True
        self.sched.spawn(
            self._serve_reply(crank, gen, span, header, wire, nbytes,
                              reply_live),
            name=f"serve_reply:{crank}")

    def _serve_reply(self, crank: int, gen: int, span, header,
                     body, nbytes: int, reply_live: Dict[int, bool]):
        """One granted (or BUSY) reply: the 32-byte status header, then
        — on a grant — the snapshot frame as its own message.  The body
        is a zero-copy view of this version's cached frame, so N
        readers of one version share one device->host copy and one
        encode however many connections are attached.  A reader that
        dies mid-reply costs this task, never the server."""
        span.mark("send")
        try:
            yield from aio_send(self.transport, header, crank, tags.PARAM,
                                live=self.live,
                                abort=self._svc_abort(crank, gen))
            if body is not None:
                yield from aio_send(self.transport, body, crank, tags.PARAM,
                                    live=self.live,
                                    abort=self._svc_abort(crank, gen))
        except (RuntimeError, DeadlineExceeded) as exc:
            # Dead reader mid-reply (transport fail-loud): drop the
            # reply; the lease reaper / re-attach path owns the rank.
            self.log.debug("reply to reader %d dropped: %r", crank, exc)
            span.end("aborted")
            return
        finally:
            if body is not None:
                self._serve_inflight_bytes -= nbytes
                self._serve_inflight_reads -= 1
            reply_live[crank] = False
        if body is not None:
            self._m_served.inc()
            span.end("served")
        else:
            span.end("busy")
        # A goodbye may have marked the last non-terminal rank STOPPED;
        # re-check the stop condition now that the reply is on the wire.
        if self.leases.all_done():
            self.live.stop()

    def _recv_grad(self, crank: int, gen: int = 0):
        """Loop: receive gradient frame, decode+apply the shard rule in
        one jitted call, ack (reference :75-90 — the server hot loop).
        Framed frames are dedup-admitted on (epoch, seq): duplicates are
        re-acked without a second apply — with the client's encode-once
        staging this is what keeps error feedback exact under retries."""
        if self._chunk.get(crank):
            yield from self._recv_grad_chunked(crank, gen=gen)
            return
        codec = self._codecs.get(crank)
        if codec is None:  # init never completed (stopped before announce)
            return
        framed = self._framed.get(crank, False)
        timing = self._timing.get(crank, False)
        hdr = self._hdr_for(crank)
        frames = self.grad_bufs[crank]
        apply_fn = self._apply_for(codec)
        while self.live.on:
            # The frame about to be received into was lent to the apply
            # two ops back.  It has run, unless pushes outpace applies
            # with no pull between them (a pull waits for its apply).
            while not frames.writable():
                yield EXEC
            gbuf = frames.frame
            got = yield from aio_recv(
                self.transport, crank, tags.GRAD, live=self.live, out=gbuf,
                abort=self._svc_abort(crank, gen),
            )
            if got is None:
                return
            epoch = seq = t_tx = t_recv = 0
            if timing:
                t_recv = obs_clock.wall_us()
                t_tx = unpack_tx_stamp(gbuf, hdr)
            span = self._spans.op("GRAD", peer=crank, side="server",
                                  rank=self.rank)
            if framed:
                epoch, seq = unpack_header(gbuf)
                span.note(epoch=epoch, seq=seq)
                self.leases.renew(crank, epoch)
                verdict = self.dedup.admit(crank, tags.GRAD, epoch, seq)
                if verdict == STALE:
                    self._m_stale.inc()
                    span.end("stale")
                    continue
                if verdict == DUP:
                    self._m_dups.inc()
                    span.mark("ack")
                    yield from self._send_ack(crank, tags.GRAD_ACK,
                                              epoch, seq, gen,
                                              t_tx=t_tx, t_recv=t_recv)
                    span.end("dup")
                    continue
                if self._stale_track.get(crank):
                    # Gradient staleness: the gap between the version the
                    # client computed against (echoed in the header) and
                    # the version this gradient lands on.  Observed once
                    # per *applied* op — dups/stales above never count,
                    # so under a deterministic fault plan the histogram
                    # matches the plan arithmetic exactly.
                    staleness = self._snap_version - unpack_version(gbuf)
                    span.note(staleness=staleness)
                    self._stale_hist(crank).observe(staleness)
            span.note(bytes=gbuf.nbytes)
            span.mark("copy")
            # The GRAD_ACK below does NOT serialize buffer reuse: the
            # jitted apply only *dispatches* before the ack goes out,
            # and jax zero-copy-aliases aligned host arrays, so the
            # next GRAD landing in the memory the apply was handed
            # would race the in-flight execution (`ps-grad-apply-owned`,
            # MT-D901; visible as wrong applied bytes whenever the
            # backend queue is backed up, e.g. first-call compiles).
            # Hence the operand is an owned copy (device path) or a
            # frame out of rotation until its apply has run (host path,
            # :class:`_GradFrames`), and the op has two phases and a
            # span of its own: ``copy`` (making the operand),
            # ``dispatch`` (the call returns when the apply is
            # enqueued), and ``apply_exec``, which the recorder's waiter
            # ends when the apply has run — after the ack, which it
            # does not delay.
            if self._hbm is not None:
                # Device-resident path: the slot's donated fused
                # decode+apply — same math, same operand order as the
                # host jit below, so both runs stay bitwise equal.
                views = frames.views
                owned: Any = (self._chunk_owned(views) if codec.identity
                              else [self._chunk_owned(v) for v in views])
                span.mark("dispatch")
                exec_span = self._exec_span(crank, span)
                self._hbm.apply_wire(codec, owned)
                self.param = self._hbm.param
                self.rule_state = self._hbm.rule_state
                token = (self._apply_token() if self._spans.enabled
                         else None)
            else:
                self._release_snapshot()
                with self._dev_ctx():
                    grad_in = frames.lend()
                    span.mark("dispatch")
                    exec_span = self._exec_span(crank, span)
                    # Whether the donation engaged is the shard's
                    # address before and after.  Reading it does not
                    # wait for the apply; a declined one pays here for
                    # the allocation of its outputs, nothing else: what
                    # pins the shard is a view taken of it ready, so
                    # nothing is queued ahead of this apply.
                    stood_at = self.param.unsafe_buffer_pointer()
                    self.param, self.rule_state = apply_fn(
                        self.param, grad_in, self.rule_state
                    )
                    inplace = self.param.unsafe_buffer_pointer() == stood_at
                    token = self._apply_token()
                frames.lent_until(token)
                self._m_inplace.inc(int(inplace))
                exec_span.note(inplace=int(inplace))
            self._spans.end_when_ready(exec_span, token)
            self._exec_pending = exec_span
            self._m_grads.inc()
            self._committed()
            if not self.live.on:
                span.end("aborted")
                continue
            span.mark("ack")
            if framed:
                yield from self._send_ack(crank, tags.GRAD_ACK, epoch, seq,
                                          gen, t_tx=t_tx, t_recv=t_recv)
            else:
                yield from aio_send(
                    self.transport, tags.EMPTY, crank, tags.GRAD_ACK,
                    live=self.live, abort=self._svc_abort(crank, gen),
                )
            self._wire_meter.note(span)
            span.end("applied")

    # -- shardctl services: shard-addressed ops over the versioned map -------

    def _sc_verdict(self, sid: int) -> int:
        """Route an op addressing shard ``sid``: OK to serve, NACK_MAP
        when the map says someone else owns it (the reply carries our
        newer map), BUSY while its state is frozen or in flight to us."""
        try:
            owner = self.smap.owner(sid) if self.smap is not None else -1
        except KeyError:
            owner = -1
        if owner != self.rank:
            return _scwire.NACK_MAP
        slot = self._slots.get(sid)
        if slot is None or slot.frozen:
            return _scwire.BUSY
        return _scwire.OK

    def _sc_ops_counter(self, sid: int):
        return self.metrics.counter("mpit_shardctl_shard_ops_total",
                                    rank=self.rank, shard=sid)

    def _sc_busy_timer(self, sid: int):
        """Busy-seconds timer for one slot (clock lives in obs — the
        MT-O4xx contract).  Spans dedup→apply→ack-complete, cooperative
        suspensions included: that *is* the time the shard's service
        occupied, which is what the rebalance policy weighs."""
        return self.metrics.timer("mpit_shardctl_shard_busy_seconds",
                                  rank=self.rank, shard=sid)

    def _sc_recv_grad(self, crank: int, gen: int = 0):
        """Shardctl GRAD loop: alloc-receive the shard-addressed frame,
        route by map, dedup on the *slot's* table (it migrates with the
        shard — at-most-once holds across owners), decode+apply in one
        jitted call, status-ack."""
        codec = self._codecs.get(crank)
        if codec is None:
            return
        while self.live.on:
            raw = yield from aio_recv(
                self.transport, crank, tags.GRAD, live=self.live,
                abort=self._svc_abort(crank, gen),
            )
            if raw is None:
                return
            buf = np.frombuffer(raw, np.uint8)
            epoch, seq, _mapver, sid = _scwire.unpack_sc_header(buf)
            span = self._spans.op("GRAD", peer=crank, side="server",
                                  rank=self.rank)
            span.note(epoch=epoch, seq=seq, shard=sid)
            self.leases.renew(crank, epoch)
            verdict = self._sc_verdict(sid)
            if verdict != _scwire.OK:
                (self._m_sc_nacks if verdict == _scwire.NACK_MAP
                 else self._m_sc_busy).inc()
                span.mark("ack")
                yield from aio_send(
                    self.transport,
                    _scwire.reply_frame(epoch, seq, verdict, sid,
                                        body=self.smap.to_wire()),
                    crank, tags.GRAD_ACK, live=self.live,
                    abort=self._svc_abort(crank, gen),
                )
                span.end("nack" if verdict == _scwire.NACK_MAP else "busy")
                continue
            slot = self._slots[sid]
            with self._sc_busy_timer(sid):
                admitted = slot.dedup.admit(crank, tags.GRAD, epoch, seq)
                if admitted == STALE:
                    self._m_stale.inc()
                    span.end("stale")
                    continue
                if admitted == DUP:
                    self._m_dups.inc()
                    span.mark("ack")
                    yield from aio_send(
                        self.transport,
                        _scwire.reply_frame(epoch, seq, _scwire.OK, sid),
                        crank, tags.GRAD_ACK, live=self.live,
                        abort=self._svc_abort(crank, gen),
                    )
                    span.end("dup")
                    continue
                span.mark("apply")
                body = buf[_scwire.SC_HDR_BYTES:]
                apply_fn = self._sc_apply_for(codec, slot.size)
                with self._dev_ctx():
                    if codec.identity:
                        grad_in: Any = jnp.asarray(body.view(self.dtype))
                    else:
                        grad_in = [jnp.asarray(v) for v in
                                   codec.split_wire(body, slot.size)]
                    slot.param, slot.rule_state = apply_fn(
                        slot.param, grad_in, slot.rule_state)
                slot.committed()
                slot.grads_applied += 1
                self._m_grads.inc()
                self._sc_ops_counter(sid).inc()
                if not self.live.on:
                    span.end("aborted")
                    continue
                span.mark("ack")
                yield from aio_send(
                    self.transport,
                    _scwire.reply_frame(epoch, seq, _scwire.OK, sid),
                    crank, tags.GRAD_ACK, live=self.live,
                    abort=self._svc_abort(crank, gen),
                )
            span.end("applied")

    def _sc_send_param(self, crank: int, gen: int = 0):
        """Shardctl read loop: fixed 32-byte PARAM_REQ header in, the
        slot's cached snapshot frame (or a NACK/BUSY status) out."""
        codec = self._codecs.get(crank)
        if codec is None:
            return
        req = self._req_buf[crank]
        while self.live.on:
            got = yield from aio_recv(
                self.transport, crank, tags.PARAM_REQ, live=self.live,
                out=req, abort=self._svc_abort(crank, gen),
            )
            if got is None:
                return
            if not self.live.io:
                continue
            epoch, seq, _mapver, sid = (int(x) for x in req)
            span = self._spans.op("PARAM", peer=crank, side="server",
                                  rank=self.rank)
            span.note(epoch=epoch, seq=seq, shard=sid)
            if epoch < self.leases.epoch(crank):
                self._m_stale.inc()  # dead incarnation's request
                span.end("stale")
                continue
            self.leases.renew(crank, epoch)
            verdict = self._sc_verdict(sid)
            if verdict != _scwire.OK:
                (self._m_sc_nacks if verdict == _scwire.NACK_MAP
                 else self._m_sc_busy).inc()
                span.mark("send")
                yield from aio_send(
                    self.transport,
                    _scwire.reply_frame(epoch, seq, verdict, sid,
                                        body=self.smap.to_wire()),
                    crank, tags.PARAM, live=self.live,
                    abort=self._svc_abort(crank, gen),
                )
                span.end("nack" if verdict == _scwire.NACK_MAP else "busy")
                continue
            slot = self._slots[sid]
            with self._sc_busy_timer(sid):
                span.mark("snapshot")
                frame, hit = slot.snapshot_wire(codec)
                (self._m_snap_hits if hit else self._m_snap_copies).inc()
                reply = _scwire.reply_frame(epoch, seq, _scwire.OK, sid,
                                            body=frame)
                span.mark("send")
                yield from aio_send(
                    self.transport, reply, crank, tags.PARAM,
                    live=self.live, abort=self._svc_abort(crank, gen),
                )
                self._m_served.inc()
                self._sc_ops_counter(sid).inc()
            span.end("served")

    def _sc_recv_push(self, crank: int, gen: int = 0):
        """Shardctl PARAM_PUSH loop (seeding and whole-shard writes):
        dedup-admitted per slot, decoded host-side, one h2d per write."""
        codec = self._codecs.get(crank)
        if codec is None:
            return
        while self.live.on:
            raw = yield from aio_recv(
                self.transport, crank, tags.PARAM_PUSH, live=self.live,
                abort=self._svc_abort(crank, gen),
            )
            if raw is None:
                return
            buf = np.frombuffer(raw, np.uint8)
            epoch, seq, _mapver, sid = _scwire.unpack_sc_header(buf)
            span = self._spans.op("PARAM_PUSH", peer=crank, side="server",
                                  rank=self.rank)
            span.note(epoch=epoch, seq=seq, shard=sid)
            self.leases.renew(crank, epoch)
            verdict = self._sc_verdict(sid)
            if verdict != _scwire.OK:
                (self._m_sc_nacks if verdict == _scwire.NACK_MAP
                 else self._m_sc_busy).inc()
                span.mark("ack")
                yield from aio_send(
                    self.transport,
                    _scwire.reply_frame(epoch, seq, verdict, sid,
                                        body=self.smap.to_wire()),
                    crank, tags.PARAM_PUSH_ACK, live=self.live,
                    abort=self._svc_abort(crank, gen),
                )
                span.end("nack" if verdict == _scwire.NACK_MAP else "busy")
                continue
            slot = self._slots[sid]
            with self._sc_busy_timer(sid):
                admitted = slot.dedup.admit(crank, tags.PARAM_PUSH, epoch,
                                            seq)
                if admitted == STALE:
                    self._m_stale.inc()
                    span.end("stale")
                    continue
                if admitted != DUP:
                    span.mark("apply")
                    body = buf[_scwire.SC_HDR_BYTES:]
                    if codec.identity:
                        host: Any = body.view(self.dtype)
                    else:
                        host = np.empty(slot.size, np.float32)
                        codec.decode_into(body, host)
                    with self._dev_ctx():
                        slot.param = jnp.asarray(host)
                    slot.committed()
                    self._sc_ops_counter(sid).inc()
                else:
                    self._m_dups.inc()
                span.mark("ack")
                yield from aio_send(
                    self.transport,
                    _scwire.reply_frame(epoch, seq, _scwire.OK, sid),
                    crank, tags.PARAM_PUSH_ACK, live=self.live,
                    abort=self._svc_abort(crank, gen),
                )
            span.end("dup" if admitted == DUP else "applied")

    # -- shardctl control plane: directives, migration, beats ----------------

    def _sc_live_abort(self) -> Callable[[], bool]:
        return lambda: not self.live.on

    def _sc_map_listener(self):
        """Perpetual MAP_UPDATE service (controller channel): INSTALL
        adopts a map; RELEASE/ACQUIRE run the live-migration handshake;
        ADOPT restores a dead peer's shard from its checkpoint."""
        while self.live.on:
            raw = yield from aio_recv(
                self.transport, self.controller_rank, tags.MAP_UPDATE,
                live=self.live, abort=self._sc_live_abort(),
            )
            if raw is None:
                return
            kind, sid, peer, smap = _scwire.parse_map_update(bytes(raw))
            if kind == _scwire.RELEASE:
                yield from self._sc_release(sid, peer, smap)
            elif kind == _scwire.ACQUIRE:
                yield from self._sc_acquire(sid, peer, smap)
            elif kind == _scwire.ADOPT:
                yield from self._sc_adopt(sid, peer, smap)
            elif kind == _scwire.RETIRE:
                yield from self._sc_retire(smap)
                return
            else:  # INSTALL / RETIRED broadcasts: adopt the newer map
                self._sc_install_map(smap)

    def _sc_release(self, sid: int, dst: int, new_map: ShardMap):
        """Source side of a live migration: flip to the new map first
        (every later op for the shard drains via NACK_MAP), freeze the
        slot, serve exactly one SHARD_PULL, ship the state, drop it."""
        span = self._spans.op("MIGRATE", peer=dst, side="server",
                              rank=self.rank)
        span.note(shard=sid, direction="out")
        slot = self._slots.get(sid)
        if slot is None:
            self.log.warning(
                "RELEASE for shard %d but this server does not hold it "
                "(raced directive?) — ignoring", sid)
            span.end("aborted")
            return
        self._sc_install_map(new_map)
        slot.frozen = True
        span.mark("freeze")
        deadline = deadline_at(_scmigrate.SC_DEADLINE_S)
        buf = np.zeros(1, np.int64)
        got = yield from aio_recv(self.transport, dst, tags.SHARD_PULL,
                                  live=self.live, out=buf,
                                  deadline=deadline)
        if got is None:
            span.end("aborted")
            return
        span.mark("snapshot")
        msgs = _scmigrate.pack_shard_state(slot)
        span.mark("send")
        for msg in msgs:
            yield from aio_send(self.transport, msg, dst, tags.SHARD_STATE,
                                live=self.live, deadline=deadline)
        del self._slots[sid]
        self._m_sc_owned.set(len(self._slots))
        self._m_sc_out.inc()
        self.log.info("released shard %d to server %d (map v%d)",
                      sid, dst, new_map.version)
        span.end("released")

    def _sc_acquire(self, sid: int, src: int, new_map: ShardMap):
        """Destination side: adopt the map, pull the frozen state, place
        it on this server's backend, echo DONE to the controller."""
        span = self._spans.op("MIGRATE", peer=src, side="server",
                              rank=self.rank)
        span.note(shard=sid, direction="in")
        self._sc_install_map(new_map)
        deadline = deadline_at(_scmigrate.SC_DEADLINE_S)
        span.mark("pull")
        yield from aio_send(self.transport, np.asarray([sid], np.int64),
                            src, tags.SHARD_PULL, live=self.live,
                            deadline=deadline)
        slot = yield from _scmigrate.recv_shard_state(
            self.transport, src, self.live, deadline=deadline)
        if slot is None:
            span.end("aborted")
            return
        span.mark("install")
        slot.param = self._place_param(slot.param)
        if slot.rule_state:
            slot.rule_state = self._place_state(slot.rule_state)
        else:
            slot.rule_state = self._init_state(slot.param)
        self._slots[sid] = slot
        self._m_sc_owned.set(len(self._slots))
        self._m_sc_in.inc()
        span.mark("ack")
        yield from aio_send(
            self.transport,
            _scwire.map_update(_scwire.DONE, sid, self.rank, self.smap),
            self.controller_rank, tags.MAP_UPDATE, live=self.live,
            deadline=deadline)
        self.log.info("acquired shard %d from server %d (map v%d)",
                      sid, src, new_map.version)
        span.end("acquired")

    def _sc_adopt(self, sid: int, dead: int, new_map: ShardMap):
        """Failover: the previous owner is gone — restore the shard from
        its latest checkpoint (shard<id>_latest.npz) and serve it.  Ops
        the dead server applied-and-checkpointed dedup as DUP; ops after
        its last checkpoint are still unacked client-side and re-apply
        exactly once (the checkpoint is the consistency cut, §6.3)."""
        span = self._spans.op("MIGRATE", peer=dead, side="server",
                              rank=self.rank)
        span.note(shard=sid, direction="adopt")
        self._sc_install_map(new_map)
        if not self._ckpt_dir:
            span.end("exhausted")
            raise RuntimeError(
                f"ADOPT shard {sid}: server {self.rank} has no ckpt_dir — "
                "failover needs shard checkpoints")
        span.mark("restore")
        slot = _scmigrate.load_shard_state(self._ckpt_dir, sid)
        slot.param = self._place_param(slot.param)
        if slot.rule_state:
            slot.rule_state = self._place_state(slot.rule_state)
        else:
            slot.rule_state = self._init_state(slot.param)
        self._slots[sid] = slot
        self._m_sc_owned.set(len(self._slots))
        self._m_sc_adopt.inc()
        span.mark("ack")
        yield from aio_send(
            self.transport,
            _scwire.map_update(_scwire.DONE, sid, self.rank, self.smap),
            self.controller_rank, tags.MAP_UPDATE, live=self.live,
            deadline=deadline_at(_scmigrate.SC_DEADLINE_S))
        self.log.warning("adopted shard %d from dead server %d (map v%d)",
                         sid, dead, new_map.version)
        span.end("adopted")

    def _sc_retire(self, new_map: ShardMap):
        """The RETIRE handshake's server side (§9.2): the controller
        drained every shard off this rank before sending the directive,
        so holding any slot here is a protocol violation — fail loud
        rather than silently drop state.  Echo DONE (shard -1) as the
        goodbye receipt, then stop: start() returns normally and the
        process exits 0 — retirement is distinguishable from a crash
        by exit shape *and* by the controller's RETIRED lease state."""
        span = self._spans.op("RETIRE", peer=self.controller_rank,
                              side="server", rank=self.rank)
        self._sc_install_map(new_map)
        if self._slots:
            span.end("exhausted")
            raise RuntimeError(
                f"RETIRE directive while still owning shards "
                f"{sorted(self._slots)} — the controller must drain "
                "before retiring (docs/PROTOCOL.md §9.2)")
        span.mark("ack")
        yield from aio_send(
            self.transport,
            _scwire.map_update(_scwire.DONE, -1, self.rank, self.smap),
            self.controller_rank, tags.MAP_UPDATE, live=self.live,
            deadline=deadline_at(_scmigrate.SC_DEADLINE_S))
        self.retired = True
        self.log.info("retired: drained, goodbye sent (map v%d)",
                      self.smap.version)
        span.end("retired")
        self.live.stop()

    def _admit_listener(self, crank: int):
        """Perpetual late-join listener (§9.6): ``crank`` was *not* in
        the launch-time client set, but is provisioned rank space that
        may announce itself any time mid-run — INIT v3/v4 is the whole
        admission handshake, exactly like a rejoin except the first
        arrival also registers the rank with the lease/stop machinery.
        Subsequent INITs from the same rank are ordinary rejoins."""
        first = True
        while self.live.on:
            payload = yield from aio_recv(self.transport, crank, tags.INIT,
                                          live=self.live,
                                          abort=self._sc_live_abort())
            if payload is None:
                return
            if first:
                # Register before negotiating: a loud negotiation
                # failure should name a known member, and the stop
                # protocol must count this rank from its first frame.
                self.cranks.append(crank)
                self.leases.admit(crank)
                self._gen.setdefault(crank, 0)
                self._svc_live.setdefault(crank, 0)
            codec = self._negotiate(crank, payload)
            if first:
                first = False
                self._m_admits.inc()
                self.log.info("admitted late client %d (epoch %d)",
                              crank, self.leases.epoch(crank))
            else:
                self._m_rejoins.inc()
            self._gen[crank] += 1
            self.leases.rejoin(crank, self.leases.epoch(crank))
            self.leases.arm(crank, self.leases.epoch(crank),
                            heartbeats=self._hb.get(crank, False))
            self._alloc_client(crank, codec)
            while self._svc_live[crank] > 0:
                yield EXEC
            self._spawn_services(crank)

    def _check_preemption(self) -> None:
        """Checkpoint-on-notice (§9.3), called from the checkpoint
        loop's safe point (between scheduler passes — no grad is
        mid-apply).  One shot: stamped atomic publish of every owned
        shard, then a PREEMPT report so the controller can decide
        whether the grace window is worth a drain.  The handler itself
        only set a flag (mtlint MT-P204); everything here runs on the
        serving thread."""
        notice = self._preempt
        if notice is None or not notice.poll() or self._preempt_handled:
            return
        self._preempt_handled = True
        self._m_preempt.inc()
        self.log.warning(
            "preemption notice: %.1fs grace — checkpointing %s now",
            notice.grace_s,
            f"shards {sorted(self._slots)}" if self._sc else "shard")
        if self._ckpt_dir and (self.param is not None or self._slots):
            self.save_state(self._ckpt_dir)
            self._m_ckpts.inc()
        self._flight.record("preemption", rank=self.rank,
                            grace_s=notice.grace_s)
        self._flight.dump("preemption", rank=self.rank)
        if self._sc and self.controller_rank is not None \
                and self.smap is not None:
            self.sched.spawn(self._send_preempt_notice(notice.grace_ms),
                             name="preempt_notice")

    def _send_preempt_notice(self, grace_ms: int):
        try:
            yield from aio_send(
                self.transport,
                _scwire.map_update(_scwire.PREEMPT, grace_ms, self.rank,
                                   self.smap),
                self.controller_rank, tags.MAP_UPDATE, live=self.live,
                deadline=deadline_at(_scmigrate.SC_DEADLINE_S))
        except DeadlineExceeded:
            pass  # controller gone too; the checkpoint already landed

    def _sc_beat(self):
        """Beat to the controller: liveness plus the per-shard load
        report (ops and busy-seconds deltas, read from this server's obs
        instruments) the rebalance policy consumes."""
        interval = self.ft.heartbeat_s if self.ft.heartbeat_s > 0 else 0.1
        while self.live.on:
            if not (yield from aio_sleep(interval, live=self.live)):
                return
            self._sc_beat_seq += 1
            words = [self.ft.epoch, self._sc_beat_seq, len(self._slots)]
            for sid in sorted(self._slots):
                ops = int(self._sc_ops_counter(sid).value)
                busy = float(self.metrics.histogram(
                    "mpit_shardctl_shard_busy_seconds",
                    rank=self.rank, shard=sid).total)
                last_ops, last_busy = self._sc_last_report.get(sid, (0, 0.0))
                words += [sid, ops - last_ops,
                          int((busy - last_busy) * 1e6)]
                self._sc_last_report[sid] = (ops, busy)
            try:
                yield from aio_send(
                    self.transport, np.asarray(words, np.int64),
                    self.controller_rank, tags.HEARTBEAT, live=self.live,
                    deadline=deadline_at(4 * interval))
            except DeadlineExceeded:
                pass  # best-effort; the next beat tries again

    def _recv_heartbeat(self, crank: int, gen: int = 0):
        """Loop: consume HEARTBEAT beacons, renew the client's lease
        (current-epoch beats only — a dead incarnation's leftovers must
        not keep its successor's lease alive).  Timing pairs get each
        beat echoed back (HEARTBEAT_ECHO with the §6.7 tail), so the
        client's clock-offset estimator refreshes from the heartbeat
        stream even while no op is in flight."""
        buf = self._hb_buf.get(crank)
        if buf is None:
            return
        timing = self._timing.get(crank, False)
        echo = np.zeros(ACK_TIMING_WORDS, np.int64) if timing else None
        while self.live.on:
            got = yield from aio_recv(
                self.transport, crank, tags.HEARTBEAT, live=self.live,
                out=buf, abort=self._svc_abort(crank, gen),
            )
            if got is None:
                return
            t_recv = obs_clock.wall_us() if timing else 0
            self._m_hb_seen.inc()
            self.leases.renew(crank, int(buf[0]))
            if timing:
                echo[0], echo[1] = buf[0], buf[1]
                echo[2], echo[3] = buf[2], t_recv
                echo[4] = obs_clock.wall_us()
                yield from aio_send(
                    self.transport, echo, crank, tags.HEARTBEAT_ECHO,
                    live=self.live, abort=self._svc_abort(crank, gen),
                )

    # -- device exchange service (mpit_tpu.dplane, docs/DEVICE.md §4) --------

    def _dp_op_counter(self, op: str):
        c = self._m_dp_ops.get(op)
        if c is None:
            c = self.metrics.counter("mpit_dplane_device_ops_total",
                                     rank=self.rank, op=op)
            self._m_dp_ops[op] = c
        return c

    def _dplane_service(self):
        """Drain the in-process device-exchange queue: tickets execute
        between scheduler passes on this server's own thread, so device
        ops serialize with wire ops under the same single-writer
        discipline — serve-latest-committed reads stay untorn, and a
        lockstep gang applies in the identical cross-client order on
        either path."""
        plane = self._plane
        try:
            while self.live.on:
                ticket = plane.pop()
                if ticket is None:
                    # Idle pacing, not a busy scan (the IDLE_USEC lesson
                    # from the reader dispatcher).
                    if not (yield from aio_sleep(0.0005, live=self.live)):
                        return
                    continue
                try:
                    self._dplane_execute(ticket)
                except BaseException as exc:
                    # A failed op fails ITS client loudly; the service
                    # (and every other client) keeps running.
                    ticket.error = exc
                finally:
                    ticket.event.set()
                yield EXEC
        finally:
            plane.close("server service exited")

    def _dplane_execute(self, ticket) -> None:
        slot = self._hbm
        if slot is None:
            raise RuntimeError(
                f"device {ticket.kind} op from client {ticket.crank} "
                "before the shard exists (INIT/seed not complete, or a "
                "shardctl gang — the device exchange serves the static "
                "cut only; see docs/DEVICE.md §3)")
        kind = ticket.kind
        name = {"grad": "GRAD", "push": "PARAM_PUSH"}.get(kind, "PARAM")
        span = self._spans.op(name, peer=ticket.crank, side="server",
                              rank=self.rank)
        span.note(dplane=1)
        if kind == "grad":
            span.mark("apply")
            slot.apply_grad(ticket.payload)
            self.param, self.rule_state = slot.param, slot.rule_state
            self._committed()
            self._m_grads.inc()
            self._dp_op_counter("grad").inc()
            span.end("applied")
        elif kind == "push":
            span.mark("apply")
            slot.seed(ticket.payload)
            self.param = slot.param
            self._committed()
            self._dp_op_counter("push").inc()
            span.end("applied")
        elif kind == "pull":
            span.mark("snapshot")
            ticket.result = slot.snapshot_host()
            self._m_served.inc()
            self._dp_op_counter("pull").inc()
            span.end("served")
        elif kind == "pull_dev":
            span.mark("snapshot")
            ticket.result = slot.pull_device()
            self._m_served.inc()
            self._dp_op_counter("pull_dev").inc()
            span.end("served")
        else:
            span.end("aborted")
            raise ValueError(f"unknown device op kind {kind!r}")

    def _recv_stop(self, crank: int, gen: int = 0):
        """Await the stop signal; all clients terminal (stopped or
        evicted) => shut down I/O (reference :115-129)."""
        got = yield from aio_recv(self.transport, crank, tags.STOP,
                                  live=self.live,
                                  abort=self._svc_abort(crank, gen))
        if got is None:
            return
        self.leases.stop(crank)
        if crank in self._reader_set:
            self._update_reader_gauge()
        if self.leases.all_done():
            self.live.stop()

    def _lease_reaper(self):
        """Periodic scan: evict ACTIVE clients whose lease lapsed.  The
        evicted client's services abort, its staging is released, and the
        stop condition re-checks — one dead worker no longer wedges the
        gang (the MXNET-MPI elasticity argument, PAPERS.md)."""
        interval = max(min(self.ft.lease_ttl_s / 4.0, 1.0), 0.005)
        while self.live.on:
            if not (yield from aio_sleep(interval, live=self.live)):
                return
            for crank in self.leases.expired():
                self.log.warning(
                    "evicting client %d: lease expired after %.3fs without "
                    "a heartbeat (pending ops dropped, staging released; "
                    "it may rejoin with a bumped epoch)",
                    crank, self.ft.lease_ttl_s,
                )
                self.leases.evict(crank)
                self._m_evictions.inc()
                self._gen[crank] += 1  # stale loops abort at next poll
                self._release_client(crank)
                if crank in self._reader_set:
                    self._update_reader_gauge()
                # Postmortem: the gang just lost a member — dump the
                # recent-event ring + live task table (obs/flight.py;
                # no-op when obs is disabled).
                self._flight.record("eviction", client=crank,
                                    rank=self.rank)
                self._flight.dump(
                    "eviction", client=crank,
                    tasks=[(t.name, t.state) for t in list(self.sched.queue)])
            if self.leases.all_done():
                self.live.stop()
                return

    # -- checkpoint / resume (beyond-reference: SURVEY §5 notes server
    # state is never checkpointed there; here Adam/RMSProp moments —
    # and now the FT dedup table + per-client negotiation — survive a
    # restart) --------------------------------------------------------------

    def _client_meta(self) -> Dict[str, Dict[str, Any]]:
        """Per-client negotiated state for the checkpoint: enough for a
        restarted server to serve retried ops without fresh INITs."""
        return {
            str(c): {
                "codec": self._codecs[c].name,
                "framed": self._framed.get(c, False),
                "hb": self._hb.get(c, False),
                "stale": self._stale_track.get(c, False),
                "timing": self._timing.get(c, False),
                "chunk": self._chunk.get(c, 0),
                "epoch": self.leases.epoch(c),
            }
            for c in self._codecs
            if c not in self._reader_set
            # Readers are excluded on purpose: they re-attach
            # through the perpetual listeners, so a restarted server
            # need not carry their negotiation.
        }

    def save_state(self, directory) -> "str":
        """Checkpoint this server's shard param + rule state (+ the FT
        dedup table and client negotiation map).  Call from the owning
        thread while no grad is mid-apply (e.g. after start() returns, or
        from a service hook between applies).  Published via the stamped
        atomic-publish path: versioned history plus a ``_latest`` alias a
        concurrent loader can always trust."""
        from mpit_tpu.utils.checkpoint import save_server_state

        if self._sc:
            # Shard-oriented checkpoints: one shard<id>_latest.npz per
            # owned slot, so failover ADOPTs by shard id regardless of
            # which server wrote the file (shardctl/migrate.py).
            if not self._slots:
                raise RuntimeError(
                    "server owns no shards to checkpoint (init not run, "
                    "or every slot migrated away)")
            path = ""
            for _sid, slot in sorted(self._slots.items()):
                path = str(_scmigrate.save_shard_state(
                    directory, slot, self.rank))
            return path
        if self.param is None:
            raise RuntimeError("server holds no shard yet (init not run)")
        if self._snap_host is not None and self._snap_host[0] == self._snap_version:
            host = self._snap_host[1]  # reuse the snapshot cache's d2h copy
        elif self._hbm is not None:
            host = self._hbm.snapshot_host()
            self._snap_host = (self._snap_version, host)
        else:
            host = np.asarray(self.param)
            self._snap_host = (self._snap_version, host)
            self._m_snap_copies.inc()
        return str(save_server_state(
            directory, self.rank, self.offset, self.size,
            host,
            {k: np.asarray(v) for k, v in (self.rule_state or {}).items()},
            meta={
                "grads_applied": self.grads_applied,
                "snap_version": self._snap_version,
                "dedup": self.dedup.state(),
                # In-flight chunk admissions for the GRAD immediate-
                # apply path ONLY: those chunks are already folded into
                # the param bytes above, so set + state cut together.
                # PARAM_PUSH partials stay out — their assembly staging
                # dies with the process (ft/dedup.py partial_state).
                "dedup_chunks": self.dedup.partial_state(
                    tags={tags.GRAD}),
                "clients": self._client_meta(),
            },
        ))

    def restore_state(self, path) -> None:
        """Load a shard checkpoint before start().  A restored server
        skips the client-seeding phase — start the clients with
        ``seed_servers=False`` (the resume flow; reference resume instead
        reloads params on the client and reseeds, plaunch.lua:62).  FT
        checkpoints also restore the dedup table and each client's
        negotiated codec/framing, so a *restarted server* rejoins a live
        gang: clients keep retrying into the new process and their
        already-applied ops dedup instead of double-counting."""
        from mpit_tpu.utils.checkpoint import load_server_state

        if self.param is not None or self.offset != -1:
            raise RuntimeError("restore_state must run before start()")
        offset, size, param, state, meta = load_server_state(path)
        self.offset, self.size = offset, size
        self.grads_applied = int(meta.get("grads_applied", 0))
        self._snap_version = int(meta.get("snap_version", 0))
        self.dedup.restore(meta.get("dedup", {}))
        self.dedup.restore_partial(meta.get("dedup_chunks", {}))
        if self._dp_cfg is not None:
            self._hbm = _dphbm.HbmSlot(size, self.rule, self.dtype,
                                       config=self._dp_cfg, rank=self.rank)
            self._hbm.seed(param)
            if state:
                self._hbm.rule_state = self._place_state(state)
            # Version continuity across the restart (the staleness
            # stamps ride it): resume the checkpointed stream, +1 for
            # the seed commit — same arithmetic as the legacy path.
            self._hbm.version = self._snap_version + 1
            self.param = self._hbm.param
            self.rule_state = self._hbm.rule_state
        else:
            with self._dev_ctx():
                # device_copy on the restore path: checkpointed arrays
                # are numpy-backed, and a restored chunked client's
                # donated applies must never consume numpy-owned
                # memory (dplane.hbm.device_copy).  Cold path — one
                # extra copy per restore.
                self.param = _dphbm.device_copy(jnp.asarray(param))
                if state:
                    self.rule_state = {
                        k: _dphbm.device_copy(jnp.asarray(v))
                        for k, v in state.items()}
                else:  # stateless rule (plain add) or legacy checkpoint
                    self.rule_state = self._init_state(self.param)
        for crank_s, info in (meta.get("clients") or {}).items():
            crank = int(crank_s)
            if crank not in self.cranks:
                continue
            self._framed[crank] = bool(info.get("framed", False))
            self._hb[crank] = bool(info.get("hb", False))
            self._stale_track[crank] = bool(info.get("stale", False))
            self._timing[crank] = bool(info.get("timing", False))
            self._chunk[crank] = int(info.get("chunk", 0))
            self.leases.arm(crank, int(info.get("epoch", 0)),
                            heartbeats=self._hb[crank])
            self._alloc_client(crank, codec_mod.get(info.get("codec", "none")))
            self._restored_clients.add(crank)
        self._committed()
        self._restored = True

    def _serve_with_checkpoints(self) -> None:
        """Drive the service queue like ``Scheduler.wait`` while writing
        the shard checkpoint every ``ckpt_interval`` seconds and once
        more at stop.  Safe point: a ping runs one generator step, and a
        grad apply commits within one step — between pings the shard is
        never torn."""
        next_save = time.monotonic() + self._ckpt_interval
        while self.sched.queue:
            self.sched.ping_pass()
            self._check_preemption()
            if time.monotonic() >= next_save:
                # A joiner that has not acquired a shard yet (or a
                # fully-drained rank awaiting RETIRE) has nothing to cut.
                if self.param is not None or self._slots:
                    self.save_state(self._ckpt_dir)
                    self._m_ckpts.inc()
                next_save = time.monotonic() + self._ckpt_interval
        if self.param is not None or self._slots:
            self.save_state(self._ckpt_dir)  # final state at stop
            self._m_ckpts.inc()
        if self.sched.errors:
            raise self.sched.errors.pop(0)

    # -- orchestration (reference pserver.lua:131-157) ----------------------

    def _spawn_services(self, crank: int) -> None:
        """Phase-3 perpetual services for one client (also the rejoin
        respawn path — hence per-generation naming)."""
        gen = self._gen[crank]
        self.sched.spawn(self._svc(crank, gen, self._recv_stop),
                         name=f"recv_stop:{crank}.g{gen}")
        if self._sc:
            self.sched.spawn(self._svc(crank, gen, self._sc_recv_grad),
                             name=f"recv_grad:{crank}.g{gen}")
            self.sched.spawn(self._svc(crank, gen, self._sc_send_param),
                             name=f"send_param:{crank}.g{gen}")
            self.sched.spawn(self._svc(crank, gen, self._sc_recv_push),
                             name=f"recv_param:{crank}.g{gen}")
            if self._hb.get(crank):
                self.sched.spawn(self._svc(crank, gen, self._recv_heartbeat),
                                 name=f"recv_heartbeat:{crank}.g{gen}")
            return
        self.sched.spawn(self._svc(crank, gen, self._recv_grad),
                         name=f"recv_grad:{crank}.g{gen}")
        self.sched.spawn(self._svc(crank, gen, self._send_param),
                         name=f"send_param:{crank}.g{gen}")
        if self._hb.get(crank):
            self.sched.spawn(self._svc(crank, gen, self._recv_heartbeat),
                             name=f"recv_heartbeat:{crank}.g{gen}")
        if self.single_mode:
            self.sched.spawn(self._svc(crank, gen, self._recv_param,
                                       once=False),
                             name=f"recv_param:{crank}.g{gen}")
        elif self._framed.get(crank):
            # Framed clients may retry a push whose first ack was lost;
            # someone must keep absorbing the duplicates and re-acking
            # after the one-shot seed service exits.  (FRESH post-seed
            # pushes only occur in the restored-server resume flow.)
            self.sched.spawn(
                self._svc(crank, gen, self._recv_param, once=False,
                          warn_unexpected=self._restored),
                name=f"recv_param:{crank}.g{gen}")

    def _drive(self) -> None:
        """Run the service queue to completion through whichever loop
        this server's posture needs (checkpoints and/or preemption
        polling; plain wait otherwise)."""
        if self._ckpt_dir:
            self._serve_with_checkpoints()
        elif self._preempt is not None:
            while self.sched.queue:
                self.sched.ping_pass()
                self._check_preemption()
            if self.sched.errors:
                raise self.sched.errors.pop(0)
        else:
            self.sched.wait()

    def start(self) -> None:
        """Run the server to completion (returns after the stop protocol).
        With a published device plane, the plane is offered for the
        server's whole lifetime and torn down loudly — a client blocked
        on a dead server's plane raises, never hangs."""
        publish = (self._dp_cfg is not None and self._dp_cfg.publish
                   and not self._sc_join)
        if not publish:
            self._run()
            return
        self._plane = _dpexchange.DevicePlane(
            self.rank, _dpexchange.backend_fingerprint())
        _dpexchange.publish(self.rank, self._plane, self._dp_cfg.namespace)
        try:
            self._run()
        finally:
            _dpexchange.withdraw(self.rank, self._dp_cfg.namespace)
            self._plane.close("server stopped")

    def _run(self) -> None:
        if self._sc_join:
            # Joiner (§9.1): spawned into a live gang by the controller.
            # No phase-1 rendezvous — nobody owes us an INIT.  Every
            # client gets a stop listener now (STOPs fan out to every
            # owner at gang end) and an admission-style INIT listener
            # (clients greet lazily before their first op to us); shards
            # arrive via ACQUIRE, beats start immediately so the
            # controller's scale_up sees the lease arm.
            if self.controller_rank is None:
                raise ValueError("a joiner server needs controller_rank — "
                                 "it exists only under a control plane")
            for crank in self.cranks:
                self.sched.spawn(self._svc(crank, 0, self._recv_stop),
                                 name=f"recv_stop:{crank}.g0")
                self.sched.spawn(self._init_listener(crank),
                                 name=f"init_listener:{crank}")
            for crank in self.admit_ranks:
                self.sched.spawn(self._admit_listener(crank),
                                 name=f"admit_listener:{crank}")
            if self.ft.lease_ttl_s > 0:
                self.sched.spawn(self._lease_reaper(), name="lease_reaper")
            self.sched.spawn(self._sc_map_listener(), name="sc_map_listener")
            self.sched.spawn(self._sc_beat(), name="sc_beat")
            self._drive()
            self.log.debug("stopped: %s",
                           self.metrics.format_summary(prefix="mpit_"))
            return
        # Phase 1: shard announcements from every client (skipped for
        # clients restored from an FT checkpoint — their negotiation is
        # already in hand and no fresh INIT is coming).
        for crank in self.cranks:
            if crank not in self._restored_clients:
                self.sched.spawn(self._svc(crank, 0, self._recv_init),
                                 name=f"recv_init:{crank}")
        self.sched.wait()
        # Phase 2: parameter seeding from the first client only
        # (init once & only once, reference README:64-67) — skipped on
        # resume, where the checkpoint already seeded the shard, and in
        # shardctl mode, where seeding arrives as ordinary dedup'd
        # PARAM_PUSH ops into the perpetual per-slot push service.
        seeder = self.cranks[0]
        if not self._restored and not self._sc:
            self.sched.spawn(self._svc(seeder, 0, self._recv_param, once=True),
                             name="seed_param")
            self.sched.wait()
        # Phase 3: perpetual services per client + stop counters.
        if self._restored and not self.single_mode and not self._framed.get(seeder):
            # A resume client wired with seed_servers=True would otherwise
            # block forever on its unconsumed push — accept it (client is
            # authoritative for params, as in the reference's -loadmodel
            # reseed, plaunch.lua:62) and warn loudly.  Framed clients get
            # the perpetual absorb service from _spawn_services instead.
            self.sched.spawn(
                self._svc(seeder, 0, self._recv_param, once=True,
                          warn_unexpected=True),
                name="unexpected_seed",
            )
        for crank in self.cranks:
            self._spawn_services(crank)
        if self._plane is not None:
            # Device exchange (mpit_tpu.dplane): ONE service task drains
            # the in-process ticket queue for every same-backend client.
            self.sched.spawn(self._dplane_service(), name="dplane_service")
        if self.readers:
            # Serving tier: ONE dispatcher task for every reader —
            # readers attach lazily, any time mid-run, and the
            # scheduler's task count stays O(in-flight replies).
            self.sched.spawn(self._reader_dispatcher(),
                             name="reader_dispatcher")
        if self.ft.server_rejoin:
            for crank in self.cranks:
                self.sched.spawn(self._init_listener(crank),
                                 name=f"init_listener:{crank}")
        for crank in self.admit_ranks:
            self.sched.spawn(self._admit_listener(crank),
                             name=f"admit_listener:{crank}")
        if self.ft.lease_ttl_s > 0:
            self.sched.spawn(self._lease_reaper(), name="lease_reaper")
        if self._sc and self.controller_rank is not None:
            self.sched.spawn(self._sc_map_listener(), name="sc_map_listener")
            self.sched.spawn(self._sc_beat(), name="sc_beat")
        self._drive()
        # End-of-run summary rendered straight from the registry — every
        # number here (and any new instrument a layer adds) shows up
        # without touching this line.
        self.log.debug("stopped: %s",
                       self.metrics.format_summary(prefix="mpit_"))
