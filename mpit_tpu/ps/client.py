"""ParamClient — shards the flat parameter vector across servers and
drives asynchronous shard transfers.

Rebuild of reference asyncsgd/pclient.lua.  The client registers two host
buffers (``param``, ``grad``) whose per-server contiguous slices are the
transfer units (numpy views = the reference's zero-copy storage-offset
views, pclient.lua:50-52).  Public surface mirrors pclient.lua:84-179:
``start``, ``async_send_grad``, ``async_recv_param``, ``async_send_param``,
``ping``, ``wait``, ``reset``, ``stop``.

The comm-aware optimizers (mpit_tpu.optim.downpour/easgd/shells) drive this
class through the ParamClientAPI protocol; device arrays stay in the
optimizer layer — the client only ever touches the registered host mirrors.

Wire codecs (beyond-reference — the EQuARX direction, PAPERS.md): the
client announces a codec in its INIT (``MPIT_PS_CODEC`` or the ``codec``
argument; mpit_tpu/comm/codec.py) and every GRAD/PARAM/PARAM_PUSH frame
to/from that server travels in that format.  For the lossy ``int8`` codec
the client holds one error-feedback residual per shard: the gradient
quantization error is added back into the next shipped gradient instead
of being lost, so DOWNPOUR/EASGD converge as if uncompressed (the shells
in mpit_tpu.optim need no changes — they keep writing fp32 into
``grad``; encode happens here at ship time).  ``codec='none'`` keeps
today's zero-copy slice sends byte-for-byte.

Fault tolerance (mpit_tpu.ft): an :class:`FTConfig` adds, each
independently opt-in,

- **heartbeats** — 16-byte HEARTBEAT beacons to every server, emitted
  opportunistically from ``ping``/``wait`` (the trainer's comm-overlap
  cadence) so liveness costs no dedicated thread;
- **op deadlines + retry** — every op encodes its frame *once* into a
  staged buffer with an int64 ``[epoch, seq]`` header (ft/wire.py) and
  resends those exact bytes on timeout under capped backoff.  Resending
  the staged frame — never re-encoding — is what keeps the int8
  error-feedback residual exact across retries: the residual was folded
  at the single encode, so a retry cannot double-count it.  Acks and
  PARAM replies echo the seq; stale echoes from earlier attempts are
  consumed and discarded, never mistaken for the awaited one.  An op
  that exhausts its attempts raises :class:`RetryExhausted` — loud
  failure, never a hang.

The header framing costs one staging copy per identity-codec frame, so
it is only active when deadlines are (``FTConfig.framed``); a default
FTConfig keeps the pre-FT zero-copy wire byte-for-byte.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Callable, Deque, Dict, Generator, List, Optional, Tuple

import numpy as np

from mpit_tpu.aio import (
    EXEC,
    DeadlineExceeded,
    LiveFlag,
    Scheduler,
    TaskError,
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
    FLAG_CHUNKED,
    FLAG_FRAMED,
    FLAG_HEARTBEAT,
    FLAG_STALENESS,
    FLAG_TIMING,
    HDR_BYTES,
    FTConfig,
    RetryExhausted,
    RetryPolicy,
    chunk_elems_for,
    chunk_hdr_bytes,
    chunk_reply_hdr_bytes,
    chunk_spans,
    chunk_stride,
    hdr_bytes,
    header_frame,
    init_v3,
    init_v5,
    with_plain_tail,
    pack_chunk_header,
    pack_header,
    pack_tx_stamp,
    pack_version,
    reply_hdr_bytes,
    timed_frame,
    unpack_chunk_reply,
    unpack_header,
    unpack_reply_stamps,
    unpack_version,
)
from mpit_tpu.obs import (
    NULL_SPAN,
    get_flight,
    get_recorder,
    obs_enabled,
    register_status_provider,
    registry_or_local,
)
from mpit_tpu.obs import clock as obs_clock
from mpit_tpu.ps import tags
from mpit_tpu.ps.sharding import Shard
from mpit_tpu.shardctl import shardmap as _shardmap
from mpit_tpu.shardctl import wire as _scwire
from mpit_tpu.utils.logging import get_logger

#: What a back-off sleep of the client's scheduler inside ``exchange`` was
#: a wait for (:meth:`ParamClient._why_asleep`): the ``round`` span's
#: ``sleep_<reason>_ms``, which sum to its ``sched_sleep_ms``.
SLEEP_REASONS = ("staging", "apply", "drain", "pull")


class ParamClient:
    def __init__(
        self,
        rank: int,
        server_ranks: list[int],
        transport: Transport,
        scheduler: Optional[Scheduler] = None,
        seed_servers: bool = False,
        codec: Optional[str] = None,
        ft: Optional[FTConfig] = None,
        shard_map: "Optional[_shardmap.ShardMap]" = None,
        shardctl: bool = False,
        controller_rank: Optional[int] = None,
        sc_shards_per_server: int = 1,
        layout: "Optional[List[Shard]]" = None,
    ):
        self.rank = rank
        self.sranks = list(server_ranks)
        self.transport = transport
        self.sched = scheduler or Scheduler()
        self.seed_servers = seed_servers  # this is the first client
        self.codec = codec_mod.get(codec)  # None/'' -> $MPIT_PS_CODEC
        self.ft = ft if ft is not None else FTConfig.from_env()
        # shardctl (mpit_tpu.shardctl): ops address *shards*, not
        # servers — the versioned map routes them, a NACK_MAP reply
        # re-routes them, and the controller's MAP_UPDATE broadcasts are
        # polled opportunistically.  Requires the FT framed machinery:
        # re-routing is retry, and at-most-once across owners is the
        # transferred dedup state.
        self._sc = bool(shardctl or shard_map is not None)
        self.smap = shard_map
        # Static weighted layout (mpit_tpu.lm flagship path): an explicit
        # contiguous cut — one Shard per server in rank order — that
        # replaces the equal-split default at start() WITHOUT turning on
        # shardctl.  The servers adopt whatever cut the first INIT
        # announces, so an uneven layout is purely a client-side choice;
        # crucially ``_sc`` stays False, so chunked streaming, staleness,
        # timing and the §13 agg tree all still negotiate on.  Every
        # client and reader of one gang must pass the identical layout
        # (servers reject mismatched re-announcements).
        #: the vector's plain ranges, which every INIT then ends in
        #: (:meth:`announce_plain`)
        self._plain: Tuple[Tuple[int, int], ...] = ()
        self._layout = list(layout) if layout is not None else None
        if self._layout is not None:
            if self._sc:
                raise ValueError(
                    "layout= is the static weighted cut; it cannot combine "
                    "with shardctl/shard_map (which own placement already)"
                )
            if len(self._layout) != len(self.sranks):
                raise ValueError(
                    f"layout has {len(self._layout)} shards for "
                    f"{len(self.sranks)} servers (need exactly one each)"
                )
        self.controller_rank = controller_rank
        # Over-partitioning (§9.1): cut the vector into k shards per
        # launch-time server so elasticity has units to move — a gang
        # that cut one shard per server can widen only by whole-shard
        # handoff, never by sharing.
        self._sc_cut = max(int(sc_shards_per_server), 1)
        #: servers this incarnation has announced itself to (INIT); a
        #: map may route shards to ranks that joined after launch —
        #: first contact greets them (the lazy INIT v4, §9.1).
        self._sc_greeted: set = set()
        self._sc_flags = 0
        #: ranks that left on purpose (RETIRED broadcasts): dropped
        #: from heartbeat and STOP fan-out — a goodbye needs no goodbye.
        self._sc_retired: set = set()
        if self._sc and self.ft.op_deadline_s <= 0:
            raise ValueError(
                "shardctl needs op deadlines + retry (FTConfig."
                "op_deadline_s > 0): map re-routing rides the retry path"
            )
        self._retry = RetryPolicy(self.ft, key=rank)
        self.live = LiveFlag()
        self.log = get_logger("pclient", rank)
        self.param: Optional[np.ndarray] = None
        self.grad: Optional[np.ndarray] = None
        self.shards: List[Shard] = []
        self._started = False
        # Staleness telemetry (mpit_tpu.obs): with FLAG_STALENESS
        # negotiated, PARAM replies carry the served snapshot version and
        # the next GRAD echoes the version this client computed against —
        # the server's mpit_ps_grad_staleness histogram measures the gap.
        # Rides the framed wire (the header grows 16 -> 24 bytes);
        # shardctl's shard-addressed header has no version slot yet, so
        # the flag negotiates off there (docs/PROTOCOL.md §6.6).
        # Pipelined streaming (PROTOCOL.md §12): with FLAG_CHUNKED
        # negotiated, GRAD/PARAM/PARAM_PUSH bodies ship as K independent
        # chunk frames so encode, wire and apply overlap.  Rides the
        # framed wire; off under shardctl (shard ops re-route — a chunk
        # stream split across owners has no single admission point).
        self._chunked = self.ft.chunked and not self._sc
        # Staleness negotiates off under chunking: the chunked PARAM
        # reply header carries the version in its own word (§12.3), and
        # the 32-byte chunk header has no basis-echo slot.
        self._stale = (self.ft.stale_track and not self._sc
                       and not self._chunked)
        # Causal-timing telemetry (obs/clock, obs/causal): with
        # FLAG_TIMING negotiated, data frames carry a wall-µs send stamp
        # and every ack/reply a [t_tx_echo, t_recv, t_ack] tail — the
        # four NTP marks that feed the per-server clock-offset estimator
        # below.  Rides the framed wire like staleness; off under
        # shardctl (the 32-byte shard header has no stamp slot, §6.7).
        self._timing = self.ft.timing_track and not self._sc
        #: per-server param version this client last read (the basis the
        #: next gradient is computed against); 0 until the first read.
        self._basis: Dict[int, int] = {}
        # Per-server codec state: encode/decode staging sized to the wire
        # format (plus the FT header when framed), plus the int8
        # error-feedback residual (grad path only).  Data frames and
        # PARAM replies size their headers independently — the timing
        # tail makes a reply header wider than a data-frame header.
        self._hdr = (hdr_bytes(self._stale, self._timing)
                     if self.ft.framed else 0)
        self._hdr_rx = (reply_hdr_bytes(self._stale, self._timing)
                        if self.ft.framed else 0)
        # Chunked header sizes + the per-server chunk plan (built at
        # start(), when the dtype is known): spans [(lo, hi)], uniform
        # frame strides — the last chunk's frame is padded to the full
        # stride so both sides receive into fixed-size staging (§12.2).
        self._chdr = chunk_hdr_bytes(self._timing)
        self._chdr_rx = chunk_reply_hdr_bytes(self._timing)
        self._chunk_elems = 0
        self._chunk_spans: Dict[int, list] = {}
        self._chunk_stride: Dict[int, int] = {}
        self._chunk_stride_rx: Dict[int, int] = {}
        self._grad_wire: Dict[int, np.ndarray] = {}
        self._param_wire: Dict[int, np.ndarray] = {}
        self._param_rx: Dict[int, np.ndarray] = {}
        self._residual: Dict[int, np.ndarray] = {}
        self._ack_buf: Dict[int, np.ndarray] = {}
        #: per-server clock-offset estimator (fed by FLAG_TIMING tails;
        #: registered so trace exports / flight dumps embed the state).
        self._clock = obs_clock.ClockEstimator()
        obs_clock.register(f"client{rank}", self._clock)
        self._m_clock: Dict[int, object] = {}
        #: per-(server, tag) op sequence numbers (FT framing identity)
        self._seq: Dict[Tuple[int, int], int] = {}
        self._hb_last = 0.0
        self._hb_seq = 0
        # Observability (mpit_tpu.obs): protocol counters live in a real
        # registry always (they are load-bearing results — the global
        # one when obs is enabled, a private one otherwise), and every
        # PS op records a span through the recorder (the null recorder
        # when disabled — no clock reads, no allocation).
        self.metrics = registry_or_local()
        self._spans = get_recorder()
        self._m_retries = self.metrics.counter(
            "mpit_ft_retries_total", rank=rank)
        self._m_backoff = self.metrics.counter(
            "mpit_ft_backoff_seconds_total", rank=rank)
        self._m_hb = self.metrics.counter(
            "mpit_ft_heartbeats_sent_total", rank=rank)
        self._m_nacks = self.metrics.counter(
            "mpit_shardctl_nacks_seen_total", rank=rank)
        self._m_reroutes = self.metrics.counter(
            "mpit_shardctl_reroutes_total", rank=rank)
        self._m_mapver = self.metrics.gauge(
            "mpit_shardctl_map_version", rank=rank)
        # Flight recorder + live introspection (obs/flight, obs/statusd):
        # the retry-exhaustion paths dump the recent-event ring so a
        # failed op leaves a postmortem; the status provider feeds the
        # /status endpoint when one is serving.  Both are null/no-op when
        # obs is disabled.
        self._flight = get_flight()
        if obs_enabled():
            register_status_provider(f"client{rank}", self._status_section)
        # shardctl per-shard state: encode staging + residual keyed by
        # shard_id (stable across migrations — placement moves, the cut
        # never does), per-(shard, tag) seq streams, one global FIFO op
        # pump (ops to different owners of one map serialize, so the
        # shared reply channels never interleave two ops' echoes).
        self._sc_wire: Dict[int, np.ndarray] = {}
        self._sc_residual: Dict[int, np.ndarray] = {}
        self._sc_seq: Dict[Tuple[int, int], int] = {}
        self._scq: Deque[Tuple[Generator, str]] = deque()
        self._sc_pump_live = False
        self._sc_pump_task: Optional[object] = None
        # Per-server FIFO op chains: ops addressed to the same server run in
        # issue order (a send_grad's ack completes before a later param
        # request is sent), while different servers stay fully concurrent.
        # Strictly stronger than the reference (which relies on coroutine
        # spawn order for freshness, pclient.lua:84-109) — this removes the
        # stale-own-write race without giving up cross-server overlap.
        self._opq: Dict[int, Deque[Tuple[Generator, str]]] = {}
        self._pump_live: Dict[int, bool] = {}
        self._pump_task: Dict[int, Optional[object]] = {}
        # The streamed round's per-shard gate and sink (stream_shards):
        # None, and no instruction beyond the test for it, unless a
        # shell installed them.
        self._staged: Optional[Callable[[Shard], int]] = None
        self._landed: Optional[Callable[[Shard, int], None]] = None
        # Where a followed shard's GRAD send gets its pieces
        # (stream_pieces): None unless a shell installed it.
        self._pieces: Optional[Callable[[Shard], Optional[Callable]]] = None
        # While recording: the scheduler's back-off sleeps by what the
        # pending ops waited for (:meth:`_why_asleep`), which the shell's
        # wire meter notes on the ``round`` span; ``_gating`` counts the
        # GRAD ops standing at the gate.
        self._gating = 0
        self._waiting = getattr(self.transport, "waiting", None)
        if self._spans.enabled:
            self.sched.why = self._why_asleep
            self.sched.sleep_by = dict.fromkeys(SLEEP_REASONS, 0.0)

    # -- lifecycle ----------------------------------------------------------

    def start(self, param: np.ndarray, grad: np.ndarray) -> None:
        """Announce shard layout + codec to every server; the first client
        seeds the servers' shards from ``param`` (reference
        pclient.lua:111-129).  INIT v2: int64 [offset, size, codec_id];
        with any FT feature active, INIT v3 adds [epoch, flags]; under
        shardctl, INIT v4 announces the whole versioned shard map."""
        self._register(param, grad)
        if self._sc:
            self._sc_start(param)
            return
        # Placement is a ShardMap even on the static path: version-0,
        # one equal shard per server in rank order — byte-identical to
        # the raw shard_layout() cut this call site used to make.  An
        # explicit ``layout=`` swaps in its weighted cut here; everything
        # downstream (chunk plans, codec staging, INIT bodies) is already
        # per-(srank, shard) and never assumes the shards are equal.
        if self._layout is not None:
            if self._layout[-1].end != len(param):
                raise ValueError(
                    f"layout covers [0, {self._layout[-1].end}) but the "
                    f"registered vector has {len(param)} elements"
                )
            self.smap = _shardmap.ShardMap.from_shards(self._layout,
                                                       self.sranks)
        else:
            self.smap = _shardmap.ShardMap.initial(len(param), self.sranks)
        self.shards = [e.shard for e in self.smap.entries]
        flags = (FLAG_FRAMED if self.ft.framed else 0) | (
            FLAG_HEARTBEAT if self.ft.heartbeat_s > 0 else 0
        ) | (FLAG_STALENESS if self._stale else 0) | (
            FLAG_TIMING if self._timing else 0) | (
            FLAG_CHUNKED if self._chunked else 0)
        if self._chunked:
            self._chunk_elems = chunk_elems_for(self.ft.chunk_bytes,
                                                param.dtype.itemsize)
        for srank, shard in zip(self.sranks, self.shards):
            body = (self.codec.wire_nbytes(shard.size)
                    if not self.codec.identity
                    else shard.size * param.dtype.itemsize)
            if self._chunked:
                # Streamed staging (§12.2): K uniform [chunk hdr | body]
                # frames, one contiguous buffer per direction.  Encode
                # lands each chunk behind its own header, so a retry
                # resends any chunk's exact bytes zero-copy, and the
                # error-feedback residual (whole-shard, sliced per
                # chunk) folds exactly once per block.
                spans = chunk_spans(shard.size, self._chunk_elems)
                full = min(self._chunk_elems, shard.size)
                cbody = (self.codec.wire_nbytes(full)
                         if not self.codec.identity
                         else full * param.dtype.itemsize)
                stride = chunk_stride(self._chdr, cbody)
                self._chunk_spans[srank] = spans
                self._chunk_stride[srank] = stride
                self._chunk_stride_rx[srank] = chunk_stride(self._chdr_rx,
                                                            cbody)
                self._grad_wire[srank] = np.zeros(stride * len(spans),
                                                  np.uint8)
                self._param_wire[srank] = np.zeros(stride * len(spans),
                                                   np.uint8)
                if self.codec.uses_residual:
                    self._residual[srank] = np.zeros(shard.size, np.float32)
                # One reusable reply-frame buffer: chunked PARAM replies
                # are uniform-size messages received one at a time.
                self._param_rx[srank] = np.zeros(self._chunk_stride_rx[srank],
                                                 np.uint8)
                self._ack_buf[srank] = np.zeros(
                    CHUNK_ACK_TIMING_WORDS if self._timing
                    else CHUNK_ACK_WORDS, np.int64)
            elif not self.codec.identity:
                self._grad_wire[srank] = np.zeros(self._hdr + body, np.uint8)
                self._param_wire[srank] = np.zeros(self._hdr + body, np.uint8)
                if self.codec.uses_residual:
                    self._residual[srank] = np.zeros(shard.size, np.float32)
            elif self._hdr:
                # Identity codec under FT framing: raw dtype bytes behind
                # the header (the one staging copy framing costs).
                self._grad_wire[srank] = np.zeros(self._hdr + body, np.uint8)
                self._param_wire[srank] = np.zeros(self._hdr + body, np.uint8)
            if self._hdr and not self._chunked:
                # PARAM replies carry the (possibly wider) reply header —
                # the timing tail rides there — so reads stage separately
                # from the identically-bodied push frames.
                self._param_rx[srank] = np.zeros(self._hdr_rx + body,
                                                 np.uint8)
                self._ack_buf[srank] = np.zeros(
                    ACK_TIMING_WORDS if self._timing else 2, np.int64)
            if self._chunked:
                cinfo = init_v5(shard.offset, shard.size,
                                self.codec.wire_id, self.ft.epoch, flags,
                                self._chunk_elems)
            elif self.ft.active:
                cinfo = init_v3(shard.offset, shard.size,
                                self.codec.wire_id, self.ft.epoch, flags)
            else:
                cinfo = np.asarray(
                    [shard.offset, shard.size, self.codec.wire_id],
                    dtype=np.int64,
                )
            self.sched.spawn(
                aio_send(self.transport, with_plain_tail(cinfo, self._plain),
                         srank, tags.INIT,
                         live=self.live, deadline=self._op_deadline()),
                name=f"send_init:{srank}",
            )
        self.wait()
        # Beat from the moment the servers know this client's epoch —
        # seeding a large shard below can outlast any reasonable lease
        # TTL, and the wait() loop is what pumps the beacons out.
        self._started = True
        self._hb_last = 0.0
        if self.seed_servers:
            self.async_send_param()
            self.wait()

    def announce_plain(self, plain) -> None:
        """Before :meth:`start`: the vector's *plain ranges*
        (``models/flat.py`` ``plain_ranges``), elements that move by
        exactly minus what a GRAD carries for them whatever the servers'
        rule is.  Every INIT then ends in them
        (``ft/wire.py`` ``with_plain_tail``) and a server's rule is
        ``optim/rules.py`` ``apply_at`` with them.  The step must arrive
        as it left, so a codec that rounds is refused, and the static
        cut is the only placement that carries the tail so far."""
        plain = tuple((int(a), int(b)) for a, b in plain)
        if not plain:
            return
        if not self.codec.identity:
            raise ValueError(
                f"the vector has plain ranges and the codec is "
                f"{self.codec.name!r}: a step of its own rule must reach "
                "the servers unrounded (docs/WORKLOADS.md, a leaf with a "
                "rule of its own)")
        if self._sc:
            raise ValueError("plain ranges travel in the static path's "
                             "INIT; shardctl's v4 does not carry them yet")
        self._plain = plain

    def _register(self, param: np.ndarray, grad: np.ndarray) -> None:
        # Dtype-agnostic: shards are element ranges; transports move bytes.
        if not isinstance(param, np.ndarray) or not isinstance(grad, np.ndarray):
            raise TypeError("param and grad must be numpy arrays (host mirrors)")
        if param.ndim != 1 or grad.shape != param.shape or grad.dtype != param.dtype:
            raise ValueError("param and grad must be 1-D with equal shape and dtype")
        if not param.flags["C_CONTIGUOUS"] or not grad.flags["C_CONTIGUOUS"]:
            raise ValueError("param and grad must be contiguous (zero-copy rule)")
        if not self.codec.identity and param.dtype != np.float32:
            raise ValueError(
                f"codec {self.codec.name!r} quantizes float32 shards; got "
                f"dtype {param.dtype} (use codec='none' for other dtypes)"
            )
        self.param, self.grad = param, grad

    def reset(self, param: np.ndarray, grad: np.ndarray) -> None:
        """Retarget transfer buffers without re-announcing shards
        (reference pclient.lua:138-151).  Error-feedback residuals are
        keyed by shard, not by buffer — they survive the retarget."""
        if self.shards and len(param) != self.shards[-1].end:
            raise ValueError("reset buffers must keep the registered length")
        self._register(param, grad)

    # -- live introspection (obs/statusd) ------------------------------------

    def _status_section(self) -> Dict[str, object]:
        """This client's /status section: identity, negotiation posture,
        per-server basis versions and the pending op-pump task table.
        Runs on the statusd thread — reads plain attributes only."""
        try:
            tasks = [t.name for t in list(self.sched.queue)]
        except RuntimeError:  # deque mutated mid-snapshot; next poll wins
            tasks = ["<scheduler busy>"]
        return {
            "role": "client",
            "rank": self.rank,
            "servers": self.sranks,
            "codec": self.codec.name,
            "epoch": self.ft.epoch,
            "framed": self.ft.framed,
            "staleness": self._stale,
            "chunked": self._chunked,
            "basis_versions": {str(s): v for s, v in self._basis.items()},
            "map_version": getattr(self.smap, "version", None),
            "retries": self.retries,
            "tasks": tasks,
        }

    def _flight_dump(self, reason: str, **fields) -> None:
        """Record + dump the flight ring on a terminal failure (no-op
        when obs is off).  The dump rides next to the raised exception:
        the exception names the op, the dump shows the ring of events
        that led to it plus the live task table."""
        self._flight.record(reason, rank=self.rank, **fields)
        try:
            tasks = [(t.name, t.state) for t in list(self.sched.queue)]
        except RuntimeError:
            tasks = None
        path = self._flight.dump(reason, tasks=tasks, **fields)
        if path:
            self.log.warning("%s: flight recorder dumped to %s", reason, path)

    # -- observability back-compat reads ------------------------------------

    @property
    def retries(self) -> int:
        """Resends performed (registry-backed; observability/test hook)."""
        return int(self._m_retries.value)

    @property
    def heartbeats_sent(self) -> int:
        return int(self._m_hb.value)

    # -- FT plumbing ---------------------------------------------------------

    def _op_deadline(self) -> Optional[float]:
        """Absolute deadline for one attempt (None when deadlines off)."""
        return deadline_at(self.ft.deadline_s)

    def _next_seq(self, srank: int, tag: int) -> int:
        seq = self._seq.get((srank, tag), 0) + 1
        self._seq[(srank, tag)] = seq
        return seq

    def _op_with_retry(self, srank: int, payload: np.ndarray, tag: int,
                       ack_tag: int, seq: int, what: str, span=NULL_SPAN):
        """Send the staged frame, await its seq-matched ack; resend the
        same bytes on deadline under the backoff policy.  Exhaustion
        raises :class:`RetryExhausted` — the never-hang guarantee.
        ``span`` (an obs op span) gets per-attempt phase marks and the
        terminal outcome, so a retried op is attributable in the trace."""
        last: Optional[BaseException] = None
        for attempt in range(self._retry.attempts):
            if attempt:
                backoff = self._retry.backoff_s(attempt)
                self._m_retries.inc()
                self._m_backoff.inc(backoff)
                span.mark("backoff")
                span.note(retries=attempt)
                self.log.debug("%s: retry %d after %r", what, attempt, last)
                if not (yield from aio_sleep(backoff, live=self.live)):
                    span.end("aborted")
                    return None
            deadline = self._op_deadline()
            try:
                span.mark("send")
                if self._timing:
                    # Re-stamped per attempt; the server echoes whichever
                    # stamp rode the frame it saw, so the NTP pairing is
                    # exact even when acks and resends cross.
                    pack_tx_stamp(payload, self._hdr, obs_clock.wall_us())
                yield from aio_send(self.transport, payload, srank, tag,
                                    live=self.live, deadline=deadline)
                span.mark("ack")
                got = yield from self._await_ack(srank, ack_tag, seq,
                                                 deadline, span=span)
                if got is not None or not self.live.io:
                    span.end("ok" if got is not None else "aborted")
                    return got
            except DeadlineExceeded as exc:
                last = exc
        span.end("exhausted")
        self._flight_dump("retry_exhausted", what=what,
                          attempts=self._retry.attempts, peer=srank)
        raise RetryExhausted(what, self._retry.attempts, last)

    def _feed_clock(self, srank: int, t_tx: int, t_recv: int,
                    t_ack: int) -> None:
        """One FLAG_TIMING exchange into the per-server estimator
        (t4 = now on this client's time base); accepted samples surface
        on the mpit_clock_offset_us gauge."""
        if self._clock.add_exchange(srank, t_tx, t_recv, t_ack,
                                    obs_clock.wall_us()):
            gauge = self._m_clock.get(srank)
            if gauge is None:
                gauge = self.metrics.gauge("mpit_clock_offset_us",
                                           rank=self.rank, peer=srank)
                self._m_clock[srank] = gauge
            gauge.set(self._clock.peer(srank).offset_us)

    def _await_ack(self, srank: int, ack_tag: int, seq: int,
                   deadline: Optional[float], span=NULL_SPAN):
        """Receive acks until the one echoing ``seq`` for the current
        epoch arrives.  Stale echoes (an earlier attempt's duplicate, a
        previous incarnation's leftovers) are consumed and dropped — on
        the attempt's unchanged deadline, so a trickle of stale acks
        cannot extend it.  Under FLAG_TIMING every current-epoch ack —
        matched or stale — is a complete NTP exchange and feeds the
        clock estimator; the matched one also lands its server stamps
        on the op span, so the trace carries both halves' marks."""
        buf = self._ack_buf[srank]
        while True:
            got = yield from aio_recv(self.transport, srank, ack_tag,
                                      live=self.live, out=buf,
                                      deadline=deadline)
            if got is None:
                return None
            epoch, aseq = int(buf[0]), int(buf[1])
            if self._timing and epoch == self.ft.epoch:
                self._feed_clock(srank, int(buf[2]), int(buf[3]),
                                 int(buf[4]))
            if epoch == self.ft.epoch and aseq == seq:
                if self._timing:
                    span.note(tx_us=int(buf[2]), srv_recv_us=int(buf[3]),
                              srv_ack_us=int(buf[4]))
                return got
            if epoch > self.ft.epoch or (epoch == self.ft.epoch and aseq > seq):
                raise RuntimeError(
                    f"ack from server {srank} is ahead of the op stream: "
                    f"got (epoch={epoch}, seq={aseq}), awaiting "
                    f"(epoch={self.ft.epoch}, seq={seq})"
                )

    def _maybe_heartbeat(self) -> None:
        """Emit a HEARTBEAT to every server when the interval elapsed.
        Piggybacks on ping()/wait() — the cadence the trainers already
        drive for comm overlap — so liveness needs no thread.  Sends are
        fire-and-forget with a bounded deadline: a dead server must not
        accumulate unbounded heartbeat tasks in the queue."""
        hb = self.ft.heartbeat_s
        if hb <= 0 or not self._started or not self.live.io:
            return
        now = time.monotonic()
        if now - self._hb_last < hb:
            return
        self._hb_last = now
        self._hb_seq += 1
        # Timing pairs stamp the beat: the server echoes the stamp back
        # with its own receive/send marks (HEARTBEAT_ECHO), so the clock
        # estimator refreshes from the heartbeat stream even when no op
        # is in flight.
        payload = (timed_frame(self.ft.epoch, self._hb_seq,
                               obs_clock.wall_us())
                   if self._timing
                   else header_frame(self.ft.epoch, self._hb_seq))
        self._m_hb.inc()
        targets = self._sc_beat_targets() if self._sc else self.sranks
        for srank in targets:
            self.sched.spawn(
                self._hb_send(payload, srank), name=f"heartbeat:{srank}"
            )

    def _hb_send(self, payload: np.ndarray, srank: int):
        try:
            yield from aio_send(
                self.transport, payload, srank, tags.HEARTBEAT,
                live=self.live, deadline=deadline_at(4 * self.ft.heartbeat_s),
            )
        except DeadlineExceeded:
            pass  # liveness is best-effort; the next beat tries again

    def _drain_clock_echoes(self) -> None:
        """Consume pending HEARTBEAT_ECHO replies (probed, never
        blocking — the _sc_poll_map pattern): each carries a complete
        [t_tx_echo, t_recv, t_ack] exchange, refreshing the per-server
        clock offset while the trainer is compute-bound between ops.  A
        lost or late echo costs nothing — the next beat makes another."""
        if not self._timing or not self._started:
            return
        for srank in self.sranks:
            while self.transport.iprobe(srank, tags.HEARTBEAT_ECHO):
                handle = self.transport.irecv(srank, tags.HEARTBEAT_ECHO)
                while not self.transport.test(handle):
                    pass  # iprobe saw a fully-assembled message
                tail = np.frombuffer(
                    bytes(self.transport.payload(handle)), np.int64)
                if (len(tail) >= ACK_TIMING_WORDS
                        and int(tail[0]) == self.ft.epoch):
                    self._feed_clock(srank, int(tail[2]), int(tail[3]),
                                     int(tail[4]))

    # -- shardctl: shard-addressed ops over the versioned map ----------------

    def _sc_start(self, param: np.ndarray) -> None:
        """INIT v4 to every server: codec + FT posture + the whole map.
        Per-shard staging is keyed by shard_id — placement moves, the
        cut never does, so buffers survive any number of migrations."""
        if self.smap is None:
            owners = [s for s in self.sranks for _ in range(self._sc_cut)]
            self.smap = _shardmap.ShardMap.initial(len(param), owners)
        if self.smap.plong != len(param):
            raise ValueError(
                f"shard map covers {self.smap.plong} elements but the "
                f"registered vector has {len(param)}")
        self.shards = [e.shard for e in self.smap.entries]
        self._m_mapver.set(self.smap.version)
        flags = FLAG_FRAMED | _scwire.FLAG_SHARDCTL | (
            FLAG_HEARTBEAT if self.ft.heartbeat_s > 0 else 0
        )
        self._sc_flags = flags
        self._sc_greeted = set(self.sranks)
        for e in self.smap.entries:
            if self.codec.identity:
                nbytes = e.shard.size * param.dtype.itemsize
            else:
                nbytes = self.codec.wire_nbytes(e.shard.size)
                if self.codec.uses_residual:
                    self._sc_residual[e.shard_id] = np.zeros(
                        e.shard.size, np.float32)
            self._sc_wire[e.shard_id] = np.zeros(
                _scwire.SC_HDR_BYTES + nbytes, np.uint8)
        cinfo = _scwire.init_v4(self.codec.wire_id, self.ft.epoch,
                                flags, self.smap)
        for srank in self.sranks:
            self.sched.spawn(
                aio_send(self.transport, cinfo, srank, tags.INIT,
                         live=self.live, deadline=self._op_deadline()),
                name=f"send_init:{srank}",
            )
        self.wait()
        self._started = True
        self._hb_last = 0.0
        if self.controller_rank is not None and self.seed_servers:
            # Hand the controller its first map (it starts blank so it
            # never has to know plong before the clients do).
            self.sched.spawn(
                aio_send(self.transport,
                         _scwire.map_update(_scwire.INSTALL, -1, self.rank,
                                            self.smap),
                         self.controller_rank, tags.MAP_UPDATE,
                         live=self.live, deadline=self._op_deadline()),
                name="send_map:controller",
            )
            self.wait()
        if self.seed_servers:
            self.async_send_param()
            self.wait()

    def _sc_next_seq(self, sid: int, tag: int) -> int:
        seq = self._sc_seq.get((sid, tag), 0) + 1
        self._sc_seq[(sid, tag)] = seq
        return seq

    def _sc_install_wire(self, body) -> bool:
        """Adopt a serialized map if it is newer than ours."""
        m = _shardmap.ShardMap.from_wire(np.frombuffer(bytes(body), np.int64))
        if self.smap is None or m.version > self.smap.version:
            self.smap = m
            self._m_mapver.set(m.version)
            return True
        return False

    def _sc_poll_map(self) -> None:
        """Drain any MAP_UPDATE broadcasts from the controller (probed,
        never blocking): proactive re-routing, and the only way to learn
        a failover map while the old owner is dead air."""
        if not self._sc or self.controller_rank is None:
            return
        while self.transport.iprobe(self.controller_rank, tags.MAP_UPDATE):
            handle = self.transport.irecv(self.controller_rank,
                                          tags.MAP_UPDATE)
            while not self.transport.test(handle):
                pass  # iprobe saw a fully-assembled message
            kind, _sid, peer, m = _scwire.parse_map_update(
                bytes(self.transport.payload(handle)))
            if kind == _scwire.RETIRED:
                # A goodbye, not a crash: drop the rank from beat/STOP
                # fan-out.  Its shards already drained (the map carried
                # here no longer routes anything to it).
                self._sc_retired.add(peer)
            if self.smap is None or m.version > self.smap.version:
                self.smap = m
                self._m_mapver.set(m.version)

    def _sc_write_op(self, sid: int, tag: int, ack_tag: int, what: str):
        """One shard write (GRAD / PARAM_PUSH): encode once into the
        shard's staging frame, then run the attempt loop.  The residual
        folds at this single encode; re-routes resend the same bytes."""
        shard = self.smap.entry(sid).shard
        span = self._spans.op(what, peer=sid, side="client",
                              rank=self.rank)
        view = (self.grad if tag == tags.GRAD else
                self.param)[shard.offset: shard.end]
        wire = self._sc_wire[sid]
        span.mark("encode")
        body = wire[_scwire.SC_HDR_BYTES:]
        if self.codec.identity:
            body[:] = view.view(np.uint8)
        else:
            residual = (self._sc_residual.get(sid)
                        if tag == tags.GRAD else None)
            self.codec.encode_into(view, body, residual=residual)
        seq = self._sc_next_seq(sid, tag)
        span.note(epoch=self.ft.epoch, seq=seq, shard=sid)
        yield from self._sc_attempts(sid, seq, wire, tag, ack_tag,
                                     out=None, span=span,
                                     what=f"{what} for shard {sid}")

    def _sc_read_op(self, sid: int):
        """One shard read: request-by-header, decode the OK reply's
        snapshot frame into the param slice."""
        shard = self.smap.entry(sid).shard
        span = self._spans.op("PARAM", peer=sid, side="client",
                              rank=self.rank)
        out = self.param[shard.offset: shard.end]
        seq = self._sc_next_seq(sid, tags.PARAM_REQ)
        span.note(epoch=self.ft.epoch, seq=seq, shard=sid)
        yield from self._sc_attempts(sid, seq, None, tags.PARAM_REQ,
                                     tags.PARAM, out=out, span=span,
                                     what=f"PARAM read for shard {sid}")

    def _sc_attempts(self, sid: int, seq: int, wire: Optional[np.ndarray],
                     tag: int, ack_tag: int, out: Optional[np.ndarray],
                     span, what: str):
        """The shardctl attempt loop: send to the shard's current owner,
        await the status reply; DeadlineExceeded retries under backoff
        (polling controller broadcasts), NACK_MAP installs the carried
        map and re-routes, BUSY backs off through a migration window.
        A re-route to a *different* owner resets the attempt budget —
        monotone map versions bound the total work.  Exhaustion raises
        :class:`RetryExhausted`; the never-hang guarantee holds."""
        attempt = 0
        nacks = 0
        max_nacks = 16 * (self._retry.attempts + 1)
        last: Optional[BaseException] = None
        while self.live.io:
            owner = self.smap.owner(sid)
            if owner not in self._sc_greeted:
                # First contact with a scaled-up server (§9.1): announce
                # this incarnation before the op — the lazy INIT v4 that
                # makes late membership transparent to the op stream.
                yield from self._sc_greet(owner)
            if wire is not None:
                _scwire.pack_sc_header(wire, self.ft.epoch, seq,
                                       self.smap.version, sid)
                payload: np.ndarray = wire
            else:
                payload = _scwire.sc_header(self.ft.epoch, seq,
                                            self.smap.version, sid)
            deadline = self._op_deadline()
            try:
                span.mark("send")
                yield from aio_send(self.transport, payload, owner, tag,
                                    live=self.live, deadline=deadline)
                span.mark("recv" if out is not None else "ack")
                while True:
                    raw = yield from aio_recv(self.transport, owner, ack_tag,
                                              live=self.live,
                                              deadline=deadline)
                    if raw is None:
                        span.end("aborted")
                        return None
                    epoch, aseq, status, rsid, body = _scwire.parse_reply(
                        bytes(raw))
                    if epoch == self.ft.epoch and rsid == sid and aseq == seq:
                        break
                    if epoch > self.ft.epoch or (
                            epoch == self.ft.epoch and rsid == sid
                            and aseq > seq):
                        raise RuntimeError(
                            f"reply from server {owner} is ahead of the op "
                            f"stream: got (epoch={epoch}, seq={aseq}, "
                            f"shard={rsid}), awaiting (epoch="
                            f"{self.ft.epoch}, seq={seq}, shard={sid})")
                    # stale echo (earlier attempt / other shard): drop on
                    # the unchanged attempt deadline
            except DeadlineExceeded as exc:
                last = exc
                attempt += 1
                if attempt >= self._retry.attempts:
                    span.end("exhausted")
                    self._flight_dump("retry_exhausted", what=what,
                                      attempts=self._retry.attempts,
                                      shard=sid)
                    raise RetryExhausted(what, self._retry.attempts, last)
                backoff = self._retry.backoff_s(attempt)
                self._m_retries.inc()
                self._m_backoff.inc(backoff)
                span.mark("backoff")
                span.note(retries=attempt)
                if not (yield from aio_sleep(backoff, live=self.live)):
                    span.end("aborted")
                    return None
                self._sc_poll_map()
                if self.smap.owner(sid) != owner:
                    # A broadcast re-routed us (failover away from a dead
                    # owner): the new destination gets a fresh budget.
                    self._m_reroutes.inc()
                    span.mark("reroute")
                    attempt = 0
                continue
            if status == _scwire.OK:
                if out is not None:
                    span.mark("decode")
                    self._sc_decode(body, out)
                span.end("ok")
                return True
            # NACK_MAP / BUSY — both may carry the server's newer map.
            nacks += 1
            self._m_nacks.inc()
            span.mark("nack")
            if nacks > max_nacks:
                span.end("exhausted")
                self._flight_dump("retry_exhausted",
                                  what=f"{what} (map churn)", nacks=nacks,
                                  shard=sid)
                raise RetryExhausted(f"{what} (map churn)", nacks, last)
            if len(body) and self._sc_install_wire(body) \
                    and self.smap.owner(sid) != owner:
                self._m_reroutes.inc()
                span.mark("reroute")
                attempt = 0
            if status == _scwire.BUSY:
                # Mid-migration freeze window: give the handoff a beat.
                if not (yield from aio_sleep(self._retry.backoff_s(1),
                                             live=self.live)):
                    span.end("aborted")
                    return None
                self._sc_poll_map()
        span.end("aborted")
        return None

    def _sc_greet(self, owner: int):
        """Announce this client (INIT v4 with the current map) to a
        server that joined after launch.  The server's listener
        negotiates and spawns services before it sees our first op —
        both tags are FIFO per channel, so ordering is the transport's."""
        cinfo = _scwire.init_v4(self.codec.wire_id, self.ft.epoch,
                                self._sc_flags, self.smap)
        yield from aio_send(self.transport, cinfo, owner, tags.INIT,
                            live=self.live, deadline=self._op_deadline())
        self._sc_greeted.add(owner)

    def _sc_beat_targets(self) -> "List[int]":
        """Liveness fan-out under shardctl: everyone this incarnation
        announced itself to, minus clean departures."""
        return sorted(self._sc_greeted - self._sc_retired)

    def _sc_decode(self, body, out: np.ndarray) -> None:
        frame = np.frombuffer(bytes(body), np.uint8)
        if self.codec.identity:
            out.view(np.uint8)[:] = frame
        else:
            self.codec.decode_into(frame, out)

    def _sc_enqueue(self, gen: Generator, name: str) -> None:
        self._scq.append((gen, name))
        if not self._sc_pump_live:
            self._sc_pump_live = True
            self._sc_pump_task = None
            task = self.sched.spawn(self._sc_pump(), name=f"scpump:{name}")
            self._sc_pump_task = task

    def _sc_pump(self):
        """One global FIFO for shardctl ops: strictly serialized, so the
        per-(owner, tag) reply channels never interleave two in-flight
        ops' echoes even when one server owns several shards.  (The
        static path keeps its per-server pumps and full cross-server
        overlap — serialization is the price of re-routable ops, paid
        only in shardctl mode.)"""
        queue = self._scq
        try:
            while queue:
                op, opname = queue.popleft()
                task = self._sc_pump_task
                if task is not None:
                    task.name = f"scpump:{opname}"
                yield from op
        finally:
            self._sc_pump_live = False

    # -- per-server transfer generators -------------------------------------

    def _send_grad(self, srank: int, shard: Shard):
        """Ship the grad slice, await the applied ack
        (reference pclient.lua:48-58).  Non-identity codecs encode into
        the per-server staging frame at ship time; the int8 residual is
        folded in and refreshed by the same pass.  Framed mode stamps
        [epoch, seq] and retries the staged bytes on deadline.  Gated
        (:meth:`stream_shards`): nothing here reads a byte of the slice
        before the shell has staged it, and the wait lies before the
        span.  Where the payload is the slice itself (:meth:`_follows`)
        the wait is for the shard's first staged byte and the send is
        made of the shell's pieces, read where the d2h left them, as they
        land (``aio_send(pieces=...)``): the slice is not read at all;
        everywhere else something reads the whole slice first, so the
        wait is for the whole shard."""
        wire = self._grad_wire.get(srank)
        feed = None
        gated_ms = None
        if self._staged is not None:
            whole = shard.size * self.grad.itemsize
            follow = self._pieces is not None and self._follows(srank)
            gated_ms = yield from self._gate(shard, 1 if follow else whole)
            feed = self._pieces(shard) if follow else None
            if follow and feed is None:
                # No feed (between rounds): the slice is the payload, and
                # nothing of it leaves before it is whole.
                yield from self._gate(shard, whole)
        if self._chunked:
            yield from self._chunked_write(srank, shard, tags.GRAD,
                                           tags.GRAD_ACK, "GRAD", gated_ms)
            return
        span = self._spans.op("GRAD", peer=srank, side="client",
                              rank=self.rank)
        if gated_ms is not None:
            span.note(gated_ms=gated_ms)
        view = self.grad[shard.offset : shard.end]
        span.mark("encode")
        payload = self._encode(view, wire, residual=self._residual.get(srank))
        span.note(bytes=payload.nbytes)
        if not self.ft.framed:
            span.mark("send")
            yield from aio_send(
                self.transport, payload if feed is None else payload.nbytes,
                srank, tags.GRAD, live=self.live,
                deadline=self._op_deadline(), pieces=feed)
            span.mark("ack")
            yield from aio_recv(self.transport, srank, tags.GRAD_ACK,
                                live=self.live, deadline=self._op_deadline())
            span.end("ok")
            return
        seq = self._next_seq(srank, tags.GRAD)
        span.note(epoch=self.ft.epoch, seq=seq)
        pack_header(payload, self.ft.epoch, seq)
        if self._stale:
            # Echo the param version this gradient was computed against
            # (the last PARAM read from this server); the server measures
            # the staleness gap at apply time.
            basis = self._basis.get(srank, 0)
            pack_version(payload, basis)
            span.note(basis=basis)
        yield from self._op_with_retry(
            srank, payload, tags.GRAD, tags.GRAD_ACK, seq,
            f"GRAD to server {srank}", span=span,
        )

    def _recv_param(self, srank: int, shard: Shard):
        """Read this server's shard into the param slice and, once the
        slice is whole (decoded, where a codec or the framed wire is
        on), say so to the shell's sink (:meth:`stream_shards`).  Where
        the slice is the receive's own buffer the sink has heard of the
        shard's front before (:meth:`_mark`)."""
        whole = yield from (self._chunked_read(srank, shard)
                            if self._chunked
                            else self._read_shard(srank, shard))
        if whole and self._landed is not None:
            self._landed(shard, shard.size * self.param.itemsize)

    def _mark(self, srank: int, shard: Shard
              ) -> Optional[Callable[[int], None]]:
        """What follows a PARAM receive of ``shard``
        (``aio_recv(landing=...)``), where the slice is the receive's own
        buffer (:meth:`_lands`) and a shell took the sink: told how many
        bytes of the slice are the message's for good, from its front, it
        tells the sink as long as the slice is not yet
        whole (that is :meth:`_recv_param`'s to say, once).  A receive
        that starts over (a negative answer: the message that had begun
        to land was abandoned) takes the mark back to 0: nothing of the
        slice is whole until all of it is.  None everywhere else: the
        sink hears of the shard once, whole."""
        if self._landed is None or not self._lands(srank):
            return None
        landed = self._landed
        whole = shard.size * self.param.itemsize

        def mark(filled: int) -> None:
            if filled < whole:
                landed(shard, max(filled, 0))

        return mark

    def _read_shard(self, srank: int, shard: Shard):
        """Request-to-read header, then receive into the param slice
        (reference pclient.lua:72-82) — via the wire staging frame when
        the codec is not identity.  Framed mode seq-tags the request and
        discards snapshot frames that echo an earlier request.  Returns
        True once the slice is whole."""
        span = self._spans.op("PARAM", peer=srank, side="client",
                              rank=self.rank)
        out = self.param[shard.offset : shard.end]
        wire = self._param_wire.get(srank)
        span.note(bytes=(out if wire is None else wire).nbytes)
        if not self.ft.framed:
            span.mark("send")
            request = aio_send(self.transport, tags.EMPTY, srank,
                               tags.PARAM_REQ, live=self.live,
                               deadline=self._op_deadline())

            def ask():
                yield from request
                span.mark("recv")

            # ``aio_recv`` posts the receive and only then lets the
            # request leave, so the shard lands in its place however
            # quick the server is.
            got = yield from aio_recv(
                self.transport, srank, tags.PARAM, live=self.live,
                out=out if wire is None else wire,
                deadline=self._op_deadline(), request=ask(),
                landing=self._mark(srank, shard),
            )
            if got is not None and wire is not None:
                span.mark("decode")
                self.codec.decode_into(wire, out)
            span.end("ok" if got is not None else "aborted")
            return got is not None
        seq = self._next_seq(srank, tags.PARAM_REQ)
        span.note(epoch=self.ft.epoch, seq=seq)
        wire = self._param_rx[srank]
        req = (timed_frame(self.ft.epoch, seq, 0) if self._timing
               else header_frame(self.ft.epoch, seq))
        last: Optional[BaseException] = None
        for attempt in range(self._retry.attempts):
            if attempt:
                backoff = self._retry.backoff_s(attempt)
                self._m_retries.inc()
                self._m_backoff.inc(backoff)
                span.mark("backoff")
                span.note(retries=attempt)
                if not (yield from aio_sleep(backoff, live=self.live)):
                    span.end("aborted")
                    return
            deadline = self._op_deadline()
            try:
                span.mark("send")
                if self._timing:
                    req[2] = obs_clock.wall_us()  # re-stamped per attempt
                yield from aio_send(self.transport, req, srank,
                                    tags.PARAM_REQ, live=self.live,
                                    deadline=deadline)
                span.mark("recv")
                while True:
                    got = yield from aio_recv(
                        self.transport, srank, tags.PARAM, live=self.live,
                        out=wire, deadline=deadline,
                    )
                    if got is None:
                        span.end("aborted")
                        return
                    epoch, aseq = unpack_header(wire)
                    if self._timing and epoch == self.ft.epoch:
                        # Any current-epoch reply — matched or a stale
                        # duplicate — is a complete NTP exchange.
                        t_tx, t_recv, t_ack = unpack_reply_stamps(
                            wire, self._hdr_rx - 24)
                        self._feed_clock(srank, t_tx, t_recv, t_ack)
                    if epoch == self.ft.epoch and aseq == seq:
                        if self._stale:
                            # The reply's version word is the basis the
                            # next gradient to this server will echo.
                            self._basis[srank] = unpack_version(wire)
                        if self._timing:
                            span.note(tx_us=t_tx, srv_recv_us=t_recv,
                                      srv_ack_us=t_ack)
                        span.mark("decode")
                        self._decode_framed(wire, out)
                        span.end("ok")
                        return True
                    # stale snapshot (earlier request's duplicate): drop
            except DeadlineExceeded as exc:
                last = exc
        span.end("exhausted")
        self._flight_dump("retry_exhausted",
                          what=f"PARAM read from server {srank}",
                          attempts=self._retry.attempts, peer=srank)
        raise RetryExhausted(
            f"PARAM read from server {srank}", self._retry.attempts, last)

    def _send_param(self, srank: int, shard: Shard):
        """Whole-shard write, await ack (reference pclient.lua:60-70).
        No residual: parameter pushes (seeding / single-worker mirror)
        are one-shot state transfers, not an accumulating signal."""
        if self._chunked:
            yield from self._chunked_write(srank, shard, tags.PARAM_PUSH,
                                           tags.PARAM_PUSH_ACK, "PARAM_PUSH")
            return
        span = self._spans.op("PARAM_PUSH", peer=srank, side="client",
                              rank=self.rank)
        view = self.param[shard.offset : shard.end]
        wire = self._param_wire.get(srank)
        span.mark("encode")
        payload = self._encode(view, wire)
        if not self.ft.framed:
            span.mark("send")
            yield from aio_send(self.transport, payload, srank,
                                tags.PARAM_PUSH, live=self.live,
                                deadline=self._op_deadline())
            span.mark("ack")
            yield from aio_recv(self.transport, srank, tags.PARAM_PUSH_ACK,
                                live=self.live, deadline=self._op_deadline())
            span.end("ok")
            return
        seq = self._next_seq(srank, tags.PARAM_PUSH)
        span.note(epoch=self.ft.epoch, seq=seq)
        pack_header(payload, self.ft.epoch, seq)
        if self._stale:
            # Pushes fill the version word too (uniform 24-byte layout);
            # the server ignores it — a whole-shard write is a state
            # transfer, not a gradient with a basis.
            pack_version(payload, self._basis.get(srank, 0))
        yield from self._op_with_retry(
            srank, payload, tags.PARAM_PUSH, tags.PARAM_PUSH_ACK, seq,
            f"PARAM_PUSH to server {srank}", span=span,
        )

    # -- pipelined streaming transfers (FLAG_CHUNKED, PROTOCOL.md §12) -------

    def _chunked_write(self, srank: int, shard: Shard, tag: int,
                       ack_tag: int, what: str,
                       gated_ms: Optional[float] = None):
        """One streamed shard write: the body ships as K independent
        chunk frames, each encoded into its own staging slot and posted
        *without* waiting — the transport moves chunk k while this
        thread encodes chunk k+1 (the double-buffered encode; on the
        event-loop TCP transport the I/O thread writes concurrently,
        on shm the peer drains concurrently).  The server acks each
        admitted chunk; a deadline resends only the chunks whose acks
        never arrived, from the same staged bytes — so the int8
        residual, folded at the single encode pass, stays exact under
        any retry pattern."""
        span = self._spans.op(what, peer=srank, side="client",
                              rank=self.rank)
        if gated_ms is not None:
            span.note(gated_ms=gated_ms)
        spans_ = self._chunk_spans[srank]
        stride = self._chunk_stride[srank]
        staging = (self._grad_wire if tag == tags.GRAD
                   else self._param_wire)[srank]
        view = (self.grad if tag == tags.GRAD
                else self.param)[shard.offset: shard.end]
        residual = (self._residual.get(srank)
                    if tag == tags.GRAD and self.codec.uses_residual
                    else None)
        seq = self._next_seq(srank, tag)
        nchunks = len(spans_)
        span.note(epoch=self.ft.epoch, seq=seq, chunks=nchunks,
                  bytes=view.nbytes)
        span.mark("encode")
        pool = comm_pool.get_pool()
        jobs: Dict[int, object] = {}

        def _stage_chunk(k: int) -> None:
            # One pure encode job per chunk: disjoint staging slot,
            # disjoint BLOCK-aligned residual slice (the int8 EF state
            # rides in the job), input views quiescent until collect.
            lo, hi = spans_[k]
            frame = staging[k * stride: (k + 1) * stride]
            body = frame[self._chdr: self._chdr + self._chunk_body(hi - lo)]
            if self.codec.identity:
                jobs[k] = pool.submit_copy(view[lo:hi].view(np.uint8), body)
            else:
                jobs[k] = pool.submit_encode(
                    self.codec, view[lo:hi], body,
                    residual=None if residual is None else residual[lo:hi])

        # With workers, chunk k+1 encodes on the pool while chunk k is
        # on the wire; serial (lookahead 0) keeps today's exact order.
        lookahead = 0 if pool.serial else 1
        pending: Dict[int, object] = {}
        for k, (lo, hi) in enumerate(spans_):
            for j in range(k, min(k + 1 + lookahead, nchunks)):
                if j not in jobs:
                    _stage_chunk(j)
            if not jobs[k].done():
                span.mark("pool_collect")
                while not jobs[k].done():
                    yield EXEC
            frame = staging[k * stride: (k + 1) * stride]
            pack_chunk_header(frame, self.ft.epoch, seq, k, nchunks)
            if self._timing:
                pack_tx_stamp(frame, self._chdr, obs_clock.wall_us())
            span.mark("send" if k == 0 else "chunk")
            pending[k] = self.transport.isend(frame, srank, tag)
            # Yield between chunks: the transport pumps chunk k toward
            # the peer (and sibling pumps get their turn) while this
            # generator comes back to collect/encode chunk k+1.
            yield EXEC
        yield from self._chunk_acks(srank, tag, ack_tag, seq, staging,
                                    pending, span, what)

    def _chunk_body(self, elems: int) -> int:
        """Logical body bytes of a chunk covering ``elems`` elements
        (the frame itself is padded to the uniform stride, §12.2)."""
        if self.codec.identity:
            return elems * self.param.dtype.itemsize
        return self.codec.wire_nbytes(elems)

    def _chunk_acks(self, srank: int, tag: int, ack_tag: int, seq: int,
                    staging: np.ndarray, pending: Dict[int, object],
                    span, what: str):
        """Await one ack per chunk; on deadline, resend only the
        missing chunks under the backoff policy.  While waiting, the
        loop also drains send-handle completions and marks ``flush``
        when the last chunk left this rank — the wall-clock point the
        causal analyzer compares against the server's first apply to
        *see* the wire/apply overlap (obs/causal.py)."""
        buf = self._ack_buf[srank]
        spans_ = self._chunk_spans[srank]
        stride = self._chunk_stride[srank]
        nchunks = len(spans_)
        acked = [False] * nchunks
        remaining = nchunks
        flushed = False
        attempt = 0
        last: Optional[BaseException] = None
        while self.live.io:
            deadline = self._op_deadline()
            try:
                while remaining:
                    if pending:
                        # Drive outstanding chunk sends (transports
                        # whose progress rides test()) and record the
                        # moment the last chunk left this rank.  FIFO
                        # prefix only: sends complete in post order, so
                        # stopping at the first incomplete handle keeps
                        # this O(1) amortized — testing every pending
                        # handle per pass is O(K²) over a big stream.
                        for k in list(pending):
                            if not self.transport.test(pending[k]):
                                break
                            del pending[k]
                    if not pending and not flushed:
                        flushed = True
                        span.mark("flush")
                    if not self.transport.iprobe(srank, ack_tag):
                        if not self.live.io:
                            span.end("aborted")
                            return None
                        if deadline is not None \
                                and time.monotonic() > deadline:
                            raise DeadlineExceeded(
                                "recv", srank, ack_tag,
                                time.monotonic() - deadline)
                        yield EXEC
                        continue
                    handle = self.transport.irecv(srank, ack_tag, out=buf)
                    while not self.transport.test(handle):
                        yield EXEC
                    epoch, aseq, idx = int(buf[0]), int(buf[1]), int(buf[2])
                    if self._timing and epoch == self.ft.epoch:
                        self._feed_clock(srank, int(buf[3]), int(buf[4]),
                                         int(buf[5]))
                    if epoch == self.ft.epoch and aseq == seq:
                        if 0 <= idx < nchunks and not acked[idx]:
                            acked[idx] = True
                            remaining -= 1
                    elif epoch > self.ft.epoch or (
                            epoch == self.ft.epoch and aseq > seq):
                        raise RuntimeError(
                            f"chunk ack from server {srank} is ahead of "
                            f"the op stream: got (epoch={epoch}, "
                            f"seq={aseq}), awaiting (epoch="
                            f"{self.ft.epoch}, seq={seq})")
                    # stale chunk ack (an earlier op's re-ack): drop on
                    # the unchanged attempt deadline
                span.mark("ack")
                span.end("ok")
                return True
            except DeadlineExceeded as exc:
                last = exc
                attempt += 1
                if attempt >= self._retry.attempts:
                    span.end("exhausted")
                    self._flight_dump("retry_exhausted", what=what,
                                      attempts=self._retry.attempts,
                                      peer=srank)
                    raise RetryExhausted(what, self._retry.attempts, last)
                backoff = self._retry.backoff_s(attempt)
                self._m_retries.inc()
                self._m_backoff.inc(backoff)
                span.mark("backoff")
                span.note(retries=attempt)
                if not (yield from aio_sleep(backoff, live=self.live)):
                    span.end("aborted")
                    return None
                # Resend ONLY the unacked chunks — identical staged
                # bytes (re-stamped send time under FLAG_TIMING).  A
                # still-pending stale handle is cancelled first so
                # buffer ownership returns before the re-post; the
                # server dedups any frame that made it through anyway.
                span.mark("send")
                for k in range(nchunks):
                    if acked[k]:
                        continue
                    stale = pending.pop(k, None)
                    if stale is not None and not self.transport.test(stale):
                        self.transport.cancel(stale)
                    frame = staging[k * stride: (k + 1) * stride]
                    if self._timing:
                        pack_tx_stamp(frame, self._chdr, obs_clock.wall_us())
                    span.mark("chunk")
                    pending[k] = self.transport.isend(frame, srank, tag)
                    yield EXEC
        span.end("aborted")
        return None

    def _chunked_read(self, srank: int, shard: Shard):
        """One streamed shard read: request-by-header as usual, then
        assemble K chunk replies — each decoded straight into its slice
        of ``param`` on arrival, so decode overlaps the remaining
        chunks' wire time.  Every chunk stamps its snapshot version;
        the assembly restarts whenever a newer version appears (a
        retried request re-served at the head), so the delivered vector
        is always a single committed version (§12.4).  FIFO channels
        guarantee no stale-version chunk arrives after a newer one.
        Returns True once the slice is whole."""
        span = self._spans.op("PARAM", peer=srank, side="client",
                              rank=self.rank)
        out = self.param[shard.offset: shard.end]
        seq = self._next_seq(srank, tags.PARAM_REQ)
        span.note(epoch=self.ft.epoch, seq=seq,
                  chunks=len(self._chunk_spans[srank]), bytes=out.nbytes)
        spans_ = self._chunk_spans[srank]
        frame = self._param_rx[srank]
        req = (timed_frame(self.ft.epoch, seq, 0) if self._timing
               else header_frame(self.ft.epoch, seq))
        last: Optional[BaseException] = None
        # Decode jobs are per-op, not per-attempt: a timed-out attempt's
        # in-flight job must be collected before the retry re-decodes
        # the same slice, or the older bytes could land second.
        pool = comm_pool.get_pool()
        jobs: Dict[int, object] = {}
        for attempt in range(self._retry.attempts):
            if attempt:
                backoff = self._retry.backoff_s(attempt)
                self._m_retries.inc()
                self._m_backoff.inc(backoff)
                span.mark("backoff")
                span.note(retries=attempt)
                if not (yield from aio_sleep(backoff, live=self.live)):
                    span.end("aborted")
                    return
            deadline = self._op_deadline()
            try:
                span.mark("send")
                if self._timing:
                    req[2] = obs_clock.wall_us()  # re-stamped per attempt
                yield from aio_send(self.transport, req, srank,
                                    tags.PARAM_REQ, live=self.live,
                                    deadline=deadline)
                span.mark("recv")
                seen: set = set()
                version: Optional[int] = None
                while True:
                    while not self.transport.iprobe(srank, tags.PARAM):
                        if not self.live.io:
                            span.end("aborted")
                            return
                        if deadline is not None \
                                and time.monotonic() > deadline:
                            raise DeadlineExceeded(
                                "recv", srank, tags.PARAM,
                                time.monotonic() - deadline)
                        yield EXEC
                    handle = self.transport.irecv(srank, tags.PARAM,
                                                  out=frame)
                    while not self.transport.test(handle):
                        yield EXEC
                    epoch, aseq, idx, cnt, ver = unpack_chunk_reply(frame)
                    if self._timing and epoch == self.ft.epoch:
                        t_tx, t_recv, t_ack = unpack_reply_stamps(
                            frame, self._chdr_rx - 24)
                        self._feed_clock(srank, t_tx, t_recv, t_ack)
                    if epoch > self.ft.epoch or (
                            epoch == self.ft.epoch and aseq > seq):
                        raise RuntimeError(
                            f"chunked PARAM reply from server {srank} is "
                            f"ahead of the op stream: got (epoch={epoch}, "
                            f"seq={aseq}), awaiting (epoch={self.ft.epoch},"
                            f" seq={seq})")
                    if epoch != self.ft.epoch or aseq != seq \
                            or not (0 <= idx < len(spans_)):
                        continue  # stale reply chunk: drop
                    if version is None or ver > version:
                        version, seen = ver, set()
                    elif ver < version:
                        continue  # an earlier serve's straggler: drop
                    if idx in seen:
                        continue  # duplicated chunk: already decoded
                    seen.add(idx)
                    lo, hi = spans_[idx]
                    span.mark("decode")
                    body = frame[self._chdr_rx:
                                 self._chdr_rx + self._chunk_body(hi - lo)]
                    if self.codec.identity:
                        # One memcpy — pooling would only add a second.
                        out[lo:hi].view(np.uint8)[:] = body
                    elif pool.serial:
                        self.codec.decode_into(body, out[lo:hi])
                    else:
                        # ``frame`` is the reused rx staging buffer: the
                        # next irecv overwrites it while a worker reads,
                        # so the job's input must be an owned snapshot
                        # (discipline 'pool-client-decode-owned').  A
                        # version restart re-decodes a chunk; the prior
                        # job must land first so the newer bytes win.
                        prior = jobs.pop(idx, None)
                        if prior is not None and not prior.done():
                            span.mark("pool_collect")
                            while not prior.done():
                                yield EXEC
                        jobs[idx] = pool.submit_decode(
                            self.codec, np.array(body), out[lo:hi])
                    if len(seen) == cnt:
                        for job in jobs.values():
                            if not job.done():
                                span.mark("pool_collect")
                                while not job.done():
                                    yield EXEC
                        span.end("ok")
                        return True
            except DeadlineExceeded as exc:
                last = exc
        span.end("exhausted")
        self._flight_dump("retry_exhausted",
                          what=f"chunked PARAM read from server {srank}",
                          attempts=self._retry.attempts, peer=srank)
        raise RetryExhausted(
            f"chunked PARAM read from server {srank}",
            self._retry.attempts, last)

    def _encode(self, view: np.ndarray, wire: Optional[np.ndarray],
                residual: Optional[np.ndarray] = None) -> np.ndarray:
        """The slice itself for the identity codec (zero-copy send);
        otherwise the encoded frame in the per-server staging buffer —
        behind the [epoch, seq] header slot when FT framing is on.  The
        encode (and its residual fold) happens exactly once per op;
        retries resend these bytes."""
        if wire is None:
            return view
        body = wire[self._hdr :]
        if self.codec.identity:
            body[:] = view.view(np.uint8)
        else:
            self.codec.encode_into(view, body, residual=residual)
        return wire

    def _decode_framed(self, wire: np.ndarray, out: np.ndarray) -> None:
        body = wire[self._hdr_rx :]
        if self.codec.identity:
            out.view(np.uint8)[:] = body
        else:
            self.codec.decode_into(body, out)

    def residual_norm(self) -> float:
        """L2 norm of the error-feedback residuals across shards — 0.0
        for residual-free codecs.  Observability/test hook."""
        residuals = list(self._residual.values()) + \
            list(self._sc_residual.values())
        if not residuals:
            return 0.0
        return float(np.sqrt(sum(float(np.dot(r, r)) for r in residuals)))

    # -- the streamed round's gate and sink (optim/sync.py) ------------------

    def stream_shards(
        self,
        staged: Callable[[Shard], int],
        landed: Callable[[Shard, int], None],
    ) -> Optional[List[Shard]]:
        """An optional extension of ``ParamClientAPI``
        (optim/client_api.py; shells test for it by name): install a
        shell's per-shard gate and sink and return the cut they will be
        called with, one shard a server in channel order.  From
        then on a GRAD op asks ``staged(shard)``, how many bytes of its
        slice of ``grad`` are staged from the front, before it touches
        the slice, yielding to the other channels until the answer is
        enough: the whole slice, or its first byte where the send reads
        the shell's pieces as they land (:meth:`stream_pieces`,
        :meth:`_send_grad`) and never the slice.  A PARAM op
        calls ``landed(shard, nbytes)`` with the bytes of its slice of
        ``param`` that are whole, from the slice's front: once, with all
        of them, when the slice is whole; and before that, where the
        receive lands in the slice itself and the transport says how far
        (:meth:`_lands`), whenever the mark has moved (a mark
        lower than the last takes the earlier ones back: a message that
        had begun to land was abandoned).  An op that is aborted says
        nothing more, and what it said of its shard is void.
        Both run on this client's thread and must not block.  The wire
        does not change: each server still
        sees its GRAD and then its PARAM request, in that order
        (docs/PROTOCOL.md §1, pairing rules).  Under shardctl the ops
        address shards that move between owners, not channels: nothing
        is installed and None is returned, and the shell moves the
        vector as one shard."""
        if self._sc:
            return None
        self._staged, self._landed = staged, landed
        return list(self.shards)

    def stream_pieces(
        self,
        pieces: Callable[[Shard], Optional[Callable[[int], List[np.ndarray]]]],
    ) -> List[bool]:
        """The second half of the extension, for a client that gave its
        cut (:meth:`stream_shards`): say which shards' GRAD sends read the
        shell's pieces where they lie, one answer a shard of the cut, and
        take the hook that hands them over.  Such a shard need not be
        staged into ``grad`` at all: its op waits for its first staged
        byte, asks ``pieces(shard)`` for this round's feed and sends what
        the feed gives, piece by piece (``aio_send(pieces=...)``: the feed
        is told how many bytes are in the ring and returns the arrays that
        landed since, on this client's thread; it must not block).
        Between rounds ``pieces(shard)`` is None and the op sends its
        slice of ``grad`` whole.  A shard is followed where the payload is
        the slice itself and the transport can hold a send made of pieces
        (:meth:`_follows`); every other shard answers False and is staged
        into ``grad`` and gated whole, as before."""
        self._pieces = pieces
        return [self._follows(srank) for srank in self.sranks]

    def _bare(self, srank: int) -> bool:
        """Whether server ``srank``'s GRAD and PARAM payloads are its
        slices of ``grad`` and ``param`` byte for byte: identity codec (a
        codec encodes from the slice and decodes into it), unframed (the
        framed wire stamps and retries staged bytes and receives into a
        frame) and unchunked."""
        return (self._grad_wire.get(srank) is None and not self.ft.framed
                and not self._chunked)

    def _follows(self, srank: int) -> bool:
        """Whether server ``srank``'s GRAD may be sent from anywhere its
        bytes lie: the payload is the slice itself (:meth:`_bare`) and the
        transport can hold a send made of pieces."""
        return self._bare(srank) and hasattr(self.transport, "append")

    def _lands(self, srank: int) -> bool:
        """Whether server ``srank``'s PARAM may be read as it lands: the
        receive's buffer is the slice itself (:meth:`_bare`) and the
        transport says how far it is filled as that moves."""
        return self._bare(srank) and hasattr(self.transport, "follow")

    def _gate(self, shard: Shard, nbytes: int):
        """Yield until the shell has staged ``nbytes`` of ``shard``;
        returns the milliseconds that took by the recorder's clock (0.0
        with obs off), which the GRAD span opened next carries as
        ``gated_ms``."""
        t0 = self._spans.clock()
        self._gating += 1
        try:
            while self._staged(shard) < nbytes:
                yield EXEC
        finally:
            self._gating -= 1
        return (self._spans.clock() - t0) * 1e3

    def _why_asleep(self) -> str:
        """What the pending ops waited for when the scheduler backed off
        (one of :data:`SLEEP_REASONS`; asked while recording only), from
        what this client and its transport already hold: ``staging``, a
        GRAD op stands at the gate or its send has placed every piece
        the shell has staged; ``apply``, a PARAM's receive is posted and
        its server has not begun to send; ``drain``, a send has bytes
        left that its server's ring has no room for; ``pull``, a PARAM is
        landing and its next chunks are not published.  Several at once
        count as the first of that order; none of them (an ack awaited:
        the server is still taking the message in) as ``drain``."""
        waits = self._waiting() if self._waiting is not None else ()
        if self._gating or "unready" in waits:
            return "staging"
        if "unanswered" in waits:
            return "apply"
        if "partial" in waits and "blocked" not in waits:
            return "pull"
        return "drain"

    # -- public async API (reference pclient.lua:84-109) --------------------

    def _enqueue(self, srank: int, gen: Generator, name: str) -> None:
        queue = self._opq.setdefault(srank, deque())
        queue.append((gen, name))
        if not self._pump_live.get(srank, False):
            self._pump_live[srank] = True
            self._pump_task[srank] = None
            task = self.sched.spawn(self._pump(srank), name=f"pump:{srank}:{name}")
            self._pump_task[srank] = task

    def _pump(self, srank: int):
        """Run this server's queued ops strictly in order, renaming the
        task per dequeued op — a pump that kept its spawn-time name
        (e.g. ``pump:3:send_grad``) for life would misattribute every
        later op in scheduler error/debug output."""
        queue = self._opq[srank]
        try:
            while queue:
                op, opname = queue.popleft()
                task = self._pump_task.get(srank)
                if task is not None:
                    task.name = f"pump:{srank}:{opname}"
                yield from op
        finally:
            self._pump_live[srank] = False

    def enqueue_wire_op(self, srank: int, gen: Generator,
                        name: str) -> None:
        """Public hook for the device exchange (mpit_tpu.dplane): run
        one wire op generator through ``srank``'s FIFO pump, exactly as
        the ``async_*`` conveniences do.  The dplane ExchangeClient
        routes per-server — device-eligible servers bypass the wire,
        everyone else enters here with codecs/framing/retry intact."""
        self._enqueue(srank, gen, name)

    def async_send_grad(self) -> None:
        if self._sc:
            for e in self.smap.entries:
                self._sc_enqueue(
                    self._sc_write_op(e.shard_id, tags.GRAD, tags.GRAD_ACK,
                                      "GRAD"), "send_grad")
            return
        for srank, shard in zip(self.sranks, self.shards):
            self._enqueue(srank, self._send_grad(srank, shard), "send_grad")

    def async_recv_param(self) -> None:
        if self._sc:
            for e in self.smap.entries:
                self._sc_enqueue(self._sc_read_op(e.shard_id), "recv_param")
            return
        for srank, shard in zip(self.sranks, self.shards):
            self._enqueue(srank, self._recv_param(srank, shard), "recv_param")

    def async_send_param(self) -> None:
        if self._sc:
            for e in self.smap.entries:
                self._sc_enqueue(
                    self._sc_write_op(e.shard_id, tags.PARAM_PUSH,
                                      tags.PARAM_PUSH_ACK, "PARAM_PUSH"),
                    "send_param")
            return
        for srank, shard in zip(self.sranks, self.shards):
            self._enqueue(srank, self._send_param(srank, shard), "send_param")

    def ping(self, n: int = 1) -> None:
        """Single-step I/O progress to overlap with compute
        (reference pclient.lua:131-136)."""
        self._maybe_heartbeat()
        self._sc_poll_map()
        self._drain_clock_echoes()
        for _ in range(n):
            self.sched.ping()

    def wait(self) -> None:
        if self.ft.heartbeat_s > 0:
            # Keep beating while blocked on slow servers: the wait loop is
            # exactly where a stalled gang would otherwise go silent and
            # get this client evicted.
            while self.sched.queue:
                self._maybe_heartbeat()
                self._sc_poll_map()
                self._drain_clock_echoes()
                self.sched.ping_pass()
            if self.sched.errors:
                self._raise_once(self.sched.errors.pop(0))
            return
        try:
            self.sched.wait()
        except TaskError as first:
            self._raise_once(first)

    def _raise_once(self, first: TaskError) -> None:
        """Raise ``first``, and forget the errors of the ops that failed
        with the very same exception: one cause met by several channels
        (a staging that died under two shards' gates) is raised once, so
        the next ``wait`` does not fail for the last round's reason."""
        self.sched.errors = [err for err in self.sched.errors
                             if err.cause is not first.cause]
        raise first

    # -- shutdown (reference pclient.lua:153-164) ---------------------------

    def stop(self) -> None:
        # Chained per server, so the stop cannot overtake in-flight ops
        # (the reference's drain-then-stop care, init.lua:50-58, README:71).
        if self._sc:
            # The global shardctl pump gives the same drain-then-stop
            # ordering; the controller counts client STOPs too — its
            # exit condition mirrors the servers'.  Membership may have
            # changed since launch: STOP every server this incarnation
            # greeted plus every current owner (a scaled-up joiner waits
            # for our STOP like any launch member), and never a retired
            # rank — it already said goodbye and exited.
            self._sc_poll_map()
            owners = set(self.smap.owners()) if self.smap is not None else set()
            stop_to = sorted(
                (set(self._sc_greeted or self.sranks) | owners)
                - self._sc_retired) + (
                [self.controller_rank] if self.controller_rank is not None
                else [])
            for dst in stop_to:
                self._sc_enqueue(
                    aio_send(self.transport, tags.EMPTY, dst, tags.STOP,
                             live=self.live, deadline=self._op_deadline()),
                    "send_stop",
                )
            self.wait()
            self.live.stop()
            return
        for srank in self.sranks:
            self._enqueue(
                srank,
                aio_send(self.transport, tags.EMPTY, srank, tags.STOP,
                         live=self.live, deadline=self._op_deadline()),
                "send_stop",
            )
        self.wait()
        self.live.stop()
