"""Pipelined streaming transfers (FLAG_CHUNKED, docs/PROTOCOL.md §12).

The contract under test: chunking a shard transfer into K independent
frames changes *when* bytes move and applies run, and nothing else —
final params are BITWISE equal to unchunked transfers under any
drop/dup/delay fault pattern for the ``none`` and ``bf16`` codecs; under
``int8`` a faulty chunked run is bitwise the fault-free chunked run,
error-feedback residual included, and chunked is unchunked to one ulp of
float32 an apply (two XLA programs round differently: §12.5).  Chunk-level faults come free from the
message-atomic FaultPlan seam: each chunk is its own message, so
``drop_every=3`` on the GRAD channel drops individual *chunks*.

Topology notes mirror tests/test_ft.py: client-side plans fault the
chunk data channels (GRAD / PARAM_REQ / PARAM_PUSH), server-side plans
the per-chunk acks and reply-chunk streams (GRAD_ACK / PARAM /
PARAM_PUSH_ACK).  Lockstep rounds pin the cross-client apply order so
faulty and fault-free runs are bitwise-comparable.
"""

import threading
import time

import numpy as np
import pytest

from mpit_tpu.aio import TaskError
from mpit_tpu.comm import codec as codec_mod
from mpit_tpu.comm.local import LocalRouter
from mpit_tpu.ft import (
    DUP,
    FRESH,
    STALE,
    DedupTable,
    FaultPlan,
    FaultyTransport,
    FTConfig,
    PacedTransport,
    RetryExhausted,
    chunk_elems_for,
    chunk_spans,
    chunk_stride,
)
from mpit_tpu.ps import ParamClient, ParamServer, tags

DATA_TAGS = frozenset({tags.GRAD, tags.PARAM_REQ, tags.PARAM_PUSH})
REPLY_TAGS = frozenset({tags.GRAD_ACK, tags.PARAM, tags.PARAM_PUSH_ACK})

#: fast retry posture for LocalRouter-speed gangs; chunk_bytes=8192 cuts
#: a f32 shard at 2048-element boundaries (block-aligned by fiat).
def stream_ft(chunk_bytes=8192, deadline=2.0, retries=10):
    return FTConfig(op_deadline_s=deadline, max_retries=retries,
                    backoff_base_s=0.005, backoff_cap_s=0.02,
                    chunk_bytes=chunk_bytes)


def join_all(threads, timeout=60):
    for t in threads:
        t.join(timeout)
        assert not t.is_alive(), "role thread did not stop (hang)"


def assert_chunked_is_unchunked(unchunked, chunked, codec_name, applies):
    """What §12.5 promises of a chunked run against an unchunked one,
    two different XLA programs: the same bits under ``none`` and
    ``bf16``; under ``int8`` one ulp of float32 an apply a shard took
    (``applies``), because the compiler contracts the decode's multiply
    and the rule's add into one fused multiply-add, one rounding, in
    one program and not in the other (on this builder's machine two
    ulps after six applies, in the first block of a tailed shard)."""
    if codec_name == "int8":
        np.testing.assert_array_max_ulp(unchunked, chunked, maxulp=applies)
    else:
        np.testing.assert_array_equal(unchunked, chunked)


# ---------------------------------------------------------------------------
# wire units


class TestChunkWire:
    def test_chunk_elems_block_aligned(self):
        assert chunk_elems_for(8192, 4) == 2048
        assert chunk_elems_for(4 << 20, 4) == 1024 * 1024
        assert chunk_elems_for(1, 4) == 1024  # floor: one block
        assert chunk_elems_for(5000, 4) == 1024  # rounds DOWN to blocks
        assert chunk_elems_for(8192, 8) == 1024

    def test_chunk_spans_cover_exactly(self):
        spans = chunk_spans(5000, 2048)
        assert spans == [(0, 2048), (2048, 4096), (4096, 5000)]
        assert chunk_spans(4096, 2048) == [(0, 2048), (2048, 4096)]
        assert chunk_spans(100, 2048) == [(0, 100)]

    def test_chunk_stride_aligned(self):
        assert chunk_stride(32, 8192) % 64 == 0
        assert chunk_stride(32, 8192) >= 32 + 8192

    @pytest.mark.parametrize("codec_name", ["none", "bf16", "int8"])
    def test_chunk_frames_bit_identical_to_full_frame(self, codec_name):
        """Per-chunk encode == the corresponding regions of the
        whole-shard encode (gather_chunk), and chunked decode == full
        decode — the §12.2 block-boundary invariant, residual fold
        included."""
        codec = codec_mod.get(codec_name)
        rng = np.random.default_rng(7)
        size = 5000
        x = rng.normal(size=size).astype(np.float32)
        full = np.zeros(codec.wire_nbytes(size), np.uint8)
        r_full = np.zeros(size, np.float32)
        codec.encode_into(x, full,
                          residual=r_full if codec.uses_residual else None)
        r_chunk = np.zeros(size, np.float32)
        out_full = np.zeros(size, np.float32)
        codec.decode_into(full, out_full)
        out_chunk = np.zeros(size, np.float32)
        for lo, hi in chunk_spans(size, 2048):
            frame = np.zeros(codec.wire_nbytes(hi - lo), np.uint8)
            codec.encode_into(
                x[lo:hi], frame,
                residual=r_chunk[lo:hi] if codec.uses_residual else None)
            ref = np.zeros_like(frame)
            codec_mod.gather_chunk(codec, full, size, lo, hi, ref)
            np.testing.assert_array_equal(frame, ref)
            codec.decode_into(frame, out_chunk[lo:hi])
            # scatter is gather's exact inverse
            back = np.zeros_like(full)
            codec_mod.scatter_chunk(codec, back, size, lo, hi, frame)
            np.testing.assert_array_equal(
                back[back != 0], full[back != 0])
        np.testing.assert_array_equal(out_full, out_chunk)
        if codec.uses_residual:
            np.testing.assert_array_equal(r_full, r_chunk)

    def test_unaligned_chunk_start_rejected(self):
        codec = codec_mod.get("int8")
        with pytest.raises(ValueError, match="aligned"):
            codec.chunk_regions(5000, 100, 2048)


# ---------------------------------------------------------------------------
# per-(op, chunk) dedup


class TestChunkDedup:
    def test_admit_commit_cycle(self):
        t = DedupTable()
        assert t.admit_chunk(1, tags.GRAD, 0, 1, 0, 3) == (FRESH, False)
        assert t.admit_chunk(1, tags.GRAD, 0, 1, 0, 3) == (DUP, False)
        assert t.admit_chunk(1, tags.GRAD, 0, 1, 2, 3) == (FRESH, False)
        assert t.admit_chunk(1, tags.GRAD, 0, 1, 1, 3) == (FRESH, True)
        # every chunk of the committed op now DUPs (re-ack path)
        assert t.admit_chunk(1, tags.GRAD, 0, 1, 1, 3) == (DUP, False)
        assert t.is_committed(1, tags.GRAD, 0, 1)
        # next op starts clean
        assert t.admit_chunk(1, tags.GRAD, 0, 2, 0, 3) == (FRESH, False)
        assert not t.is_committed(1, tags.GRAD, 0, 2)

    def test_stale_epoch_and_abandoned_partial(self):
        t = DedupTable()
        t.admit_chunk(1, tags.GRAD, 1, 1, 0, 2)
        assert t.admit_chunk(1, tags.GRAD, 0, 9, 0, 2)[0] == STALE
        # a newer seq abandons the in-flight partial silently
        assert t.admit_chunk(1, tags.GRAD, 1, 2, 0, 2) == (FRESH, False)
        assert t.admit_chunk(1, tags.GRAD, 1, 2, 1, 2) == (FRESH, True)

    def test_partial_state_roundtrip_grad_only(self):
        t = DedupTable()
        t.admit_chunk(1, tags.GRAD, 0, 5, 1, 3)
        t.admit_chunk(1, tags.PARAM_PUSH, 0, 2, 0, 3)
        part = t.partial_state(tags={tags.GRAD})
        assert list(part) == [f"1:{tags.GRAD}"]
        fresh = DedupTable()
        fresh.restore_partial(part)
        # the restored partial dedups the already-applied chunk and
        # commits on the remainder — the restart consistency cut
        assert fresh.admit_chunk(1, tags.GRAD, 0, 5, 1, 3) == (DUP, False)
        assert fresh.admit_chunk(1, tags.GRAD, 0, 5, 0, 3) == (FRESH, False)
        assert fresh.admit_chunk(1, tags.GRAD, 0, 5, 2, 3) == (FRESH, True)


# ---------------------------------------------------------------------------
# gang harness (test_ft.py idiom, chunked)


def launch_stream(nservers, nclients, client_ft, client_plans=None,
                  server_plan=None, rule="add", codec=None,
                  pace_mbs=0.0):
    n = nservers + nclients
    router = LocalRouter(n)
    sranks = list(range(nservers))
    cranks = list(range(nservers, n))
    servers, threads = [], []
    for r in sranks:
        ep = router.endpoint(r)
        if pace_mbs:
            ep = PacedTransport(ep, pace_mbs)
        if server_plan is not None:
            ep = FaultyTransport(ep, server_plan)
        servers.append(ParamServer(r, cranks, ep, rule=rule,
                                   ft=FTConfig(rejoin=True)))
        threads.append(threading.Thread(target=servers[-1].start,
                                        daemon=True))
    for t in threads:
        t.start()
    clients = []
    for i, r in enumerate(cranks):
        ep = router.endpoint(r)
        if pace_mbs:
            ep = PacedTransport(ep, pace_mbs)
        plan = (client_plans or {}).get(i)
        if plan is not None:
            ep = FaultyTransport(ep, plan)
        clients.append(ParamClient(r, sranks, ep,
                                   seed_servers=(r == cranks[0]),
                                   codec=codec, ft=client_ft))
    return servers, clients, threads


def run_gang(nservers, nclients, client_ft, rounds=3, size=10000,
             client_plans=None, server_plan=None, rule="add", codec=None,
             pace_mbs=0.0, seed=42):
    """Seed, run lockstep rounds, read back: returns (final params of
    client 0, stats)."""
    rng = np.random.default_rng(seed)
    w0 = rng.normal(size=size).astype(np.float32)
    gtab = rng.normal(size=(nclients, max(rounds, 1), size)).astype(
        np.float32)
    servers, clients, threads = launch_stream(
        nservers, nclients, client_ft, client_plans=client_plans,
        server_plan=server_plan, rule=rule, codec=codec,
        pace_mbs=pace_mbs)
    params, starters = [], []
    for i, c in enumerate(clients):
        p = w0.copy() if i == 0 else np.zeros(size, np.float32)
        g = np.zeros(size, np.float32)
        params.append((p, g))
        starters.append(threading.Thread(target=c.start, args=(p, g),
                                         daemon=True))
    for t in starters:
        t.start()
    join_all(starters)
    for r in range(rounds):
        for i, c in enumerate(clients):
            params[i][1][:] = gtab[i, r]
            c.async_send_grad()
            c.wait()
    clients[0].async_recv_param()
    clients[0].wait()
    stats = {
        "applied": sum(s.grads_applied for s in servers),
        "dups": sum(s.dup_ops for s in servers),
        "retries": sum(c.retries for c in clients),
    }
    for c in clients:
        c.stop()
    join_all(threads)
    return params[0][0].copy(), stats


# ---------------------------------------------------------------------------
# end-to-end bitwise equality


class TestChunkedBitwise:
    @pytest.mark.parametrize("codec_name", ["none", "bf16", "int8"])
    @pytest.mark.parametrize("size", [10000, 16384])
    def test_chunked_equals_unchunked(self, codec_name, size):
        """Fault-free: a chunked gang's final params equal the
        unchunked framed gang's — bitwise under ``none`` and ``bf16``,
        to an ulp an apply under ``int8`` — on tailed (10000 ⇒
        5000/server) and block-multiple (16384) shards, which exercise
        both roundings of the fused-vs-host chunk apply (§12.5); and
        the chunked configuration run twice gives the same bits."""
        clean, _ = run_gang(2, 2, stream_ft(chunk_bytes=0), size=size,
                            codec=codec_name)
        chunked, st = run_gang(2, 2, stream_ft(), size=size,
                               codec=codec_name)
        assert_chunked_is_unchunked(clean, chunked, codec_name,
                                    applies=3 * 2)
        assert st["retries"] == 0
        again, _ = run_gang(2, 2, stream_ft(), size=size, codec=codec_name)
        np.testing.assert_array_equal(chunked, again)

    def test_chunked_equals_unchunked_stateful_rule(self):
        clean, _ = run_gang(2, 2, stream_ft(chunk_bytes=0), rule="rmsprop",
                            codec="int8")
        chunked, _ = run_gang(2, 2, stream_ft(), rule="rmsprop",
                              codec="int8")
        np.testing.assert_array_equal(clean, chunked)

    def test_chunk_drop_dup_matrix_bitwise(self):
        """The §12 acceptance matrix: every 3rd chunk message dropped +
        every 4th duplicated client-side, every 5th ack/reply chunk
        dropped + every 3rd duplicated server-side — final params must
        equal the fault-free *unchunked* run bitwise, with retries and
        dups actually flowing."""
        clean, _ = run_gang(2, 2, stream_ft(chunk_bytes=0))
        client_plans = {
            i: FaultPlan(seed=i, drop_every=3, dup_every=4, tags=DATA_TAGS)
            for i in range(2)
        }
        server_plan = FaultPlan(seed=9, drop_every=5, dup_every=3,
                                tags=REPLY_TAGS)
        faulty, st = run_gang(
            2, 2, stream_ft(deadline=0.3), client_plans=client_plans,
            server_plan=server_plan)
        np.testing.assert_array_equal(clean, faulty)
        assert st["retries"] > 0, "the plan never forced a chunk resend?"
        assert st["dups"] > 0, "no duplicate chunk was ever re-acked?"

    def test_int8_error_feedback_exact_under_chunk_faults(self):
        """The safety: a chunked run under chunk faults is the
        fault-free chunked run to the bit — every error-feedback block
        folded exactly once, a retry resending the same bytes — and
        both are the unchunked run to an ulp an apply (§12.5)."""
        clean, _ = run_gang(2, 2, stream_ft(), codec="int8")
        client_plans = {
            i: FaultPlan(seed=31 + i, drop_every=3, dup_every=5,
                         tags=DATA_TAGS)
            for i in range(2)
        }
        faulty, st = run_gang(2, 2, stream_ft(deadline=0.3),
                              client_plans=client_plans, codec="int8")
        np.testing.assert_array_equal(clean, faulty)
        assert st["retries"] > 0
        unchunked, _ = run_gang(2, 2, stream_ft(chunk_bytes=0), codec="int8")
        assert_chunked_is_unchunked(unchunked, faulty, "int8", applies=3 * 2)

    def test_unsplittable_rule_refused_loudly(self):
        """Adam's scalar step counter cannot split across chunks — the
        negotiation must refuse, not corrupt quietly (§12.5)."""
        with pytest.raises((TaskError, RetryExhausted, AssertionError)):
            run_gang(1, 1, stream_ft(deadline=0.3, retries=2),
                     rounds=1, rule="adam")

    def test_paced_link_runs_clean(self):
        """The PacedTransport link model (bench/smoke seam) preserves
        correctness: a chunked gang over a modeled 200 MB/s link stays
        bitwise-equal to the unpaced unchunked control."""
        clean, _ = run_gang(1, 1, stream_ft(chunk_bytes=0), rounds=2)
        paced, _ = run_gang(1, 1, stream_ft(deadline=5.0), rounds=2,
                            pace_mbs=200.0)
        np.testing.assert_array_equal(clean, paced)


# ---------------------------------------------------------------------------
# legacy interop


class TestLegacyInterop:
    def test_no_flag_pairs_byte_for_byte_unchanged(self):
        """A pair that never negotiates FLAG_CHUNKED produces the exact
        pre-§12 wire: v3 announcements, whole-frame messages, 2-word
        acks.  (Byte-compat is asserted at the message level via the
        router mailboxes.)"""
        router = LocalRouter(2)
        sent = []
        ep = router.endpoint(1)
        inner_isend = ep.isend

        def spy(data, dst, tag):
            sent.append((tag, np.asarray(data).nbytes
                         if isinstance(data, np.ndarray) else len(data)))
            return inner_isend(data, dst, tag)

        ep.isend = spy
        server = ParamServer(0, [1], router.endpoint(0), rule="add")
        th = threading.Thread(target=server.start, daemon=True)
        th.start()
        ft = FTConfig(op_deadline_s=5.0)  # framed, NOT chunked
        client = ParamClient(1, [0], ep, seed_servers=True, ft=ft)
        size = 4096
        client.start(np.zeros(size, np.float32),
                     np.ones(size, np.float32))
        client.async_send_grad()
        client.wait()
        client.stop()
        join_all([th])
        init = [n for t, n in sent if t == tags.INIT]
        assert init == [40], f"framed non-chunked INIT must stay v3: {init}"
        grads = [n for t, n in sent if t == tags.GRAD]
        assert grads == [16 + 4 * size], (
            "non-chunked GRAD must stay one whole [hdr|body] frame")

    def test_chunked_init_is_v5(self):
        router = LocalRouter(2)
        sent = []
        ep = router.endpoint(1)
        inner_isend = ep.isend

        def spy(data, dst, tag):
            sent.append((tag, np.asarray(data).nbytes
                         if isinstance(data, np.ndarray) else len(data)))
            return inner_isend(data, dst, tag)

        ep.isend = spy
        server = ParamServer(0, [1], router.endpoint(0), rule="add")
        th = threading.Thread(target=server.start, daemon=True)
        th.start()
        client = ParamClient(1, [0], ep, seed_servers=True, ft=stream_ft())
        size = 4096
        client.start(np.zeros(size, np.float32),
                     np.ones(size, np.float32))
        client.async_send_grad()
        client.wait()
        client.stop()
        join_all([th])
        init = [n for t, n in sent if t == tags.INIT]
        assert init == [48], f"chunked INIT must be v5 (48 B): {init}"
        grads = [(t, n) for t, n in sent if t == tags.GRAD]
        # 4096 f32 at 2048-elem chunks = 2 uniform frames
        assert len(grads) == 2
        assert len({n for _t, n in grads}) == 1, "chunk frames not uniform"

    def test_readonly_chunked_announce_rejected(self):
        from mpit_tpu.ft import FLAG_CHUNKED, FLAG_FRAMED, FLAG_READONLY

        server = ParamServer(0, [1], LocalRouter(3).endpoint(0),
                             rule="add", reader_ranks=[2])
        with pytest.raises(ValueError, match="READONLY"):
            server._negotiate(2, np.asarray(
                [0, 1024, 0, 0,
                 FLAG_FRAMED | FLAG_READONLY | FLAG_CHUNKED, 1024],
                np.int64).tobytes())


# ---------------------------------------------------------------------------
# server restart mid-stream (checkpoint consistency cut)


class TestChunkedRestart:
    def test_checkpoint_carries_grad_chunk_partials(self, tmp_path):
        """A checkpoint cut between chunk applies persists the partial
        admission set next to the partially-updated params, so a
        restarted server re-acks the applied chunks and the client
        completes the op by resending only the rest (§12.6)."""
        from mpit_tpu.utils.checkpoint import load_server_state

        router = LocalRouter(2)
        server = ParamServer(0, [1], router.endpoint(0), rule="add",
                             ft=FTConfig(rejoin=True))
        # Negotiate a chunked client by hand (INIT v5).
        from mpit_tpu.ft import FLAG_CHUNKED, FLAG_FRAMED, init_v5
        codec = server._negotiate(1, np.asarray(init_v5(
            0, 4096, 0, 0, FLAG_FRAMED | FLAG_CHUNKED, 2048)).tobytes())
        server._alloc_client(1, codec)
        # Admit + apply chunk 0 of seq 1 only.
        v, done = server.dedup.admit_chunk(1, tags.GRAD, 0, 1, 0, 2)
        assert (v, done) == (FRESH, False)
        grad = np.ones(2048, np.float32)
        server._apply_chunk(1, codec, grad.view(np.uint8), 0, 2048,
                            commit=False)
        path = server.save_state(str(tmp_path))
        _off, _size, _param, _state, meta = load_server_state(path)
        assert meta["dedup_chunks"] == {f"1:{tags.GRAD}": [0, 1, 2, [0]]}
        restarted = ParamServer(0, [1], router.endpoint(0), rule="add",
                                ft=FTConfig(rejoin=True))
        restarted.restore_state(path)
        # The resent chunk 0 dedups; chunk 1 completes the op.
        assert restarted.dedup.admit_chunk(1, tags.GRAD, 0, 1, 0, 2) == \
            (DUP, False)
        assert restarted.dedup.admit_chunk(1, tags.GRAD, 0, 1, 1, 2) == \
            (FRESH, True)
        assert restarted._chunk.get(1) == 2048
        np.testing.assert_array_equal(
            np.asarray(restarted.param)[:2048], grad)


# ---------------------------------------------------------------------------
# dplane chunk-apply parity


class TestHbmChunkApply:
    @pytest.mark.parametrize("codec_name", ["none", "int8"])
    def test_chunk_apply_matches_whole_apply(self, codec_name):
        """HbmSlot.apply_wire_chunk over every chunk == apply_wire of
        the whole frame, bitwise, for a block-multiple slot (the fused
        chunk rounding case) — and the donated update still consumes
        its buffers."""
        from mpit_tpu.dplane.hbm import HbmSlot, PlaneConfig
        from mpit_tpu.optim.rules import make as make_rule

        codec = codec_mod.get(codec_name)
        size = 4096
        rng = np.random.default_rng(3)
        g = rng.normal(size=size).astype(np.float32)
        wire = np.zeros(codec.wire_nbytes(size), np.uint8)
        codec.encode_into(g, wire)

        whole = HbmSlot(size, make_rule("add"), config=PlaneConfig())
        if codec.identity:
            whole.apply_wire(codec, wire.view(np.float32))
        else:
            whole.apply_wire(codec, codec.split_wire(wire, size))

        chunked = HbmSlot(size, make_rule("add"), config=PlaneConfig())
        spans = chunk_spans(size, 2048)
        for k, (lo, hi) in enumerate(spans):
            frame = np.zeros(codec.wire_nbytes(hi - lo), np.uint8)
            codec_mod.gather_chunk(codec, wire, size, lo, hi, frame)
            payload = (frame.view(np.float32) if codec.identity
                       else codec.split_wire(frame, hi - lo))
            chunked.apply_wire_chunk(codec, payload, lo, hi - lo,
                                     commit=(k == len(spans) - 1))
        assert chunked.version == whole.version == 1
        np.testing.assert_array_equal(np.asarray(whole.param),
                                      np.asarray(chunked.param))


# ---------------------------------------------------------------------------
# the §12 property test (ISSUE 13 satellite): random chunk-level plans


@pytest.mark.parametrize("codec_name", ["none", "bf16", "int8"])
@pytest.mark.parametrize("seed", range(5))
def test_property_chunk_faults_bitwise_or_loud(seed, codec_name):
    """Seed-deterministic random {drop, dup, delay} plans at CHUNK
    granularity (each chunk is its own message) across ≥5 seeds × every
    codec: the run either completes with final params equal to the
    fault-free *unchunked* control (bitwise under ``none`` and ``bf16``;
    under ``int8`` bitwise-equal to the fault-free *chunked* control,
    error feedback included, and within an ulp an apply of the unchunked
    one: §12.5) or fails loudly (RetryExhausted / TaskError).  Never a
    hang: the worker runs under a hard timeout."""
    rng = np.random.default_rng(seed * 1000 + codec_mod.get(
        codec_name).wire_id)
    nclients = int(rng.integers(1, 3))
    rounds = 2
    size = int(rng.choice([6144, 10000]))  # block-multiple AND tailed

    clean, _ = run_gang(2, nclients, stream_ft(chunk_bytes=0),
                        rounds=rounds, size=size, codec=codec_name,
                        seed=seed)

    client_plans = {
        i: FaultPlan(seed=seed * 17 + i, drop_rate=0.10, dup_rate=0.08,
                     delay_rate=0.15, delay_polls=4, tags=DATA_TAGS)
        for i in range(nclients)
    }
    server_plan = FaultPlan(seed=seed * 31 + 7, drop_rate=0.08,
                            dup_rate=0.08, delay_rate=0.15, delay_polls=4,
                            tags=REPLY_TAGS)
    box: dict = {}

    def run():
        try:
            box["params"], box["stats"] = run_gang(
                2, nclients,
                stream_ft(deadline=0.3, retries=8),
                rounds=rounds, size=size, client_plans=client_plans,
                server_plan=server_plan, codec=codec_name, seed=seed)
        except (TaskError, RetryExhausted, AssertionError) as exc:
            box["error"] = exc  # loud is an acceptable outcome

    worker = threading.Thread(target=run, daemon=True)
    worker.start()
    worker.join(120)  # the hard timeout: a hang is the forbidden outcome
    assert not worker.is_alive(), (
        "chunked faulty run HUNG (never-hang contract broken)")
    if "params" in box:
        assert_chunked_is_unchunked(clean, box["params"], codec_name,
                                    applies=rounds * nclients)
        if codec_name == "int8":
            chunked, _ = run_gang(2, nclients, stream_ft(), rounds=rounds,
                                  size=size, codec=codec_name, seed=seed)
            np.testing.assert_array_equal(chunked, box["params"])
    else:
        assert "error" in box  # failed loudly


# ---------------------------------------------------------------------------
# PacedTransport model units


class TestPacedTransport:
    def test_paces_serially_and_preserves_fifo(self):
        router = LocalRouter(2)
        paced = PacedTransport(router.endpoint(0), rate_mbs=1.0,
                               min_bytes=0)
        rx = router.endpoint(1)
        a = np.zeros(1 << 20, np.uint8)  # 1 MB = 1 s of modeled link
        t0 = time.monotonic()
        h1 = paced.isend(a, 1, 50)
        h2 = paced.isend(a[:1024], 1, 50)
        assert not rx.iprobe(0, 50)
        # pump below the due time: still on the link
        paced.test(h1)
        assert not h1.done and not rx.iprobe(0, 50)
        # tiny messages queue BEHIND the big one (serial link)
        deadline = time.monotonic() + 10
        while not (paced.test(h1) and paced.test(h2)):
            assert time.monotonic() < deadline
            time.sleep(0.01)
        assert time.monotonic() - t0 >= 1.0
        assert rx.iprobe(0, 50)

    def test_min_bytes_pass_through(self):
        router = LocalRouter(2)
        paced = PacedTransport(router.endpoint(0), rate_mbs=0.001,
                               min_bytes=4096)
        h = paced.isend(np.zeros(16, np.uint8), 1, 50)
        while not paced.test(h):
            pass
        assert router.endpoint(1).iprobe(0, 50)
