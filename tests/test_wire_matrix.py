"""The flag-lattice negotiation matrix (ISSUE 15): every one of the
2^7 client flag words (six assigned bits and the retired bit 5) × INIT
v1–v5, against every server posture config,
checked against the wire-schema registry's negotiation oracle
(mpit_tpu.analysis.schema.negotiate) — and one real wire op round-tripped
for every combination the lattice declares legal.

Two layers:

- ``TestNegotiationMatrix`` drives ``ParamServer._negotiate`` directly
  (transport=None) for every (version, flags, posture) cell and asserts
  accept/refuse AND the negotiated per-pair posture equal the oracle's
  verdict.  The schema registry and the server cannot quietly diverge:
  a new flag bit, requires edge, or negotiate-off rule lands in
  analysis/schema.py first or this matrix fails.
- ``TestLegalRoundTrips`` runs every oracle-accepted combination through
  a real in-process gang with a hand-rolled wire driver whose frame
  layouts are *derived from the oracle's effective posture* (ft/wire
  helpers) — announce, seed/push one op, read it back bitwise, stop.
  If the server's wire for a legal combo disagrees with the schema's
  predicted layout, the driver mis-frames and the leg fails loudly
  (deadline-bounded, never a hang).
"""

import threading
import time

import numpy as np
import pytest

import mpit_tpu.ft.wire as ftw
from mpit_tpu.analysis import schema
from mpit_tpu.comm.local import LocalRouter
from mpit_tpu.ps import ParamServer, tags
from mpit_tpu.shardctl import wire as scwire
from mpit_tpu.shardctl.shardmap import ShardMap

SIZE = 1024  # one codec block => single-chunk streams under FLAG_CHUNKED
CHUNK_ELEMS = 1024

#: (name, ParamServer kwargs, oracle kwargs) — the announcing rank is 1.
CONFIGS = [
    ("plain", {}, {}),
    ("reader", {"reader_ranks": [1]}, {"reader_rank": True,
                                       "serves_readers": True}),
]


def _announce_bytes(version: int, flags: int) -> bytes:
    if version == 1:
        return np.asarray([0, SIZE], np.int64).tobytes()
    if version == 2:
        return np.asarray([0, SIZE, 0], np.int64).tobytes()
    if version == 3:
        return ftw.init_v3(0, SIZE, 0, 0, flags).tobytes()
    if version == 5:
        return ftw.init_v5(0, SIZE, 0, 0, flags, CHUNK_ELEMS).tobytes()
    if version == 4:
        return scwire.init_v4(0, 0, flags,
                              ShardMap.initial(SIZE, [0])).tobytes()
    raise AssertionError(version)


def _await(cond, what="condition", deadline_s=30.0):
    """Bounded wait — a leg that cannot proceed fails the test instead
    of hanging it."""
    t0 = time.monotonic()
    while not cond():
        assert time.monotonic() - t0 < deadline_s, \
            f"{what} not within {deadline_s}s"
        time.sleep(0.0005)


def _fresh_server(server_kw, transport=None):
    # client_ranks=[2] keeps rank 1 free for the reader posture.
    return ParamServer(0, [2], transport, rule="add", **server_kw)


class TestNegotiationMatrix:
    """All 2^7 flag words × v1–v5 × 2 server postures: the real
    ``_negotiate`` must agree with the schema oracle cell for cell —
    refusals loud, acceptances with the exact effective posture."""

    @pytest.mark.parametrize("config", CONFIGS, ids=lambda c: c[0])
    @pytest.mark.parametrize("version", [1, 2, 3, 4, 5])
    def test_matrix_matches_oracle(self, config, version):
        name, server_kw, oracle_kw = config
        flag_sets = range(128) if version in (3, 4, 5) else [0]
        mismatches = []
        for flags in flag_sets:
            want = schema.negotiate(version, flags, **oracle_kw)
            server = _fresh_server(server_kw)
            try:
                server._negotiate(1, _announce_bytes(version, flags))
                accepted = True
            except (ValueError, AssertionError):
                accepted = False
            ctx = f"{name} v{version} flags={flags:#04x}"
            if accepted != want.accepted:
                mismatches.append(
                    f"{ctx}: server {'accepted' if accepted else 'refused'}"
                    f" but the schema says "
                    f"{'accept' if want.accepted else 'refuse'}"
                    + (f" ({want.reason})" if want.reason else ""))
                continue
            if not accepted:
                continue
            got = {
                "framed": server._framed.get(1, False),
                "heartbeat": server._hb.get(1, False),
                "staleness": server._stale_track.get(1, False),
                "timing": server._timing.get(1, False),
                "readonly": server._readonly.get(1, False),
                "chunked": bool(server._chunk.get(1, 0)),
                "shardctl": server._sc,
            }
            exp = {k: bool(getattr(want, k)) for k in got}
            if got != exp:
                diff = {k: (exp[k], got[k]) for k in got
                        if got[k] != exp[k]}
                mismatches.append(f"{ctx}: posture drift "
                                  f"(schema, server) = {diff}")
        assert not mismatches, "\n".join(mismatches)

    @pytest.mark.parametrize("role", ["writer", "reader"])
    @pytest.mark.parametrize("version", [3, 5])
    def test_retired_bit5_is_refused(self, version, role):
        """Bit 5 (32) of the v3/v5 flags word went with the multi-cell
        fabric and is not reused: the oracle refuses every word that
        carries it, naming the bit, and so does a running server that
        is sent one, where it gives the same word without the bit the
        oracle's verdict.  (The v4 word is another space — bit 2 there
        is FLAG_SHARDCTL — and a v4 server reads nothing of it but
        FRAMED and HEARTBEAT.)"""
        retired = schema.RETIRED_V3_BITS
        reader = role == "reader"
        oracle_kw = ({"reader_rank": True, "serves_readers": True}
                     if reader else {})
        for flags in range(128):
            if flags & retired:
                want = schema.negotiate(version, flags, **oracle_kw)
                assert not want.accepted and "bit 5" in want.reason, flags
        base = schema.flag_bits(
            "FRAMED", *(["READONLY"] if reader else []),
            *(["CHUNKED"] if version == 5 else []))
        for word in (base, base | retired):
            want = schema.negotiate(version, word, **oracle_kw)
            router = LocalRouter(3)
            server = ParamServer(0, [2], router.endpoint(0), rule="add",
                                 reader_ranks=[1] if reader else None,
                                 admit_ranks=None if reader else [1])
            failed = []

            def run():
                try:
                    server.start()
                except Exception as exc:  # noqa: BLE001 — the verdict
                    failed.append(exc)

            t = threading.Thread(target=run, daemon=True)
            t.start()
            try:
                writer = router.endpoint(2)
                writer.send(np.asarray([0, SIZE], np.int64), 0, tags.INIT)
                writer.send(np.arange(SIZE, dtype=np.float32), 0,
                            tags.PARAM_PUSH)
                _recv(writer, 0, tags.PARAM_PUSH_ACK)
                peer = router.endpoint(1)
                peer.send(np.frombuffer(_announce_bytes(version, word),
                                        np.int64), 0, tags.INIT)
                if want.accepted:
                    _await(lambda: 1 in server._codecs, "the attach")
                    peer.send(tags.EMPTY, 0, tags.STOP)
                else:
                    _await(lambda: server.sched.errors or failed,
                           "the refusal")
                writer.send(tags.EMPTY, 0, tags.STOP)
                _join(server, t)
            finally:
                server.live.stop()
            assert bool(failed) == (not want.accepted), (word, failed)
            if word & retired:
                assert "ValueError" in str(failed[0])
                assert "bit 5" in str(failed[0])

    def test_matrix_has_both_verdicts(self):
        """Sanity on the oracle itself: the v3 space must contain both
        legal and refused cells for every posture config."""
        for name, _, oracle_kw in CONFIGS:
            verdicts = {schema.negotiate(3, f, **oracle_kw).accepted
                        for f in range(128)}
            assert verdicts == {True, False}, name


# ---------------------------------------------------------------------------
# Round trips — one real op per legal combination
# ---------------------------------------------------------------------------


def _legal(version, **oracle_kw):
    flag_sets = range(128) if version in (3, 4, 5) else [0]
    return [f for f in flag_sets
            if schema.negotiate(version, f, **oracle_kw).accepted]


def _recv(wire, src, tag, deadline_s=30.0):
    """Bounded blocking receive returning the raw payload bytes."""
    _await(lambda: wire.iprobe(src, tag),
           f"message from {src} on tag {tag}", deadline_s)
    return bytes(wire.recv(src, tag))


def _push_and_read(wire, out: "schema.Outcome", w0: np.ndarray) -> None:
    """Seed-push w0 then read it back, framing every message exactly as
    the oracle's effective posture dictates."""
    body = w0.view(np.uint8)
    if out.chunked:
        chdr = ftw.chunk_hdr_bytes(out.timing)
        stride = ftw.chunk_stride(chdr, body.size)
        frame = np.zeros(stride, np.uint8)
        ftw.pack_chunk_header(frame, 0, 1, 0, 1)
        if out.timing:
            ftw.pack_tx_stamp(frame, chdr, 1)
        frame[chdr:chdr + body.size] = body
        wire.send(frame, 0, tags.PARAM_PUSH)
        ack = np.frombuffer(_recv(wire, 0, tags.PARAM_PUSH_ACK), np.int64)
        assert ack.size == (ftw.CHUNK_ACK_TIMING_WORDS if out.timing
                            else ftw.CHUNK_ACK_WORDS)
        assert (int(ack[0]), int(ack[1]), int(ack[2])) == (0, 1, 0)
    elif out.shardctl:
        frame = np.zeros(scwire.SC_HDR_BYTES + body.size, np.uint8)
        scwire.pack_sc_header(frame, 0, 1, 0, 0)
        frame[scwire.SC_HDR_BYTES:] = body
        wire.send(frame, 0, tags.PARAM_PUSH)
        ep, seq, status, sid, _ = scwire.parse_reply(
            _recv(wire, 0, tags.PARAM_PUSH_ACK))
        assert (ep, seq, status, sid) == (0, 1, scwire.OK, 0)
    elif out.framed:
        hdr = ftw.hdr_bytes(out.staleness, out.timing)
        frame = np.zeros(hdr + body.size, np.uint8)
        ftw.pack_header(frame, 0, 1)
        if out.staleness:
            ftw.pack_version(frame, 0)
        if out.timing:
            ftw.pack_tx_stamp(frame, hdr, 1)
        frame[hdr:] = body
        wire.send(frame, 0, tags.PARAM_PUSH)
        ack = np.frombuffer(_recv(wire, 0, tags.PARAM_PUSH_ACK), np.int64)
        assert ack.size == (ftw.ACK_TIMING_WORDS if out.timing else 2)
        assert (int(ack[0]), int(ack[1])) == (0, 1)
    else:
        wire.send(w0, 0, tags.PARAM_PUSH)
        assert _recv(wire, 0, tags.PARAM_PUSH_ACK) == b""

    # -- read it back -----------------------------------------------------
    if out.chunked:
        req = (ftw.timed_frame(0, 2, 1) if out.timing
               else ftw.header_frame(0, 2))
        wire.send(req, 0, tags.PARAM_REQ)
        raw = _recv(wire, 0, tags.PARAM)
        chdr = ftw.chunk_reply_hdr_bytes(out.timing)
        words = np.frombuffer(raw[:8 * ftw.CHUNK_REPLY_WORDS], np.int64)
        assert (int(words[0]), int(words[1])) == (0, 2)
        assert (int(words[2]), int(words[3])) == (0, 1)  # chunk 0 of 1
        got = np.frombuffer(raw[chdr:chdr + w0.nbytes], np.float32)
    elif out.shardctl:
        wire.send(scwire.sc_header(0, 1, 0, 0), 0, tags.PARAM_REQ)
        ep, seq, status, sid, payload = scwire.parse_reply(
            _recv(wire, 0, tags.PARAM))
        assert (ep, seq, status, sid) == (0, 1, scwire.OK, 0)
        got = np.frombuffer(payload, np.float32)
    elif out.framed:
        req = (ftw.timed_frame(0, 2, 1) if out.timing
               else ftw.header_frame(0, 2))
        wire.send(req, 0, tags.PARAM_REQ)
        raw = _recv(wire, 0, tags.PARAM)
        hdr = ftw.reply_hdr_bytes(out.staleness, out.timing)
        echo = np.frombuffer(raw[:16], np.int64)
        assert (int(echo[0]), int(echo[1])) == (0, 2)
        got = np.frombuffer(raw[hdr:], np.float32)
    else:
        wire.send(tags.EMPTY, 0, tags.PARAM_REQ)
        got = np.frombuffer(_recv(wire, 0, tags.PARAM), np.float32)
    np.testing.assert_array_equal(got, w0)


def _run_server(server):
    t = threading.Thread(target=server.start, daemon=True)
    t.start()
    return t


def _join(server, t):
    t.join(30)
    alive = t.is_alive()
    if alive:
        server.live.stop()
        t.join(5)
    assert not alive, "server did not stop (stop-protocol hang)"


class TestLegalRoundTrips:
    """Every oracle-accepted (version, flags, posture) combination ships
    one real op over the in-process transport and reads it back
    bitwise."""

    @pytest.mark.parametrize("version", [1, 2, 3, 5, 4])
    def test_writer_combos(self, version):
        w0 = np.arange(SIZE, dtype=np.float32)
        for flags in _legal(version):
            out = schema.negotiate(version, flags)
            router = LocalRouter(2)
            server = ParamServer(0, [1], router.endpoint(0), rule="add")
            t = _run_server(server)
            try:
                wire = router.endpoint(1)
                wire.send(np.frombuffer(
                    _announce_bytes(version, flags), np.int64), 0,
                    tags.INIT)
                _push_and_read(wire, out, w0)
                wire.send(tags.EMPTY, 0, tags.STOP)
                _join(server, t)
            finally:
                server.live.stop()

    def test_reader_combos(self):
        """READ-ONLY legs: status-framed reads (§8) for every legal
        reader flag set."""
        w0 = np.arange(SIZE, dtype=np.float32)
        legal = _legal(3, reader_rank=True, serves_readers=True)
        assert len(legal) == 8, legal  # {RO,FRAMED} x {HB,STALE,TIMING}
        for flags in legal:
            router = LocalRouter(3)
            server = ParamServer(0, [2], router.endpoint(0), rule="add",
                                 reader_ranks=[1])
            t = _run_server(server)
            try:
                writer = router.endpoint(2)
                writer.send(np.asarray([0, SIZE], np.int64), 0, tags.INIT)
                writer.send(w0, 0, tags.PARAM_PUSH)
                _recv(writer, 0, tags.PARAM_PUSH_ACK)
                reader = router.endpoint(1)
                reader.send(ftw.init_v3(0, SIZE, 0, 0, flags), 0,
                            tags.INIT)
                reader.send(ftw.header_frame(0, 1), 0, tags.PARAM_REQ)
                status = np.frombuffer(_recv(reader, 0, tags.PARAM),
                                       np.int64)
                assert status.size == 4
                assert (int(status[0]), int(status[1])) == (0, 1)
                assert int(status[2]) == scwire.OK
                got = np.frombuffer(_recv(reader, 0, tags.PARAM),
                                    np.float32)
                np.testing.assert_array_equal(got, w0)
                reader.send(tags.EMPTY, 0, tags.STOP)
                writer.send(tags.EMPTY, 0, tags.STOP)
                _join(server, t)
            finally:
                server.live.stop()
