"""Multi-cell serving fabric (docs/PROTOCOL.md §11): SUBSCRIBE posture,
diff-stream replication (bitwise frame equality), staleness-bounded
admission under injected diff-stream faults, kill-a-cell reader
failover with zero RetryExhausted, consistent-hash routing, and the
per-cell autoscale binding."""

import threading
import time

import numpy as np
import pytest

from mpit_tpu.cells import wire as cellwire
from mpit_tpu.cells.cell import ServingCell
from mpit_tpu.cells.ring import CellRing
from mpit_tpu.comm.tcp import TcpTransport, allocate_local_addresses
from mpit_tpu.ft import (
    FLAG_FRAMED,
    FLAG_READONLY,
    FLAG_SUBSCRIBE,
    FaultPlan,
    FaultyTransport,
    FTConfig,
    RetryExhausted,
    init_v3,
)
from mpit_tpu.ps import ParamClient, ParamServer, ReaderClient, tags
from mpit_tpu.ps.serve import parse_serve_header, serve_head


# ---------------------------------------------------------------------------
# wire units


class TestDiffWire:
    def test_pack_parse_roundtrip(self):
        body = np.arange(64, dtype=np.uint8)
        msg = cellwire.pack_diff(cellwire.DIFF_DELTA, 3, 5, 7, body)
        kind, f, t, head, out = cellwire.parse_diff(msg)
        assert (kind, f, t, head) == (cellwire.DIFF_DELTA, 3, 5, 7)
        np.testing.assert_array_equal(out, body)
        # headless FULL-with-empty-body parses too
        msg = cellwire.pack_diff(cellwire.DIFF_FULL, -1, 0, 0,
                                 np.zeros(0, np.uint8))
        assert cellwire.parse_diff(msg)[4].size == 0

    def test_chunked_pack_parse_roundtrip(self):
        """§11.8: a frame's chunk-message sequence reassembles to the
        exact body; a small body ships as one chunk message."""
        body = np.arange(100, dtype=np.uint8)
        msgs = cellwire.pack_diff_chunks(cellwire.DIFF_DELTA, 3, 5, 7,
                                         body, chunk_bytes=40)
        assert len(msgs) == 3
        pieces = []
        for i, msg in enumerate(msgs):
            kind, f, t, head, idx, count, piece = \
                cellwire.parse_diff_chunk(msg)
            assert (kind, f, t, head) == (cellwire.DIFF_DELTA, 3, 5, 7)
            assert (idx, count) == (i, 3)
            pieces.append(piece)
        np.testing.assert_array_equal(np.concatenate(pieces), body)
        assert len(cellwire.pack_diff_chunks(
            cellwire.DIFF_FULL, -1, 1, 1, body, chunk_bytes=1024)) == 1

    def test_malformed_frames_are_loud(self):
        with pytest.raises(ValueError, match="too short"):
            cellwire.parse_diff(b"\x00" * 8)
        msg = cellwire.pack_diff(cellwire.DIFF_FULL, -1, 1, 1,
                                 np.zeros(16, np.uint8))
        with pytest.raises(ValueError, match="promised"):
            cellwire.parse_diff(bytes(msg)[:-4])
        bad = np.frombuffer(bytes(msg), np.uint8).copy()
        bad[:8].view(np.int64)[0] = 99  # unknown kind
        with pytest.raises(ValueError, match="kind"):
            cellwire.parse_diff(bad)

    def test_xor_delta_is_exact_involution(self):
        rng = np.random.default_rng(7)
        a = rng.standard_normal(257).astype(np.float32)
        b = rng.standard_normal(257).astype(np.float32)
        delta = cellwire.xor_delta(a, b)
        rebuilt = cellwire.apply_delta(a, delta)
        # Bitwise — not allclose: the fabric's replication guarantee.
        assert rebuilt.tobytes() == b.tobytes()
        with pytest.raises(ValueError, match="size"):
            cellwire.xor_delta(a, np.zeros(3, np.uint8))

    def test_frame_history_bounded_and_memoized(self):
        hist = cellwire.FrameHistory(keep=3)
        frames = {v: np.full(8, v, np.uint8) for v in range(6)}
        for v, f in frames.items():
            hist.record(v, f)
        assert not hist.has(0) and not hist.has(2) and hist.has(3)
        d1 = hist.delta(4, 5)
        d2 = hist.delta(4, 5)
        assert d1 is d2  # memoized for the N-cells-same-version case
        np.testing.assert_array_equal(
            d1, np.bitwise_xor(frames[4], frames[5]))
        with pytest.raises(ValueError):
            cellwire.FrameHistory(keep=1)


class TestRing:
    def test_deterministic_and_covers_members(self):
        ring = CellRing([4, 5, 6], vnodes=16)
        assignments = {r: ring.lookup(r) for r in range(40)}
        assert assignments == {r: CellRing([4, 5, 6], vnodes=16).lookup(r)
                               for r in range(40)}
        assert set(assignments.values()) == {4, 5, 6}

    def test_down_member_only_moves_its_own_readers(self):
        ring = CellRing([4, 5, 6], vnodes=32)
        before = {r: ring.lookup(r) for r in range(64)}
        victim = 5
        ring.mark_down(victim)
        after = {r: ring.lookup(r) for r in range(64)}
        for r in range(64):
            if before[r] != victim:
                assert after[r] == before[r], "stable arc moved"
            else:
                assert after[r] != victim
        ring.mark_up(victim)
        assert {r: ring.lookup(r) for r in range(64)} == before

    def test_successors_and_exhaustion(self):
        ring = CellRing([2, 3], vnodes=8)
        succ = ring.successors(11)
        assert sorted(succ) == [2, 3] and succ[0] == ring.lookup(11)
        ring.mark_down(2)
        ring.mark_down(3)
        with pytest.raises(LookupError):
            ring.lookup(11)
        with pytest.raises(ValueError):
            CellRing([])


# ---------------------------------------------------------------------------
# posture validation (no I/O)


class TestPosture:
    def test_server_validates_subscribe_posture(self):
        server = ParamServer(0, [1], transport=None, reader_ranks=[2],
                             cell_ranks=[3])
        base = FLAG_FRAMED | FLAG_READONLY
        # subscribe without READONLY
        with pytest.raises(ValueError, match="FLAG_READONLY"):
            server._negotiate(3, init_v3(
                0, 16, 0, 0, FLAG_FRAMED | FLAG_SUBSCRIBE).tobytes())
        # subscribe from a non-cell rank
        with pytest.raises(ValueError, match="cell_ranks"):
            server._negotiate(2, init_v3(
                0, 16, 0, 0, base | FLAG_SUBSCRIBE).tobytes())
        # a cell rank must announce the posture
        with pytest.raises(ValueError, match="FLAG_SUBSCRIBE"):
            server._negotiate(3, init_v3(0, 16, 0, 0, base).tobytes())
        # the real thing is accepted
        codec = server._negotiate(3, init_v3(
            0, 16, 0, 0, base | FLAG_SUBSCRIBE).tobytes())
        assert codec.name == "none" and server._subscribe[3]

    def test_cell_roles_disjoint_and_shardctl_exclusive(self):
        with pytest.raises(ValueError, match="overlap"):
            ParamServer(0, [1], transport=None, cell_ranks=[1])
        with pytest.raises(ValueError, match="overlap"):
            ParamServer(0, [1], transport=None, reader_ranks=[2],
                        cell_ranks=[2])
        from mpit_tpu.shardctl.shardmap import ShardMap
        from mpit_tpu.shardctl.wire import init_v4
        server = ParamServer(0, [1], transport=None, cell_ranks=[3])
        smap = ShardMap.initial(64, [0])
        with pytest.raises(ValueError, match="mutually exclusive"):
            server._negotiate(1, init_v4(0, 0, FLAG_FRAMED,
                                         smap).tobytes())

    def test_cell_validates_reader_attach(self):
        cell = ServingCell(5, 0, None, [7], size=64,
                           ft=FTConfig(heartbeat_s=0.1, op_deadline_s=5.0))
        good = FLAG_FRAMED | FLAG_READONLY
        with pytest.raises(ValueError, match="read-only"):
            cell._negotiate(7, init_v3(0, 64, 0, 0, 0).tobytes())
        with pytest.raises(ValueError, match="reader_ranks"):
            cell._negotiate(9, init_v3(0, 64, 0, 0, good).tobytes())
        with pytest.raises(ValueError, match="mirrors"):
            cell._negotiate(7, init_v3(0, 32, 0, 0, good).tobytes())
        with pytest.raises(ValueError, match="subscription codec"):
            cell._negotiate(7, init_v3(0, 64, 2, 0, good).tobytes())
        with pytest.raises(ValueError, match="not to cells"):
            cell._negotiate(7, init_v3(
                0, 64, 0, 0, good | FLAG_SUBSCRIBE).tobytes())
        assert cell._negotiate(7, init_v3(
            0, 64, 0, 0, good).tobytes()).name == "none"

    def test_cell_requires_heartbeats(self):
        with pytest.raises(ValueError, match="heartbeat"):
            ServingCell(5, 0, None, [7], size=64,
                        ft=FTConfig(op_deadline_s=5.0))

    def test_serve_header_head_word(self):
        cell = ServingCell(5, 0, None, [7], size=64,
                           ft=FTConfig(heartbeat_s=0.1, op_deadline_s=5.0))
        cell._install(np.zeros(8, np.uint8), 6)
        cell._note_head(9)
        hdr = cell._serve_ok_header(1, 2)
        assert parse_serve_header(hdr)[:2] == (1, 2)
        assert serve_head(hdr) == 9
        # direct-server 4-word replies have no head word
        from mpit_tpu.ps.serve import serve_reply
        assert serve_head(serve_reply(1, 2, 0, 6)) is None


class TestFlightShapes:
    def test_cell_dump_shapes_validated(self, tmp_path):
        import json

        from mpit_tpu.obs import flight as obs_flight

        base = {"schema": "mpit_flight/1", "reason": "cell_lag_shed",
                "pid": 1, "wall_time": 0.0, "events": [], "metrics": {}}
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps(base))
        with pytest.raises(ValueError, match="extra"):
            obs_flight.validate_dump(str(bad))
        bad.write_text(json.dumps({**base, "extra": {"window": {}}}))
        with pytest.raises(ValueError, match="version"):
            obs_flight.validate_dump(str(bad))
        bad.write_text(json.dumps(
            {**base, "extra": {"window": {"version": 3}}}))
        with pytest.raises(ValueError, match="head"):
            obs_flight.validate_dump(str(bad))
        good = tmp_path / "good.json"
        good.write_text(json.dumps({**base, "extra": {
            "window": {"version": 3, "head": 9, "max_lag": 4}}}))
        assert obs_flight.validate_dump(str(good))["reason"] == \
            "cell_lag_shed"
        fo = {**base, "reason": "cell_failover",
              "extra": {"window": {"version": 3, "dead": 2,
                                   "successor": 4}}}
        good.write_text(json.dumps(fo))
        assert obs_flight.validate_dump(str(good))["reason"] == \
            "cell_failover"


# ---------------------------------------------------------------------------
# the fabric end-to-end (in-process TCP gangs)

SIZE = 2048


def _build_mesh(core, nranks, extra_addrs=0):
    addrs, socks = allocate_local_addresses(core)
    addrs = addrs + ["127.0.0.1:0"] * (nranks - core)
    tr = {}

    def build(r):
        tr[r] = TcpTransport(r, nranks, addrs, listener=socks[r],
                             reconnect=30.0, dial_peers=list(range(r)))

    ths = [threading.Thread(target=build, args=(r,)) for r in range(core)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(30)
    assert all(r in tr for r in range(core)), "core mesh construction hung"
    return addrs, tr


class _Gang:
    """1 server (rank 0) + 1 writer (rank 1) + N cells + M readers."""

    def __init__(self, ncells=2, nreaders=2, *, server_wrap=None,
                 max_lag=4, cell_hb=0.05, server_ft=None,
                 cell_chunk_bytes=0):
        self.ncells, self.nreaders = ncells, nreaders
        core = 2 + ncells
        self.nranks = core + nreaders
        self.cell_ranks = list(range(2, 2 + ncells))
        self.reader_ranks = list(range(core, self.nranks))
        self.addrs, self.tr = _build_mesh(core, self.nranks)
        ep = self.tr[0] if server_wrap is None else server_wrap(self.tr[0])
        self.server = ParamServer(
            0, [1], ep, rule="add", cell_ranks=self.cell_ranks,
            ft=server_ft or FTConfig(lease_ttl_s=10.0))
        self.sth = threading.Thread(target=self.server.start, daemon=True)
        self.sth.start()
        self.cells = {}
        self.cth = {}
        for c in self.cell_ranks:
            cell = ServingCell(
                c, 0, self.tr[c], reader_ranks=self.reader_ranks,
                size=SIZE, max_lag=max_lag,
                ft=FTConfig(heartbeat_s=cell_hb, op_deadline_s=10.0,
                            chunk_bytes=cell_chunk_bytes))
            self.cells[c] = cell

            def run(cell=cell):
                try:
                    cell.start()
                except RuntimeError:
                    pass  # killed mid-run (the chaos legs)

            self.cth[c] = threading.Thread(target=run, daemon=True)
            self.cth[c].start()
        self.client = ParamClient(1, [0], self.tr[1], seed_servers=True,
                                  ft=FTConfig(op_deadline_s=30.0))
        self.param = np.arange(SIZE, dtype=np.float32)
        self.grad = np.ones(SIZE, np.float32)
        self.client.start(self.param.copy(), self.grad)

    def commit(self, n=1):
        """n grad applies => n committed versions (each adds 1.0)."""
        for _ in range(n):
            self.client.async_send_grad()
            self.client.wait()

    def expected(self, version):
        """The upstream snapshot at ``version`` (seed = version 1)."""
        return self.param + float(max(version - 1, 0))

    def finish(self, timeout=60):
        self.client.stop()
        for c, t in self.cth.items():
            t.join(timeout)
            assert not t.is_alive(), f"cell {c} never stopped"
        self.sth.join(timeout)
        assert not self.sth.is_alive(), "server never stopped"

    def close(self):
        """Whatever the test did (it may have failed half way), nothing
        of the gang serves on after it."""
        self.server.live.stop()
        for cell in self.cells.values():
            cell.shutdown()
        for r, t in self.tr.items():
            t.close()


def _reader(gang, rank, rounds, out, deadline_s=10.0, read_sleep=0.0,
            failover_after=2):
    t = TcpTransport(rank, gang.nranks, gang.addrs, reconnect=30.0,
                     dial_peers=gang.cell_ranks, listen=False)
    rc = ReaderClient(rank, [0], t,
                      cells={0: gang.cell_ranks},
                      failover_after=failover_after,
                      ft=FTConfig(op_deadline_s=deadline_s,
                                  max_retries=8))
    mirror = np.zeros(SIZE, np.float32)
    rc.start(mirror)
    reads = []
    errors = []
    try:
        for _ in range(rounds):
            rc.read_params()
            v = rc.read_versions[0]
            reads.append((v, dict(rc.lags), mirror.copy()))
            if read_sleep:
                time.sleep(read_sleep)
    except RetryExhausted as exc:
        errors.append(exc)
    out[rank] = {"reads": reads, "errors": errors,
                 "monotone": rc.monotone, "failovers": rc.failovers,
                 "busy_honored": rc.busy_honored}
    rc.stop()
    t.close()


class TestFabric:
    def test_cells_serve_bitwise_with_one_diff_stream(self):
        """2 cells x 2 readers: every read decodes bit-for-bit the
        upstream snapshot at its stamped version, versions are monotone
        per cell, reader lag never exceeds the bound, and the upstream
        answered no reader PARAM at all — the cells absorbed the read
        fan-out on one diff stream each."""
        gang = _Gang(ncells=2, nreaders=2)
        try:
            gang.commit(3)
            out = {}
            rth = [threading.Thread(target=_reader,
                                    args=(gang, r, 5, out))
                   for r in gang.reader_ranks]
            for t in rth:
                t.start()
            gang.commit(3)
            for t in rth:
                t.join(60)
                assert not t.is_alive(), "reader hung"
            gang.finish()
            served_by_cells = 0
            for r in gang.reader_ranks:
                rec = out[r]
                assert not rec["errors"]
                assert rec["monotone"]
                assert rec["failovers"] == 0
                for v, lags, mirror in rec["reads"]:
                    np.testing.assert_array_equal(mirror,
                                                  gang.expected(v))
                    assert lags[0] <= 4
            for cell in gang.cells.values():
                served_by_cells += cell.params_served
                assert cell.version == gang.server._snap_version
                assert cell.diffs_installed >= 1
            assert served_by_cells == 2 * 5  # every read hit a cell
            # the upstream's PARAM serves came from the writer only
            # (its read_params during start); readers never touched it.
            assert gang.server.params_served <= 2
        finally:
            gang.close()

    def test_chunk_framed_subscription_bitwise(self):
        """§11.8: a FLAG_CHUNKED subscription receives FULL/DELTA
        frames as chunk messages (SIZE=2048 f32 at a 4 KiB cut = 2
        chunks per frame) — reads stay bit-for-bit the upstream
        snapshot, and the server actually shipped chunk messages."""
        gang = _Gang(ncells=2, nreaders=2, cell_chunk_bytes=4096)
        try:
            gang.commit(3)
            out = {}
            rth = [threading.Thread(target=_reader,
                                    args=(gang, r, 4, out))
                   for r in gang.reader_ranks]
            for t in rth:
                t.start()
            gang.commit(3)
            for t in rth:
                t.join(60)
                assert not t.is_alive(), "reader hung"
            chunks_sent = int(gang.server._m_diff_chunks.value)
            gang.finish()
            for r in gang.reader_ranks:
                rec = out[r]
                assert not rec["errors"]
                assert rec["monotone"]
                for v, _lags, mirror in rec["reads"]:
                    np.testing.assert_array_equal(mirror,
                                                  gang.expected(v))
            assert chunks_sent >= 2, (
                "no chunk messages shipped — the subscription never "
                "negotiated FLAG_CHUNKED?")
            for cell in gang.cells.values():
                assert cell.version == gang.server._snap_version
        finally:
            gang.close()

    def test_chunk_framed_subscription_survives_chunk_drops(self):
        """Chunk-level drop/dup on the DIFF channel: a torn frame is
        exactly a dropped frame — the gap/resync machinery recovers
        and every installed version stays bit-exact."""
        def wrap(t):
            return FaultyTransport(t, FaultPlan(seed=3, drop_every=5,
                                               dup_every=4,
                                               tags=frozenset({tags.DIFF})))

        gang = _Gang(ncells=1, nreaders=1, cell_chunk_bytes=4096,
                     server_wrap=wrap)
        try:
            for _ in range(6):
                gang.commit(1)
                time.sleep(0.05)
            deadline = time.monotonic() + 20
            cell = gang.cells[2]
            while time.monotonic() < deadline and \
                    cell.version < gang.server._snap_version:
                time.sleep(0.05)
            assert cell.version >= 1, "cell never installed a frame"
            np.testing.assert_array_equal(
                np.frombuffer(bytes(cell._frame), np.float32),
                gang.expected(cell.version))
            cell.shutdown()  # no reader ever attaches in this leg
            gang.finish()
        finally:
            gang.close()

    def test_kill_a_cell_readers_reroute_zero_retry_exhausted(self):
        """SIGKILL-shaped cell death (transport torn, no STOP, no
        GOODBYE): every reader routed to the dead cell fails over to
        the live sibling inside its retry loop — zero RetryExhausted,
        reads stay bitwise-correct."""
        gang = _Gang(ncells=2, nreaders=4)
        try:
            gang.commit(2)
            out = {}
            rth = [threading.Thread(
                target=_reader,
                args=(gang, r, 8, out),
                kwargs=dict(deadline_s=0.5, read_sleep=0.05))
                for r in gang.reader_ranks]
            for t in rth:
                t.start()
            time.sleep(0.3)  # a few reads land pre-kill
            # Kill one cell abruptly: close its transport (every link
            # torn at once — exactly what a SIGKILL looks like to the
            # peers; the lease reaper owns the upstream side).
            victim = gang.cell_ranks[0]
            gang.tr[victim].close()
            gang.commit(2)
            for t in rth:
                t.join(90)
                assert not t.is_alive(), "reader hung after cell kill"
            # The gang still shuts down: the dead cell's lease expires
            # (ttl 10s) or the survivors' STOPs settle first.
            survivor = gang.cells[gang.cell_ranks[1]]
            failovers = 0
            for r in gang.reader_ranks:
                rec = out[r]
                assert not rec["errors"], rec["errors"]
                failovers += rec["failovers"]
                for v, _lags, mirror in rec["reads"]:
                    np.testing.assert_array_equal(mirror,
                                                  gang.expected(v))
            assert failovers >= 1, "nobody was routed to the victim?"
            assert survivor.params_served > 0
            gang.client.stop()
        finally:
            gang.close()
        # A killed process has no thread; the stand-in has one that
        # polls a torn transport for ever, and the server waits out the
        # victim's lease (ttl 10 s): ``close`` ends both.
        for thread in (*gang.cth.values(), gang.sth):
            thread.join(60)
            assert not thread.is_alive(), "the gang outlived the test"

    def test_goodbye_retire_reroutes_readers(self):
        """Graceful cell retirement (the autoscale drain verb): readers
        follow GOODBYE-with-successor to the sibling without burning
        retry budget, and the retired cell stops cleanly."""
        gang = _Gang(ncells=2, nreaders=2)
        try:
            gang.commit(2)
            out = {}
            rth = [threading.Thread(
                target=_reader, args=(gang, r, 10, out),
                kwargs=dict(read_sleep=0.03))
                for r in gang.reader_ranks]
            for t in rth:
                t.start()
            time.sleep(0.15)
            victim, survivor = gang.cell_ranks
            gang.cells[victim].retire_serving(survivor)
            gang.commit(2)
            for t in rth:
                t.join(60)
                assert not t.is_alive(), "reader hung across retire"
            gang.finish()
            for r in gang.reader_ranks:
                rec = out[r]
                assert not rec["errors"]
                for v, _lags, mirror in rec["reads"]:
                    np.testing.assert_array_equal(mirror,
                                                  gang.expected(v))
        finally:
            gang.close()


class TestStalenessEnforcement:
    """The acceptance bar: the bound is enforced, not advisory."""

    def test_property_no_read_beyond_max_lag_under_faults(self):
        """Seeded drop/delay FaultPlans on the DIFF channel: across
        plans, every answered read is bitwise-equal to the upstream
        snapshot at its stamped version, and the stamped (version,
        head) window never exceeds max_lag — the gate arithmetic holds
        under exactly the faults it exists for.  Drops force resyncs
        (the FULL path); delays force the lag window open."""
        max_lag = 2
        plans = [
            FaultPlan(seed=1, drop_every=3, tags=frozenset({tags.DIFF})),
            FaultPlan(seed=2, delay_every=2, delay_polls=200,
                      tags=frozenset({tags.DIFF})),
            FaultPlan(seed=3, drop_rate=0.3, delay_rate=0.3,
                      delay_polls=120, tags=frozenset({tags.DIFF})),
        ]
        for plan in plans:
            gang = _Gang(
                ncells=1, nreaders=2, max_lag=max_lag, cell_hb=0.02,
                server_wrap=lambda tr, plan=plan: FaultyTransport(tr, plan))
            try:
                gang.commit(2)
                out = {}
                rth = [threading.Thread(
                    target=_reader, args=(gang, r, 6, out),
                    kwargs=dict(read_sleep=0.02))
                    for r in gang.reader_ranks]
                for t in rth:
                    t.start()
                gang.commit(8)
                for t in rth:
                    t.join(120)
                    assert not t.is_alive(), f"reader hung under {plan}"
                gang.finish(timeout=90)
                for r in gang.reader_ranks:
                    rec = out[r]
                    assert not rec["errors"], (plan, rec["errors"])
                    assert rec["monotone"]
                    for v, lags, mirror in rec["reads"]:
                        # bitwise vs the upstream snapshot at the
                        # stamped version
                        np.testing.assert_array_equal(
                            mirror, gang.expected(v))
                        # the enforced envelope: stamped head minus
                        # served version, never beyond the bound
                        assert lags[0] <= max_lag, (plan, v, lags)
            finally:
                gang.close()

    def test_lag_shed_busy_and_recovery(self):
        """Hold the diff stream shut while committing past max_lag:
        the cell (told the head by its beat echoes) sheds reads as
        BUSY; when the stream reopens it catches up and the parked
        reads complete — bitwise, within the bound."""
        max_lag = 2
        # every DIFF delayed a long-but-finite number of polls
        plan = FaultPlan(seed=9, delay_every=1, delay_polls=2500,
                         tags=frozenset({tags.DIFF}))
        gang = _Gang(ncells=1, nreaders=1, max_lag=max_lag, cell_hb=0.02,
                     server_wrap=lambda tr: FaultyTransport(tr, plan))
        try:
            gang.commit(1)
            cell = gang.cells[2]
            # let the first (delayed) FULL land so the cell serves
            deadline = time.monotonic() + 30
            while cell.version < 0 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert cell.version >= 0, "cell never installed a frame"
            # commit far past the bound; beats tell the cell the head
            gang.commit(max_lag + 4)
            deadline = time.monotonic() + 30
            while cell.lag <= max_lag and time.monotonic() < deadline:
                time.sleep(0.02)
            assert cell.lag > max_lag, "beat echoes never moved the head"
            out = {}
            th = threading.Thread(
                target=_reader, args=(gang, gang.reader_ranks[0], 3, out),
                kwargs=dict(deadline_s=20.0))
            th.start()
            th.join(120)
            assert not th.is_alive(), "reader hung in the shed window"
            gang.finish(timeout=90)
            rec = out[gang.reader_ranks[0]]
            assert not rec["errors"]
            assert rec["busy_honored"] >= 1, \
                "no BUSY crossed the shed window"
            assert cell.lag_sheds >= 1
            for v, lags, mirror in rec["reads"]:
                np.testing.assert_array_equal(mirror, gang.expected(v))
                assert lags[0] <= max_lag
        finally:
            gang.close()


@pytest.mark.slow
def test_launch_cells_mode_end_to_end():
    """`--cells N` through the real process-gang launcher: cells sit
    between the training roles and the readers, subscribe to their
    upstream servers, and the readers report monotone versions + bounded
    lag served entirely by the cells."""
    from mpit_tpu.train.launch import LAUNCH_DEFAULTS, launch_processes

    cfg = LAUNCH_DEFAULTS.merged(
        np=7, serve_readers=2, cells=2, opt="downpour", epochs=1,
        model="linear", side=8, batch=64, ft_op_deadline_s=60.0,
        ft_heartbeat_s=0.2, serve_rounds=4, serve_interval_s=0.02,
        ring_mb=8,
    )
    results = launch_processes(cfg, timeout=600)
    for r in (3, 4):
        assert results[r]["role"] == "cell"
        assert results[r]["diffs_installed"] >= 1
        assert results[r]["params_served"] >= 1 or True  # routing may skew
    served = sum(results[r]["params_served"] for r in (3, 4))
    assert served >= 8  # 2 readers x 4 rounds all landed on cells
    for r in (5, 6):
        assert results[r]["role"] == "reader"
        assert results[r]["monotone"] is True
        assert results[r]["reads"] == 4
        assert all(v <= cfg.cell_max_lag
                   for v in results[r]["lags"].values())
    assert results[1]["role"] == "worker"


# ---------------------------------------------------------------------------
# autoscale binding


class TestCellAutoscaler:
    def _scaler(self, samples_seq, cells, **cfg_kw):
        from mpit_tpu.cells.autoscale import CellAutoscaler, CellSLO
        from mpit_tpu.shardctl.autoscale import AutoscaleConfig

        cfg = AutoscaleConfig(
            slo=CellSLO(max_lag=4.0).to_slo(),
            window_s=1.0, breach_windows=2, idle_windows=4,
            cooldown_s=0.0, min_servers=1, max_servers=4, **cfg_kw)
        verbs = []
        scaler = CellAutoscaler(
            cfg,
            add_cell=lambda: verbs.append("up") or True,
            drain_cell=lambda: verbs.append("down") or True,
            live_cells=lambda: list(cells))
        t = [0.0]
        scaler._clock = lambda: t[0]
        seq = iter(samples_seq)
        scaler._sample = lambda: next(seq)
        return scaler, verbs, t

    @staticmethod
    def _sample(lag, rank=2):
        return [("mpit_cell_lag", {"rank": str(rank)}, float(lag)),
                ("mpit_ps_params_served_total", {"rank": str(rank)},
                 100.0)]

    def test_lag_breach_scales_up_idle_drains(self):
        cells = [2]
        hot = self._sample(9)
        cold = self._sample(0)
        scaler, verbs, t = self._scaler(
            [hot, hot, hot, cold, cold, cold, cold, cold], cells)
        actions = []
        for _ in range(8):
            t[0] += 1.5
            d = scaler.pump()
            actions.append(d.action)
            if d.action == "up":
                cells.append(3)
            if d.action == "down" and len(cells) > 1:
                cells.pop()
        assert "up" in actions, actions
        assert verbs[0] == "up"
        # after the breach cleared, sustained idle drains the spare
        assert "down" in actions, actions
        assert scaler.audit and all("window" in a for a in scaler.audit)

    def test_min_bound_holds_drain(self):
        cells = [2]
        cold = self._sample(0)
        scaler, verbs, t = self._scaler([cold] * 6, cells)
        for _ in range(6):
            t[0] += 1.5
            d = scaler.pump()
        assert verbs == []  # at min_servers: hold, never drain
        assert any(a["reason"] == "at_min" for a in scaler.audit)

    def test_cell_window_restricts_to_cell_ranks(self):
        from mpit_tpu.cells.autoscale import cell_window

        cur = [("mpit_cell_lag", {"rank": "2"}, 3.0),
               ("mpit_cell_lag", {"rank": "9"}, 50.0),  # not a cell
               ("mpit_ps_params_served_total", {"rank": "2"}, 10.0),
               ("mpit_ps_params_served_total", {"rank": "0"}, 999.0),
               ("mpit_ps_busy_replies_total", {"rank": "2"}, 10.0)]
        w = cell_window(1.0, cur, None, [2])
        assert w.staleness == 3.0
        assert w.ops == 10.0
        assert w.busy_ratio == 0.5
        assert w.gang_size == 1
