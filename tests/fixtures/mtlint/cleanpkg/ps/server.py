"""Clean yield-atomicity + ownership twins (mtlint fixture — zero
findings).  Same declared-discipline surface as badpkg/ps/server.py:
the read window stays yield-free (``sched.spawn`` of a generator
is NOT a yield — spawn primes only the new task), the plane pop stays
inside the single-writer closure even one helper down, and every buffer
crossing the donation seam is provably owned."""

import numpy as np

EXEC = "EXEC"


class PS:
    def _snapshot_wire(self):
        return self._wire

    def _dispatch_read(self, req):
        wire = self._snapshot_wire()
        header = (self.version, len(wire))
        # spawn primes the NEW task one step; it does not yield this one.
        self.sched.spawn(self._serve_reply(req, header, wire))

    def _serve_reply(self, req, header, wire):
        yield EXEC
        req.reply(header, wire)

    def _reader_dispatcher(self):
        while self.live:
            req = yield EXEC
            self._dispatch_read(req)

    def _drain_once(self):
        ticket = self._plane.pop()
        if ticket is not None:
            self.execute(ticket)

    def _dplane_service(self):
        while self.live:
            yield EXEC
            self._drain_once()

    def _chunk_owned(self, view):
        return np.array(view)

    def _staged(self, blob):
        out = np.empty(len(blob) // 4, np.float32)
        self.codec.decode_into(blob, out)
        return out

    def good_apply(self, codec, view, lo):
        self._hbm.apply_wire_chunk(codec, self._chunk_owned(view), lo)

    def staged_apply(self, codec, blob, lo):
        self._hbm.apply_wire_chunk(codec, self._staged(blob), lo)

    def _recv_param_chunked(self, codec, asm, lo, hi, blob):
        # The owning snapshot exists only as the pool submit argument —
        # the declared pool-server-scatter-owned shape.
        self.pool.submit_scatter(
            codec, asm, self.size, lo, hi, np.array(blob))
