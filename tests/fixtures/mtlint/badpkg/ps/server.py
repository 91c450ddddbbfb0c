"""Seeded yield-atomicity + ownership violations (mtlint fixture —
parsed, never imported).  The rel-path suffix ``ps/server.py`` makes
the declared disciplines in mpit_tpu.analysis.disciplines apply here:
the read window, the device-plane single-writer set and the
chunk-apply donation seam."""

import numpy as np

EXEC = "EXEC"


class PS:
    def _over_budget(self):
        if self.inflight > self.budget:
            return True
        return False

    def _dispatch_read(self, req):
        wire = self._snapshot_wire()
        # MT-Y801: scheduler yield inside the declared read window.
        yield EXEC
        self.serve(wire, req, self._over_budget())

    def steal_ticket(self):
        # MT-Y802: pops the device plane outside the declared writer set.
        return self._plane.pop()

    def bad_apply(self, codec, blob, lo):
        # MT-D901: a frombuffer view of the receive ring reaches the
        # donated chunk apply.
        self._hbm.apply_wire_chunk(codec, np.frombuffer(blob, np.float32), lo)

    def lazy_apply(self, codec, grad, lo):
        # MT-D903: ownership of a bare parameter cannot be proven at
        # the declared seam.
        self._hbm.apply_wire_chunk(codec, grad, lo)

    def _snapshot_wire(self):
        # MT-C204: blocking pool wait inside the declared yield-free
        # read-path window (ps-read-path-helpers).
        self.job.result()
        return self._wire

    def _recv_param_chunked(self, codec, asm, lo, hi, blob):
        # MT-D901 (pool-server-scatter-owned): a frombuffer view of the
        # reused receive buffer submitted to the worker pool.
        self.pool.submit_scatter(
            codec, asm, self.size, lo, hi, np.frombuffer(blob, np.uint8))
        # MT-D903 (pool-server-scatter-owned-copy): a stray owning copy
        # outside the submit boundary.
        return np.array(blob)
