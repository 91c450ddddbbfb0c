"""Worker-pool data-plane tests (comm/pool.py + native mt_pool_*).

Two suites for the ISSUE 17 seam:

* **Pooled-vs-serial bitwise parity.**  Every kernel the pool runs
  (codec encode/decode, f32 fold, chunk gather/scatter) must
  produce bytes identical to the serial fallback — per codec, per chunk
  geometry (BLOCK-aligned and tailed shards), per thread count, across
  seeds.  This is the determinism contract the module docstring pins:
  completion order never influences bytes, and ``MPIT_POOL_THREADS=0``
  is the same bytes, not a different path.  Includes int8
  error-feedback residual exactness under a chunk retry (re-encode from
  the pre-encode residual snapshot must reproduce the identical frame).

* **Lifecycle.**  ``close()`` drains queued jobs before the workers
  exit, any submit after close raises :class:`PoolClosedError` loudly
  (serial pools included), and 32 open/close cycles leak no OS thread.

The parity suite needs the compiled library; it skips only where the
library is switched off (MPIT_PS_CODEC_NATIVE=0) — a failed build
raises at collection.
"""

import numpy as np
import pytest

from mpit_tpu.comm import codec as codec_mod
from mpit_tpu.comm import pool as pool_mod

HAVE_NATIVE = codec_mod._native() is not None

pooled = pytest.mark.skipif(
    not HAVE_NATIVE,
    reason="native library switched off (MPIT_PS_CODEC_NATIVE=0)")

BLOCK = codec_mod.BLOCK
#: one BLOCK-aligned shard, one tailed (size % BLOCK != 0) shard
SIZES = [3 * BLOCK, 5 * BLOCK + 137]
SEEDS = range(5)
CODEC_NAMES = ["none", "bf16", "int8"]


def rnd(n, seed, scale=3.0):
    return (scale * np.random.default_rng(seed).standard_normal(n)).astype(
        np.float32)


def chunk_bounds(size):
    """One interior BLOCK-aligned chunk plus the (possibly tailed)
    trailing chunk — the §12 chunk geometry int8 frames require."""
    mid = max(BLOCK, (size // (2 * BLOCK)) * BLOCK)
    return [(0, mid), (mid, size)]


def _encode_chunks(pool, codec, x, residual):
    """Encode every chunk of ``x`` through ``pool``, collecting in
    submission order; returns the per-chunk wire frames."""
    wires = []
    jobs = []
    for lo, hi in chunk_bounds(x.size):
        wire = np.zeros(codec.wire_nbytes(hi - lo), np.uint8)
        res = residual[lo:hi] if residual is not None else None
        jobs.append(pool.submit_encode(codec, x[lo:hi], wire, res))
        wires.append(wire)
    for j in jobs:
        j.result()
    return wires


@pooled
@pytest.mark.parametrize("threads", [1, 2, 4])
class TestPooledSerialParity:
    """Bitwise equality: pooled kernels vs the serial fallback."""

    def test_codec_chunk_roundtrip_bitwise(self, threads):
        pool = pool_mod.WorkerPool(threads)
        serial = pool_mod.WorkerPool(0)
        try:
            assert not pool.serial and pool.threads == threads
            assert serial.serial
            for seed in SEEDS:
                for name in CODEC_NAMES:
                    codec = codec_mod.get(name)
                    for size in SIZES:
                        x = rnd(size, seed)
                        res_p = (np.zeros(size, np.float32)
                                 if codec.uses_residual else None)
                        res_s = (np.zeros(size, np.float32)
                                 if codec.uses_residual else None)
                        wp = _encode_chunks(pool, codec, x, res_p)
                        ws = _encode_chunks(serial, codec, x, res_s)
                        for a, b in zip(wp, ws):
                            assert a.tobytes() == b.tobytes(), (
                                seed, name, size)
                        if codec.uses_residual:
                            assert np.array_equal(res_p, res_s)
                        # decode the serial frames back through both
                        out_p = np.zeros(size, np.float32)
                        out_s = np.zeros(size, np.float32)
                        jobs = []
                        for (lo, hi), w in zip(chunk_bounds(size), ws):
                            jobs.append(pool.submit_decode(
                                codec, w, out_p[lo:hi]))
                            serial.submit_decode(
                                codec, w, out_s[lo:hi]).result()
                        for j in jobs:
                            j.result()
                        assert out_p.tobytes() == out_s.tobytes(), (
                            seed, name, size)
        finally:
            pool.close()
            serial.close()

    def test_fold_bitwise(self, threads):
        pool = pool_mod.WorkerPool(threads)
        serial = pool_mod.WorkerPool(0)
        try:
            for seed in SEEDS:
                n = int(np.random.default_rng(seed).integers(BLOCK, 4 * BLOCK))
                own = rnd(n, seed)
                children = [rnd(n, seed * 7 + k + 1) for k in range(3)]
                f_p = np.empty(n, np.float32)
                f_s = np.empty(n, np.float32)
                pool.submit_fold_f32(own, children, f_p).result()
                serial.submit_fold_f32(own, children, f_s).result()
                assert f_p.tobytes() == f_s.tobytes()
                ref = own.copy()
                for child in children:  # numpy's association order
                    ref += child
                assert f_s.tobytes() == ref.tobytes()
        finally:
            pool.close()
            serial.close()

    def test_gather_scatter_bitwise(self, threads):
        pool = pool_mod.WorkerPool(threads)
        serial = pool_mod.WorkerPool(0)
        try:
            for seed in SEEDS:
                for name in CODEC_NAMES:
                    codec = codec_mod.get(name)
                    for size in SIZES:
                        full = np.zeros(codec.wire_nbytes(size), np.uint8)
                        serial.submit_encode(
                            codec, rnd(size, seed), full,
                            np.zeros(size, np.float32)
                            if codec.uses_residual else None).result()
                        for lo, hi in chunk_bounds(size):
                            nb = codec.wire_nbytes(hi - lo)
                            c_p = np.zeros(nb, np.uint8)
                            c_s = np.zeros(nb, np.uint8)
                            pool.submit_gather(
                                codec, full, size, lo, hi, c_p).result()
                            serial.submit_gather(
                                codec, full, size, lo, hi, c_s).result()
                            assert c_p.tobytes() == c_s.tobytes()
                            f_p = np.zeros_like(full)
                            f_s = np.zeros_like(full)
                            pool.submit_scatter(
                                codec, f_p, size, lo, hi, c_s).result()
                            serial.submit_scatter(
                                codec, f_s, size, lo, hi, c_s).result()
                            assert f_p.tobytes() == f_s.tobytes()
        finally:
            pool.close()
            serial.close()

    def test_int8_residual_exact_under_chunk_retry(self, threads):
        """A chunk retry re-encodes from the pre-encode residual
        snapshot (the §12.4 retry rule): the retried frame and the
        post-encode residual must be bit-identical to the first
        attempt's, pooled and serial alike."""
        pool = pool_mod.WorkerPool(threads)
        serial = pool_mod.WorkerPool(0)
        codec = codec_mod.get("int8")
        try:
            for seed in SEEDS:
                size = 5 * BLOCK + 137
                x = rnd(size, seed)
                res0 = rnd(size, seed + 100, scale=0.01)  # warm EF state
                for lo, hi in chunk_bounds(size):
                    nb = codec.wire_nbytes(hi - lo)
                    frames, residuals = [], []
                    for p in (pool, serial):
                        for _attempt in range(2):  # original + retry
                            res = res0.copy()
                            wire = np.zeros(nb, np.uint8)
                            p.submit_encode(
                                codec, x[lo:hi], wire,
                                res[lo:hi]).result()
                            frames.append(wire.tobytes())
                            residuals.append(res.tobytes())
                    assert len(set(frames)) == 1, (seed, lo, hi)
                    assert len(set(residuals)) == 1, (seed, lo, hi)
        finally:
            pool.close()
            serial.close()


def _os_threads() -> int:
    """This process's OS thread count (native pool workers are pthreads
    invisible to the threading module)."""
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("Threads:"):
                return int(line.split()[1])
    raise AssertionError("no Threads: line in /proc/self/status")


class TestLifecycle:
    def test_submit_after_close_raises_serial(self):
        pool = pool_mod.WorkerPool(0)
        pool.close()
        with pytest.raises(pool_mod.PoolClosedError):
            pool.submit_copy(np.zeros(8, np.uint8), np.zeros(8, np.uint8))

    @pooled
    def test_close_drains_queued_jobs(self):
        pool = pool_mod.WorkerPool(1)
        n = 1 << 20
        a = np.random.default_rng(0).integers(0, 256, n).astype(np.uint8)
        outs = [np.zeros(n, np.uint8) for _ in range(8)]
        jobs = [pool.submit_copy(a, out) for out in outs]
        pool.close()  # must drain, not drop
        for out in outs:
            assert out.tobytes() == a.tobytes()
        for j in jobs:  # collecting after close is a no-op, not a hang
            j.result()
            assert j.done()
        with pytest.raises(pool_mod.PoolClosedError):
            pool.submit_copy(a, outs[0])

    @pooled
    def test_no_thread_leak_across_open_close_cycles(self):
        a = np.arange(4096, dtype=np.uint8)
        out = np.empty_like(a)
        # a first cycle warms lazy state (ctypes, obs registry)
        p = pool_mod.WorkerPool(2)
        p.submit_copy(a, out).result()
        p.close()
        before = _os_threads()
        for _ in range(32):
            p = pool_mod.WorkerPool(2)
            assert p.threads == 2
            p.submit_copy(a, out).result()
            p.close()
            p.close()  # idempotent
        assert _os_threads() == before

    @pooled
    def test_done_polls_without_blocking(self):
        pool = pool_mod.WorkerPool(1)
        try:
            n = 1 << 22
            a = np.ones(n, np.uint8)
            out = np.zeros(n, np.uint8)
            job = pool.submit_copy(a, out)
            while not job.done():  # scheduler-style poll, no result()
                pass
            assert out.tobytes() == a.tobytes()
        finally:
            pool.close()

    def test_configure_replaces_and_closes_previous(self):
        first = pool_mod.configure(0)
        second = pool_mod.configure(0)
        assert second is pool_mod.get_pool()
        assert second is not first
        with pytest.raises(pool_mod.PoolClosedError):
            first.submit_copy(np.zeros(4, np.uint8), np.zeros(4, np.uint8))
