"""Wire-codec tests: registry/negotiation surface, round-trip property
bounds per codec, int8 error-feedback behavior, and the zero-copy
send-buffer rule (comm/transport.py as_bytes_view regression).
"""

import numpy as np
import pytest

from mpit_tpu.comm import codec as codec_mod
from mpit_tpu.comm.transport import as_bytes_view

SIZES = [1, 7, 1023, 1024, 1025, 4096, 5000, codec_mod._TILE * 2 + 511]


def rnd(n, seed=0, scale=3.0):
    return (scale * np.random.default_rng(seed).standard_normal(n)).astype(
        np.float32
    )


class TestRegistry:
    def test_default_is_none(self, monkeypatch):
        monkeypatch.delenv(codec_mod.ENV, raising=False)
        assert codec_mod.get().name == "none"
        assert codec_mod.get("").name == "none"

    def test_env_selection(self, monkeypatch):
        monkeypatch.setenv(codec_mod.ENV, "int8")
        assert codec_mod.get().name == "int8"
        # an explicit name beats the env
        assert codec_mod.get("bf16").name == "bf16"

    def test_unknown_name_fails_loudly(self):
        with pytest.raises(ValueError, match="unknown PS codec"):
            codec_mod.get("zstd")

    def test_unknown_wire_id_fails_loudly(self):
        with pytest.raises(ValueError, match="unknown codec wire id"):
            codec_mod.by_wire_id(99)

    def test_wire_ids_are_stable(self):
        # Wire ids are protocol constants (docs/PROTOCOL.md) — changing
        # one breaks INIT interop with every deployed peer.
        assert {c: codec_mod.get(c).wire_id
                for c in codec_mod.names()} == {
            "none": 0, "bf16": 1, "int8": 2}


class TestRoundTrip:
    @pytest.mark.parametrize("size", SIZES)
    def test_none_exact(self, size):
        c = codec_mod.get("none")
        x = rnd(size)
        wire = np.zeros(c.wire_nbytes(size), np.uint8)
        c.encode_into(x, wire)
        out = np.empty_like(x)
        c.decode_into(wire, out)
        np.testing.assert_array_equal(out, x)

    @pytest.mark.parametrize("size", SIZES)
    def test_bf16_truncation_bound(self, size):
        # bf16 keeps 7 explicit mantissa bits; truncation (round toward
        # zero) error is < one ulp = 2^-7 relative, element-wise.
        c = codec_mod.get("bf16")
        x = rnd(size, seed=1)
        wire = np.zeros(c.wire_nbytes(size), np.uint8)
        c.encode_into(x, wire)
        out = np.empty_like(x)
        c.decode_into(wire, out)
        assert np.all(np.abs(out - x) <= np.abs(x) * 2.0**-7 + 1e-30)
        # truncation, not rounding: magnitude never grows
        assert np.all(np.abs(out) <= np.abs(x))

    @pytest.mark.parametrize("size", SIZES)
    def test_int8_per_block_bound(self, size):
        # Each element's error is bounded by its OWN block's scale/2 =
        # absmax/254 — the per-block guarantee whole-tensor scaling
        # cannot give.
        c = codec_mod.get("int8")
        B = codec_mod.BLOCK
        x = rnd(size, seed=2)
        if size > B:  # make block magnitudes wildly different
            x[:B] *= 1000.0
        wire = np.zeros(c.wire_nbytes(size), np.uint8)
        c.encode_into(x, wire)
        out = np.empty_like(x)
        c.decode_into(wire, out)
        err = np.abs(out - x)
        for lo in range(0, size, B):
            blk = slice(lo, min(lo + B, size))
            bound = np.abs(x[blk]).max() / 254.0
            assert err[blk].max() <= bound * (1 + 1e-5) + 1e-30

    @pytest.mark.parametrize("name", ["none", "bf16", "int8"])
    def test_zero_vector_round_trips(self, name):
        c = codec_mod.get(name)
        x = np.zeros(2048, np.float32)
        wire = np.zeros(c.wire_nbytes(2048), np.uint8)
        c.encode_into(x, wire)
        out = np.full(2048, 7.0, np.float32)
        c.decode_into(wire, out)
        np.testing.assert_array_equal(out, 0.0)

    @pytest.mark.parametrize("name", ["none", "bf16", "int8"])
    def test_split_wire_matches_host_decode(self, name):
        """decode_parts (the server's fused jit path) must equal
        decode_into (the client's host path) bit for bit."""
        import jax.numpy as jnp

        c = codec_mod.get(name)
        size = 3 * codec_mod.BLOCK + 77
        x = rnd(size, seed=3)
        wire = np.zeros(c.wire_nbytes(size), np.uint8)
        c.encode_into(x, wire)
        host = np.empty_like(x)
        c.decode_into(wire, host)
        parts = [jnp.asarray(v) for v in c.split_wire(wire, size)]
        fused = np.asarray(c.decode_parts(parts, size))
        np.testing.assert_array_equal(fused, host)


class TestErrorFeedback:
    def test_residual_drains_to_zero_on_constant_grads(self):
        # A constant vector sits exactly on the quantization grid (every
        # element IS its block's absmax), so one EF step representing it
        # exactly leaves nothing behind.
        c = codec_mod.get("int8")
        g = np.full(4096, 0.37, np.float32)
        r = np.full(4096, 0.123, np.float32)  # start dirty
        wire = np.zeros(c.wire_nbytes(4096), np.uint8)
        for _ in range(2):
            c.encode_into(g, wire, residual=r)
        assert np.abs(r).max() == 0.0

    def test_residual_is_exact_quantization_error(self):
        c = codec_mod.get("int8")
        x = rnd(5000, seed=4)
        r = np.zeros_like(x)
        wire = np.zeros(c.wire_nbytes(5000), np.uint8)
        c.encode_into(x, wire, residual=r)
        out = np.empty_like(x)
        c.decode_into(wire, out)
        np.testing.assert_allclose(r, x - out, atol=1e-6)

    def test_cumulative_feedback_tracks_true_sum(self):
        # EF invariant: sum of decoded frames = sum of true grads minus
        # the current residual — compression error never accumulates.
        c = codec_mod.get("int8")
        size = 2048
        r = np.zeros(size, np.float32)
        wire = np.zeros(c.wire_nbytes(size), np.uint8)
        true_sum = np.zeros(size, np.float64)
        dec_sum = np.zeros(size, np.float64)
        out = np.empty(size, np.float32)
        for step in range(20):
            g = rnd(size, seed=10 + step)
            true_sum += g
            c.encode_into(g, wire, residual=r)
            c.decode_into(wire, out)
            dec_sum += out
        np.testing.assert_allclose(dec_sum + r, true_sum, atol=2e-3)
        # and the residual itself stays bounded by one quantization step
        assert np.abs(r).max() < 0.2

    def test_no_residual_matches_zero_residual(self):
        c = codec_mod.get("int8")
        x = rnd(3000, seed=5)
        w1 = np.zeros(c.wire_nbytes(3000), np.uint8)
        w2 = np.zeros_like(w1)
        c.encode_into(x, w1)
        c.encode_into(x, w2, residual=np.zeros_like(x))
        assert bytes(w1) == bytes(w2)


class TestNativeParity:
    """The native kernels (comm/native/transport.cpp mt_codec_*) must be
    bit-identical to the numpy reference paths — build.py pins
    -ffp-contract=off precisely so this holds.  Skipped only where the
    native lib is switched off (MPIT_PS_CODEC_NATIVE=0); a failed build
    raises."""

    @pytest.mark.parametrize("size", SIZES)
    @pytest.mark.parametrize("name", ["bf16", "int8"])
    def test_native_matches_numpy_oracle(self, name, size, monkeypatch):
        if codec_mod._native() is None:
            pytest.skip("native codec kernels unavailable")
        c = codec_mod.get(name)
        x = rnd(size, seed=6)
        use_res = c.uses_residual
        rn = np.full(size, 0.01, np.float32)
        rv = rn.copy()
        wn = np.zeros(c.wire_nbytes(size), np.uint8)
        wv = np.zeros_like(wn)
        ov = np.empty(size, np.float32)
        c.encode_into(x, wv, residual=rv if use_res else None)  # native
        c.decode_into(wv, ov)
        monkeypatch.setattr(codec_mod, "_native_lib", False)  # numpy path
        assert codec_mod._native() is None
        c.encode_into(x, wn, residual=rn if use_res else None)
        on = np.empty(size, np.float32)
        c.decode_into(wv, on)  # numpy decode of the native frame
        assert bytes(wn) == bytes(wv)
        np.testing.assert_array_equal(on, ov)
        if use_res:
            np.testing.assert_array_equal(rn, rv)

    def test_env_kill_switch(self, monkeypatch):
        import os

        monkeypatch.setattr(codec_mod, "_native_lib", None)
        monkeypatch.setenv(codec_mod._NATIVE_ENV, "0")
        assert codec_mod._native() is None
        monkeypatch.setattr(codec_mod, "_native_lib", None)
        monkeypatch.delenv(codec_mod._NATIVE_ENV)
        # cache reset: default path retries the build lazily
        codec_mod._native()
        monkeypatch.setattr(codec_mod, "_native_lib", None)


class TestZeroCopySendRule:
    """Satellite regression: as_bytes_view used to silently
    ascontiguousarray-copy non-contiguous send buffers, detaching the
    transport from the caller's buffer under the documented liveness
    contract."""

    def test_non_contiguous_send_buffer_raises(self):
        arr = np.arange(16, dtype=np.float32)[::2]
        assert not arr.flags["C_CONTIGUOUS"]
        with pytest.raises(ValueError, match="C-contiguous"):
            as_bytes_view(arr)

    def test_contiguous_is_zero_copy(self):
        arr = np.arange(4, dtype=np.float32)
        view = as_bytes_view(arr)
        arr[0] = 42.0  # the view must alias the caller's memory
        assert np.frombuffer(view, np.float32)[0] == 42.0

    def test_bytes_and_memoryview_still_accepted(self):
        assert bytes(as_bytes_view(b"abc")) == b"abc"
        assert bytes(as_bytes_view(memoryview(b"xy"))) == b"xy"

    def test_transport_isend_propagates_the_error(self):
        from mpit_tpu.comm.local import LocalRouter

        router = LocalRouter(2)
        a = router.endpoint(0)
        handle = a.isend(np.arange(16, dtype=np.float32)[::2], 1, 5)
        with pytest.raises(ValueError, match="C-contiguous"):
            while not a.test(handle):
                pass
