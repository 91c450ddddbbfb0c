"""Wire codecs for the PS hot path — quantized shard transfer.

Every GRAD / PARAM / PARAM_PUSH message used to ship the full fp32
shard.  This registry provides the EQuARX-style alternative (PAPERS.md:
block-quantized comms inside the collective): a codec turns a float32
shard slice into a smaller wire frame and back, selected by name via
``MPIT_PS_CODEC`` and negotiated per client<->server pair through the
INIT v2 announcement (``[offset, size, codec_id]`` — ps/tags.py).

Codecs
------
- ``none``  (wire id 0) — identity.  The client/server hot paths special
  -case it (``identity=True``) to keep today's zero-copy sends.
- ``bf16``  (wire id 1) — fp32 -> bfloat16 by mantissa truncation (the
  top 16 bits of the IEEE-754 word).  2x smaller, ~2^-8 relative error.
- ``int8``  (wire id 2) — per-block absmax scaling: each 1024-element
  block ships one fp32 scale (absmax/127) plus int8 codes, ~3.9x
  smaller.  Lossy enough to need **error feedback** on the gradient
  path: the client keeps a per-shard residual, adds it to the next
  gradient before quantizing, and stores the fresh quantization error
  back (``encode_into(..., residual=r)``).  The compression error is
  then re-shipped instead of lost, which preserves DOWNPOUR/EASGD
  convergence (the standard EF-SGD argument; see docs/PROTOCOL.md).

Frame layout (``int8``, for an n-element slice with B=1024)::

    [ scales: ceil(n/B) x f32 | codes: n x i8 ]

The layout is a pure function of ``size``, so both sides derive buffer
sizes from the INIT announcement — frames carry no per-message header.
A codec mismatch therefore shows up as a wire-size mismatch and fails
loudly in the transports' exact-size receive contract (never as
silently corrupt parameters); negotiation itself is validated at INIT
time (ps/server.py).

Decode on the server gradient path is **fused into the jitted shard
update**: ``decode_parts`` is pure jax-traceable math over the typed
views of the staging buffer (``split_wire``), so one XLA call per
gradient decodes + applies, exactly as the fp32 path does today.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, List, Optional, Tuple

import numpy as np

from mpit_tpu.obs import metrics as _obs

_LITTLE = sys.byteorder == "little"

# Native kernels (comm/native/transport.cpp, mt_codec_*): the same math
# as the numpy paths below in 2 cache-resident passes per block instead
# of ~8 per tile — measured ~3x encode throughput at the 640 MB ptest
# scale, and ctypes releases the GIL for the call.  Results are
# bit-identical to the numpy paths (build.py pins -ffp-contract=off;
# parity-tested in tests/test_codec.py), so the numpy code stays as the
# oracle and as the path MPIT_PS_CODEC_NATIVE=0 or a big-endian host
# selects.  A build that FAILS raises: the shm transport needs the same
# library, so a gang that runs at all has it, and a codec gone quietly
# serial would only show up as a slower run.
_NATIVE_ENV = "MPIT_PS_CODEC_NATIVE"
_native_lib: Optional[object] = None  # None: untried; False: switched off


def _native():
    global _native_lib
    if _native_lib is None:
        if os.environ.get(_NATIVE_ENV, "1") == "0" or not _LITTLE:
            _native_lib = False
        else:
            from mpit_tpu.comm.native import build
            from mpit_tpu.comm.native._bindings import NativeTransportLib

            _native_lib = NativeTransportLib(build.ensure_built())
    return _native_lib or None

#: int8 per-block absmax granularity.  4 bytes of scale per 1024 codes
#: keeps the overhead at ~0.4% while bounding each element's error by
#: its own block's absmax/254 (tighter than one whole-shard scale).
BLOCK = 1024

#: int8 host-codec tile: elements processed per pass so the working
#: temporaries (~2 f32 tiles = 2 MB) stay cache-resident — the encoder's
#: DRAM traffic then approaches the compulsory read/write minimum
#: instead of one full sweep per ufunc (measured ~1.8x encode throughput
#: on the 640 MB ptest host, 1-core Xeon with 2 MB L2).
_TILE = 256 * BLOCK

ENV = "MPIT_PS_CODEC"


def _nblocks(size: int) -> int:
    return (size + BLOCK - 1) // BLOCK


class Codec:
    """One wire format.  Stateless — error-feedback residuals live with
    the caller (the client owns one per shard)."""

    name: str = "?"
    wire_id: int = -1
    identity: bool = False  # hot paths skip encode/decode entirely
    uses_residual: bool = False

    def wire_nbytes(self, size: int) -> int:
        """Exact frame bytes for ``size`` float32 elements."""
        raise NotImplementedError

    def encode_into(
        self,
        x: np.ndarray,
        wire: np.ndarray,
        residual: Optional[np.ndarray] = None,
    ) -> None:
        """Encode float32 ``x`` into the uint8 ``wire`` buffer.  With
        ``residual`` (same shape as ``x``), quantize ``x + residual``
        and store the new quantization error back into ``residual``
        (error feedback — gradient path only).

        Observability: encode time and produced wire bytes feed the obs
        registry (``mpit_codec_*``) when obs is enabled; disabled, the
        wrap is one ``enabled`` attribute read per call (the clock lives
        in the registry timer, never here — the MT-O4xx contract)."""
        reg = _obs.get_registry()
        if not reg.enabled:
            self._encode_into(x, wire, residual)
            return
        with reg.timer("mpit_codec_encode_seconds", codec=self.name):
            self._encode_into(x, wire, residual)
        reg.counter("mpit_codec_encode_bytes_total",
                    codec=self.name).inc(int(wire.nbytes))

    def decode_into(self, wire: np.ndarray, out: np.ndarray) -> None:
        """Decode a frame into the float32 ``out`` buffer (host path).
        Timed into the obs registry like :meth:`encode_into`."""
        reg = _obs.get_registry()
        if not reg.enabled:
            self._decode_into(wire, out)
            return
        with reg.timer("mpit_codec_decode_seconds", codec=self.name):
            self._decode_into(wire, out)
        reg.counter("mpit_codec_decode_bytes_total",
                    codec=self.name).inc(int(wire.nbytes))

    def _encode_into(self, x, wire, residual=None) -> None:
        raise NotImplementedError

    def _decode_into(self, wire, out) -> None:
        raise NotImplementedError

    def split_wire(self, wire: np.ndarray, size: int) -> List[np.ndarray]:
        """Typed zero-copy views over a staging buffer, in the order
        ``decode_parts`` consumes them."""
        raise NotImplementedError

    #: bytes per element for codecs whose frame is one homogeneous
    #: region (none: 4, bf16: 2); frame-structured codecs override
    #: chunk_regions instead.
    _flat_stride: int = 0

    def chunk_regions(self, size: int, lo: int,
                      hi: int) -> "List[Tuple[int, int, int]]":
        """``(full_off, chunk_off, nbytes)`` copy spans mapping the
        *independent* chunk frame for elements ``[lo, hi)`` onto the
        full-``size`` frame's byte regions (streaming transfers,
        docs/PROTOCOL.md §12).  ``lo`` must sit on a BLOCK boundary —
        the invariant that makes a per-chunk encode bit-identical to
        the same region of a whole-shard encode, residual fold
        included."""
        if lo % BLOCK:
            raise ValueError(
                f"chunk start {lo} is not BLOCK({BLOCK})-aligned — "
                "chunk frames are only bit-stable on block boundaries")
        stride = self._flat_stride
        if not stride:
            raise NotImplementedError
        return [(stride * lo, 0, stride * (hi - lo))]

    def decode_parts(self, parts: List, size: int):
        """jax-traceable decode of ``split_wire`` parts -> float32[size].
        Called inside the server's jitted update program."""
        raise NotImplementedError


class NoneCodec(Codec):
    name = "none"
    wire_id = 0
    identity = True
    _flat_stride = 4

    def wire_nbytes(self, size: int) -> int:
        return 4 * size

    def _encode_into(self, x, wire, residual=None):
        wire.view(np.float32)[: x.size] = x

    def _decode_into(self, wire, out):
        out[:] = wire.view(np.float32)[: out.size]

    def split_wire(self, wire, size):
        return [wire.view(np.float32)[:size]]

    def decode_parts(self, parts, size):
        return parts[0]


class Bf16Codec(Codec):
    name = "bf16"
    wire_id = 1
    _flat_stride = 2

    def wire_nbytes(self, size: int) -> int:
        return 2 * size

    def _encode_into(self, x, wire, residual=None):
        # Truncation: keep the top 16 bits of the fp32 word.  On a
        # little-endian host that is one strided copy of the high
        # half-words — no whole-shard uint32 temporaries, which at the
        # 640 MB ptest scale would cost two extra DRAM sweeps plus the
        # allocations.  (Residual is accepted for interface uniformity
        # but bf16's ~2^-8 relative error needs no feedback; it stays
        # zero.)
        lib = _native()
        if lib is not None:
            lib.mt_codec_bf16_encode(x, x.size, wire)
        elif _LITTLE:
            wire.view(np.uint16)[: x.size] = x.view(np.uint16)[1::2]
        else:  # pragma: no cover - big-endian fallback
            wire.view(np.uint16)[: x.size] = (
                x.view(np.uint32) >> 16
            ).astype(np.uint16)

    def _decode_into(self, wire, out):
        lib = _native()
        if lib is not None:
            lib.mt_codec_bf16_decode(wire, out.size, out)
        elif _LITTLE:
            o16 = out.view(np.uint16)
            o16[0::2] = 0  # low mantissa halves
            o16[1::2] = wire.view(np.uint16)[: out.size]
        else:  # pragma: no cover - big-endian fallback
            out.view(np.uint32)[:] = (
                wire.view(np.uint16)[: out.size].astype(np.uint32) << 16
            )

    def split_wire(self, wire, size):
        import ml_dtypes  # ships with jax

        return [wire.view(ml_dtypes.bfloat16)[:size]]

    def decode_parts(self, parts, size):
        import jax.numpy as jnp

        return parts[0].astype(jnp.float32)


class Int8Codec(Codec):
    name = "int8"
    wire_id = 2
    uses_residual = True

    def wire_nbytes(self, size: int) -> int:
        return 4 * _nblocks(size) + size

    def _views(self, wire: np.ndarray, size: int):
        nb = _nblocks(size)
        scales = wire[: 4 * nb].view(np.float32)
        codes = wire[4 * nb : 4 * nb + size].view(np.int8)
        return scales, codes

    def _encode_into(self, x, wire, residual=None):
        # Cache-tiled and pass-frugal on purpose: the encoder competes
        # with the wire for the same memory bandwidth, so every DRAM
        # sweep shows up 1:1 in PS throughput.  The slice is processed
        # in _TILE-element tiles whose temporaries stay cache-resident —
        # DRAM traffic approaches the compulsory minimum (read x[/r],
        # write codes[/r]) instead of one full sweep per ufunc.  absmax
        # uses max/min (no |x| temp); codes come from one multiply by
        # the reciprocal scale + in-place rint; no clip pass — |work| <=
        # block absmax guarantees |rint(work * (1/scale))| <= 127.
        size = x.size
        nb = _nblocks(size)
        nfull, main = size // BLOCK, (size // BLOCK) * BLOCK
        scales, codes = self._views(wire, size)
        lib = _native()
        if lib is not None:
            lib.mt_codec_int8_encode(x, residual, size, scales, codes)
            return
        if nfull:
            work = np.empty(min(_TILE, main), np.float32)
            q = np.empty_like(work)
            inv = np.empty(min(_TILE, main) // BLOCK, np.float32)
            for lo in range(0, main, _TILE):
                hi = min(lo + _TILE, main)
                tb = (hi - lo) // BLOCK  # tile block count
                w2 = work[: hi - lo].reshape(tb, BLOCK)
                q2 = q[: hi - lo].reshape(tb, BLOCK)
                if residual is None:
                    np.copyto(work[: hi - lo], x[lo:hi])
                else:
                    np.add(x[lo:hi], residual[lo:hi],
                           out=work[: hi - lo])
                sc = scales[lo // BLOCK : lo // BLOCK + tb]
                np.max(w2, axis=1, out=sc)
                np.min(w2, axis=1, out=inv[:tb])
                np.maximum(sc, -inv[:tb], out=sc)
                # scale = absmax/127; zero blocks keep scale 1.0 (codes
                # are all zero either way; avoids inf reciprocals).
                np.divide(sc, 127.0, out=sc)
                sc[sc == 0.0] = 1.0
                np.divide(1.0, sc, out=inv[:tb])
                np.multiply(w2, inv[:tb, None], out=q2)
                np.rint(q2, out=q2)
                np.copyto(codes[lo:hi].reshape(tb, BLOCK), q2,
                          casting="unsafe")
                if residual is not None:
                    q2 *= sc[:, None]  # q2 is now the dequantized value
                    np.subtract(w2, q2,
                                out=residual[lo:hi].reshape(tb, BLOCK))
        if main < size:
            # Pure-f32 scalar math, same op order as the full blocks and
            # the native kernel — the tail frame is bit-identical to
            # what mt_codec_int8_encode produces.
            tail = (x[main:] if residual is None
                    else x[main:] + residual[main:])
            absmax = np.float32(max(tail.max(initial=0.0),
                                    -tail.min(initial=0.0)))
            scales[nb - 1] = (np.float32(1.0) if absmax == 0.0
                              else absmax / np.float32(127.0))
            t = tail * (np.float32(1.0) / scales[nb - 1])
            np.rint(t, out=t)
            np.copyto(codes[main:], t, casting="unsafe")
            if residual is not None:
                t *= scales[nb - 1]
                np.subtract(tail, t, out=residual[main:])

    def _decode_into(self, wire, out):
        # Tiled like encode_into: dequantize straight into the caller's
        # slice, int8->f32 cast riding the same cache-resident pass as
        # the scale multiply.
        size = out.size
        nb = _nblocks(size)
        nfull, main = size // BLOCK, (size // BLOCK) * BLOCK
        scales, codes = self._views(wire, size)
        lib = _native()
        if lib is not None:
            lib.mt_codec_int8_decode(scales, codes, size, out)
            return
        for lo in range(0, main, _TILE):
            hi = min(lo + _TILE, main)
            tb = (hi - lo) // BLOCK
            o2 = out[lo:hi].reshape(tb, BLOCK)
            np.copyto(o2, codes[lo:hi].reshape(tb, BLOCK), casting="unsafe")
            o2 *= scales[lo // BLOCK : lo // BLOCK + tb, None]
        if main < size:
            out[main:] = codes[main:].astype(np.float32) * scales[nb - 1]

    def split_wire(self, wire, size):
        return list(self._views(wire, size))

    def chunk_regions(self, size, lo, hi):
        # The chunk frame is itself an int8 frame for (hi - lo)
        # elements: [chunk scales | chunk codes].  Block alignment of
        # ``lo`` makes its scale blocks a contiguous run of the full
        # frame's scale region — two copy spans, no per-block walk.
        if lo % BLOCK:
            raise ValueError(
                f"chunk start {lo} is not BLOCK({BLOCK})-aligned — "
                "chunk frames are only bit-stable on block boundaries")
        nb_chunk = _nblocks(hi - lo)
        return [
            (4 * (lo // BLOCK), 0, 4 * nb_chunk),
            (4 * _nblocks(size) + lo, 4 * nb_chunk, hi - lo),
        ]

    def decode_parts(self, parts, size):
        import jax.numpy as jnp

        scales, codes = parts
        nfull, main = size // BLOCK, (size // BLOCK) * BLOCK
        pieces = []
        if nfull:
            pieces.append(
                (codes[:main].reshape(nfull, BLOCK).astype(jnp.float32)
                 * scales[:nfull, None]).reshape(-1)
            )
        if main < size:
            pieces.append(codes[main:].astype(jnp.float32) * scales[-1])
        return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)


_REGISTRY: Dict[str, Codec] = {}
_BY_WIRE_ID: Dict[int, Codec] = {}

for _codec in (NoneCodec(), Bf16Codec(), Int8Codec()):
    _REGISTRY[_codec.name] = _codec
    _BY_WIRE_ID[_codec.wire_id] = _codec


def get(name: Optional[str] = None) -> Codec:
    """Codec by name; None/'' falls back to ``$MPIT_PS_CODEC`` (default
    'none').  Unknown names fail loudly — a typo must not silently train
    uncompressed."""
    if not name:
        name = os.environ.get(ENV, "none") or "none"
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown PS codec {name!r}; available: {sorted(_REGISTRY)}"
        ) from None


def by_wire_id(wire_id: int) -> Codec:
    """Codec from an INIT v2 announcement id.  Unknown ids fail loudly —
    decoding with the wrong codec would corrupt parameters."""
    try:
        return _BY_WIRE_ID[wire_id]
    except KeyError:
        raise ValueError(
            f"unknown codec wire id {wire_id} in INIT announcement; "
            f"known: { {c.wire_id: c.name for c in _REGISTRY.values()} }"
        ) from None


def names() -> List[str]:
    return sorted(_REGISTRY)


# -- chunk-frame <-> full-frame copies (streaming transfers, §12) ------------


def _chunk_copy_spans(codec: Codec, size: int, lo: int, hi: int,
                      itemsize: int) -> List[Tuple[int, int, int]]:
    """Identity codecs carry arbitrary dtypes — their regions scale by
    the *registered* itemsize, not the f32 the quantizers assume."""
    if codec.identity:
        if lo % BLOCK:
            raise ValueError(
                f"chunk start {lo} is not BLOCK({BLOCK})-aligned — "
                "chunk frames are only bit-stable on block boundaries")
        return [(itemsize * lo, 0, itemsize * (hi - lo))]
    return codec.chunk_regions(size, lo, hi)


def gather_chunk(codec: Codec, full: np.ndarray, size: int, lo: int,
                 hi: int, chunk: np.ndarray, itemsize: int = 4) -> None:
    """Copy the ``[lo, hi)`` chunk's independent frame out of a
    full-shard frame (the PARAM serve path: one shared snapshot encode,
    per-chunk frames cut from it)."""
    for full_off, chunk_off, nbytes in _chunk_copy_spans(
            codec, size, lo, hi, itemsize):
        chunk[chunk_off:chunk_off + nbytes] = full[full_off:full_off + nbytes]


def scatter_chunk(codec: Codec, full: np.ndarray, size: int, lo: int,
                  hi: int, chunk: np.ndarray, itemsize: int = 4) -> None:
    """Copy a chunk frame into its regions of a full-shard frame (the
    PARAM_PUSH assembly path: chunks land in staging, one decode+seed
    at completion)."""
    for full_off, chunk_off, nbytes in _chunk_copy_spans(
            codec, size, lo, hi, itemsize):
        full[full_off:full_off + nbytes] = chunk[chunk_off:chunk_off + nbytes]
