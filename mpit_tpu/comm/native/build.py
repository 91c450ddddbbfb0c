"""Build the native transport shared library (the CMakeLists analog,
reference CMakeLists.txt:25-29 — one translation unit, one artifact).

Compiled on first use and reused while the recorded hash of the source
and the flags (``libmt_transport.so.hash``, beside the library) matches;
force with ``python -m mpit_tpu.comm.native.build``.  Neither file is a
repository file: a checkout builds its own on the machine that runs it.
"""

from __future__ import annotations

import hashlib
import os
import pathlib
import subprocess
import threading

HERE = pathlib.Path(__file__).resolve().parent
SRC = HERE / "transport.cpp"
LIB = HERE / "libmt_transport.so"
STAMP = HERE / "libmt_transport.so.hash"

_lock = threading.Lock()

# -O3 for the auto-vectorizer (GCC<12 does not vectorize at -O2; the codec
# kernels need it), -march=native because the library is always built on
# the host that runs it (baseline x86-64 is SSE2, which has no vector
# rounding insn — the int8 quantize loop needs SSE4.1+ vroundps),
# -fno-math-errno so rintf lowers to that insn, and -ffp-contract=off so
# the codec's float results stay bit-identical to the numpy reference
# implementations (tests/test_codec.py parity oracle).
CXXFLAGS = ["-std=c++17", "-O3", "-march=native", "-fPIC", "-shared",
            "-pthread", "-Wall", "-fno-math-errno", "-ffp-contract=off"]


def _cpu_flags() -> bytes:
    """What ``-march=native`` resolves against: a library built on one
    CPU must not be reused on another (an illegal-instruction crash)."""
    try:
        with open("/proc/cpuinfo", "rb") as fh:
            return next((ln for ln in fh if ln.startswith(b"flags")), b"")
    except OSError:
        return b""


def source_hash() -> str:
    """Hash of everything the library's bytes depend on: the source, the
    flags, and the CPU features ``-march=native`` stands for."""
    h = hashlib.sha256(SRC.read_bytes())
    h.update(" ".join(CXXFLAGS).encode())
    h.update(_cpu_flags())
    return h.hexdigest()


def ensure_built(force: bool = False) -> pathlib.Path:
    """The library, built if the recorded hash does not match — mtimes
    decide nothing (a copied tree keeps neither order nor machine).  The
    compiler writes to a temporary name in the same directory and the
    result is renamed into place, so a concurrent reader sees the old
    library or the new one, never half of either."""
    with _lock:
        want = source_hash()
        if (not force and LIB.exists() and STAMP.exists()
                and STAMP.read_text().strip() == want):
            return LIB
        tmp = LIB.with_name(f"{LIB.name}.{os.getpid()}.tmp")
        cmd = ["g++", *CXXFLAGS, str(SRC), "-o", str(tmp), "-lrt"]
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True)
            if proc.returncode != 0:
                raise RuntimeError(
                    f"native transport build failed:\n$ {' '.join(cmd)}\n"
                    f"{proc.stderr}")
            os.replace(tmp, LIB)
        finally:
            tmp.unlink(missing_ok=True)
        stamp_tmp = STAMP.with_name(f"{STAMP.name}.{os.getpid()}.tmp")
        stamp_tmp.write_text(want + "\n")
        os.replace(stamp_tmp, STAMP)
        return LIB


def main() -> None:
    path = ensure_built(force=True)
    print(f"built {path}")


if __name__ == "__main__":
    main()
