"""chipbench: the on-chip benchmark of the parameter-server training path.

``BENCHMARK.json`` at the repo's root names the cells; everything else is
here and is found by the names that file gives:

- ``run.py``: the one command; ``child.py``: a rank of the gang it starts
  (the program's roles unchanged, the worker's loop the benchmark's);
  ``measure.py``: end-to-end metrics and ``correct``; ``spec.py``: reads a
  cell from data; ``reduce.py``: device trace to numbers; ``flops.py`` and
  ``peaks.json``: the chip's peaks and the shares taken of them;
  ``compare.py``: the comparison every plain reference is held by.
- ``configs/<config>.json``, ``traffic/<mix>.json`` (with the one
  generator, ``traffic/packed_bytes.py``), ``layers/<metric>.py`` (one
  reader per per-layer metric), and what a configuration's file names:
  ``reference/<module>.py`` (its plain reference and tolerance) and
  ``arithmetic/<module>.py`` (its parameters, FLOPs and kernel families);
  ``fixtures/`` (a recorded trace and the numbers its reduction must
  give, a hand-made one, and the self-check's throw-away configuration);
  ``tests/`` (pytest, on the CPU: ``python3 -m pytest chipbench/tests``).
- by hand: ``selfcheck.py`` (the CPU rehearsal, before any chip call),
  ``rehearse_compile.py`` (compiles each cell's step for the described
  chip), ``sweep_lr.py`` and ``reference/probe_tolerance.py`` (how ``lr``
  and the tolerance were chosen), ``fixtures/record_fixture.py``.

A later PR adds a cell, a configuration, a mix, a per-layer metric or a
model whose block the benchmark has never seen with new files and new
entries; it edits nothing that is here (``spec.py`` has the contract of
a configuration's file).  PERF.md at the
root says what the numbers mean.
"""
