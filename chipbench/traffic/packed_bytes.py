"""The benchmark's own copy of the packed byte stream: the batches are a
pure function of ``(seed, step)`` and of the mix's ``batch`` and the
configuration's sequence length, and are what the worker's loop feeds the
program.  Copied from ``mpit_tpu/data/tokens.py`` (documents) and
``mpit_tpu/lm/data.py`` (packing) so that a later PR cannot change the
traffic; the self-check and every run's set-up compare it with the
program's for three ``(seed, step)`` pairs.  numpy only.

Documents are modular walks ``tok[i] = (start + i * stride) % 256`` of
8..96 bytes with an odd stride from a small set: the unigram is flat
(ln 256 = 5.545 nats to start from) and the next byte follows from the
last two, so a decoder that learns falls well under it.  Documents are
concatenated with a 0 byte between them into ``batch x (seq + 1)`` cells
with no padding; the extra column gives inputs ``[:, :-1]`` and targets
``[:, 1:]`` from one array.
"""

from __future__ import annotations

from typing import List

import numpy as np

VOCAB = 256
STRIDES = (1, 3, 5, 7, 11)
MIN_DOC = 8
MAX_DOC = 96
EOS = 0


def doc_batch(seed: int, step: int, budget: int) -> List[np.ndarray]:
    """Documents of step ``step`` of stream ``seed``, at least ``budget``
    bytes in all, from a Philox generator keyed by ``(seed, step)``."""
    rng = np.random.Generator(np.random.Philox(
        key=[seed & 0xFFFFFFFF, step & 0xFFFFFFFF]))
    docs: List[np.ndarray] = []
    total = 0
    while total < budget:
        length = int(rng.integers(MIN_DOC, MAX_DOC + 1))
        start = int(rng.integers(0, VOCAB))
        stride = int(STRIDES[int(rng.integers(0, len(STRIDES)))])
        doc = (start + stride * np.arange(length, dtype=np.int64)) % VOCAB
        docs.append(doc.astype(np.int32))
        total += length
    return docs


def packed_batch(seed: int, step: int, batch: int, seq_len: int) -> np.ndarray:
    """The ``(batch, seq_len + 1)`` int32 grid of step ``step``."""
    n_cells = batch * (seq_len + 1)
    flat = np.full(n_cells, EOS, np.int32)
    pos = 0
    for doc in doc_batch(seed, step, n_cells):
        if pos >= n_cells:
            break
        take = min(len(doc), n_cells - pos)
        flat[pos:pos + take] = doc[:take]
        pos += take
        if pos < n_cells:
            flat[pos] = EOS
            pos += 1
    return flat.reshape(batch, seq_len + 1)
