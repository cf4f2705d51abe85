"""The one generator of training traffic: batch `index` of a seeded stream.

A copy of the system's own synthetic feed (counter-based Philox, so batch
k is the same whenever and wherever it is made): ids uniform in
``[0, vocab)``, one extra column so that the targets are the tokens
shifted by one.  The harness hands the system only the seed and the
shapes; the reference takes its batches from here.
"""
from __future__ import annotations

import numpy as np


def batch(vocab: int, rows: int, seq: int, seed: int, index: int):
    """(tokens, targets), each int32 (rows, seq)."""
    rng = np.random.Generator(np.random.Philox(key=seed, counter=index))
    ids = rng.integers(0, vocab, size=(rows, seq + 1),
                       dtype=np.int64).astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


def batches(vocab: int, rows: int, seq: int, seed: int, n: int):
    return [batch(vocab, rows, seq, seed, i) for i in range(n)]
