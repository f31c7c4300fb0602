"""Seeded inputs: what every cell's runner draws its token ids from.

    token_block(seed, rows, seq, vocab)   -> int32 [rows, seq]

A training cell's mix is the `traffic` object of its file under
`workloads/` (sequence length, microbatch, global batch, warm-up steps,
learning rate): sizes only, the same in every run, so `--seed` changes the
tokens and the weights and never the amount of work.
"""

from __future__ import annotations

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    # Seeds may exceed 32 signed bits; SeedSequence takes any whole number.
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def token_block(seed: int, rows: int, seq: int, vocab: int) -> np.ndarray:
    return _rng(seed, 3).integers(0, vocab, (rows, seq), dtype=np.int32)
