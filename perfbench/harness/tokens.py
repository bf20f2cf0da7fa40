"""Seeded token rows: every id in the vocabulary equally likely. Every
seed gives the same shapes; only the ids differ."""
from __future__ import annotations

import numpy as np


def token_rows(seed: int, n_rows: int, seq_len: int, vocab: int) -> np.ndarray:
    """(n_rows, seq_len + 1) int32 ids in [0, vocab)."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, (n_rows, seq_len + 1)).astype(np.int32)


def batch_pool(seed: int, traffic: dict, vocab: int) -> list[np.ndarray]:
    """``batch_pool`` distinct step batches of (workers, rows, L + 1)."""
    L, n = traffic["seq_len"], traffic["batch_pool"]
    shape = (traffic["workers"], traffic["rows_per_worker"])
    per = int(np.prod(shape))
    rows = token_rows(seed, n * per, L, vocab)
    return [rows[i * per:(i + 1) * per].reshape(shape + (L + 1,))
            for i in range(n)]


def tokens_per_step(traffic: dict) -> int:
    return traffic["workers"] * traffic["rows_per_worker"] * traffic["seq_len"]
