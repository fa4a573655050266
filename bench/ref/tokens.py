"""Synthetic token batches: a pure function of (seed, step, row, position).

A copy of the program's ``data.pipeline.TokenPipeline`` arithmetic
(SplitMix64 hash of a counter, mapped through a Zipf CDF over the
vocabulary), so every row of every step differs and the same seed gives
the same stream.
"""
from __future__ import annotations

import numpy as np


def _hash64(x: np.ndarray) -> np.ndarray:
    x = (x + np.uint64(0x9E3779B97F4A7C15))
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


class Tokens:
    def __init__(self, vocab: int, seq_len: int, batch: int, seed: int,
                 zipf: float = 1.1):
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        w = 1.0 / ranks ** zipf
        self.cdf = np.cumsum(w) / w.sum()
        self.vocab, self.seq_len, self.batch = vocab, seq_len, batch
        self.seed = np.uint64(seed % (1 << 64))

    def batch_at(self, step: int) -> dict[str, np.ndarray]:
        """``{"tokens", "labels"}``, each ``(batch, seq_len)`` int32."""
        b_idx = np.arange(self.batch, dtype=np.uint64)[:, None]
        s_idx = np.arange(self.seq_len + 1, dtype=np.uint64)[None, :]
        key = (self.seed * np.uint64(0x1000003)
               + np.uint64(step) * np.uint64(0x85EBCA77))
        h = _hash64(key + b_idx * np.uint64(1_000_003) + s_idx)
        u = (h >> np.uint64(11)).astype(np.float64) / float(1 << 53)
        toks = np.clip(np.searchsorted(self.cdf, u), 0,
                       self.vocab - 1).astype(np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
