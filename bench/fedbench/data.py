"""The traffic generator: each silo's private token stream, from the seed.

A silo draws tokens from its own Dirichlet(``alpha``) distribution over
the vocabulary (non-IID across silos, as cross-silo federations are).
Batch ``k`` of silo ``i`` is a pure function of ``(seed, i, k)``, so the
reference draws exactly the batches the program was served, in any
order, and every seed serves the same shapes in the same number.
"""
from __future__ import annotations

import time

import numpy as np


def silo_rng(seed: int, silo: int, *tail: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed), int(silo), *tail]))


class SiloStream:
    """One silo's dataset as the program sees it: ``batch``, ``stats``.

    ``calls`` logs ``(perf_counter, batch index)`` for every batch
    served, so the harness can tell which batches a training span drew.
    """

    def __init__(self, silo_id: str, index: int, *, seed: int, vocab: int,
                 seq_len: int, alpha: float):
        self.silo_id = silo_id
        self.index = index
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.seq_len = int(seq_len)
        self.n_examples = None           # unbounded: equal FedAvg weights
        probs = silo_rng(seed, index, 0).dirichlet(np.full(vocab, alpha))
        self._cdf = np.cumsum(probs / probs.sum())
        self._cdf[-1] = 1.0
        self.calls: list = []

    def tokens(self, k: int, batch_size: int) -> np.ndarray:
        u = silo_rng(self.seed, self.index, 1, k).random(
            (batch_size, self.seq_len))
        return np.searchsorted(self._cdf, u, side="right").astype(np.int32)

    def batch(self, batch_size: int) -> dict:
        k = len(self.calls)
        self.calls.append((time.perf_counter(), k))
        return {"tokens": self.tokens(k, batch_size)}

    def stats(self) -> dict:
        p = np.diff(self._cdf, prepend=0.0)
        return {"vocab": self.vocab, "seq_len": self.seq_len,
                "entropy": float(-(p * np.log(p + 1e-12)).sum()),
                "top_token": int(p.argmax())}
