"""Synthetic prompts by topic: a first-order Markov chain over a zipf
vocabulary, one chain per topic name, seeded from the name. Each topic
permutes which tokens are frequent, so topics route to different experts
even under random router weights.

Copied from the program's ``repro.data.synthetic.SyntheticLM`` so that
the benchmark's traffic cannot change with the program.
"""
from __future__ import annotations

import hashlib

import numpy as np


def _seed_of(name: str) -> int:
    return int.from_bytes(hashlib.sha256(name.encode()).digest()[:4], "big")


class SyntheticLM:
    def __init__(self, vocab_size: int, *, name: str, branch: int = 16,
                 zipf_a: float = 1.2):
        self.vocab_size = vocab_size
        rng = np.random.default_rng(_seed_of(name))
        base = np.arange(1, vocab_size + 1, dtype=np.float64) ** (-zipf_a)
        base /= base.sum()
        base = base[rng.permutation(vocab_size)]
        self.succ = rng.choice(vocab_size, size=(vocab_size, branch), p=base)
        self.succ_w = rng.dirichlet(np.ones(branch) * 0.5, size=vocab_size)
        self.base = base

    def sample(self, rng: np.random.Generator, batch: int,
               seq_len: int) -> np.ndarray:
        out = np.empty((batch, seq_len), np.int32)
        cur = rng.choice(self.vocab_size, size=batch, p=self.base)
        out[:, 0] = cur
        for t in range(1, seq_len):
            rows = self.succ[cur]
            ws = self.succ_w[cur]
            pick = (ws.cumsum(-1) > rng.random((batch, 1))).argmax(-1)
            cur = rows[np.arange(batch), pick].astype(np.int32)
            out[:, t] = cur
        return out
