"""Token batches for the training cells: Zipf-distributed unigrams over the
configuration's vocabulary, with a copied span in a share of the rows, so the
loss has structure to learn. Every row is a pure function of (seed, step,
row): steps and rows all differ, and the reference makes the same batches
again. The same stream as `repro.data.synthetic.SyntheticLM`, kept here so
that the yardstick does not move with the program.

Traffic file keys: seq_len, batch, zipf_a, copy_prob.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


class LMStream:
    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.seq_len = int(traffic["seq_len"])
        self.batch_size = int(traffic["batch"])
        self.copy_prob = float(traffic["copy_prob"])
        self.seed = int(seed)
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        p = ranks ** (-float(traffic["zipf_a"]))
        self.p = p / p.sum()
        self.vocab = vocab

    @property
    def tokens_per_step(self) -> int:
        return self.seq_len * self.batch_size

    def row(self, step: int, row: int) -> np.ndarray:
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, step, row]))
        seq = rng.choice(self.vocab, size=self.seq_len + 1, p=self.p)
        if rng.random() < self.copy_prob and self.seq_len >= 32:
            span = self.seq_len // 4
            start = rng.integers(0, self.seq_len - 2 * span)
            seq[start + span:start + 2 * span] = seq[start:start + span]
        return seq.astype(np.int32)

    def batch(self, step: int) -> Dict[str, np.ndarray]:
        toks = np.stack([self.row(step, r) for r in range(self.batch_size)])
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def make(traffic: dict, seed: int, config: dict) -> LMStream:
    return LMStream(traffic, seed, int(config["vocab_size"]))
