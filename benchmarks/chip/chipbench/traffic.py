"""The training traffic: Zipf tokens with a bigram hint, from the seed.

A copy of the program's synthetic corpus (``repro.data.synthetic``), kept
here so that the benchmark's inputs cannot change under a later PR. Every
(seed, step, node) gives its own rows; every seed gives the same sizes.
The parameters come from the traffic file.
"""
from __future__ import annotations

import numpy as np


class ZipfTokens:
    """``batch(step)`` -> {"tokens", "labels"}: int32 [J, B, S] numpy."""

    def __init__(self, mix: dict, vocab: int, nodes: int, seed: int):
        self.vocab, self.nodes, self.seed = vocab, nodes, int(seed)
        self.batch_size, self.seq = mix["batch_per_node"], mix["seq_len"]
        self.mult, self.add = mix["bigram_mult"], mix["bigram_add"]
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        p = ranks ** (-mix["zipf_a"])
        self.probs = (p / p.sum()).astype(np.float32)

    def batch(self, step: int) -> dict:
        toks = np.empty((self.nodes, self.batch_size, self.seq), np.int32)
        for node in range(self.nodes):
            rng = np.random.default_rng(
                (self.seed * 7_919 + node) * 2_654_435_761 + step)
            t = rng.choice(self.vocab, p=self.probs,
                           size=(self.batch_size, self.seq))
            t[:, 1::2] = (t[:, 0::2] * self.mult + self.add) % self.vocab
            toks[node] = t
        labels = np.roll(toks, -1, axis=-1)
        labels[:, :, -1] = -1                      # no target for the last
        return {"tokens": toks, "labels": labels}
