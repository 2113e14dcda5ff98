"""Token batches for a training cell, from a traffic file and the seed.

A traffic file holds the job as the scheduler hands it to the runtime: the
execution plan, the global batch, the sequence length, and how tokens are
drawn.  ``kind: markov`` is the repo's synthetic stream
(``repro.data.pipeline.SyntheticTokens``), copied here so that the yardstick
does not move when the program's pipeline does: uniform tokens, each of
which, with probability ``follow``, is replaced by a fixed function of the
token before it, so the loss can fall.  Every batch is a pure function of
(seed, step), and the rows of every batch differ.
"""

from __future__ import annotations

import numpy as np


class MarkovTokens:
    def __init__(self, vocab_size: int, traffic: dict, seed: int):
        spec = traffic["tokens"]
        if spec["kind"] != "markov":
            raise ValueError(f"unknown token kind {spec['kind']!r}")
        self.vocab = vocab_size
        self.batch_size = traffic["global_batch"]
        self.seq = traffic["seq_len"]
        self.follow = spec["follow"]
        self.seed = seed
        self._mix = np.random.default_rng(seed).integers(
            1, vocab_size, size=spec["mix"]).astype(np.int64)

    def batch(self, step: int) -> np.ndarray:
        """(global_batch, seq_len) int32 tokens of step ``step``."""
        rng = np.random.default_rng((self.seed * 1_000_003 + step) % 2**31)
        b = rng.integers(0, self.vocab, size=(self.batch_size, self.seq),
                         dtype=np.int64)
        key = self._mix[b[:, :-1] % len(self._mix)]
        b[:, 1:] = np.where(rng.random(b[:, 1:].shape) < self.follow,
                            (b[:, :-1] + key) % self.vocab, b[:, 1:])
        return b.astype(np.int32)
