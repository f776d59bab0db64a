"""Training traffic: a seeded stream of random token batches, a new one for
every optimizer step. ``batch(step)`` is a pure function of the seed and the
step, so two runs with one seed train on the same tokens."""

from __future__ import annotations

import numpy as np


class TokenStream:
    def __init__(self, params: dict, seed: int, vocab_size: int):
        self.sequences = int(params["sequences_per_step"])
        self.seq_len = int(params["seq_len"])
        self.vocab_size = int(vocab_size)
        self.seed = int(seed)

    @property
    def tokens_per_step(self) -> int:
        return self.sequences * self.seq_len

    def batch(self, step: int) -> dict:
        # negative steps are the warm-up's and the check's: a stream apart
        rng = np.random.default_rng([self.seed, int(step < 0), abs(int(step))])
        ids = rng.integers(0, self.vocab_size,
                           (self.sequences, self.seq_len), dtype=np.int32)
        return {"input_ids": ids}


def generate(params: dict, seed: int, seconds: float,
             context: dict) -> TokenStream:
    return TokenStream(params, seed, context["vocab_size"])
