"""Clipped lognormal lengths, shared by the serving generators.

A fixed amount of work drawn from the seed: ``stratified_lengths`` gives n
lengths that are the n mid-quantiles of the distribution in a seeded order,
so every run of n requests holds the same multiset of lengths and only
their order differs. (Independent draws would make a window of a few dozen
requests differ from seed to seed by its mix of lengths far more than by
anything the server does.)"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def clip_round(x: float, spec: dict) -> int:
    return int(min(max(round(x), spec["min"]), spec["max"]))


def lognormal_length(rng: np.random.Generator, spec: dict) -> int:
    """One length from ``{"median", "sigma", "min", "max"}``: lognormal
    around the median, rounded, clipped to [min, max]."""
    return clip_round(rng.lognormal(math.log(spec["median"]),
                                    spec["sigma"]), spec)


def stratified_lengths(rng: np.random.Generator, spec: dict, n: int) -> list:
    """n lengths: the quantiles (i + 0.5) / n of the clipped lognormal, in
    an order drawn from ``rng``."""
    normal = NormalDist()
    values = [clip_round(math.exp(math.log(spec["median"]) + spec["sigma"]
                                  * normal.inv_cdf((i + 0.5) / n)), spec)
              for i in range(n)]
    return [values[i] for i in rng.permutation(n)]


def make_request(rng: np.random.Generator, n_prompt: int, n_out: int,
                 vocab_size: int, context_len: int):
    """(prompt tokens, max_new_tokens) with prompt + output <= context.
    Tokens are uniform over [1, vocab): no two prompts share a prefix
    beyond chance, so the prefix cache has nothing to hit."""
    n_out = max(1, min(n_out, context_len - n_prompt))
    return rng.integers(1, vocab_size, n_prompt, dtype=np.int32), n_out


def request(rng: np.random.Generator, params: dict, vocab_size: int,
            context_len: int):
    """One request with independently drawn lengths."""
    return make_request(rng, lognormal_length(rng, params["prompt_len"]),
                        lognormal_length(rng, params["output_len"]),
                        vocab_size, context_len)
