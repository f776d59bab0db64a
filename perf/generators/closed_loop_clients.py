"""Closed loop: ``clients`` callers that each wait for a reply and send the
next request the moment it comes (no think time unless ``think_s`` says so).
A slow server is offered less; the queue never empties. The k-th request of
client c is a pure function of (seed, c, k). Its due time is the moment the
previous reply was seen; the first ones are due ``lead_in_s`` before the
window opens."""

from __future__ import annotations

import numpy as np

from perf.generators._lengths import request


class ClosedLoop:
    closed = True

    def __init__(self, params: dict, seed: int, seconds: float,
                 vocab_size: int, context_len: int):
        self.params = params
        self.seed = int(seed)
        self.vocab_size = vocab_size
        self.context_len = context_len
        self.think_s = float(params.get("think_s", 0.0))
        self.clients = int(params["clients"])
        self._sent = [0] * self.clients
        self._pending = [self.make(c, 0, -float(params["lead_in_s"]))
                         for c in range(self.clients)]
        self.requests = []

    def make(self, client: int, k: int, due_s: float) -> dict:
        rng = np.random.default_rng([self.seed, 2, client, k])
        prompt, n_out = request(rng, self.params, self.vocab_size,
                                self.context_len)
        return {"key": (client, k), "client": client, "due_s": due_s,
                "prompt": prompt, "max_new_tokens": n_out}

    def due(self, now_s: float) -> list:
        out = [s for s in self._pending if s["due_s"] <= now_s]
        if out:
            self._pending = [s for s in self._pending if s["due_s"] > now_s]
            self.requests.extend(out)
        return out

    def next_due_s(self):
        return min((s["due_s"] for s in self._pending), default=None)

    def on_finished(self, spec: dict, now_s: float) -> None:
        c = spec["client"]
        self._sent[c] += 1
        self._pending.append(self.make(c, self._sent[c],
                                       now_s + self.think_s))


def generate(params: dict, seed: int, seconds: float, context: dict):
    return ClosedLoop(params, seed, seconds, context["vocab_size"],
                      context["context_len"])
