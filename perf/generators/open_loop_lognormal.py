"""Open loop: independent users. Arrivals at a fixed rate, whatever the
server does; prompt and output lengths clipped lognormal; greedy; no shared
prefix. Times are seconds relative to the opening of the measured window:
arrivals start ``lead_in_s`` before it, so the window opens on a server that
already holds requests, and go on ``tail_s`` past its end, so the last
requests due in the window are served under the same load.

A fixed amount of work: each of the three stretches (lead-in, window, tail)
holds exactly round(rate x its length) requests, due at sorted uniform times
(a Poisson process given its count), with the stratified lengths of
``_lengths.stratified_lengths``: the same number of requests with the same
multiset of lengths whatever is drawn.

The schedule (who is due when, with how long a prompt and answer) is drawn
from the traffic file's ``schedule_seed`` and is the same in every run; the
run's seed sets the tokens alone. Which long answers overlap moves the live
slots, and so the length of a step, by several per cent from one draw of
the times to the next, far more than two runs of one draw differ (PR 45: a
run's own draw spread the median gap 2-4 %, one schedule 0.5-0.8 %)."""

from __future__ import annotations

import numpy as np

from perf.generators._lengths import make_request, stratified_lengths


class OpenLoop:
    closed = False

    def __init__(self, params: dict, seed: int, seconds: float,
                 vocab_size: int, context_len: int):
        rate = float(params["rate_per_s"])
        rng = np.random.default_rng([int(seed), 1])
        plan = np.random.default_rng([int(params["schedule_seed"]), 1])
        stretches = [(-float(params["lead_in_s"]), 0.0),
                     (0.0, float(seconds)),
                     (float(seconds), float(seconds)
                      + float(params["tail_s"]))]
        self.requests = []
        for t0, t1 in stretches:
            n = int(round(rate * (t1 - t0)))
            if n == 0:
                continue
            due = np.sort(plan.uniform(t0, t1, n))
            prompts = stratified_lengths(plan, params["prompt_len"], n)
            outputs = stratified_lengths(plan, params["output_len"], n)
            for t, n_prompt, n_out in zip(due, prompts, outputs):
                prompt, n_out = make_request(rng, n_prompt, n_out,
                                             vocab_size, context_len)
                self.requests.append({"key": len(self.requests),
                                      "due_s": float(t), "prompt": prompt,
                                      "max_new_tokens": n_out})
        self._next = 0

    def due(self, now_s: float) -> list:
        """The requests whose due time has come, in order, each once."""
        out = []
        while self._next < len(self.requests) and \
                self.requests[self._next]["due_s"] <= now_s:
            out.append(self.requests[self._next])
            self._next += 1
        return out

    def next_due_s(self):
        if self._next < len(self.requests):
            return self.requests[self._next]["due_s"]
        return None

    def on_finished(self, spec: dict, now_s: float) -> None:
        pass


def generate(params: dict, seed: int, seconds: float, context: dict):
    return OpenLoop(params, seed, seconds, context["vocab_size"],
                    context["context_len"])
