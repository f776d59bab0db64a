"""Drafters: propose K tokens per live slot for one verify forward.

A drafter is HOST-side policy with a fixed-shape contract: given one
history per slot (``None`` for dead slots AND for slots still
``PREFILLING`` under stall-free chunked admission — the serving engine
withholds their histories, so no draft is ever proposed against a
half-written cache row), return ``(tokens, counts)`` where ``tokens``
is ``(num_slots, K)`` int32 and ``counts`` is ``(num_slots,)`` int32
with ``counts[i]`` real proposals in row ``i`` (the rest is padding the
verifier masks). A slot with ``counts == 0`` degrades to a plain decode
step inside the same verify program — no shape change, no recompile,
just zero accepted drafts.

Correctness never depends on the drafter: verification accepts exactly
the prefix the target model reproduces (greedy) or rejection-samples
losslessly (``do_sample``), so a bad proposal costs only wasted verify
width, never wrong output.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_MIN_DRAFT_BUCKET = 16


def bucket_width(n: int, cap: int) -> int:
    """Next power-of-two >= n (min 16), capped at ``cap`` — the same
    bucketing the serving engine uses for prefill, bounding draft-side
    recompiles at log2(capacity) across arbitrary history lengths."""
    b = _MIN_DRAFT_BUCKET
    while b < n:
        b *= 2
    return min(b, cap)


class Drafter:
    """Pluggable proposal interface (see module docstring contract)."""

    name = "drafter"

    def propose(self, histories: List[Optional[np.ndarray]], k: int
                ) -> Tuple[np.ndarray, np.ndarray]:
        """``histories[slot]`` is prompt+generated tokens (int32, includes
        the not-yet-decoded current token) or ``None`` for a dead slot.
        Returns ``(tokens (num_slots, k) int32, counts (num_slots,) int32)``.

        Failure contract: ``propose`` runs inside the serving engine's
        exception-safe step — a drafter that raises aborts the step
        cleanly (``ServingEngine._abort_step``: no slot leaks, running
        requests FAIL with ``finish_reason="error"``, the error
        propagates to the caller). A drafter that cannot produce drafts
        should return ``counts`` of zeros instead of raising — zero-draft
        rows reduce verify to plain decode at zero extra cost."""
        raise NotImplementedError


class NGramDrafter(Drafter):
    """Prompt-lookup decoding: propose the continuation of the most
    recent earlier occurrence of the history's own suffix (Saxena 2023
    prompt-lookup; the assisted-generation candidate strategy). Zero
    model cost — pure host suffix matching — so its draft overhead is
    microseconds and any acceptance at all is profit. Wins on
    repetitive/extractive traffic (summarization, code edits, retrieval
    answers that quote the prompt); on non-repetitive text acceptance
    tends to zero and throughput degrades gracefully to plain decode."""

    name = "ngram"

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1):
        if not (1 <= min_ngram <= max_ngram):
            raise ValueError(f"need 1 <= min_ngram({min_ngram}) <= "
                             f"max_ngram({max_ngram})")
        self.max_ngram = max_ngram
        self.min_ngram = min_ngram

    def _continuation(self, h: np.ndarray, k: int) -> Optional[np.ndarray]:
        T = len(h)
        # longest suffix first: a longer matched context extrapolates
        # better; fall through to shorter n on no match
        for n in range(min(self.max_ngram, T - 1), self.min_ngram - 1, -1):
            pat = h[T - n:]
            # candidate windows h[s:s+n] must end before the final
            # position so at least one continuation token exists
            win = np.lib.stride_tricks.sliding_window_view(h[:T - 1], n)
            hits = np.nonzero((win == pat).all(axis=1))[0]
            if len(hits):
                s = int(hits[-1])  # most recent occurrence
                return h[s + n:s + n + k]
        return None

    def propose(self, histories, k):
        B = len(histories)
        tokens = np.zeros((B, k), np.int32)
        counts = np.zeros((B,), np.int32)
        for i, h in enumerate(histories):
            if h is None:
                continue
            h = np.asarray(h, np.int32)
            if len(h) < self.min_ngram + 1:
                continue
            cont = self._continuation(h, k)
            if cont is not None and len(cont):
                tokens[i, :len(cont)] = cont
                counts[i] = len(cont)
        return tokens, counts


class SmallModelDrafter(Drafter):
    """Draft with a second (smaller) ``InferenceEngine`` sharing the
    target's tokenizer — the classic two-model speculative setup.

    Stateless per step: one bucketed batched ``prefill_last`` over every
    live slot's history (per-slot ``last_pos``, right-padded to a
    power-of-two width) seeds a fresh draft KV cache, then ``k-1``
    single-token greedy decode steps extend it. Recompiles stay bounded
    (log2 prefill buckets + one decode program). The per-step draft
    prefill is O(history) — worth it only when the draft model is much
    smaller than the target; for repetitive traffic prefer
    :class:`NGramDrafter`, whose overhead is microseconds.

    Proposals are greedy, i.e. deterministic given the context, so the
    verifier's point-mass rejection-sampling treatment stays lossless
    for ``do_sample`` too.
    """

    name = "model"

    def __init__(self, engine):
        self.engine = engine
        self._argmax = None

    def propose(self, histories, k):
        eng = self.engine
        eng._ensure_params(jnp.zeros((1, 2), jnp.int32))
        if getattr(eng, "_jit_prefill_at", None) is None:
            raise ValueError("SmallModelDrafter requires the draft module "
                             "to expose prefill_last(input_ids, last_pos)")
        spec = eng.kv_cache_spec()
        if spec is None:
            raise ValueError("SmallModelDrafter requires the draft module "
                             "to declare kv_cache_spec()")
        cap = int(spec.max_seq_len)
        B = len(histories)
        # keep the most recent window that still leaves room for k draft
        # positions; truncation only shifts absolute positions the draft
        # model sees (draft quality, never correctness — verify guards)
        keep = max(cap - k - 1, 1)
        rows = [None if h is None else np.asarray(h, np.int32)[-keep:]
                for h in histories]
        lens = np.array([0 if r is None else len(r) for r in rows], np.int32)
        W = bucket_width(max(int(lens.max()), 1), cap)
        ids = np.zeros((B, W), np.int32)
        for i, r in enumerate(rows):
            if r is not None:
                ids[i, :len(r)] = r
        last_pos = np.maximum(lens - 1, 0).astype(np.int32)
        logits, cache = eng._jit_prefill_at(eng.params, jnp.asarray(ids),
                                            jnp.asarray(last_pos))
        # the batched prefill ran at padded width W; per-slot TRUE lengths
        # mask the right-padding's garbage KV, exactly as the slot pool's
        # admit does (vector index is the slot-pooled decode contract)
        cs = dict(cache["cache_store"])
        cs["index"] = jnp.asarray(lens)
        cache = {"cache_store": cs}
        if self._argmax is None:
            self._argmax = jax.jit(lambda lg: jnp.argmax(
                lg[:, -1, :].astype(jnp.float32), axis=-1).astype(jnp.int32))
        cur = self._argmax(logits)
        toks = [cur]
        for _ in range(k - 1):
            # the (B,) tokens as they are, positions from the cache's own
            # index (``lens`` and one more a step, inside the capacity by
            # ``keep``): nothing is put and nothing runs before the program
            logits, cache = eng._jit_decode(eng.params, cache, cur)
            cur = self._argmax(logits)
            toks.append(cur)
        tokens = np.stack([np.asarray(t) for t in toks], axis=1)
        counts = np.where(lens > 0, k, 0).astype(np.int32)
        return tokens.astype(np.int32), counts
