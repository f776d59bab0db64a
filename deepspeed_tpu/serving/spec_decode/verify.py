"""The verification/acceptance program — pure function, jitted once.

One fixed-shape chunked-decode forward scores the current token plus K
draft positions for ALL ``num_slots`` rows (``(B, K+1)`` inputs,
per-slot ``(B,)`` cache offsets), then acceptance runs in the same
compiled program:

* **greedy** — accept the longest draft prefix whose tokens equal the
  target model's own argmax continuations; the token at the first
  mismatch is the argmax the target would have produced anyway, so
  emitted output is bitwise identical to plain decoding.
* **do_sample** — rejection sampling (Leviathan et al. 2023 §2.3).
  Both shipped drafters are deterministic given context, so the draft
  distribution q is a point mass and ``min(1, p/q)`` reduces to
  ``p(d_j)`` under the serving sampler's filtered distribution; on the
  first rejection the replacement is drawn from the residual (p with
  the rejected token removed, renormalized), which keeps the output
  distribution exactly the target model's.

The cache comes back with every verified position written (the chunk
writes K+1 positions for every row, dead slots included — their writes
land in masked padding). ROLLBACK of rejected positions is the caller's
per-slot ``index`` update (:meth:`SlotPool.advance`): stale K/V beyond
the accepted length is dead by masking, never reshaped or recompiled.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def make_verify_fn(decode_fn, filter_fn):
    """Build the verify body over the engine's traced ``decode_fn``
    ((params, cache, tokens) -> (logits, cache), positions from the
    cache's own index) and its sampling ``filter_fn`` ((..., V) logits,
    temperature, top_k, top_p) — the SAME filter the serving sampler
    uses, so acceptance probabilities match the distribution plain decode
    would have sampled from."""

    def verify(params, cache, cur, draft, draft_len, rng, temperature,
               greedy, top_k, top_p):
        """cur: (B,) int32, the server's current-token twin as it is;
        draft: (B, K) int32; draft_len: (B,) int32 in [0, K] (0 = not
        speculating / dead); rng: the caller's key, split here. The rows
        scored are [current, draft_0..draft_{K-1}] at the cache's own
        per-slot index. Returns (cache, out (B, K+1) int32, n_emit (B,)
        int32, rng'): row i emits out[i, :n_emit[i]] — accepted prefix +
        bonus/correction — and the caller keeps ``rng'`` for its next
        call."""
        tokens = jnp.concatenate([cur[:, None], draft], axis=1)
        B, T = tokens.shape
        K = T - 1
        rng, sub = jax.random.split(rng)
        logits, cache = decode_fn(params, cache, tokens)
        last = logits.astype(jnp.float32)            # (B, K+1, V)
        V = last.shape[-1]
        targets = jnp.argmax(last, axis=-1)          # (B, K+1) greedy next
        in_draft = jnp.arange(K)[None, :] < draft_len[:, None]

        # greedy: accept while the target reproduces the draft
        g_accept = (draft == targets[:, :K]) & in_draft
        # sampling: accept d_j w.p. p(d_j) under the filtered distribution
        # (point-mass q — both drafters are deterministic given context)
        filt = filter_fn(last, temperature, top_k, top_p)
        probs = jax.nn.softmax(filt, axis=-1)
        p_draft = jnp.take_along_axis(probs[:, :K], draft[..., None],
                                      axis=-1)[..., 0]
        rng_acc, rng_bonus = jax.random.split(sub)
        u = jax.random.uniform(rng_acc, (B, K))
        s_accept = (u < p_draft) & in_draft

        accept = jnp.where(greedy, g_accept, s_accept)
        acc = jnp.cumprod(accept.astype(jnp.int32), axis=1)
        n_acc = acc.sum(axis=1)                      # (B,) in [0, K]

        # bonus/correction token from position n_acc: greedy takes the
        # argmax (== what plain decode emits there); sampling draws from
        # the residual — p with the rejected token removed when the stop
        # was a true rejection (not draft exhaustion)
        bonus_filt = jnp.take_along_axis(filt, n_acc[:, None, None],
                                         axis=1)[:, 0]          # (B, V)
        rejected = jnp.take_along_axis(draft,
                                       jnp.clip(n_acc, 0, K - 1)[:, None],
                                       axis=1)[:, 0]
        was_rejection = n_acc < draft_len
        residual = jnp.where((jnp.arange(V)[None, :] == rejected[:, None])
                             & was_rejection[:, None], -1e30, bonus_filt)
        sampled = jax.random.categorical(rng_bonus, residual, axis=-1)
        g_bonus = jnp.take_along_axis(targets, n_acc[:, None], axis=1)[:, 0]
        bonus = jnp.where(greedy, g_bonus, sampled).astype(jnp.int32)

        j = jnp.arange(K + 1)[None, :]
        draft_pad = jnp.pad(draft, ((0, 0), (0, 1)))
        out = jnp.where(j < n_acc[:, None], draft_pad,
                        jnp.where(j == n_acc[:, None], bonus[:, None], 0))
        return cache, out.astype(jnp.int32), n_acc + 1, rng

    return verify
