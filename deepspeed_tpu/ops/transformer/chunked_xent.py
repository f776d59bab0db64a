"""Streaming (chunked) softmax cross-entropy over a large vocabulary.

The LM loss tail is the single largest activation in training: the
logits tensor is (B, T, V) — at GPT-2 Large scale (mbs 2, T 1024,
V 50257) that is ~400 MB fp32 PER COPY, and the forward + softmax +
backward chain holds several copies, adding GBs of peak HBM. This is
what kept the 774M single-chip row on full remat: selective ("dots")
remat missed fitting by ~0.6 GB (rounds 1-5; PERF.md §8).

This module computes the same masked mean cross-entropy WITHOUT ever
materializing the full logits: positions stream through in chunks of
``chunk_size``; each chunk projects onto the vocabulary, reduces to
(logsumexp - target logit) * mask, and is summed. ``jax.checkpoint``
on the chunk body makes the backward rematerialize each chunk's logits
in turn, so peak memory is O(B * chunk_size * V) in both passes.

The per-position math is IDENTICAL to the dense path (the projection
runs in the model's compute dtype, exactly like flax ``Embed.attend`` /
the fp32 lm_head; reductions in fp32) — only the summation order
differs, so losses match to fp32 round-off and gradients to matching
tolerance (parity-tested in tests/unit/models/test_chunked_xent.py).

The reference has no analog (its fused softmax-xent kernels still
materialize logits); this is TPU-native memory engineering in the
spirit of its fused-loss CUDA kernels
(csrc/transformer/general_kernels.cu).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def chunked_softmax_xent(x, w, targets, mask, chunk_size: int,
                         compute_dtype=jnp.float32):
    """Masked cross-entropy summed over positions, streaming over T.

    Args:
      x: (B, T, C) final hidden states (pre-projection).
      w: (V, C) projection matrix — the tied embedding table, or the
        lm_head kernel transposed.
      targets: (B, T) int32 target ids (already causally shifted).
      mask: (B, T) float32 — 0 for ignored positions.
      chunk_size: positions per streamed chunk (clamped to T).
      compute_dtype: dtype of the projection dot (the model's compute
        dtype — bf16 for the tied ``Embed.attend`` path, fp32 for an
        fp32 lm_head), matching the dense path bit-for-bit per chunk.

    Returns the SUM of masked per-position nll (caller divides by the
    mask sum for the mean).
    """
    B, T, C = x.shape
    chunk_size = min(chunk_size, T)
    pad = (-T) % chunk_size
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    n = (T + pad) // chunk_size
    xs = x.reshape(B, n, chunk_size, C).transpose(1, 0, 2, 3)
    ts = targets.reshape(B, n, chunk_size).transpose(1, 0, 2)
    ms = mask.reshape(B, n, chunk_size).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk_nll_sum(w, xc, tc, mc):
        logits = jnp.dot(xc.astype(compute_dtype),
                         w.T.astype(compute_dtype))
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        return ((lse - tgt) * mc).sum()

    def body(acc, args):
        xc, tc, mc = args
        return acc + chunk_nll_sum(w, xc, tc, mc), None

    loss, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ts, ms))
    return loss
