"""Learned block-sparse attention (InfLLM-V2, arXiv:2509.24663; MiniCPM4's
``sparse_config``, arXiv:2506.07900): what stands between the projections
and the page read. A query does not read every key before it: it scores
COMPRESSED keys, turns the scores into a choice of key blocks, and reads the
chosen blocks and a sliding window.

**The equations**, for one KV head whose ``rep`` query heads share its keys
(``sizes``: ``kernel_size`` k, ``kernel_stride`` s, ``block_size`` Bk,
``init_blocks``, ``window_size`` W, ``topk``, ``dense_len``), a query at
position ``i``:

1. compressed key ``j`` is ``mean(key[s j : s j + k])``, visible iff its
   last token ``s j + k - 1 <= i``;
2. ``i + 1 < dense_len``: the query reads every ``p <= i``. Else, for a
   head ``h``, ``p^h = softmax_j(q^h . Kc_j * scale)`` over the visible
   ``j``; the group's ``P_j = sum_h p^h_j``; block ``b`` scores ``max P_j``
   over the compressed keys that overlap it; the first ``init_blocks``
   score ``+inf``; a block that meets the window ``[i - W + 1, i]`` scores
   ``-inf`` (the window reads it); the ``topk`` best are chosen (all, where
   fewer are finite);
3. the query reads the tokens ``p <= i`` of the chosen blocks and of the
   window.

**The cache for the index** (:func:`group_sums_write`). With ``k = m s`` a
compressed key is the mean of ``m`` GROUP means, a group being ``s``
consecutive keys, and a group lies inside one page. A page pool keeps the
group means beside the K/V pages under the same table: a leaf ``(layers,
pages, KV, page_size // s, D)`` float32, a page's ``page_size // s``
groups. A token joins its group's mean as it arrives (the first token of a
group starts it anew), so a compressed key is whole when its last token
has arrived, which is when it becomes visible: nothing reads a group that
is still filling. One sublane tile of float32 a page a head at a stride of
16 and pages of 128: no padding, 1 / 16 of the keys' bytes at twice their
precision.

**The choice** (:func:`choose_blocks`) is XLA's, under the scope
``sparse_index``: the scores against the group means at
``Precision.HIGHEST`` in float32 (a choice is not continuous: the 64th and
the 65th block swap under a coarser product), the means of ``m`` neighbours,
the softmax, the group's sum, the blocks' maxima as a strided window, and
``lax.top_k``. Its result is a mask over blocks a (query, KV head), which
``sparse_read.py`` turns into the page read's work list, a (row, KV head)
its own, in place of "every page up to the length". Below ``dense_len`` the
mask names every block: one program whatever the context.

:func:`sparse_attention_dense` is the same mathematics over keys that lie in
one piece (the forward without a cache, a contiguous cache row): group
means from the keys themselves, the choice, a mask over the full scores."""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["SparseSizes", "group_means", "group_sums_write", "choose_blocks",
           "token_mask", "sparse_attention_dense",
           "tokens_read", "index_rows", "pages_most"]

HIGHEST = jax.lax.Precision.HIGHEST
NEG_INF = -1e30


class SparseSizes(NamedTuple):
    kernel_size: int
    kernel_stride: int
    block_size: int
    init_blocks: int
    window_size: int
    topk: int
    dense_len: int

    def check(self, page_size: int = 0) -> None:
        s, k, bk = self.kernel_stride, self.kernel_size, self.block_size
        if min(self) < 1 or k % s or bk % s or (
                page_size and (page_size % bk or page_size % s)):
            raise ValueError(
                f"sparse attention keeps group means of kernel_stride keys: "
                f"kernel_size and block_size are multiples of it, and a page "
                f"holds whole blocks; got {self}, page_size {page_size}")


def group_means(keys, stride: int):
    """``keys`` (..., S, D) as the means of its groups of ``stride``
    consecutive keys: (..., S // stride, D) float32 (S a multiple)."""
    *lead, S, D = keys.shape
    return jnp.mean(keys.astype(jnp.float32).reshape(
        *lead, S // stride, stride, D), axis=-2)


def group_sums_write(leaf, layer, k, table, start, valid, running, *,
                     page_size: int, stride: int):
    """The index's leaf with the keys of one step joined to their groups.

    ``leaf`` (L, P, KV, page_size // stride, D) float32; ``k`` (B, T, KV,
    D): row ``b``'s keys at positions ``start[b] + t``, of which the first
    ``valid[b]`` are real; ``table`` (B, pages_per_slot); ``running`` (B,)
    bool: a row that does not run writes nothing. A group whose first token
    is among the step's starts from zero; one that began before goes on
    from what the leaf holds. The pages the step touches are read and
    written whole (a page a KV head is one tile: a scatter of single
    groups had XLA keep the leaf in a layout of its own and copy it whole
    into and out of every program, compiled for a described v5e, PR 56)."""
    B, T, KV, D = k.shape
    P, G = leaf.shape[1], leaf.shape[3]
    maxP = table.shape[1]
    f32 = jnp.float32
    n = (T - 1) // page_size + 2 if T > 1 else 1    # pages a row can touch
    at = jnp.arange(T, dtype=jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    first = start // page_size                      # (B,) the first entry
    rel = (start[:, None] + at[None, :]) // stride - first[:, None] * G
    real = at[None, :] < jnp.asarray(valid, jnp.int32)[:, None]
    member = (rel[..., None] == jnp.arange(n * G)) & real[..., None]
    sums = jnp.einsum("btg,btkd->bkgd", member.astype(f32), k.astype(f32),
                      precision=HIGHEST) / stride       # (B, KV, n G, D)
    sums = sums.reshape(B, KV, n, G, D).transpose(0, 2, 1, 3, 4)
    entry = first[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
    lo = (entry[..., None] * G + jnp.arange(G)) * stride    # (B, n, G)
    touched = (lo + stride > start[:, None, None]) \
        & (lo < start[:, None, None] + T)
    began = lo < start[:, None, None]
    written = jnp.any(touched, axis=-1) & (entry < maxP) \
        & jnp.asarray(running, bool)[:, None]
    page = jnp.where(
        written, jnp.take_along_axis(table, jnp.minimum(entry, maxP - 1),
                                     axis=1), P)    # the sentinel: dropped
    old = leaf[layer, jnp.minimum(page, P - 1)]             # (B,n,KV,G,D)
    new = jnp.where(touched[:, :, None, :, None],
                    jnp.where(began[:, :, None, :, None], old, 0.0) + sums,
                    old)
    return leaf.at[layer, page].set(new, mode="drop")


def choose_blocks(q, means, qpos, sizes: SparseSizes, scale: float,
                  precision=HIGHEST):
    """Stage 2 of the equations: which key blocks each query of each KV
    head reads. ``q`` (B, T, H, D); ``means`` (B, KV, M, D): the group
    means of the row's keys in order, group ``g`` the keys ``[stride g,
    stride g + stride)``; ``qpos`` (B, T). Returns a bool mask (B, T, KV,
    nb) over the ``nb = M stride // block_size`` blocks: the chosen ones,
    every block for a query under ``dense_len``. The window's tokens are
    not in it (:func:`token_mask` joins them)."""
    B, T, H, D = q.shape
    KV, M = means.shape[1], means.shape[2]
    st, bk = sizes.kernel_stride, sizes.block_size
    m, per = sizes.kernel_size // st, bk // st
    nb = M // per
    f32 = jnp.float32
    with jax.named_scope("sparse_index"):
        qg = q.astype(f32).reshape(B, T, KV, H // KV, D)
        sa = jnp.einsum("btkrd,bkmd->btkrm", qg, means.astype(f32),
                        precision=precision) * scale
        # compressed key j: the mean of groups j .. j + m - 1
        n = M - m + 1
        sk = sum(sa[..., u:u + n] for u in range(m)) / m
        last = jnp.arange(n, dtype=jnp.int32) * st + sizes.kernel_size - 1
        vis = last[None, None, :] <= qpos[..., None]           # (B, T, n)
        vis = vis[:, :, None, None, :]
        top = jnp.max(jnp.where(vis, sk, NEG_INF), axis=-1, keepdims=True)
        e = jnp.where(vis, jnp.exp(sk - top), 0.0)
        p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
        pg = jnp.sum(p, axis=3)                                # (B,T,KV,n)
        # block b: the compressed keys per b - m + 1 .. per (b + 1) - 1
        pg = jnp.pad(pg, ((0, 0),) * 3 + ((m - 1, 2 * (m - 1)),))
        score = jax.lax.reduce_window(
            pg, -jnp.inf, jax.lax.max, (1, 1, 1, per + m - 1),
            (1, 1, 1, per), "VALID")[..., :nb]
        block = jnp.arange(nb, dtype=jnp.int32)
        q3 = qpos[..., None]
        score = jnp.where(block < sizes.init_blocks, jnp.inf, score)
        meets = (block + 1) * bk - 1 >= q3 - sizes.window_size + 1
        score = jnp.where(meets[:, :, None, :], -jnp.inf, score)
        val, idx = jax.lax.top_k(score, min(sizes.topk, nb))
        chosen = jnp.any((idx[..., None] == block) & (val[..., None]
                                                      > -jnp.inf), axis=-2)
        return chosen | (q3 + 1 < sizes.dense_len)[:, :, None, :]


def token_mask(blocks, qpos, sizes: SparseSizes, width: int):
    """The positions ``[0, width)`` each query may see beside its causal
    limit: those of its chosen ``blocks`` (B, T, KV, nb) and of its window.
    (B, KV, T, width) bool."""
    pos = jnp.arange(width, dtype=jnp.int32)
    chosen = jnp.repeat(blocks, sizes.block_size, axis=-1)[..., :width]
    window = pos > qpos[..., None] - sizes.window_size          # (B,T,S)
    return (chosen | window[:, :, None, :]).transpose(0, 2, 1, 3)


def sparse_attention_dense(q, k, v, qpos, sizes: SparseSizes, scale: float,
                           precision=HIGHEST):
    """The layer over keys that lie in one piece. ``q`` (B, T, H, D) at
    positions ``qpos`` (B, T); ``k``, ``v`` (B, S, KV, D): the keys at
    positions ``0 .. S - 1`` (what lies past a query's position is never
    read). Returns ``(y (B, T, H, D) float32, blocks)``, the choice beside
    the output."""
    B, T, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    pad = -S % math.lcm(sizes.block_size, sizes.kernel_stride)
    if pad:
        k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                for x in (k, v))
    means = group_means(k.transpose(0, 2, 1, 3), sizes.kernel_stride)
    blocks = choose_blocks(q, means, qpos, sizes, scale, precision)
    may = token_mask(blocks, qpos, sizes, S + pad)          # (B,KV,T,S)
    may = may & (jnp.arange(S + pad) <= qpos[..., None])[:, None]
    f32 = jnp.float32
    qg = q.astype(f32).reshape(B, T, KV, H // KV, D)
    att = jnp.einsum("btkrd,bskd->bkrts", qg, k.astype(f32)) * scale
    att = jax.nn.softmax(jnp.where(may[:, :, None], att, NEG_INF), axis=-1)
    y = jnp.einsum("bkrts,bskd->btkrd", att, v.astype(f32))
    return y.reshape(B, T, H, D), blocks


def tokens_read(pos, sizes: SparseSizes):
    """How many tokens the EQUATIONS read for a query at position ``pos``
    (NumPy or ``jax.numpy``), a KV head: all ``pos + 1`` under
    ``dense_len``, else the window's and the chosen blocks' (whole blocks
    before the window, as many as there are at most)."""
    np_ = jnp if isinstance(pos, jax.Array) else np
    window = np_.minimum(pos + 1, sizes.window_size)
    before = np_.maximum(pos - sizes.window_size + 1, 0) // sizes.block_size
    sparse = window + np_.minimum(before, sizes.topk) * sizes.block_size
    return np_.where(pos + 1 < sizes.dense_len, pos + 1, sparse)


def index_rows(pos, sizes: SparseSizes):
    """How many compressed keys are visible to a query at ``pos``."""
    np_ = jnp if isinstance(pos, jax.Array) else np
    return np_.maximum(pos - sizes.kernel_size + 1, -1) \
        // sizes.kernel_stride + 1


def pages_most(pos, sizes: SparseSizes, page_size: int):
    """The most pages a (decode row, KV head) at position ``pos`` can list
    (``sparse_read.rows_plan``'s ``count``): every page up to ``pos`` under
    ``dense_len`` (exact), else its window's pages and a page each for as
    many chosen blocks as lie before them (a bound: two chosen blocks may
    share a page, and one may lie in the window's first)."""
    np_ = jnp if isinstance(pos, jax.Array) else np
    first = np_.maximum(pos - sizes.window_size + 1, 0)
    far = np_.minimum(np_.minimum(first // sizes.block_size, sizes.topk),
                      first // page_size)
    sparse = pos // page_size - first // page_size + 1 + far
    return np_.where(pos + 1 < sizes.dense_len, pos // page_size + 1, sparse)
