"""Gated power retention of degree 2 (Manifest AI, "Scaling Context
Requires Rethinking Attention", arXiv:2507.04239): the feature map, the
plain forms, and the two Pallas TPU kernels a server runs,
``retention_decode`` and ``retention_chunk``.

**The layer.** With ``d`` the head width, ``log g_t <= 0`` the gate of a KV
head at token ``t`` and ``G_t`` its running sum, a query head ``h`` of KV
head ``j`` computes ``o_t = sum_s a_ts v_s / (sum_s a_ts + eps)`` over
``s <= t`` with ``a_ts = exp(G_t - G_s) (q_t . k_s / sqrt(d)) ** 2``. The
weights are non-negative and the decay at most 1: there is no running
maximum.

**The feature map** (:func:`feature_map`). ``phi(a) . phi(b) = (a . b) ** 2``
exactly, with ``phi(a)[r, i] = c_r a_i a_{(i - r) mod d}`` for the rotations
``r = 0 .. d/2``, ``c = 1`` for ``r = 0`` and ``r = d/2`` and ``sqrt(2)``
between: every unordered pair of coordinates once (the pairs half a turn
apart twice, at weight 1). ``D_phi = (d/2 + 1) d``: 8,320 at ``d`` = 128,
0.8 % over the symmetric square's 8,256, and every rotation is one whole
128-lane row made by one lane rotation of the vector itself: no gather, no
selection matrix, no padding.

**The state** of a KV head is ONE float32 leaf ``(d/2 + 2, d, d)``: a plane
a rotation, ``s[r, e, i] = sum_t w_t phi(k_t)[r, i] v_t[e]`` (the value's
coordinate on the sublanes, the key's on the lanes, so a rotation's row of
``phi`` meets its ``(d, d)`` tile as it is made), and a last plane whose
first ``d/2 + 1`` rows are ``z``, the same sum without ``v``. A token: ``s
<- g s + phi(k) v^T``, ``z`` alike, ``o_h = phi(q_h)^T s / (phi(q_h) . z +
eps)``; ``q`` and ``k`` come in divided by ``d ** (1/4)``. The stacked leaf
of a pool is ``(L, rows, KV, d/2 + 2, d, d)``: one operand to alias and one
block to fetch a step; the plane is 1.5 % more state. The kernels pin the
leaf to HBM (``state_rows.in_hbm``): XLA keeps a buffer that fits the chip's
fast memory there across the layer scan, and a Mosaic operand aliased to its
result read nothing of it there (``z`` as a 38 MB leaf of its own: every
denominator wrong on the chip, right in interpret mode).

**The kernels** take the stacked leaves whole and find their block by
``(layer, row)`` from scalar prefetch, return them through
``input_output_aliases`` and run one grid step a (running row, KV head):
the grid is as long as the work list, which the device alone knows (PR 31's
dynamic grid). A row that is not in the list is no step: its state is
neither read nor written and comes back bit for bit, which for a state is
a matter of correctness (a K/V column written for a row that does not run is
hidden by the row's length; a state has no such index). A row whose first
position is 0 reads no state: whatever the row held is replaced (a select,
not a product: what a failed request left may not be finite).

* ``retention_decode``: one token a row. ``z`` first (``d/2 + 1`` rows, a
  static loop), then the planes in slabs of 32 value rows: for each rotation the
  slab is decayed, takes ``v phi(k)^T`` and is multiplied into the ``rep``
  query heads' accumulators on the VPU (an ``(8, d) x (d, d)`` product a
  rotation would reload the MXU's weights for 8 rows of work; float32
  needs six passes besides). 2 x 4.33 MB of state a step at ``d`` 128.
* ``retention_chunk``: ``T`` tokens of a row after a carried state (the
  chunk form): inside the chunk the attention form on the MXU, the carried
  part ``exp(G_t) phi(q_t)^T s`` and the update ``s <- exp(G_T) s +
  sum_s exp(G_T - G_s) v_s phi(k_s)^T`` rotation by rotation, ``rep * T``
  rows to one load of a ``(d, d)`` tile. Tokens past a row's ``length`` are
  padding: the caller zeroes their ``k``, ``v`` and ``log g``.

float32 throughout, products at ``Precision.HIGHEST``. Interpret mode off
the TPU, as the other kernels."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import backend
from ..state_rows import (compiler_params, in_hbm, prefetch_operands,
                          work_list)

__all__ = ["feature_map", "state_shape", "retention_attention",
           "retention_recurrence", "retention_chunk_plain",
           "retention_decode", "retention_chunk", "retention_prefill",
           "work_list", "EPS", "CHUNK"]

EPS = 1e-6
CHUNK = 128             # tokens a call of retention_chunk takes at most
SLAB = 32               # value rows of the state the decode loop holds
UNROLL = 5              # rotations a trip of that loop (1: 3.37 ms a layer
# call of 16 rows at the served shape, 5 or 13: 1.75, the bytes alone 1.35;
# slabs of 8 to 128 rows all within 3 %: chip runs, PR 32)
HIGHEST = jax.lax.Precision.HIGHEST


def rotations(d: int) -> int:
    return d // 2 + 1


def state_shape(d: int):
    """The shape of one KV head's state: see the module text."""
    return (rotations(d) + 1, d, d)


def _coefficient(r, d: int):
    return jnp.where((r == 0) | (r == d // 2), 1.0, math.sqrt(2.0)) \
        .astype(jnp.float32)


def feature_map(a):
    """``phi(a)``: ``(..., d) -> (..., d/2 + 1, d)``, float32."""
    a = a.astype(jnp.float32)
    d = a.shape[-1]
    n = rotations(d)
    rolled = jnp.stack([jnp.roll(a, r, axis=-1) for r in range(n)], axis=-2)
    return _coefficient(jnp.arange(n), d)[:, None] * a[..., None, :] * rolled


# ---------------------------------------------------------------------------
# the plain forms (jax.numpy): the no-cache forward, and what the kernels
# are tested against
# ---------------------------------------------------------------------------
def retention_attention(q, k, v, log_g):
    """The attention form over whole sequences. ``q`` (B, T, H, d), ``k``,
    ``v`` (B, T, KV, d), ``log_g`` (B, T, KV) -> (B, T, H, d) float32.
    ``q`` and ``k`` as the model has them (the ``1 / sqrt(d)`` is here)."""
    B, T, H, d = q.shape
    KV = k.shape[2]
    rep = H // KV
    q = q.astype(jnp.float32).reshape(B, T, KV, rep, d)
    k, v = k.astype(jnp.float32), v.astype(jnp.float32)
    G = jnp.cumsum(log_g.astype(jnp.float32), axis=1)           # (B, T, KV)
    scores = jnp.einsum("btjrd,bsjd->bjrts", q, k,
                        precision=HIGHEST) / math.sqrt(d)
    diff = G.transpose(0, 2, 1)[:, :, :, None] \
        - G.transpose(0, 2, 1)[:, :, None, :]                   # (B,KV,T,S)
    causal = jnp.tril(jnp.ones((T, T), bool))
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    a = scores * scores * decay[:, :, None]
    num = jnp.einsum("bjrts,bsjd->btjrd", a, v, precision=HIGHEST)
    den = a.sum(-1).transpose(0, 3, 1, 2)[..., None]            # (B,T,KV,rep,1)
    return (num / (den + EPS)).reshape(B, T, H, d)


def retention_recurrence(q, k, v, log_g, s, z):
    """The state form, token by token (a ``lax.scan``): ``q`` (T, rep, d),
    ``k``, ``v`` (T, d), ``log_g`` (T,) of ONE KV head, ``q`` and ``k``
    already divided by ``d ** (1/4)``; ``s`` (d/2 + 1, d, d), ``z``
    (d/2 + 1, d): the leaf's planes and the rows of its last plane.
    Returns ``(o (T, rep, d), s, z)``."""
    def step(carry, x):
        s, z = carry
        q_t, k_t, v_t, lg = x
        g = jnp.exp(lg)
        pk = feature_map(k_t)                                   # (n, d)
        s = g * s + pk[:, None, :] * v_t[None, :, None]
        z = g * z + pk
        pq = feature_map(q_t)                                   # (rep, n, d)
        num = jnp.einsum("hri,rei->he", pq, s, precision=HIGHEST)
        den = jnp.einsum("hri,ri->h", pq, z, precision=HIGHEST)
        return (s, z), num / (den[:, None] + EPS)

    (s, z), o = jax.lax.scan(step, (s, z), (q, k, v, log_g))
    return o, s, z


def retention_chunk_plain(q, k, v, log_g, s, z):
    """The chunk form in ``jax.numpy`` for ONE KV head, arguments and
    result as :func:`retention_recurrence`."""
    T, rep, d = q.shape
    G = jnp.cumsum(log_g)
    pq, pk = feature_map(q), feature_map(k)         # (T, rep, n, d), (T, n, d)
    scores = jnp.einsum("thd,sd->hts", q, k, precision=HIGHEST)
    causal = jnp.tril(jnp.ones((T, T), bool))
    a = scores * scores * jnp.exp(jnp.where(causal, G[:, None] - G[None, :],
                                            -jnp.inf))
    carried = jnp.exp(G)[:, None, None]
    num = carried * jnp.einsum("thri,rei->the", pq, s, precision=HIGHEST) \
        + jnp.einsum("hts,se->the", a, v, precision=HIGHEST)
    den = carried[..., 0] * jnp.einsum("thri,ri->th", pq, z,
                                       precision=HIGHEST) \
        + a.sum(-1).T
    w = jnp.exp(G[-1] - G)
    s = jnp.exp(G[-1]) * s + jnp.einsum("s,sri,se->rei", w, pk, v,
                                        precision=HIGHEST)
    z = jnp.exp(G[-1]) * z + jnp.einsum("s,sri->ri", w, pk,
                                        precision=HIGHEST)
    return num / (den[..., None] + EPS), s, z


# ---------------------------------------------------------------------------
# the work list (ops/state_rows.py: shared with the state-space kernels)
# ---------------------------------------------------------------------------
def _by_batch(*shape):
    """Block spec of an operand (B, KV, *shape): the batch entry of the
    step's work item (the second operand of the scalar prefetch)."""
    return pl.BlockSpec(
        (1, 1) + shape,
        lambda w, j, layer, batch, *_: (batch[w], j, 0, 0))


def _state_spec(s):
    """Block spec of the stacked leaf (L, R, KV, n + 1, d, d): one (layer,
    row, KV head) a step, the row from the work list (the third operand of
    the scalar prefetch)."""
    return pl.BlockSpec(
        (1, 1, 1) + s.shape[3:],
        lambda w, j, layer, batch, row, *_: (layer[0], row[w], j, 0, 0, 0))


def _check(d: int, rep: int, s) -> None:
    assert d % 8 == 0 and rep < d and s.shape[3:] == state_shape(d), \
        (d, rep, s.shape)
    if backend.pallas_interpret():
        return
    # the lane rotation of a Mosaic kernel wants whole 128-lane rows
    if d % 128:
        raise ValueError(f"the retention kernels need head_dim % 128 == 0 "
                         f"on the TPU, got {d}")


# ---------------------------------------------------------------------------
# retention_decode
# ---------------------------------------------------------------------------
def _decode_kernel(layer_ref, batch_ref, row_ref, fresh_ref,
                   x_ref, vt_ref, lg_ref, s_ref, so_ref, o_ref, *, rep: int):
    w = pl.program_id(0)
    n, d = s_ref.shape[3] - 1, s_ref.shape[5]     # plane n holds z
    n8 = -(-n // 8) * 8
    slab = min(SLAB, d)
    unroll = max(u for u in range(1, UNROLL + 1) if n % u == 0)
    fresh = fresh_ref[w] != 0
    x = x_ref[0, 0]                       # (R, d): rep query rows, the key
    g = jnp.exp(lg_ref[0, 0])             # (1, d), one value on every lane

    def phi(r):
        rolled = x if isinstance(r, int) and r == 0 \
            else pltpu.roll(x, r, axis=1)
        return _coefficient(jnp.asarray(r), d) * x * rolled

    # z, and the rep denominators: row h of ``dacc`` gathers phi(q_h) . z'
    z_old = jnp.where(fresh, 0.0, s_ref[0, 0, 0, n, 0:n8, :])
    so_ref[0, 0, 0, n] = jnp.zeros((d, d), jnp.float32)
    dacc = jnp.zeros(x.shape, jnp.float32)
    for r in range(n):
        p = phi(r)
        z_row = g * z_old[r:r + 1] + p[rep:rep + 1]
        so_ref[0, 0, 0, n, r:r + 1, :] = z_row
        dacc = dacc + p * z_row
    den = jnp.sum(dacc, axis=1, keepdims=True) + EPS        # (R, 1)

    lane = jax.lax.broadcasted_iota(jnp.int32, (slab, d), 1)
    for e0 in range(0, d, slab):
        vb = jnp.broadcast_to(vt_ref[0, 0, e0:e0 + slab, :], (slab, d))

        def body(trip, accs, e0=e0, vb=vb):
            for u in range(unroll):         # (Mosaic unrolls all or none)
                r = trip * unroll + u
                p = phi(r)
                tile = jnp.where(fresh, 0.0,
                                 s_ref[0, 0, 0, r, e0:e0 + slab, :])
                tile = g * tile + vb * p[rep:rep + 1]
                so_ref[0, 0, 0, r, e0:e0 + slab, :] = tile
                accs = tuple(acc + tile * p[h:h + 1]
                             for h, acc in enumerate(accs))
            return accs

        accs = jax.lax.fori_loop(
            0, n // unroll, body,
            (jnp.zeros((slab, d), jnp.float32),) * rep)
        out = jnp.zeros((slab, d), jnp.float32)
        for h, acc in enumerate(accs):      # head h on lane h
            out = jnp.where(lane == h,
                            jnp.sum(acc, axis=1, keepdims=True)
                            / den[h:h + 1], out)
        o_ref[0, 0, e0:e0 + slab, :] = out


def retention_decode(q, k, v, log_g, s, layer, rows, fresh):
    """One token a running row, state updated in place.

    Args:
      q: (B, H, d), k, v: (B, KV, d), the model's (``1 / sqrt(d)`` is
        applied here); log_g: (B, KV).
      s: the stacked leaf (L, R, KV, d/2 + 2, d, d), aliased to the
        result.
      layer: int32 scalar (traced). rows: (B,) int32, the pool row of each
        batch entry, out of range for an entry that does not run (its
        output is 0 and its state untouched). fresh: (B,) bool, the entry
        stands at position 0 and reads no state.

    Returns ``(o (B, H, d) float32, s)``."""
    B, H, d = q.shape
    KV = k.shape[1]
    rep = H // KV
    _check(d, rep, s)
    R = -(-(rep + 1) // 8) * 8
    scale = d ** -0.25
    x = jnp.concatenate([
        q.astype(jnp.float32).reshape(B, KV, rep, d) * scale,
        k.astype(jnp.float32)[:, :, None] * scale,
        jnp.zeros((B, KV, R - rep - 1, d), jnp.float32)], axis=2)
    vt = v.astype(jnp.float32)[..., None]                       # (B,KV,d,1)
    lg = jnp.broadcast_to(log_g.astype(jnp.float32)[:, :, None, None],
                          (B, KV, 1, d))
    prefetch, total, runs = prefetch_operands(layer, rows, fresh, s)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(total, KV),
        in_specs=[_by_batch(R, d), _by_batch(d, 1), _by_batch(1, d),
                  _state_spec(s)],
        out_specs=[_state_spec(s), _by_batch(d, d)],
    )
    s, s_shape = in_hbm(s)
    s, o = pl.pallas_call(
        functools.partial(_decode_kernel, rep=rep),
        name="retention_decode",
        grid_spec=grid_spec,
        out_shape=[s_shape,
                   jax.ShapeDtypeStruct((B, KV, d, d), jnp.float32)],
        input_output_aliases={7: 0},
        compiler_params=compiler_params(),
        interpret=backend.pallas_interpret(),
    )(*prefetch, x, vt, lg, s)
    # (B, KV, e, lane h) -> (B, H, e); the blocks of rows that did not run
    # were never written
    o = jnp.where(runs[:, None, None, None], o[..., :rep], 0.0)
    return o.transpose(0, 1, 3, 2).reshape(B, H, d), s


# ---------------------------------------------------------------------------
# retention_chunk
# ---------------------------------------------------------------------------
def _chunk_kernel(layer_ref, batch_ref, row_ref, fresh_ref,
                  q_ref, k_ref, v_ref, vt_ref, gq_ref, gr_ref, s_ref,
                  so_ref, o_ref, acc_ref, dacc_ref, *, rep: int):
    w = pl.program_id(0)
    n, d = s_ref.shape[3] - 1, s_ref.shape[5]     # plane n holds z
    T = k_ref.shape[2]
    fresh = fresh_ref[w] != 0
    q, k = q_ref[0, 0], k_ref[0, 0]               # (rep * T, d), (T, d)
    g_rows = gq_ref[0, 0]                         # (rep * T, 1): G_t
    g_cols = gr_ref[0, 0]                         # (1, T): G_s
    g_end = g_cols[:, T - 1:T]                    # (1, 1): G_T
    decay_end = jnp.exp(g_end)
    weight = jnp.exp(g_end - g_cols)              # (1, T): exp(G_T - G_s)
    vt_w = vt_ref[0, 0] * weight                  # (d, T)
    k_w = k * jnp.exp(g_end - g_rows[:T])         # (T, d)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    dacc_ref[...] = jnp.zeros(dacc_ref.shape, jnp.float32)
    so_ref[0, 0, 0, n] = jnp.zeros((d, d), jnp.float32)

    def body(r, carry):
        c = _coefficient(r, d)
        pq = c * q * pltpu.roll(q, r, axis=1)
        pk = c * k * pltpu.roll(k, r, axis=1)
        tile = jnp.where(fresh, 0.0, s_ref[0, 0, 0, r])             # (e, i)
        z_row = jnp.where(fresh, 0.0, s_ref[0, 0, 0, n, pl.ds(r, 1), :])
        acc_ref[...] += jax.lax.dot_general(
            pq, tile, (((1,), (1,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32)
        dacc_ref[...] += pq * z_row
        so_ref[0, 0, 0, r] = decay_end * tile + jax.lax.dot_general(
            vt_w, pk, (((1,), (0,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32)
        so_ref[0, 0, 0, n, pl.ds(r, 1), :] = decay_end * z_row + jnp.sum(
            c * k_w * pltpu.roll(k, r, axis=1), axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, n, body, 0)

    causal = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    inside = jnp.exp(jnp.where(causal, g_rows[:T] - g_cols, -1e30))
    v = v_ref[0, 0]
    for h in range(rep):
        rows = slice(h * T, (h + 1) * T)
        scores = jax.lax.dot_general(
            q[rows], k, (((1,), (1,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32)
        a = scores * scores * inside
        carried = jnp.exp(g_rows[rows])
        num = carried * acc_ref[rows, :] + jax.lax.dot_general(
            a, v, (((1,), (0,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32)
        den = carried * jnp.sum(dacc_ref[rows, :], axis=1, keepdims=True) \
            + jnp.sum(a, axis=1, keepdims=True)
        o_ref[0, 0, rows, :] = num / (den + EPS)


def retention_chunk(q, k, v, log_g, s, layer, rows, fresh):
    """``T`` tokens of every running row after its carried state (the
    chunk form), state updated in place. ``q`` (B, T, H, d), ``k``, ``v``
    (B, T, KV, d), ``log_g`` (B, T, KV), ``T`` a multiple of 8 and at most
    :data:`CHUNK`; the rest as :func:`retention_decode`. A padding token
    comes with ``k``, ``v`` and ``log_g`` zero. Returns ``(o (B, T, H, d)
    float32, s)``."""
    B, T, H, d = q.shape
    KV = k.shape[2]
    rep = H // KV
    assert T % 8 == 0, T
    _check(d, rep, s)
    scale = d ** -0.25
    q = (q.astype(jnp.float32) * scale).reshape(B, T, KV, rep, d) \
        .transpose(0, 2, 3, 1, 4).reshape(B, KV, rep * T, d)
    k = k.astype(jnp.float32).transpose(0, 2, 1, 3) * scale     # (B,KV,T,d)
    v = v.astype(jnp.float32).transpose(0, 2, 1, 3)
    G = jnp.cumsum(log_g.astype(jnp.float32), axis=1).transpose(0, 2, 1)
    g_rows = jnp.tile(G, (1, 1, rep))[..., None]                # (B,KV,rep*T,1)
    prefetch, total, runs = prefetch_operands(layer, rows, fresh, s)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(total, KV),
        in_specs=[_by_batch(rep * T, d), _by_batch(T, d), _by_batch(T, d),
                  _by_batch(d, T), _by_batch(rep * T, 1), _by_batch(1, T),
                  _state_spec(s)],
        out_specs=[_state_spec(s), _by_batch(rep * T, d)],
        scratch_shapes=[pltpu.VMEM((rep * T, d), jnp.float32),
                        pltpu.VMEM((rep * T, d), jnp.float32)],
    )
    s, s_shape = in_hbm(s)
    s, o = pl.pallas_call(
        functools.partial(_chunk_kernel, rep=rep),
        name="retention_chunk",
        grid_spec=grid_spec,
        out_shape=[s_shape,
                   jax.ShapeDtypeStruct((B, KV, rep * T, d), jnp.float32)],
        input_output_aliases={10: 0},
        compiler_params=compiler_params(),
        interpret=backend.pallas_interpret(),
    )(*prefetch, q, k, v, v.transpose(0, 1, 3, 2), g_rows, G[:, :, None, :],
      s)
    o = jnp.where(runs[:, None, None, None], o, 0.0)
    return o.reshape(B, KV, rep, T, d).transpose(0, 3, 1, 2, 4) \
        .reshape(B, T, H, d), s


def retention_prefill(q, k, v, log_g, s, layer, rows, fresh, length=None):
    """:func:`retention_chunk` over a sequence of any length: tokens at or
    past ``length`` (B,) are padding, the sequence is cut into chunks of at
    most :data:`CHUNK` tokens and the state rides from one to the next in
    place. ``fresh`` holds for the first chunk only."""
    B, T, H, d = q.shape
    if length is not None:
        real = jnp.arange(T)[None, :] < jnp.asarray(length)[:, None]
        k = jnp.where(real[..., None, None], k, 0)
        v = jnp.where(real[..., None, None], v, 0)
        log_g = jnp.where(real[..., None], log_g, 0)
    C = min(CHUNK, -(-T // 8) * 8)
    pad = -T % C
    if pad:
        q, k, v, log_g = (jnp.pad(x, ((0, 0), (0, pad))
                                  + ((0, 0),) * (x.ndim - 2))
                          for x in (q, k, v, log_g))
    chunks = (T + pad) // C
    fresh = jnp.asarray(fresh, bool)
    if chunks == 1:
        o, s = retention_chunk(q, k, v, log_g, s, layer, rows, fresh)
        return o[:, :T], s

    def cut(x):     # (B, chunks * C, ...) -> (chunks, B, C, ...)
        return jnp.moveaxis(x.reshape((B, chunks, C) + x.shape[2:]), 1, 0)

    def step(carry, xs):
        s, first = carry
        o, s = retention_chunk(*xs, s, layer, rows, fresh & first)
        return (s, jnp.zeros((), bool)), o

    (s, _), o = jax.lax.scan(step, (s, jnp.ones((), bool)),
                             tuple(cut(x) for x in (q, k, v, log_g)))
    return jnp.moveaxis(o, 0, 1).reshape(B, chunks * C, H, d)[:, :T], s
