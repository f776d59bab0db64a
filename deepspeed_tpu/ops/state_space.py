"""The selective state-space layer of Mamba-2 (Dao & Gu, "Transformers are
SSMs", arXiv:2405.21060): the plain forms and the two Pallas TPU kernels a
server runs, ``ssm_decode`` and ``ssm_chunk``.

**The layer**, for one head ``h`` of width ``P`` with a state of ``N``
columns, after the convolution: ``x_t`` (P), ``B_t``, ``C_t`` (N, shared by
the heads: one group), ``dt_t > 0`` (after the softplus), ``A_h < 0``::

    H_t = exp(dt_t A_h) H_{t-1} + dt_t x_t (x) B_t        (H is P x N)
    y_t = H_t C_t

(the ``D`` skip, the gate and the norm are the model's). The decay is at
most 1 and the weights carry no maximum to track.

**The state** of a sequence in a layer is ONE float32 leaf ``(tiles, N,
lanes)``: the TRANSPOSE of ``H``, the state's column ``n`` on the sublanes
and ``(head, p)`` on the lanes, ``G = 128 // P`` heads to a tile of
``lanes = G P`` (heads of 64: two to a 128-lane tile, 32 tiles of (128,
128) for 64 heads). Every per-head quantity of a token (``exp(dt A)``,
``dt x``, ``y``) is then a lane-dense ROW of ``(tiles, lanes)`` that
broadcasts down the sublanes, ``B`` and ``C`` are columns, and ``y`` is a
sum down the sublanes: no transpose and no lane slice inside a kernel.
The stacked leaf of a pool is ``(L, rows, tiles, N, lanes)``
(:func:`state_shape`), pinned to HBM as the retention state is
(``state_rows.in_hbm``).

**The kernels** take the stacked leaf whole, find their block by ``(layer,
row)`` from scalar prefetch, return the leaf through
``input_output_aliases`` and run one grid step a (running row, block of
tiles): the grid is as long as the work list (``state_rows.work_list``;
the scalar prefetch and the raised VMEM limit are that module's too).
A row that is not in the list is no step: its state comes back bit for
bit. A row whose first position is 0 reads no state (a select, not a
product).

* ``ssm_decode``: one token a row. A step holds :data:`DECODE_TILES` tiles
  of the row: each is decayed, takes ``B (x) dt x`` and is summed against
  ``C``, on the VPU. 2 x ``4 tiles N lanes`` bytes of state a row a layer.
  ONE group: ``B`` and ``C`` are the same two columns under every tile. A
  layer whose heads have their own (Lightning's key and query) keeps this
  leaf and brings its own decode kernel, ``ops/lightning.py``.
* ``ssm_chunk``: ``T <= CHUNK`` tokens of a row after its carried state,
  in the chunked (state-space-dual) form, one tile a step. With ``L_t`` the
  running sum of ``dt A`` of a head: ``y_t = exp(L_t) C_t H_0 + sum_{s <=
  t} exp(L_t - L_s) (C_t . B_s) dt_s x_s`` and ``H_T = exp(L_T) H_0 +
  sum_s exp(L_T - L_s) dt_s x_s (x) B_s``. The three products of a head
  (the masked ``(C B^T (.) decay) X``, ``C H_0``, ``B^T X``) are on the
  MXU inside the kernel; what is made in XLA before it, under the scope
  ``ssm_chunk_prep``, is the decay itself (``exp(L_t - L_s)``, masked,
  times ``C B^T``: a head's (T, T), which a kernel would need the head's
  ``L`` as a row AND as a column for) and the lane-dense rows. A token at or
  past a row's ``length`` is padding: the caller's ``dt`` is 0 there, which
  is decay 1 and weight 0. The block is the whole call: ``CHUNK`` = 128
  tokens (the published ``mamba_chunk_size`` 256 is a blocking of the same
  sums, not mathematics; 128 is the server's ``prefill_chunk`` and one MXU
  tile); longer sequences go block by block with the state carried in
  place (:func:`ssm_prefill`). ``b``, ``c`` (B, T, H, N), a head's own,
  are taken too (``lightning_chunk``: the caller's ``name``).

float32 throughout, products at ``Precision.HIGHEST``. Interpret mode off
the TPU, as the other kernels."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend
from .state_rows import (by_batch as _by_batch, compiler_params, in_hbm,
                         prefetch_operands, state_spec as _state_spec)

__all__ = ["state_shape", "to_tiles", "from_tiles", "causal_conv",
           "gated_norm", "ssm_recurrence",
           "ssm_chunk_plain", "ssm_sequence", "ssm_decode", "ssm_chunk",
           "ssm_prefill", "CHUNK", "DECODE_TILES"]

CHUNK = 128             # tokens a call of ssm_chunk takes at most
DECODE_TILES = 16       # tiles of a row's state a step of ssm_decode holds
HIGHEST = jax.lax.Precision.HIGHEST


def heads_per_tile(n_heads: int, d_head: int) -> int:
    """How many heads share a tile's lanes: as many as fill 128."""
    g = max(1, 128 // d_head)
    while n_heads % g:
        g -= 1
    return g


def state_shape(n_heads: int, d_head: int, d_state: int):
    """The shape of one sequence's state in one layer: see the module
    text. ``(tiles, N, lanes)``, float32."""
    g = heads_per_tile(n_heads, d_head)
    return (n_heads // g, d_state, g * d_head)


def to_tiles(h):
    """``H`` (..., heads, P, N), as the equations have it, in the leaf's
    layout (..., tiles, N, G P)."""
    *lead, H, P, N = h.shape
    g = heads_per_tile(H, P)
    h = h.reshape(*lead, H // g, g, P, N)
    return jnp.moveaxis(h, -1, -3).reshape(*lead, H // g, N, g * P)


def from_tiles(s, d_head: int):
    """The inverse of :func:`to_tiles`."""
    *lead, tiles, N, lanes = s.shape
    g = lanes // d_head
    s = s.reshape(*lead, tiles, N, g, d_head)
    return jnp.moveaxis(s, -3, -1).reshape(*lead, tiles * g, d_head, N)


# ---------------------------------------------------------------------------
# what stays XLA's beside the kernels: the convolution and the gated norm
# ---------------------------------------------------------------------------
def causal_conv(xbc, tail, w, b, valid, silu: bool = True):
    """The causal depthwise convolution with its bias and the silu
    (``silu`` False: the convolution alone), after a carried tail. ``xbc``
    (B, T, C), ``tail`` (B, K - 1, C): the K - 1 inputs before the first
    token (zeros before a sequence), ``w`` (K, C),
    ``b`` (C,) or None (no bias), ``valid`` (B,): how many of the T tokens
    are real. Returns
    ``(silu(conv) (B, T, C) float32, tail')``: the last K - 1 inputs up to
    the last REAL token, which is what the next call continues from
    (padding shifts nothing in)."""
    with jax.named_scope("ssm_conv"):
        K, T = w.shape[0], xbc.shape[1]
        f32 = jnp.float32
        seq = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
        out = sum(
            w[j].astype(f32) * seq[:, j:j + T].astype(f32) for j in range(K))
        if b is not None:
            out = b.astype(f32) + out
        tail = jax.vmap(lambda sq, n: jax.lax.dynamic_slice_in_dim(
            sq, n, K - 1, 0))(seq, jnp.asarray(valid, jnp.int32))
        return (jax.nn.silu(out) if silu else out), tail


def gated_norm(y, z, weight, eps: float):
    """``w (.) g / rms(g)`` with ``g = y (.) silu(z)``: the gate first,
    then the norm over the whole width (one group). float32."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    return g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps) \
        * weight.astype(jnp.float32)


# ---------------------------------------------------------------------------
# the plain forms (jax.numpy): the forward without a cache, and what the
# kernels are tested against
# ---------------------------------------------------------------------------
def ssm_recurrence(x, dt, a, b, c, h0):
    """The recurrence, token by token (a ``lax.scan``), ONE sequence: ``x``
    (T, H, P), ``dt`` (T, H), ``a`` (H,), ``b``, ``c`` (T, N), ``h0``
    (H, P, N). Returns ``(y (T, H, P), h_T)``."""
    def step(h, xs):
        x_t, dt_t, b_t, c_t = xs
        h = jnp.exp(dt_t * a)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[..., None] * b_t
        return h, jnp.einsum("hpn,n->hp", h, c_t, precision=HIGHEST)

    h, y = jax.lax.scan(step, h0, (x, dt, b, c))
    return y, h


def _dual(dt, a, b, c):
    """What the chunk form multiplies with, of (B, T, ...) operands: the
    running log decay ``L`` (B, T, H) and ``C B^T`` under each head's
    masked decay, (B, H, T, T)."""
    T = dt.shape[1]
    L = jnp.cumsum(dt * a, axis=1)
    Lh = L.transpose(0, 2, 1)                                   # (B, H, T)
    causal = jnp.tril(jnp.ones((T, T), bool))
    decay = jnp.exp(jnp.where(causal, Lh[..., :, None] - Lh[..., None, :],
                              -jnp.inf))
    if b.ndim == 4:     # a head's own B and C: (B, T, H, N)
        return L, jnp.einsum("bthn,bshn->bhts", c, b,
                             precision=HIGHEST) * decay
    cb = jnp.einsum("btn,bsn->bts", c, b, precision=HIGHEST)
    return L, cb[:, None] * decay


def ssm_chunk_plain(x, dt, a, b, c, h0):
    """The chunk form in ``jax.numpy``: ``x`` (B, T, H, P), ``dt`` (B, T,
    H), ``b``, ``c`` (B, T, N) or, a head's own, (B, T, H, N), ``h0`` (B, H,
    P, N). Returns ``(y, h_T)``."""
    L, gm = _dual(dt, a, b, c)
    xd = dt[..., None] * x
    tn = "bthn" if b.ndim == 4 else "btn"   # (a head's own B and C)
    y = jnp.exp(L)[..., None] * jnp.einsum(f"{tn},bhpn->bthp", c, h0,
                                           precision=HIGHEST) \
        + jnp.einsum("bhts,bshp->bthp", gm, xd, precision=HIGHEST)
    w = jnp.exp(L[:, -1:] - L)                                  # (B, T, H)
    h = jnp.exp(L[:, -1])[..., None, None] * h0 \
        + jnp.einsum(f"bsh,bshp,{tn.replace('t', 's')}->bhpn", w, xd, b,
                     precision=HIGHEST)
    return y, h


def ssm_sequence(x, dt, a, b, c, block: int = CHUNK):
    """Whole sequences from an empty state, block by block through
    :func:`ssm_chunk_plain` (the forward without a cache). Returns ``y``
    (B, T, H, P)."""
    B, T, H, P = x.shape
    Q = min(block, T)
    pad = -T % Q
    ops = [jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
           for v in (x, dt, b, c)]              # (dt 0: padding)
    cut = [jnp.moveaxis(v.reshape((B, -1, Q) + v.shape[2:]), 1, 0)
           for v in ops]

    def step(h, xs):
        y, h = ssm_chunk_plain(xs[0], xs[1], a, xs[2], xs[3], h)
        return h, y

    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, b.shape[-1]), jnp.float32),
                        tuple(cut))
    return jnp.moveaxis(y, 0, 1).reshape(B, T + pad, H, P)[:, :T]


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------
def _head_rows(v, d_head: int, tiles: int):
    """A head's scalar (..., H) on each of its lanes: (..., tiles, lanes)."""
    v = jnp.repeat(v, d_head, axis=-1)
    return v.reshape(v.shape[:-1] + (tiles, -1))


def _lane_rows(v, tiles: int):
    """(..., H, P) as the lane-dense rows (..., tiles, lanes)."""
    return v.reshape(v.shape[:-2] + (tiles, -1))


def _decode_kernel(layer_ref, batch_ref, row_ref, fresh_ref,
                   da_ref, dtx_ref, b_ref, c_ref, s_ref, so_ref, y_ref):
    w = pl.program_id(0)
    fresh = fresh_ref[w] != 0
    tiles, N, lanes = s_ref.shape[2:]
    bb = jnp.broadcast_to(b_ref[0], (N, lanes))     # B_n down the sublanes
    cb = jnp.broadcast_to(c_ref[0], (N, lanes))

    def body(j, carry):
        tile = jnp.where(fresh, 0.0, s_ref[0, 0, j])
        tile = da_ref[0, pl.ds(j, 1), :] * tile \
            + dtx_ref[0, pl.ds(j, 1), :] * bb
        so_ref[0, 0, j] = tile
        y_ref[0, pl.ds(j, 1), :] = jnp.sum(tile * cb, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, tiles, body, 0)


def ssm_decode(x, dt, a, b, c, s, layer, rows, fresh):
    """One token a running row, state updated in place.

    Args:
      x: (B, H, P), after the convolution; dt: (B, H), after the softplus;
        a: (H,), negative; b, c: (B, N), one group: shared by the heads (a
        head's own key and query, a linear-attention layer's, have a kernel
        of their own: ``ops/lightning.py``).
      s: the stacked leaf (L, R, tiles, N, lanes), aliased to the result.
      layer: int32 scalar (traced). rows: (B,) int32, the pool row of each
        batch entry, out of range for an entry that does not run (its
        output is 0 and its state untouched). fresh: (B,) bool, the entry
        stands at position 0 and reads no state.

    Returns ``(y (B, H, P) float32, s)``, ``y`` without the ``D`` skip."""
    B, H, P = x.shape
    tiles, N, lanes = s.shape[2:]
    assert (tiles, N, lanes) == state_shape(H, P, N), (x.shape, s.shape)
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    da = _head_rows(jnp.exp(dt * a), P, tiles)
    dtx = _lane_rows(dt[..., None] * x, tiles)
    step = DECODE_TILES if tiles % DECODE_TILES == 0 else tiles
    prefetch, total, runs = prefetch_operands(layer, rows, fresh, s)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(total, tiles // step),
        in_specs=[_by_batch((1, step, lanes), True),
                  _by_batch((1, step, lanes), True),
                  _by_batch((1, N, 1), False), _by_batch((1, N, 1), False),
                  _state_spec(s, step)],
        out_specs=[_state_spec(s, step), _by_batch((1, step, lanes), True)],
    )
    s, s_shape = in_hbm(s)
    s, y = pl.pallas_call(
        _decode_kernel,
        name="ssm_decode",
        grid_spec=grid_spec,
        out_shape=[s_shape, jax.ShapeDtypeStruct((B, tiles, lanes), f32)],
        input_output_aliases={8: 0},
        compiler_params=compiler_params(),
        interpret=backend.pallas_interpret(),
    )(*prefetch, da, dtx, b.astype(f32)[..., None], c.astype(f32)[..., None],
      s)
    # the blocks of rows that did not run were never written
    return jnp.where(runs[:, None, None], y, 0.0).reshape(B, H, P), s


def _chunk_kernel(layer_ref, batch_ref, row_ref, fresh_ref,
                  gm_ref, xd_ref, e_ref, c_ref, bt_ref, w_ref, s_ref,
                  so_ref, y_ref, *, heads: int, by_head: bool = False):
    w = pl.program_id(0)
    fresh = fresh_ref[w] != 0
    T, lanes = xd_ref.shape[2:]
    width = lanes // heads
    dot = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32)
    h0 = jnp.where(fresh, 0.0, s_ref[0, 0, 0])                  # (N, lanes)
    xd, e = xd_ref[0, 0], e_ref[0, 0]                           # (T, lanes)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    # the carried part (``by_head``: a head's own C and B, its lanes kept)
    y = 0.0 if by_head else e * dot(c_ref[0], h0)
    upd = jnp.zeros(h0.shape, jnp.float32)
    for g in range(heads):      # a head's decay over the tile, its lanes kept
        mine = (lane >= g * width) & (lane < (g + 1) * width)
        y = y + jnp.where(mine, dot(gm_ref[0, g], xd), 0.0)
        if by_head:
            y = y + jnp.where(mine, e * dot(c_ref[0, g], h0), 0.0)
        upd = upd + jnp.where(mine, dot(
            (bt_ref[0, g] if by_head else bt_ref[0]) * w_ref[0, g], xd), 0.0)
    y_ref[0, 0] = y
    so_ref[0, 0, 0] = e[T - 1:T, :] * h0 + upd


def ssm_chunk(x, dt, a, b, c, s, layer, rows, fresh, name="ssm_chunk"):
    """``T`` tokens of every running row after its carried state (the
    chunk form), state updated in place. ``x`` (B, T, H, P), ``dt`` (B, T,
    H), ``b``, ``c`` (B, T, N), or (B, T, H, N) where every head has its
    own (``name`` is then the caller's), ``T`` a multiple of 8 and at most
    :data:`CHUNK`; the rest as :func:`ssm_decode`. A padding token comes
    with ``dt`` zero. Returns ``(y (B, T, H, P) float32, s)``."""
    B, T, H, P = x.shape
    tiles, N, lanes = s.shape[2:]
    assert T % 8 == 0 and T <= CHUNK, T
    assert (tiles, N, lanes) == state_shape(H, P, N), (x.shape, s.shape)
    f32 = jnp.float32
    heads = H // tiles
    by_head = b.ndim == 4
    with jax.named_scope(name + "_prep"):
        x, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))
        L, gm = _dual(dt, a, b, c)
        # (B, T, tiles, lanes) -> (B, tiles, T, lanes)
        xd = jnp.moveaxis(_lane_rows(dt[..., None] * x, tiles), 1, 2)
        e = jnp.moveaxis(_head_rows(jnp.exp(L), P, tiles), 1, 2)
        w = jnp.exp(L[:, -1:] - L).transpose(0, 2, 1)[:, :, None]   # (B,H,1,T)
        if by_head:
            c = c.transpose(0, 2, 1, 3)                         # (B, H, T, N)
            bt = b.transpose(0, 2, 3, 1)                        # (B, H, N, T)
        else:
            bt = b.transpose(0, 2, 1)                           # (B, N, T)
    prefetch, total, runs = prefetch_operands(layer, rows, fresh, s)
    c_spec, bt_spec = (_by_batch((1, heads, T, N), True),
                       _by_batch((1, heads, N, T), True)) if by_head else \
        (_by_batch((1, T, N), False), _by_batch((1, N, T), False))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(total, tiles),
        in_specs=[_by_batch((1, heads, T, T), True),
                  _by_batch((1, 1, T, lanes), True),
                  _by_batch((1, 1, T, lanes), True),
                  c_spec, bt_spec,
                  _by_batch((1, heads, 1, T), True),
                  _state_spec(s, 1)],
        out_specs=[_state_spec(s, 1), _by_batch((1, 1, T, lanes), True)],
    )
    s, s_shape = in_hbm(s)
    s, y = pl.pallas_call(
        functools.partial(_chunk_kernel, heads=heads, by_head=True)
        if by_head else functools.partial(_chunk_kernel, heads=heads),
        name=name,
        grid_spec=grid_spec,
        out_shape=[s_shape,
                   jax.ShapeDtypeStruct((B, tiles, T, lanes), f32)],
        input_output_aliases={10: 0},
        compiler_params=compiler_params(),
        interpret=backend.pallas_interpret(),
    )(*prefetch, gm, xd, e, c, bt, w, s)
    y = jnp.where(runs[:, None, None, None], y, 0.0)
    return jnp.moveaxis(y, 1, 2).reshape(B, T, H, P), s


def ssm_prefill(x, dt, a, b, c, s, layer, rows, fresh, length=None,
                block: int = CHUNK, name="ssm_chunk"):
    """:func:`ssm_chunk` over a sequence of any length: tokens at or past
    ``length`` (B,) are padding (their ``dt`` is zeroed here), the sequence
    is cut into blocks of at most ``block`` tokens and the state rides from
    one to the next in place. ``fresh`` holds for the first block only."""
    B, T = x.shape[:2]
    if length is not None:
        real = jnp.arange(T)[None, :] < jnp.asarray(length)[:, None]
        dt = jnp.where(real[..., None], dt, 0)
    Q = min(block, -(-T // 8) * 8)
    pad = -T % Q
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad))
                               + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    blocks = (T + pad) // Q
    fresh = jnp.asarray(fresh, bool)
    # (found by its module name at the call, under its own name as it was:
    # the builder's tools wrap ``ssm_chunk`` from outside)
    chunk = ssm_chunk if name == "ssm_chunk" else functools.partial(
        ssm_chunk, name=name)
    if blocks == 1:
        y, s = chunk(x, dt, a, b, c, s, layer, rows, fresh)
        return y[:, :T], s

    def cut(v):     # (B, blocks * Q, ...) -> (blocks, B, Q, ...)
        return jnp.moveaxis(v.reshape((B, blocks, Q) + v.shape[2:]), 1, 0)

    def step(carry, xs):
        s, first = carry
        y, s = chunk(xs[0], xs[1], a, xs[2], xs[3], s, layer, rows,
                     fresh & first)
        return (s, jnp.zeros((), bool)), y

    (s, _), y = jax.lax.scan(step, (s, jnp.ones((), bool)),
                             tuple(cut(v) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1).reshape((B, blocks * Q) + x.shape[2:])[:, :T], s
