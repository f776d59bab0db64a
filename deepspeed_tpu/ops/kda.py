"""Kimi Delta Attention (KDA; Kimi Linear, arXiv:2510.26692): the gated
delta rule with a decay a channel. The plain forms and the two Pallas TPU
kernels a server runs, ``kda_decode`` and ``kda_chunk``.

**The layer**, for one head with keys of ``K`` channels and values of ``V``,
after the convolutions and the norms: ``q_t``, ``k_t`` (K; ``k`` of unit
length, ``q`` already scaled), ``v_t`` (V), the log decay ``g_t <= 0`` (K, a
channel) and ``beta_t`` in (0, 1)::

    S' = Diag(exp g_t) S_{t-1}                      (S is K x V, float32)
    u_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T            o_t = S_t^T q_t

(the output norm and gate are the model's). The decay is at most 1 and
nothing carries a maximum to track.

**Over a chunk** of ``T`` tokens after a carried ``S_0``, with the running
log decay ``G_r = sum_{s <= r} g_s`` (K, non-increasing) and the pairs
``<a_r, b_i>_G = sum_c a_r[c] b_i[c] exp(G_r[c] - G_i[c])`` for ``i <= r``::

    A_ri = beta_r <k_r, k_i>_G  (i < r)         P_ri = <q_r, k_i>_G  (i <= r)
    U = (I + A)^{-1} [beta (.) (V - (K (.) e^G) S_0)]
    O = (Q (.) e^G) S_0 + P U
    S_T = Diag(e^{G_T}) S_0 + (K (.) e^{G_T - G})^T U

Every decay is the exponential of a difference of logs that is <= 0, never
a quotient of two exponentials (``e^{-G_i}`` overflows float32 within a
chunk where a channel decays fast). The pairs are made in sub-blocks of
:data:`SUB` tokens (:func:`_pairs`): a row block against everything before
it is ONE product with both sides taken from the block's first row
(``a_r e^{G_r - G_first}``, ``b_i e^{G_first - G_i}``, both exponents <=
0), and inside a block the pairs are summed a channel at a time as
written. ``(I + A)^{-1}`` (:func:`_unit_lower_inverse`) is the finite
series over a block of :data:`SUB` rows (``A`` is strictly lower: its 16th
power is 0) and the exact block recursion above it.

**The state** of a sequence in a layer is one float32 leaf ``(H, K, V)``:
a head's keys' channels on the sublanes, its values' on the lanes; the
stacked leaf of a pool is ``(L, rows, H, K, V)``, pinned to HBM
(``state_rows.in_hbm``), found by ``(layer, row)`` from scalar prefetch and
returned through ``input_output_aliases``: a row that is not in the work
list (``state_rows.work_list``) is no grid step and comes back bit for
bit, and a row whose first position is 0 reads no state (a select).

* ``kda_decode``: one token a running row, one grid step a row with all
  its heads: each head's tile is decayed, read against ``k``, takes the
  outer product and is read against ``q``, on the VPU; sums run down the
  sublanes. What varies down the sublanes (``exp g``, ``k``, ``q``: a
  channel of the keys) comes in as the COLUMNS of one ``(K, 128)`` block a
  row (:func:`_columns`: lane ``vector * H + head``), 64 KB beside 2 MB of
  state; ``beta v`` and ``beta`` come as lane-dense rows. 2 x ``H K V`` x 4
  bytes of state a row a layer.
* ``kda_chunk``: ``T <= CHUNK`` tokens of a row, one head a grid step:
  the five products of the chunk form above on the MXU (float32,
  ``Precision.HIGHEST``). What does not depend on the carried state is made
  in XLA before it under the scope ``kda_chunk_prep``: the decays, the
  pairs and the inverse. A token at or past a row's ``length`` is padding:
  its ``g`` and ``beta`` are 0, which leaves the state as it was. Longer
  sequences go block by block with the state carried in place
  (:func:`kda_prefill`).

**One log decay a head** (Gated DeltaNet; Qwen3-Next): ``g_t`` a number a
head, ``(T, H)`` where KDA's is ``(T, H, K)``. Broadcast over the channels
it is the rule above, and every function here takes either: the decode
kernel is handed the broadcast column, and the chunk form's preparation is
the cheaper one (:func:`_scalar_decay_operands`): the decay leaves the sum
over the channels, so the pairs of a chunk are ONE product ``a b^T`` times
the ``(T, T)`` mask ``exp(G_r - G_i)``, every exponent <= 0 as before, and
no sum a channel at a time. ``q`` and ``k`` may then come with fewer heads
than ``v`` (``H = rep x Hk``: key head ``j`` serves value heads ``rep j ..
rep j + rep - 1``); the products are made once a key head and what the
kernels are handed, which carries a value head's decay, a value head. The
calls take their ``name`` from the caller (``gdn_decode``, ``gdn_chunk``:
the v5e trace attributes by name alone); the kernels are the same.

Interpret mode off the TPU, as the other kernels."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend
from .state_rows import (by_batch as _by_batch, compiler_params, in_hbm,
                         prefetch_operands, state_spec as _state_spec)

__all__ = ["kda_recurrence", "kda_chunk_plain", "kda_sequence", "kda_decode",
           "kda_chunk", "kda_prefill", "CHUNK", "SUB"]

CHUNK = 128             # tokens a call of kda_chunk takes at most
SUB = 16                # tokens of a sub-block of the pairs and the inverse
HIGHEST = jax.lax.Precision.HIGHEST
_einsum = functools.partial(jnp.einsum, precision=HIGHEST)


# ---------------------------------------------------------------------------
# the plain forms (jax.numpy): the forward without a cache, and what the
# kernels are tested against
# ---------------------------------------------------------------------------
def _a_value_head(q, k, g, heads: int, axis: int):
    """``q``, ``k`` repeated to ``heads`` along ``axis`` where they come a
    key head, and a decay a head (one dimension short of ``k``) broadcast
    over the channels: the operands as the rule with a decay a channel
    reads them."""
    rep = heads // q.shape[axis]
    if rep > 1:
        q, k = (jnp.repeat(x, rep, axis=axis) for x in (q, k))
    if g.ndim < k.ndim:
        g = jnp.broadcast_to(g[..., None], k.shape)
    return q, k, g


def kda_recurrence(q, k, v, g, beta, s0):
    """The recurrence, token by token (a ``lax.scan``), ONE sequence: ``q``,
    ``k``, ``g`` (T, H, K), ``v`` (T, H, V), ``beta`` (T, H), ``s0`` (H, K,
    V); or a decay a head, ``g`` (T, H), and ``q``, ``k`` a key head.
    Returns ``(o (T, H, V), s_T)``."""
    q, k, g = _a_value_head(q, k, g, v.shape[1], 1)

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[..., None] * s
        u = b_t[:, None] * (v_t - _einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[..., None] * u[:, None, :]
        return s, _einsum("hkv,hk->hv", s, q_t)

    s, o = jax.lax.scan(step, s0, (q, k, v, g, beta))
    return o, s


def _pairs(lhs, k, G, sub: int):
    """``<lhs_r, k_i>_G`` for ``i <= r`` and 0 above the diagonal: ``lhs``
    (..., T, K) against ``k`` (..., T, K) under the running log decay ``G``
    (..., T, K); ``T`` a multiple of ``sub``. Returns (..., T, T)."""
    T, K = k.shape[-2:]
    n = T // sub
    Gb, lb, kb = (x.reshape(x.shape[:-2] + (n, sub, K)) for x in (G, lhs, k))
    first = Gb[..., :1, :]                                  # (..., n, 1, K)
    # a row block against every token before it, both sides taken from the
    # block's first row
    left = lb * jnp.exp(Gb - first)
    before = jnp.arange(T)[None, :] < (jnp.arange(n) * sub)[:, None]  # (n, T)
    right = jnp.where(
        before[..., None],
        k[..., None, :, :] * jnp.exp(jnp.minimum(
            first - G[..., None, :, :], 0.0)), 0.0)         # (..., n, T, K)
    off = _einsum("...nrc,...nic->...nri", left, right)     # (..., n, sub, T)
    # inside a block, a channel at a time as written
    low = jnp.tril(jnp.ones((sub, sub), bool))
    decay = jnp.exp(jnp.where(
        low[..., None], Gb[..., :, None, :] - Gb[..., None, :, :], -jnp.inf))
    own = jnp.sum(lb[..., :, None, :] * kb[..., None, :, :] * decay, -1)
    own = own[..., None, :] * jnp.eye(n, dtype=own.dtype)[:, None, :, None]
    return (off + own.reshape(off.shape)).reshape(off.shape[:-3] + (T, T))


def _unit_lower_inverse(a, sub: int):
    """``(I + a)^{-1}`` of a strictly lower triangular ``a`` (..., T, T),
    ``T`` a multiple of ``sub``: the finite series ``(I + b)(I + b^2)(I +
    b^4)...`` with ``b = -a`` over a block of ``sub`` rows (``b^sub`` is
    0), and above it ``[[X, 0], [C, Y]]^{-1} = [[X^-1, 0], [-Y^-1 C X^-1,
    Y^-1]]``."""
    T = a.shape[-1]
    if T <= sub:
        b = -a
        inv = jnp.eye(T, dtype=a.dtype) + b
        power = 2
        while power < T:
            b = _einsum("...ij,...jk->...ik", b, b)
            inv = inv + _einsum("...ij,...jk->...ik", inv, b)
            power *= 2
        return inv
    h = (T // sub // 2) * sub
    x = _unit_lower_inverse(a[..., :h, :h], sub)
    y = _unit_lower_inverse(a[..., h:, h:], sub)
    c = -_einsum("...ij,...jk,...kl->...il", y, a[..., h:, :h], x)
    return jnp.concatenate(
        [jnp.concatenate([x, jnp.zeros(x.shape[:-1] + (T - h,), a.dtype)],
                         -1),
         jnp.concatenate([c, y], -1)], -2)


def _chunk_operands(q, k, v, g, beta, sub: int = SUB):
    """What the chunk form multiplies with and that does not depend on the
    carried state, of head-major operands: ``q``, ``k``, ``g`` (B, H, T,
    K), ``v`` (B, H, T, V), ``beta`` (B, H, T); ``T`` a multiple of
    ``sub``. Returns ``(inverse (T, T), pairs P (T, T), beta k e^G, q e^G
    (T, K), (k e^{G_T - G})^T (K, T), beta v (T, V), e^{G_T} (1, K))``,
    each after the leading ``(B, H)``. A decay a head, ``g`` (B, H, T):
    :func:`_scalar_decay_operands`."""
    if g.ndim == beta.ndim:
        return _scalar_decay_operands(q, k, v, g, beta, sub)
    G = jnp.cumsum(g, axis=2)
    both = _pairs(jnp.stack([k, q]), k[None], G[None], sub)
    strict = jnp.tril(jnp.ones(both.shape[-2:], bool), -1)
    a = jnp.where(strict, beta[..., None] * both[0], 0.0)
    e = jnp.exp(G)
    last = G[:, :, -1:]
    return (_unit_lower_inverse(a, sub), both[1], beta[..., None] * k * e,
            q * e, jnp.swapaxes(k * jnp.exp(last - G), -1, -2),
            beta[..., None] * v, jnp.exp(last))


def _scalar_decay_operands(q, k, v, g, beta, sub: int = SUB):
    """:func:`_chunk_operands` under ONE log decay a head: ``g``, ``beta``
    (B, H, T), ``v`` (B, H, T, V), ``q``, ``k`` (B, Hk, T, K) with ``H`` a
    multiple of ``Hk``. The decay does not depend on the channel, so it
    leaves the sum over them: ``<a_r, b_i>_G = (a_r . b_i) exp(G_r - G_i)``,
    one product a KEY head and a ``(T, T)`` mask a value head (the exponent
    of a pair ``i <= r`` is <= 0; above the diagonal the mask is 0)."""
    B, H, T = g.shape
    Hk, K = q.shape[1], q.shape[-1]
    G = jnp.cumsum(g, axis=2)
    low = jnp.tril(jnp.ones((T, T), bool))
    mask = jnp.exp(jnp.where(low, G[..., :, None] - G[..., None, :],
                             -jnp.inf))                     # (B, H, T, T)

    def heads(x):       # (B, Hk, ...) seen a value head: (B, Hk, rep, ...)
        return x.reshape((B, Hk, 1) + x.shape[2:])

    def value(x):       # (B, Hk, rep, ...) -> (B, H, ...)
        return x.reshape((B, H) + x.shape[3:])

    by_key = mask.reshape(B, Hk, H // Hk, T, T)
    both = [value(heads(_einsum("bhrc,bhic->bhri", lhs, k)) * by_key)
            for lhs in (k, q)]
    a = jnp.where(jnp.tril(low, -1), beta[..., None] * both[0], 0.0)

    def scaled(x, by):  # x (B, Hk, T, K) times a value head's by (B, H, T)
        return value(heads(x) * by.reshape(B, Hk, H // Hk, T, 1))

    e, last = jnp.exp(G), G[:, :, -1:]
    return (_unit_lower_inverse(a, sub), both[1], scaled(k, beta * e),
            scaled(q, e), jnp.swapaxes(scaled(k, jnp.exp(last - G)), -1, -2),
            beta[..., None] * v,
            jnp.broadcast_to(jnp.exp(last)[..., None], (B, H, 1, K)))


def _chunk_apply(inv, p, bkg, qg, kt, bv, eg, s0, dot):
    """The five products of the chunk form for one head (or, with a
    batching ``dot``, for several): ``(o (T, V), s_T (K, V))``. ``eg`` (1,
    K) scales the rows of ``s0``: as a diagonal matrix, so that nothing
    has to lie down the sublanes."""
    K = s0.shape[-2]
    u = dot(inv, bv - dot(bkg, s0))
    o = dot(qg, s0) + dot(p, u)
    rows = jax.lax.broadcasted_iota(jnp.int32, (K, K), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (K, K), 1)
    diag = jnp.where(rows == cols, eg, 0.0)
    return o, dot(diag, s0) + dot(kt, u)


def kda_chunk_plain(q, k, v, g, beta, s0, sub: int = SUB):
    """The chunk form in ``jax.numpy``: ``q``, ``k``, ``g`` (B, T, H, K),
    ``v`` (B, T, H, V), ``beta`` (B, T, H), ``s0`` (B, H, K, V); ``T`` a
    multiple of ``sub``; or a decay a head, ``g`` (B, T, H), and ``q``,
    ``k`` a key head. Returns ``(o (B, T, H, V), s_T)``."""
    heads = [jnp.swapaxes(x, 1, 2) for x in (q, k, v, g)]
    ops = _chunk_operands(*heads, jnp.swapaxes(beta, 1, 2), sub)
    o, s = _chunk_apply(*ops, s0, functools.partial(
        _einsum, "...ij,...jk->...ik"))
    return jnp.swapaxes(o, 1, 2), s


def _blocks(T: int, block: int, sub: int):
    """``(tokens a block, padding)`` of a sequence of ``T`` tokens cut into
    blocks of at most ``block``, each a multiple of ``sub``."""
    Q = min(block, -(-T // sub) * sub)
    return Q, -T % Q


def _pad_tokens(values, pad: int):
    return [jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in values] if pad else list(values)


def kda_sequence(q, k, v, g, beta, block: int = CHUNK):
    """Whole sequences from an empty state, block by block through
    :func:`kda_chunk_plain` (the forward without a cache). Returns ``o``
    (B, T, H, V)."""
    B, T, _, K = q.shape
    H = v.shape[2]
    Q, pad = _blocks(T, block, SUB)
    ops = _pad_tokens((q, k, v, g, beta), pad)      # (g, beta 0: padding)
    cut = [jnp.moveaxis(x.reshape((B, -1, Q) + x.shape[2:]), 1, 0)
           for x in ops]

    def step(s, xs):
        o, s = kda_chunk_plain(*xs, s)
        return s, o

    _, o = jax.lax.scan(step, jnp.zeros((B, H, K, v.shape[-1]), jnp.float32),
                        tuple(cut))
    return jnp.moveaxis(o, 0, 1).reshape(B, T + pad, H, -1)[:, :T]


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------
def _columns(*vectors):
    """``vectors`` (B, H, K) each, as the columns of ONE lane-dense block a
    batch entry, (B, K, lanes): lane ``i * H + h`` holds vector ``i`` of
    head ``h`` down the sublanes, the lanes padded to whole tiles."""
    B, H, K = vectors[0].shape
    cols = jnp.concatenate(vectors, axis=1)                 # (B, n H, K)
    lanes = -(-cols.shape[1] // 128) * 128
    cols = jnp.pad(cols, ((0, 0), (0, lanes - cols.shape[1]), (0, 0)))
    return jnp.swapaxes(cols, 1, 2)


def _decode_kernel(layer_ref, batch_ref, row_ref, fresh_ref,
                   cols_ref, rows_ref, s_ref, so_ref, o_ref, *, heads: int):
    fresh = fresh_ref[pl.program_id(0)] != 0
    for h in range(heads):
        def column(i, h=h):         # (K, 1): one channel a sublane
            return cols_ref[0, :, i * heads + h:i * heads + h + 1]

        s = jnp.where(fresh, 0.0, s_ref[0, 0, h]) * column(0)
        kc = column(1)
        bv = rows_ref[0, h:h + 1, :]
        b = rows_ref[0, heads + h:heads + h + 1, :]
        u = bv - b * jnp.sum(s * kc, axis=0, keepdims=True)     # (1, V)
        s = s + kc * u
        so_ref[0, 0, h] = s
        o_ref[0, h:h + 1, :] = jnp.sum(s * column(2), axis=0, keepdims=True)


def kda_decode(q, k, v, g, beta, s, layer, rows, fresh,
               name: str = "kda_decode"):
    """One token a running row, state updated in place.

    Args:
      q, k, g: (B, H, K); v: (B, H, V); beta: (B, H) (see the module text);
        or a decay a head, g: (B, H), and q, k a key head, (B, Hk, K): the
        kernel is handed the columns of every value head all the same (64 KB
        a row beside 2 MB of state).
      s: the stacked leaf (L, R, H, K, V) float32, aliased to the result.
      layer: int32 scalar (traced). rows: (B,) int32, the pool row of each
        batch entry, out of range for an entry that does not run (its
        output is 0 and its state untouched). fresh: (B,) bool, the entry
        stands at position 0 and reads no state.
      name: the Pallas call's (what a trace attributes its time by).

    Returns ``(o (B, H, V) float32, s)``."""
    B, H, V = v.shape
    K = q.shape[-1]
    assert s.shape[2:] == (H, K, V), (q.shape, v.shape, s.shape)
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    q, k, g = _a_value_head(q, k, g, H, 1)
    cols = _columns(jnp.exp(g), k, q)
    lane_rows = jnp.concatenate(
        [beta[..., None] * v, jnp.broadcast_to(beta[..., None], v.shape)],
        axis=1)                                             # (B, 2 H, V)
    prefetch, total, runs = prefetch_operands(layer, rows, fresh, s)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(total, 1),
        in_specs=[_by_batch((1,) + cols.shape[1:], False),
                  _by_batch((1, 2 * H, V), False), _state_spec(s, H)],
        out_specs=[_state_spec(s, H), _by_batch((1, H, V), False)],
    )
    s, s_shape = in_hbm(s)
    s, o = pl.pallas_call(
        functools.partial(_decode_kernel, heads=H),
        name=name,
        grid_spec=grid_spec,
        out_shape=[s_shape, jax.ShapeDtypeStruct((B, H, V), f32)],
        input_output_aliases={6: 0},
        compiler_params=compiler_params(),
        interpret=backend.pallas_interpret(),
    )(*prefetch, cols, lane_rows, s)
    # the blocks of rows that did not run were never written
    return jnp.where(runs[:, None, None], o, 0.0), s


def _chunk_kernel(layer_ref, batch_ref, row_ref, fresh_ref, inv_ref, p_ref,
                  bkg_ref, qg_ref, kt_ref, bv_ref, eg_ref, s_ref, so_ref,
                  o_ref):
    fresh = fresh_ref[pl.program_id(0)] != 0
    dot = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32)
    o, s = _chunk_apply(
        inv_ref[0, 0], p_ref[0, 0], bkg_ref[0, 0], qg_ref[0, 0],
        kt_ref[0, 0], bv_ref[0, 0], eg_ref[0, 0],
        jnp.where(fresh, 0.0, s_ref[0, 0, 0]), dot)
    o_ref[0, 0] = o
    so_ref[0, 0, 0] = s


def kda_chunk(q, k, v, g, beta, s, layer, rows, fresh,
              name: str = "kda_chunk"):
    """``T`` tokens of every running row after its carried state (the chunk
    form), state updated in place. ``q``, ``k``, ``g`` (B, T, H, K), ``v``
    (B, T, H, V), ``beta`` (B, T, H), ``T`` a multiple of :data:`SUB` and
    at most :data:`CHUNK`; or a decay a head, ``g`` (B, T, H), and ``q``,
    ``k`` a key head (the preparation is then the scalar-decay one); the
    rest as :func:`kda_decode`. A padding token comes with ``g`` and
    ``beta`` zero. What XLA prepares lies under the scope ``<name>_prep``.
    Returns ``(o (B, T, H, V) float32, s)``."""
    B, T, H, V = v.shape
    K = q.shape[-1]
    assert T % SUB == 0 and T <= CHUNK, T
    assert s.shape[2:] == (H, K, V), (q.shape, v.shape, s.shape)
    f32 = jnp.float32
    with jax.named_scope(f"{name}_prep"):
        heads = [jnp.swapaxes(x.astype(f32), 1, 2) for x in (q, k, v, g)]
        ops = _chunk_operands(*heads,
                              jnp.swapaxes(beta.astype(f32), 1, 2))
    prefetch, total, runs = prefetch_operands(layer, rows, fresh, s)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(total, H),
        in_specs=[_by_batch((1, 1) + x.shape[2:], True) for x in ops]
        + [_state_spec(s, 1)],
        out_specs=[_state_spec(s, 1), _by_batch((1, 1, T, V), True)],
    )
    s, s_shape = in_hbm(s)
    s, o = pl.pallas_call(
        _chunk_kernel,
        name=name,
        grid_spec=grid_spec,
        out_shape=[s_shape, jax.ShapeDtypeStruct((B, H, T, V), f32)],
        input_output_aliases={4 + len(ops): 0},
        compiler_params=compiler_params(),
        interpret=backend.pallas_interpret(),
    )(*prefetch, *ops, s)
    o = jnp.where(runs[:, None, None, None], o, 0.0)
    return jnp.swapaxes(o, 1, 2), s


def kda_prefill(q, k, v, g, beta, s, layer, rows, fresh, length=None,
                block: int = CHUNK, name: str = "kda_chunk"):
    """:func:`kda_chunk` over a sequence of any length: tokens at or past
    ``length`` (B,) are padding (their ``g`` and ``beta`` are zeroed here),
    the sequence is cut into blocks of at most ``block`` tokens and the
    state rides from one to the next in place. ``fresh`` holds for the
    first block only."""
    B, T = q.shape[:2]
    if length is not None:
        real = jnp.arange(T)[None, :] < jnp.asarray(length)[:, None]
        g = jnp.where(real[(...,) + (None,) * (g.ndim - 2)], g, 0)
        beta = jnp.where(real[..., None], beta, 0)
    Q, pad = _blocks(T, block, SUB)
    ops = _pad_tokens((q, k, v, g, beta), pad)
    blocks = (T + pad) // Q
    fresh = jnp.asarray(fresh, bool)
    # (looked up by its module name at every call, and called as it always
    # was under its own name: perf/tools/kimi_limits.py wraps it)
    chunk = kda_chunk if name == "kda_chunk" \
        else functools.partial(kda_chunk, name=name)
    if blocks == 1:
        o, s = chunk(*ops, s, layer, rows, fresh)
        return o[:, :T], s

    def cut(x):     # (B, blocks * Q, ...) -> (blocks, B, Q, ...)
        return jnp.moveaxis(x.reshape((B, blocks, Q) + x.shape[2:]), 1, 0)

    def step(carry, xs):
        s, first = carry
        o, s = chunk(*xs, s, layer, rows, fresh & first)
        return (s, jnp.zeros((), bool)), o

    (s, _), o = jax.lax.scan(step, (s, jnp.ones((), bool)),
                             tuple(cut(x) for x in ops))
    return jnp.moveaxis(o, 0, 1).reshape(
        (B, blocks * Q) + v.shape[2:])[:, :T], s
