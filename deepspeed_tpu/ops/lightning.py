"""Lightning attention's state (Qin et al., "Lightning Attention-2",
arXiv:2401.04658) in the leaf of ``state_space.py``.

A head ``h`` of width ``d`` keeps ``S`` (d x d, float32) with ONE constant
decay ``l_h``::

    S_t = l_h S_{t-1} + k_t v_t^T         o_t = S_t^T q_t

(the scale, the norms, the rotary and the gate are the model's). That is
the state-space recurrence ``H_t = exp(dt A) H_{t-1} + dt x_t (x) B_t``,
``y_t = H_t C_t`` with ``x = v``, ``B = k`` and ``C = q`` a head's own,
``dt = 1`` for a token and 0 for padding, ``A_h = log l_h``; the leaf is
that module's ((layers, rows, tiles, d, lanes): ``H`` transposed is ``S``,
the key's channel on the sublanes and the value's on the lanes), and a
trace tells the kernels from a state-space layer's by their names,
``lightning_decode`` / ``lightning_chunk``.

* ``lightning_decode``: one token a running row, one grid step a row with
  all its tiles, on the VPU as ``kda_decode`` is: a head's ``k`` and ``q``
  vary down its tile's sublanes, so they are COLUMNS beside the tile,
  ``k_col * v_row`` a broadcast product and ``sum(S * q_col)`` a sum down
  the sublanes (a float32 product with ONE row on the MXU is six bfloat16
  passes over a tile that is itself the stationary operand). ``k`` and
  ``q`` come in as the lane-dense rows they are, (H, d) a row, and the
  kernel turns them once a row: a transpose in XLA changes how XLA fuses
  the rotary before it in the program that carries a chunk (PERF.md
  section 6, PR 59). Where several heads share a tile (heads narrower than
  128) each head's column is taken on that head's lanes.
* ``lightning_chunk``: ``state_space.ssm_chunk`` with a head's own ``B``
  and ``C``: ``o_t = l^t q_t^T S_0 + sum_{s <= t} l^(t - s) (q_t . k_s)
  v_s`` on the MXU, 128 tokens a call, the decay's (T, T) made in XLA under
  the scope ``lightning_chunk_prep``.

The decay is Lightning Attention's slope a head, ``l_h = exp(-2^(-8 (h +
1) / H))``: the same in every layer (the published configuration has no
key for it: ``perf/configs/minicpm-sala-9b-sparse.json`` ``assumed``)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend
from . import state_space as ss
from .state_rows import (by_batch, compiler_params, in_hbm,
                         prefetch_operands, state_spec)

__all__ = ["decay_log", "state_shape", "lightning_sequence",
           "lightning_decode", "lightning_prefill"]


def decay_log(n_heads: int):
    """``log l_h`` of each head: ``-2^(-8 (h + 1) / H)``, float32."""
    h = jnp.arange(1, n_heads + 1, dtype=jnp.float32)
    return -jnp.exp2(-8.0 * h / n_heads)


def state_shape(n_heads: int, d_head: int):
    """One sequence's state in one layer: ``state_space.state_shape`` with
    as many columns as a head is wide."""
    return ss.state_shape(n_heads, d_head, d_head)


def _ones(v):
    return jnp.ones(v.shape[:-1], jnp.float32)


def lightning_sequence(q, k, v):
    """Whole sequences from an empty state: ``q``, ``k``, ``v`` (B, T, H,
    d). Returns ``o`` (B, T, H, d) float32."""
    return ss.ssm_sequence(v, _ones(v), decay_log(v.shape[2]), k, q)


def _decode_kernel(layer_ref, batch_ref, row_ref, fresh_ref,
                   k_ref, q_ref, decay_ref, v_ref, s_ref, so_ref, o_ref, *,
                   heads: int):
    """A row's tiles, ``heads`` heads to a tile; ``k_ref``, ``q_ref`` (1,
    H, d): lane-dense rows a head, turned into columns here."""
    fresh = fresh_ref[pl.program_id(0)] != 0
    tiles, d, lanes = s_ref.shape[2:]
    lane = jax.lax.broadcasted_iota(jnp.int32, (d, lanes), 1)
    cols = jnp.concatenate([k_ref[0], q_ref[0]], axis=0).T      # (d, 2 H)

    def column(first):      # the tile's heads' vectors, each on its lanes
        col = cols[:, first:first + 1]
        for g in range(1, heads):
            col = jnp.where(lane < g * (lanes // heads), col,
                            cols[:, first + g:first + g + 1])
        return col

    for t in range(tiles):
        s = decay_ref[t:t + 1, :] * jnp.where(fresh, 0.0, s_ref[0, 0, t]) \
            + column(t * heads) * v_ref[0, t:t + 1, :]
        so_ref[0, 0, t] = s
        o_ref[0, t:t + 1, :] = jnp.sum(
            s * column((tiles + t) * heads), axis=0, keepdims=True)


def lightning_decode(q, k, v, s, layer, rows, fresh):
    """One token a running row, the stacked leaf ``s`` (L, R, tiles, d,
    lanes) updated in place (aliased to the result): ``q``, ``k``, ``v``
    (B, H, d); ``layer``, ``rows``, ``fresh`` as ``state_space.ssm_decode``.
    Returns ``(o (B, H, d) float32, s)``."""
    B, H, d = v.shape
    tiles, _, lanes = s.shape[2:]
    assert s.shape[2:] == state_shape(H, d), (v.shape, s.shape)
    f32 = jnp.float32
    decay = ss._head_rows(jnp.exp(decay_log(H)), d, tiles)
    prefetch, total, runs = prefetch_operands(layer, rows, fresh, s)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(total, 1),
        in_specs=[by_batch((1, H, d), False), by_batch((1, H, d), False),
                  pl.BlockSpec((tiles, lanes), lambda w, j, *_: (0, 0)),
                  by_batch((1, tiles, lanes), False), state_spec(s, tiles)],
        out_specs=[state_spec(s, tiles), by_batch((1, tiles, lanes), False)],
    )
    s, s_shape = in_hbm(s)
    s, o = pl.pallas_call(
        functools.partial(_decode_kernel, heads=H // tiles),
        name="lightning_decode",
        grid_spec=grid_spec,
        out_shape=[s_shape, jax.ShapeDtypeStruct((B, tiles, lanes), f32)],
        input_output_aliases={8: 0},
        compiler_params=compiler_params(),
        interpret=backend.pallas_interpret(),
    )(*prefetch, k.astype(f32), q.astype(f32), decay,
      ss._lane_rows(v.astype(f32), tiles), s)
    # the blocks of rows that did not run were never written
    return jnp.where(runs[:, None, None], o, 0.0).reshape(B, H, d), s


def lightning_prefill(q, k, v, s, layer, rows, fresh, length):
    """``T`` tokens of every running row after its carried state, block by
    block in the chunk form; tokens at or past ``length`` (B,) are padding."""
    return ss.ssm_prefill(v, _ones(v), decay_log(v.shape[2]), k, q, s, layer,
                          rows, fresh, length=length, name="lightning_chunk")
