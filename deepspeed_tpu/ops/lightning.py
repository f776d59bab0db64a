"""Lightning attention's state (Qin et al., "Lightning Attention-2",
arXiv:2401.04658) on the kernels of ``state_space.py``.

A head ``h`` of width ``d`` keeps ``S`` (d x d, float32) with ONE constant
decay ``l_h``::

    S_t = l_h S_{t-1} + k_t v_t^T         o_t = S_t^T q_t

(the scale, the norms, the rotary and the gate are the model's). That is
the state-space recurrence ``H_t = exp(dt A) H_{t-1} + dt x_t (x) B_t``,
``y_t = H_t C_t`` with ``x = v``, ``B = k`` and ``C = q`` a head's own,
``dt = 1`` for a token and 0 for padding, ``A_h = log l_h``; the leaf is
that module's ((layers, rows, tiles, d, lanes): ``H`` transposed is ``S``,
the key's channel on the sublanes and the value's on the lanes) and so are
the kernels, freed of their one-group rule (``B`` and ``C`` (.., H, N)
arrive as lane-dense rows a head and both products of a decode step are the
MXU's) and called under the names ``lightning_decode`` / ``lightning_chunk``
so that a trace tells them from a state-space layer's. The chunk form is
``o_t = l^t q_t^T S_0 + sum_{s <= t} l^(t - s) (q_t . k_s) v_s`` on the
MXU, 128 tokens a call, the decay's (T, T) made in XLA under the scope
``lightning_chunk_prep``.

The decay is Lightning Attention's slope a head, ``l_h = exp(-2^(-8 (h +
1) / H))``: the same in every layer (the published configuration has no
key for it: ``perf/configs/minicpm-sala-9b-sparse.json`` ``assumed``)."""

from __future__ import annotations

import jax.numpy as jnp

from . import state_space as ss

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


def lightning_decode(q, k, v, s, layer, rows, fresh):
    """One token a running row, the stacked leaf ``s`` updated in place:
    ``q``, ``k``, ``v`` (B, H, d); the rest as ``ssm_decode``."""
    return ss.ssm_decode(v, _ones(v), decay_log(v.shape[1]), k, q, s, layer,
                         rows, fresh, name="lightning_decode")


def lightning_prefill(q, k, v, s, layer, rows, fresh, length):
    """``T`` tokens of every running row after its carried state, block by
    block in the chunk form; tokens at or past ``length`` (B,) are padding."""
    return ss.ssm_prefill(v, _ones(v), decay_log(v.shape[2]), k, q, s, layer,
                          rows, fresh, length=length, name="lightning_chunk")
