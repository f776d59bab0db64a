"""Gated DeltaNet (Yang et al., arXiv:2412.06464; Qwen3-Next's linear
attention) in the attention's place of a ``TransformerBlock``
(``models/transformer_lm.py``): ``layer_types`` "gdn", a state layer beside
``attention`` layers. The delta rule of ``ops/kda.py`` with ONE log decay a
head: the state's leaf is KDA's (a value head's (K, V) matrix, float32),
the kernels are KDA's under the names ``gdn_decode`` / ``gdn_chunk``, and
the chunk form's preparation is the scalar-decay one."""

from __future__ import annotations

import math
from typing import Union

import flax.linen as nn
import jax
import jax.numpy as jnp

from .lm_config import TransformerConfig
from .lm_parts import _by_row_group, _chunk_shaped, _dense, _traced_once
from .state_layers import _conv_after_tail, _uniform_log


class GatedDeltaNetMixer(nn.Module):
    """With ``x`` the normed input, ``Hk = gdn_n_key_heads`` key heads,
    ``H = gdn_n_value_heads`` value heads (key head ``j`` serves value
    heads ``rep j .. rep j + rep - 1``, ``rep = H / Hk``) of ``d =
    gdn_d_head`` channels::

        [q ; k ; v ; z] = W_qkvz x      (Hk d + Hk d + H d + H d columns)
        [b ; a] = W_ba x                (H + H)
        [q ; k ; v] <- silu(conv([q ; k ; v]))      (one convolution)
        q_j <- l2norm(q_j) / sqrt(d)    k_j <- l2norm(k_j)
        beta_h = sigmoid(b_h)
        g_h = -exp(A_log_h) softplus(a_h + dt_bias_h)       (float32)
        o_h = the gated delta rule over (q_j, k_j, v_h, g_h, beta_h)
        out = W_o [rmsnorm_d(o_h; w) (.) silu(z_h)]

    ``conv`` is causal and depthwise over ``gdn_d_conv`` taps, no bias; the
    decay is ONE number a value head a token; the output norm's weight is a
    plain one (``w``, not ``1 + w``). Three forms, as ``KDAMixer``: without
    a cache whole sequences from an empty state (``kda_sequence``); with
    one, ``kv_cache`` holds the stacked leaves whole, ``s`` (float32, (L,
    rows, H, d, d)) and ``conv`` (the last ``gdn_d_conv - 1`` inputs of the
    convolution, time-major on the minor axis), with ``layer``, ``start``,
    ``rows`` and ``valid``: one token takes ``kda_decode`` (named
    ``gdn_decode``), more take ``kda_chunk`` block by block (``gdn_chunk``;
    what XLA prepares for it lies under the scope ``gdn_chunk_prep``). A
    token at or past ``valid`` is padding: it advances neither the state
    (its ``g`` and ``beta`` are 0) nor the tail. An entry whose first
    position is 0 reads neither. The convolution and the tail's shift are
    XLA's (``ops/state_space.causal_conv``, scope ``ssm_conv``)."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x, *, decode: Union[bool, str] = False,
                 deterministic: bool = True, kv_cache=None, layer=None):
        from ..ops import kda

        cfg = self.config
        B, T, C = x.shape
        Hk, H, D = cfg.gdn_n_key_heads, cfg.gdn_n_value_heads, cfg.gdn_d_head
        taps, ch = cfg.gdn_d_conv, cfg.gdn_channels
        keys, values = Hk * D, H * D
        f32 = jnp.float32

        qkvz = _dense(cfg, ch + values, use_bias=False, name="qkvz_proj")(x)
        qkv, z = qkvz[..., :ch], qkvz[..., ch:]
        # (b and a are 2 H columns, no multiple of 128 lanes: a leaf of
        # their own, as Mamba2Mixer's dt)
        ba = _dense(cfg, 2 * H, use_bias=False, name="ba_proj")(x).astype(f32)
        bound = 1.0 / math.sqrt(taps)       # (torch's Conv1d default)
        draw = nn.initializers.uniform(2 * bound)
        conv_w = self.param("conv_w", lambda *a: draw(*a) - bound, (taps, ch))
        a = jnp.exp(self.param("A_log", _uniform_log(1.0, 16.0, jnp.log),
                               (H,)).astype(f32))
        dt_bias = self.param(
            "dt_bias", _uniform_log(1e-3, 1e-1, lambda v: v + jnp.log(
                -jnp.expm1(-v))), (H,)).astype(f32)
        beta = jax.nn.sigmoid(ba[..., :H])                      # (B, T, H)
        g = -a * jax.nn.softplus(ba[..., H:] + dt_bias)         # (B, T, H)
        o_norm = self.param("o_norm", nn.initializers.ones, (D,))

        cached = bool(decode)

        def unit(v):
            return v * jax.lax.rsqrt(
                jnp.sum(v * v, axis=-1, keepdims=True) + 1e-6)

        def mix(cache, qkv, g, beta):
            """The convolution and the state of one group of rows (B, T):
            ``(o, leaves)``."""
            B, T = beta.shape[:2]
            qkv, where, conv_leaf = _conv_after_tail(cache, qkv, conv_w,
                                                     None)
            q = qkv[..., :keys].reshape(B, T, Hk, D)
            k = qkv[..., keys:2 * keys].reshape(B, T, Hk, D)
            v = qkv[..., 2 * keys:].reshape(B, T, H, D)
            q, k = unit(q) * (1.0 / math.sqrt(D)), unit(k)
            if not cached:
                return kda.kda_sequence(q, k, v, g, beta), None
            li, rows, fresh, valid = where
            if T == 1:
                o, s = kda.kda_decode(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                      beta[:, 0], cache["s"], li, rows, fresh,
                                      name="gdn_decode")
                o = o[:, None]
            else:
                o, s = _traced_once(kda.kda_prefill, "name",
                                    chunk=_chunk_shaped(q))(
                    q, k, v, g, beta, cache["s"], li, rows, fresh,
                    length=valid, name="gdn_chunk")
            return o, {"s": s, "conv": conv_leaf}

        o, leaves = _by_row_group(kv_cache, mix, qkv, g, beta) if cached \
            else mix(None, qkv, g, beta)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg.layer_norm_epsilon) * o_norm.astype(f32)
        y = o.reshape(B, T, values) * jax.nn.silu(z.astype(f32))
        return _dense(cfg, C, use_bias=False, name="o_proj")(
            y.astype(cfg.dtype)), leaves
