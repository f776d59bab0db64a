"""The layers that keep a state of fixed size a sequence in the
attention's place of a ``TransformerBlock`` (``models/transformer_lm.py``),
and the helpers they share (a state's rows, the convolution after its
tail, reading and writing the rows of a stacked leaf).

``power_retention`` (:class:`PowerRetention`) keeps no K/V: its cache is a
state of fixed size a sequence (``KVCacheSpec.state``), whose stacked leaf
rides the scan's carry whole and is updated in place by the kernels of
``ops/attention/power_retention.py``, for the rows the caller names
(``rows``) and no others. Every layer of a model is of this kind or none
is.

``mamba`` (:class:`Mamba2Mixer`), ``kda`` (:class:`KDAMixer`, Kimi Linear's
gated delta rule with a decay a channel) and ``conv``
(:class:`ShortConvMixer`, LFM2's gated short convolution) stand BESIDE
``attention`` layers in one model. Their cache is a state group
(``KVCacheSpec.state_group``), a row a sequence and no positions: ``s``,
the state (mamba's, or a KDA head's (K, V) matrix; ``conv`` layers have
none), and ``conv``, the last inputs of the layer's convolutions.
``lightning``, the fourth of ``STATE_KINDS``, is
``models/lightning_sparse.py``'s."""

from __future__ import annotations

import functools
import math
from typing import Union

import flax.linen as nn
import jax
import jax.numpy as jnp

from .lm_config import TransformerConfig
from .lm_parts import (_by_row_group, _chunk_shaped, _dense, _norm_qk,
                       _project_qkv, _traced_once, apply_rotary)


def _half_life_logit(key, shape, dtype=jnp.float32):
    """The gate's bias: ``logit(g)`` for ``g = 2 ** (-1 / half-life)`` with
    half-lives drawn log-uniformly from 16 to 4,096 tokens, one a KV head
    a layer (the scan splits the key by layer). A zero bias is ``g`` 0.5:
    the state would forget in two tokens, and nothing that compares outputs
    could see a wrong carried state."""
    half_life = 16.0 * 256.0 ** jax.random.uniform(key, shape)
    g = 2.0 ** (-1.0 / half_life)
    return (jnp.log(g) - jnp.log1p(-g)).astype(dtype)


class PowerRetention(nn.Module):
    """Gated power retention of degree 2 in the attention's place
    (``ops/attention/power_retention.py`` has the equations): ``q``, ``k``
    with their norm and the rotary, ``v``, one gate a KV head
    (``log g = log sigmoid(W_g x + b_g)``, float32), output projection.

    Modes as :class:`CachedAttention`'s. Without a cache: the attention
    form in ``jax.numpy``. With one, ``kv_cache`` holds the stacked state
    leaf ``s`` whole with ``layer``, ``start`` and, from a caller
    that runs only some rows or maps its batch to other rows, ``rows``
    (B,) (the cache row of each batch entry, out of range: the entry does
    not run and its row's state is not touched) and ``valid`` (B,) (tokens
    from there on are padding and leave the state alone). One token takes
    ``retention_decode``, more take ``retention_chunk``; an entry whose
    first position is 0 reads no state."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x, *, decode: Union[bool, str] = False,
                 deterministic: bool = True, kv_cache=None, layer=None):
        from ..ops.attention import power_retention as pr

        cfg = self.config
        B, T, C = x.shape
        H, KV, D = cfg.n_head, cfg.kv_heads, cfg.head_dim
        q, k, v = _project_qkv(cfg, x)
        if cfg.qk_norm:
            q, k = _norm_qk(cfg, q, k)
        log_g = jax.nn.log_sigmoid(nn.Dense(
            KV, dtype=jnp.float32, bias_init=_half_life_logit,
            name="g_proj")(x))                                  # (B, T, KV)
        start = kv_cache["start"] if decode else jnp.zeros((), jnp.int32)
        if cfg.pos_emb == "rotary":
            positions = (start[:, None] if jnp.ndim(start) == 1 else start) \
                + jnp.arange(T)[None, :]
            rd = int(cfg.rotary_pct * D) // 2 * 2
            q = apply_rotary(q, positions, rotary_dim=rd,
                             theta=cfg.rope_theta)
            k = apply_rotary(k, positions, rotary_dim=rd,
                             theta=cfg.rope_theta)
        o_proj = _dense(cfg, C, use_bias=cfg.qkv_bias, name="o_proj")
        if not decode:
            y = pr.retention_attention(q, k, v, log_g)
            return o_proj(y.astype(cfg.dtype).reshape(B, T, H * D)), None
        rows = kv_cache.get("rows")
        if rows is None:
            rows = jnp.arange(B, dtype=jnp.int32)
        fresh = jnp.broadcast_to(start == 0, (B,))
        state = (kv_cache["s"], kv_cache["layer"], rows, fresh)
        if T == 1:
            y, s = pr.retention_decode(q[:, 0], k[:, 0], v[:, 0],
                                       log_g[:, 0], *state)
        else:
            y, s = pr.retention_prefill(q, k, v, log_g, *state,
                                        length=kv_cache.get("valid"))
        y = y.astype(cfg.dtype).reshape(B, T, H * D)
        return o_proj(y), {"s": s}


def _uniform_log(lo: float, hi: float, inverse=None):
    """An initializer: values drawn log-uniformly from ``lo`` to ``hi``,
    then through ``inverse`` (what the module applies to the parameter)."""
    def init(key, shape, dtype=jnp.float32):
        v = lo * (hi / lo) ** jax.random.uniform(key, shape)
        return (v if inverse is None else inverse(v)).astype(dtype)
    return init


def _state_rows(cache, B: int, T: int):
    """What a state layer's mixer reads off the cache it is handed for a
    group of rows (B, T): ``(layer, rows, fresh, valid)``: the layer's
    index among those that keep a state, the cache row of each entry (its
    own place where the caller names none), whether an entry stands at
    position 0 (it reads neither its state nor its tail), and how many of
    its T tokens are real."""
    rows = cache.get("rows")
    if rows is None:
        rows = jnp.arange(B, dtype=jnp.int32)
    valid = jnp.full((B,), T, jnp.int32)
    if cache.get("valid") is not None:
        valid = jnp.minimum(cache["valid"], T)
    return (cache["layer"], rows,
            jnp.broadcast_to(cache["start"] == 0, (B,)), valid)


def _conv_after_tail(cache, x, w, b, silu: bool = True):
    """A state layer's causal convolution of one group of rows ``x`` (B, T,
    C) after the tail its cache carries (``ops/state_space.causal_conv``;
    ``silu`` False: without its activation): ``(conv(x), where, conv
    leaf)``. ``cache`` None: whole sequences from nothing, no ``where`` and
    no leaf. Else ``where`` is :func:`_state_rows`'s, the tail is read from
    the leaf ``conv`` and the tail at the last REAL token is written back to
    it."""
    from ..ops import state_space

    conv = state_space.causal_conv if silu else functools.partial(
        state_space.causal_conv, silu=False)
    B, T = x.shape[:2]
    tail = jnp.zeros((B, w.shape[0] - 1, x.shape[-1]), x.dtype)
    if cache is None:
        return conv(x, tail, w, b, jnp.full((B,), T, jnp.int32))[0], \
            None, None
    where = layer, rows, fresh, valid = _state_rows(cache, B, T)
    tail = _read_rows(cache["conv"], layer, rows, fresh, tail.shape[1:])
    x, tail = conv(x, tail, w, b, valid)
    return x, where, _write_rows(cache["conv"], layer, rows,
                                 tail.reshape(B, -1))


def _read_rows(leaf, layer, rows, fresh, shape):
    """Rows ``rows`` (B,) of layer ``layer`` of ``leaf`` (L, R, W), each as
    ``shape``: zeros for an entry that is ``fresh`` or out of ``[0, R)``
    (one that does not run)."""
    R = leaf.shape[1]
    at = (layer, jnp.where((rows >= 0) & (rows < R), rows, R))
    return jnp.where(fresh[:, None, None], 0, leaf.at[at].get(
        mode="fill", fill_value=0).reshape((rows.shape[0],) + shape))


def _write_rows(leaf, layer, rows, values):
    """``leaf`` (L, R, W) with ``values`` (B, W) written to rows ``rows``
    (B,) of layer ``layer``; an entry out of ``[0, R)`` writes nothing.
    A few entries are a scatter of their rows. From ``_SLAB_FROM`` on they
    go through the layer's whole slab, each row taking the entry that names
    it (a select and ONE update of (R, W)): XLA expands a scatter of B rows
    into a loop of B single-row updates, 5.6 ms a step of 128 rows over 9
    KDA layers where the slabs' bytes are 0.2 ms (my traced run, PR 50,
    call 3: ``dynamic-update-slice`` over ``bf16[9,128,36864]`` with its
    bounds check, 17 % of the busy device; the mamba layers' tail of 64
    rows x 36 layers went the same way). The slab costs the same at every
    B and more than a scatter of two: a layer of (64, 13056) 11.9 us
    against 4.9 at B = 2, 9.3 at 8, 17.3 at 16, 55.7 at 64; a layer of
    (128, 36864) 63 us against 18 at B = 2, 66 at 8, 130 at 16, 973 at
    128 (my chip run, PR 50, call 94: each form alone, every layer once)."""
    B, R = rows.shape[0], leaf.shape[1]
    values = values.astype(leaf.dtype)
    if B < _SLAB_FROM:
        at = (layer, jnp.where((rows >= 0) & (rows < R), rows, R))
        return leaf.at[at].set(values, mode="drop")
    named = rows[None, :] == jnp.arange(R, dtype=rows.dtype)[:, None]  # R, B
    slab = jax.lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False)
    slab = jnp.where(jnp.any(named, axis=1)[:, None],
                     values[jnp.argmax(named, axis=1)], slab)
    return jax.lax.dynamic_update_slice_in_dim(leaf, slab[None], layer, 0)


# entries from which _write_rows takes the slab (where the two forms met
# on both cells' leaves, see there)
_SLAB_FROM = 8


class Mamba2Mixer(nn.Module):
    """The Mamba-2 mixer in the attention's place
    (``ops/state_space.py`` has the state's equations). With ``u`` the
    normed input::

        [z ; xBC ; dt] = W_in u         xBC <- silu(conv(xBC))
        [x ; B ; C] = xBC               dt <- softplus(dt + dt_bias)
        H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t      A = -exp(A_log)
        y_t = H_t C_t + D x_t
        out = W_out (w (.) g / rms(g))          g = y (.) silu(z)

    ``conv`` is causal and depthwise over ``mamba_d_conv`` taps with a
    bias; the gate comes BEFORE the norm. Three forms of that one
    mathematics. Without a cache: whole sequences from an empty state
    (``ssm_sequence``). With one, ``kv_cache`` holds the stacked leaves
    whole, ``s`` (the state, float32) and ``conv`` (the last
    ``mamba_d_conv - 1`` inputs of the convolution, time-major on the minor
    axis: ``(L, rows, (taps - 1) * channels)`` in the model's dtype), with
    ``layer``, ``start`` and, as :class:`PowerRetention`, ``rows`` and
    ``valid``: one token takes ``ssm_decode``, more take ``ssm_chunk``
    block by block. A token at or past ``valid`` is padding: it advances
    neither the state (its ``dt`` is 0) nor the tail (taken at the last
    real token). An entry whose first position is 0 reads neither. The
    convolution and the tail's shift are XLA's, under the scope
    ``ssm_conv``."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, u, *, decode: Union[bool, str] = False,
                 deterministic: bool = True, kv_cache=None, layer=None):
        from ..ops import state_space as ss

        cfg = self.config
        B, T, C = u.shape
        H, P, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
        inner, ch, K = H * P, cfg.mamba_channels, cfg.mamba_d_conv
        f32 = jnp.float32
        # W_in as two leaves, [z ; xBC] and dt: the published matrix is
        # 2 * inner + 2 * N + H wide (8,512 at the published widths, no
        # multiple of 128 lanes), and the chip's client stores such a leaf
        # with the OTHER dimension minor, which every program then copies
        # whole before its layer loop (1.25 GB a step there: compiled for
        # a described v5e, PR 47). z and xBC are whole lane tiles; dt's 64
        # columns are a leaf of their own, small enough to copy
        zx = _dense(cfg, inner + ch, use_bias=False, name="in_proj")(u)
        z, xbc = zx[..., :inner], zx[..., inner:]
        dt = _dense(cfg, H, use_bias=False, name="dt_proj")(u)
        bound = 1.0 / math.sqrt(K)      # (torch's Conv1d default)
        taps = nn.initializers.uniform(2 * bound)
        conv_w = self.param("conv_w", lambda *a: taps(*a) - bound, (K, ch))
        conv_b = self.param("conv_b", lambda *a: taps(*a) - bound, (ch,))
        a = -jnp.exp(self.param("A_log", _uniform_log(1.0, 16.0, jnp.log),
                                (H,)).astype(f32))
        skip = self.param("D", nn.initializers.ones, (H,)).astype(f32)
        dt_bias = self.param(
            "dt_bias", _uniform_log(1e-3, 1e-1, lambda v: v + jnp.log(
                -jnp.expm1(-v))), (H,)).astype(f32)
        gate_norm = self.param("norm", nn.initializers.ones, (inner,))
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias)          # (B, T, H)

        cached = bool(decode)

        def mix(cache, xbc, dt):
            """The convolution and the state of one group of rows (B, T):
            ``(y + D x, leaves)``."""
            B, T = dt.shape[:2]
            xbc, where, conv_leaf = _conv_after_tail(cache, xbc, conv_w,
                                                     conv_b)
            x = xbc[..., :inner].reshape(B, T, H, P)
            b, c = xbc[..., inner:inner + N], xbc[..., inner + N:]
            if not cached:
                y, leaves = ss.ssm_sequence(x, dt, a, b, c), None
            else:
                li, rows, fresh, valid = where
                if T == 1:
                    y, s = ss.ssm_decode(x[:, 0], dt[:, 0], a, b[:, 0],
                                         c[:, 0], cache["s"], li, rows, fresh)
                    y = y[:, None]
                else:
                    y, s = _traced_once(ss.ssm_prefill,
                                        chunk=_chunk_shaped(x))(
                        x, dt, a, b, c, cache["s"], li, rows, fresh,
                        length=valid)
                leaves = {"s": s, "conv": conv_leaf}
            return (y + skip[:, None] * x).reshape(B, T, inner), leaves

        y, leaves = _by_row_group(kv_cache, mix, xbc, dt) if cached \
            else mix(None, xbc, dt)
        g = ss.gated_norm(y, z, gate_norm, cfg.layer_norm_epsilon)
        out = _dense(cfg, C, use_bias=False, name="out_proj")(
            g.astype(cfg.dtype))
        return out, leaves


class KDAMixer(nn.Module):
    """Kimi Delta Attention in the attention's place (``ops/kda.py`` has
    the state's equations). With ``x`` the normed input, a head ``h`` of
    ``d = kda_d_head`` channels::

        [q ; k ; v] = silu(conv(W_qkv x))       (one convolution a channel)
        q_h <- l2norm(q_h) / sqrt(d)            k_h <- l2norm(k_h)
        g_h = -exp(A_log_h) softplus(W_f^up W_f^down x + dt_bias)_h
        beta_h = sigmoid(W_beta x)_h
        o_h = the gated delta rule over (q_h, k_h, v_h, g_h, beta_h)
        out = W_o [rmsnorm_d(o_h; w) (.) sigmoid(W_g^up W_g^down x)_h]

    ``conv`` is causal and depthwise over ``kda_d_conv`` taps, no bias; the
    decay ``g`` is a channel's, through a low rank of ``d``, and so is the
    output gate. Three forms, as :class:`Mamba2Mixer`: without a
    cache whole sequences from an empty state (``kda_sequence``); with one,
    ``kv_cache`` holds the stacked leaves whole, ``s`` (float32, (L, rows,
    H, d, d)) and ``conv`` (the last ``kda_d_conv - 1`` inputs of the
    convolution over [q ; k ; v], time-major on the minor axis), with
    ``layer``, ``start``, ``rows`` and ``valid``: one token takes
    ``kda_decode``, more take ``kda_chunk`` block by block. A token at or
    past ``valid`` is padding: it advances neither the state (its ``g``
    and ``beta`` are 0) nor the tail. An entry whose first position is 0
    reads neither. The convolution and the tail's shift are XLA's
    (``ops/state_space.causal_conv``)."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x, *, decode: Union[bool, str] = False,
                 deterministic: bool = True, kv_cache=None, layer=None):
        from ..ops import kda

        cfg = self.config
        B, T, C = x.shape
        H, D, taps = cfg.kda_n_heads, cfg.kda_d_head, cfg.kda_d_conv
        inner, rank = cfg.kda_width, D      # (the low rank: a head's width)
        f32 = jnp.float32

        def dense(width, name):
            return _dense(cfg, width, use_bias=False, name=name)

        qkv = dense(3 * inner, "qkv_proj")(x)
        bound = 1.0 / math.sqrt(taps)       # (torch's Conv1d default)
        draw = nn.initializers.uniform(2 * bound)
        conv_w = self.param("conv_w", lambda *a: draw(*a) - bound,
                            (taps, 3 * inner))
        a = jnp.exp(self.param("A_log", _uniform_log(1.0, 16.0, jnp.log),
                               (H,)).astype(f32))
        dt_bias = self.param(
            "dt_bias", _uniform_log(1e-3, 1e-1, lambda v: v + jnp.log(
                -jnp.expm1(-v))), (inner,)).astype(f32)
        f = dense(inner, "f_b_proj")(dense(rank, "f_a_proj")(x))
        g = -a[:, None] * jax.nn.softplus(
            f.astype(f32) + dt_bias).reshape(B, T, H, D)
        beta = jax.nn.sigmoid(dense(H, "b_proj")(x).astype(f32))
        gate = dense(inner, "g_b_proj")(dense(rank, "g_a_proj")(x))
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
            q, k, v = (qkv[..., i * inner:(i + 1) * inner].reshape(
                B, T, H, D) for i in range(3))
            q, k = unit(q) * (1.0 / math.sqrt(D)), unit(k)
            if not cached:
                return kda.kda_sequence(q, k, v, g, beta), None
            li, rows, fresh, valid = where
            if T == 1:
                o, s = kda.kda_decode(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                      beta[:, 0], cache["s"], li, rows, fresh)
                o = o[:, None]
            else:
                o, s = _traced_once(kda.kda_prefill,
                                    chunk=_chunk_shaped(q))(
                    q, k, v, g, beta, cache["s"], li, rows, fresh,
                    length=valid)
            return o, {"s": s, "conv": conv_leaf}

        o, leaves = _by_row_group(kv_cache, mix, qkv, g, beta) if cached \
            else mix(None, qkv, g, beta)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg.layer_norm_epsilon) * o_norm.astype(f32)
        y = o.reshape(B, T, inner) * jax.nn.sigmoid(gate.astype(f32))
        return dense(C, "o_proj")(y.astype(cfg.dtype)), leaves


class ShortConvMixer(nn.Module):
    """LFM2's gated short convolution in the attention's place. With ``u``
    the normed input (C wide)::

        [B ; C ; z] = W_in u            (three gates of C channels, this order)
        v = B (.) z
        c_t = sum_j w_j (.) v_{t - (K - 1) + j}     j = 0 .. K - 1
        out = W_out (C (.) c)

    The convolution is causal and depthwise over ``conv_taps`` = K taps, no
    bias and NO activation. There is no matrix state: what a sequence
    carries is the convolution's tail, the last K - 1 rows of ``v``, the
    one leaf ``conv`` of the state group (``(L, rows, (K - 1) * C)`` in the
    model's dtype, time-major on the minor axis), read and written through
    :func:`_conv_after_tail` with ``layer``, ``start``, ``rows`` and
    ``valid`` as :class:`Mamba2Mixer`: a decode row, a chunk and a whole
    sequence are one code path, a token at or past ``valid`` shifts nothing
    into the tail, an entry whose first position is 0 reads none, and a
    row that does not run keeps its tail. All of it is XLA's, under the
    scope ``short_conv``."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, u, *, decode: Union[bool, str] = False,
                 deterministic: bool = True, kv_cache=None, layer=None):
        cfg = self.config
        C, K = u.shape[-1], cfg.conv_taps
        bcz = _dense(cfg, 3 * C, use_bias=False, name="in_proj")(u)
        bound = 1.0 / math.sqrt(K)      # (torch's Conv1d default)
        taps = nn.initializers.uniform(2 * bound)
        conv_w = self.param("conv_w", lambda *a: taps(*a) - bound, (K, C))

        def mix(cache, bcz):
            """The gates and the convolution of one group of rows (B, T):
            ``(C (.) c, leaves)``."""
            with jax.named_scope("short_conv"):
                b, c, z = (bcz[..., i * C:(i + 1) * C] for i in range(3))
                conv, _, leaf = _conv_after_tail(cache, b * z, conv_w, None,
                                                 silu=False)
                y = (c.astype(jnp.float32) * conv).astype(cfg.dtype)
            return y, None if cache is None else {"conv": leaf}

        y, leaves = _by_row_group(kv_cache, mix, bcz) if decode \
            else mix(None, bcz)
        return _dense(cfg, C, use_bias=False, name="out_proj")(y), leaves
