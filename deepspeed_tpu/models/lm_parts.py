"""What the layers of ``models/transformer_lm.py`` share: the projection
and norm constructors, the rotary and ALiBi tables, the fused q/k/v
projection, and the helpers by which a layer reads the cache it is handed
(a chunk's positions, the row groups of a step, a kernel traced once)."""

from __future__ import annotations

import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops import backend
from .lm_config import TransformerConfig


def _dense(cfg: TransformerConfig, features: int, *, use_bias: bool,
           name: str, dtype=None):
    """nn.Dense, or its int8-at-rest serving twin when ``cfg.int8_weights``
    — params become int8 kernel + f32 per-channel scale consumed by the
    Pallas dequant-GEMM (ops/quantization); the inference engine's
    quantization tier builds that tree from a bf16 checkpoint."""
    if cfg.int8_weights:
        from ..ops.quantization import QuantDense

        return QuantDense(features, use_bias=use_bias, dtype=dtype or cfg.dtype,
                          kernel_mode=cfg.int8_kernel, name=name)
    return nn.Dense(features, use_bias=use_bias, dtype=dtype or cfg.dtype,
                    name=name)


class ZeroCentredRMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * (1 + w)`` over the last axis, in
    float32, cast to ``dtype`` (``TransformerConfig.norm`` "rmsnorm1p":
    Qwen3-Next's norm). A trained ``w`` starts at 0; a SEEDED one is drawn
    normal with spread 0.1, so that ``1 + w`` differs from ``w`` and from 1
    for whatever compares outputs."""

    epsilon: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.initializers.normal(0.1), (x.shape[-1],))
        x = x.astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                              + self.epsilon)
        return (x * (1.0 + w.astype(jnp.float32))).astype(self.dtype)


def _rms_norm(cfg: TransformerConfig, name: str):
    """The configuration's RMSNorm: the zero-centred one under
    ``norm="rmsnorm1p"``, flax's otherwise."""
    cls = ZeroCentredRMSNorm if cfg.norm == "rmsnorm1p" else nn.RMSNorm
    return cls(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype, name=name)


def _norm(cfg: TransformerConfig, name: str):
    if cfg.norm in ("rmsnorm", "rmsnorm1p"):
        return _rms_norm(cfg, name)
    return nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype, name=name)


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rotary(x, positions, *, rotary_dim: int, theta: float):
    """NeoX-style rotary embedding on the first ``rotary_dim`` channels.
    x: (B, T, H, D); positions: (B, T) absolute token positions."""
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                                / rotary_dim))
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # (B,T,rd/2)
    angles = jnp.concatenate([angles, angles], axis=-1)[:, :, None, :]  # (B,T,1,rd)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    rot32 = rot.astype(jnp.float32)
    out = rot32 * cos + _rotate_half(rot32) * sin
    return jnp.concatenate([out.astype(x.dtype), rest], axis=-1)


def rope_inv_freq(rotary_dim: int, rope: dict):
    """``(inv_freq (rotary_dim // 2,), factor)`` of one ``rope_parameters``
    section: ``default`` is ``theta ** (-2i / d)`` with factor 1; ``yarn``
    is the static YaRN of the ``transformers`` library (``truncate`` at its
    default): frequencies under ``low`` keep ``f_i``, over ``high`` take
    ``f_i / s``, a linear ramp between, and cos and sin are multiplied by
    ``attention_factor`` (``0.1 ln s + 1`` where the section gives none)."""
    d = rotary_dim
    theta = float(rope.get("rope_theta", 10000.0))
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    kind = rope.get("rope_type", "default")
    if kind == "default":
        return f.astype(np.float32), 1.0
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}: know default | yarn")
    s = float(rope["factor"])
    L0 = float(rope["original_max_position_embeddings"])

    def corr(beta):
        return d * math.log(L0 / (2 * math.pi * beta)) / (2 * math.log(theta))

    low = max(math.floor(corr(float(rope.get("beta_fast", 32.0)))), 0)
    high = min(math.ceil(corr(float(rope.get("beta_slow", 1.0)))), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    factor = rope.get("attention_factor")
    if factor is None:
        factor = 0.1 * math.log(s) + 1.0
    return (f / s * ramp + f * (1 - ramp)).astype(np.float32), float(factor)


def layer_rope_tables(cfg: "TransformerConfig"):
    """Per layer of a ``layer_types`` configuration: ``(inv_freq (L, rd/2),
    factor (L,), window (L,) bool)`` as constants the scanned layer indexes
    by the scan's counter."""
    rd = int(cfg.rotary_pct * cfg.head_dim) // 2 * 2
    sections = {k: dict(v) for k, v in (cfg.rope_parameters or ())}
    rows, factors = [], []
    for kind in cfg.layer_types:
        inv, factor = rope_inv_freq(
            rd, sections.get(kind, {"rope_theta": cfg.rope_theta}))
        rows.append(inv)
        factors.append(factor)
    return (np.stack(rows), np.asarray(factors, np.float32),
            np.asarray([k == "sliding_attention" for k in cfg.layer_types]))


def apply_rotary_table(x, positions, inv_freq, factor, rotary_dim: int):
    """:func:`apply_rotary` with the frequencies given (a layer's row of
    :func:`layer_rope_tables`) and cos, sin scaled by ``factor``."""
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)[:, :, None, :]
    cos, sin = jnp.cos(angles) * factor, jnp.sin(angles) * factor
    rot32 = rot.astype(jnp.float32)
    out = rot32 * cos + _rotate_half(rot32) * sin
    return jnp.concatenate([out.astype(x.dtype), rest], axis=-1)


def alibi_slopes(n_head: int) -> jnp.ndarray:
    """Per-head ALiBi slopes (Press et al.), matching the reference's alibi
    computation used for bloom (csrc attention alibi path)."""
    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    if math.log2(n_head).is_integer():
        return jnp.asarray(pow2_slopes(n_head), jnp.float32)
    closest = 2 ** math.floor(math.log2(n_head))
    base = pow2_slopes(closest)
    extra = pow2_slopes(2 * closest)[0::2][: n_head - closest]
    return jnp.asarray(base + extra, jnp.float32)


def _settled(*projected):
    """The results of an attention block's input projections, as values
    the compiler may not look through (``optimization_barrier``).

    What it prevents: the cache write and the read kernels take their
    operands head_dim-major, and XLA's layout assignment carried that wish
    back through ``reshape``, the rotary and the projection's dot onto the
    WEIGHT. It then computed ``W^T x^T``, and to have ``W^T`` it sliced the
    layer's matrix out of the stacked leaf into a buffer of its own and
    copied that into the other layout, a layer a projection a step (8 MB
    twice where the result is 256 KB: ``constant_dynamic-slice_fusion`` and
    ``copy`` over ``bf16[1, C, C']`` in a decode or chunk program, a
    quarter of the chat cell's busy device time, ledger PR 39). Behind the
    barrier the dot is an ordinary one with the slice of the stacked leaf
    fused into it, as ``o_proj``'s and the MLP's are, and the layout the
    kernels want is made on the small result. The values are what they
    were.

    How to see it come back: ``tests/unit/accelerator/test_chip_path.py``
    ``test_attention_projections_read_the_stacked_leaf`` compiles the step
    programs for a described v5e and looks for those two instructions."""
    return jax.lax.optimization_barrier(projected)


def _project_qkv(cfg: TransformerConfig, x):
    """``q_proj``, ``k_proj``, ``v_proj`` of ``x`` (B, T, C), settled, as
    (B, T, heads, head_dim). Called inside an attention module's
    ``__call__``: the three ``Dense`` are that module's."""
    B, T, _ = x.shape
    H, KV, D = cfg.n_head, cfg.kv_heads, cfg.head_dim
    q, k, v = _settled(*(
        _dense(cfg, heads * D, use_bias=cfg.qkv_bias, name=name)(x)
        for heads, name in ((H, "q_proj"), (KV, "k_proj"), (KV, "v_proj"))))
    return (q.reshape(B, T, H, D), k.reshape(B, T, KV, D),
            v.reshape(B, T, KV, D))


def _norm_qk(cfg: TransformerConfig, q, k):
    """``qk_norm``: RMSNorm over each head of ``q`` and of ``k`` (one
    learned weight of ``head_dim`` each; the zero-centred norm where the
    configuration's is), before the rotary. Called inside an attention
    module's ``__call__``: the two norms are that module's."""
    return tuple(_rms_norm(cfg, name)(x)
                 for name, x in (("q_norm", q), ("k_norm", k)))


def _gated(cfg: TransformerConfig, y, u):
    """``y (.) sigmoid(W_z u)``: the gate on a mixer's output, a projection
    of the mixer's own input (called inside the mixer: ``z_proj`` is its)."""
    z = _dense(cfg, y.shape[-1], use_bias=False, name="z_proj")(u)
    return (y.astype(jnp.float32)
            * jax.nn.sigmoid(z.astype(jnp.float32))).astype(cfg.dtype)


def _store_columns(buf, new, start):
    """Write the new positions-minor columns at each row's offset: one
    DUS for scalar start; per-slot (B,) starts vmap the DUS over the batch
    (lowers to a scatter — each slot writes at its own cache offset)."""
    if jnp.ndim(start) == 1:
        return jax.vmap(
            lambda c, n, s: jax.lax.dynamic_update_slice(
                c, n, (0,) * (c.ndim - 1) + (s,)))(buf, new, start)
    return jax.lax.dynamic_update_slice(
        buf, new, (0,) * (buf.ndim - 1) + (start,))


@functools.lru_cache(maxsize=None)
def _traced_once_on(fn, mesh, interpret: bool, static: tuple):
    return jax.jit(fn, static_argnames=static)


def _traced_once(fn, *static, chunk: bool = True):
    """``fn``, one of the cache kernels' entry points, as a function JAX
    traces ONCE for each set of operand shapes: an inner ``jit``, inlined
    where the program is compiled. For a prefill CHUNK's calls alone
    (``chunk``: several query rows of ONE slot): a layer's body is traced
    twice by the scan that holds it, and ``paged_chunk`` and the one
    program of a chunk beside the decode rows trace the same kernels; the
    kernel bodies were two thirds of a step program's trace time
    (``paged_chunk`` 2.6 s, ``kernel_decode`` 2.3 s, the two as one
    program 5.2 s on the chip's host with every compile served from the
    cache: my chip run, PR 48; all of it ``setup_s``). Keyed by what the
    kernels' wrappers read while they are traced: the mesh
    (``ops.backend.shard_kernel``) and the interpret switch.

    Every other call, a decode or a verify step's over all the slots, is
    ``fn`` itself, so those programs are the parent's. Measured, not
    supposed: behind an inlined call the decode read's work list
    (``s32[slots x pages_per_slot]``, the same for every layer) is
    computed again in every layer instead of once a program, 0.4-0.6 ms a
    step in docs and ide (my chip runs, PR 48, call 6: ``serve_tok_s``
    5,199 against 5,423, 6,851 against 7,049); and the plain decode
    program's compiled text is pinned instruction for instruction
    (``tests/unit/accelerator/test_chip_path.py``, ``_KERNEL_DECODE_TEXT``),
    which an inlined call renumbers. (One path for both shapes needs the
    work list handed to the kernels, a change under ``ops/``: PERF.md
    section 7.)"""
    if not chunk:
        return fn
    from ..ops import backend
    from ..parallel import mesh as mesh_mod

    return _traced_once_on(
        fn, mesh_mod.get_mesh() if mesh_mod.has_mesh() else None,
        backend.pallas_interpret(), static)


def _chunk_shaped(rows) -> bool:
    """Whether ``rows`` (slots, T, ...) are a prefill chunk's: several of
    one slot (:func:`_traced_once`)."""
    return rows.shape[0] == 1 and rows.shape[1] > 1


def _chunk_positions(kv_cache, T: int):
    """Positions (1, T) of the rows of a step that carries a prefill chunk
    beside its decode rows (``"chunk"`` in the cache a layer is handed,
    :meth:`TransformerLM.chunk_beside_decode`): the chunk's ``C`` tokens from
    its start, then each decode row at its own."""
    start = kv_cache["start"]
    at = kv_cache["chunk"]["start"][:, None] \
        + jnp.arange(T - start.shape[0])[None, :]
    return jnp.concatenate([at, start[None, :]], axis=1)


def _by_row_group(kv_cache, step, *rows):
    """What a mixer does against its cache, ``step(kv_cache, *rows) -> (y,
    leaves)`` over ``rows`` (B, T, ...), for every group of rows of the
    call. A call has one group, itself, unless a prefill chunk rides beside
    the decode rows: then ``rows`` are (1, C + B, ...), the chunk's C rows
    of ONE slot ahead of B slots' one row each, everything that read a
    weight has run over all of them at once, and what reads the cache runs
    a group at a time with the kernels it has: the chunk's rows as (1, C)
    through the addressing under ``kv_cache["chunk"]`` (its start, its
    slot's table row(s), its state row), then the decode rows as (B, 1)
    through the call's own, on the leaves as the chunk left them. That
    order is the two programs' this replaces: the decode row of the slot in
    mid-prefill reads what the chunk wrote and writes its dead column
    behind it."""
    chunk = kv_cache.get("chunk")
    if chunk is None:
        return step(kv_cache, *rows)
    cache = {key: val for key, val in kv_cache.items() if key != "chunk"}
    C = rows[0].shape[1] - cache["start"].shape[0]
    y_chunk, leaves = step(dict(cache, **chunk), *(r[:, :C] for r in rows))
    y, leaves = step(dict(cache, **leaves),
                     *(r[0, C:][:, None] for r in rows))
    return jnp.concatenate([y_chunk, y[:, 0][None]], axis=1), leaves
