"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference (v0.9.5) predates DeepSpeed-Ulysses and has NO sequence
parallelism (SURVEY §5.7, grep-verified); its long-sequence levers are sparse
attention and activation partitioning. This module is the TPU-idiomatic
long-context answer the build plan calls for (SURVEY §7 step 12): a
first-class ``seq`` mesh axis with two interchangeable attention strategies,

* **ring attention** — K/V chunks rotate around the ``seq`` axis via
  ``lax.ppermute`` while each device keeps its Q chunk; per-step partial
  attention folds into a running (max, sum, acc) online softmax, so the full
  (S×S) score matrix never materializes and peak memory is O(S/sp) per
  device. The ppermute rides neighbor ICI links — bandwidth-optimal on a
  torus. (Liu et al., Ring Attention with Blockwise Transformers, 2023.)
* **Ulysses all-to-all** — two ``lax.all_to_all``s re-shard the activations
  from sequence-sharded to head-sharded, run *local* full attention (dense or
  the Pallas flash kernel), and scatter back. Comm volume is O(S·C/sp) per
  device (vs allgathering K/V = O(S·C)), the DeepSpeed-Ulysses insight.

Both are exposed (a) as ``shard_map``-wrapped drop-ins taking globally-shaped
arrays, and (b) as ``*_local`` collectives usable inside an existing
``shard_map``/pjit region. ``DistributedAttention`` mirrors the module API
DeepSpeed later shipped (deepspeed.sequence.layer.DistributedAttention) so
users migrating from newer DeepSpeed find the same surface.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ...parallel.mesh import DATA_AXIS, EXPERT_AXIS, SEQ_AXIS, get_mesh

NEG_INF = -1e30


def _dense_attention(q, k, v, *, causal: bool, scale: float,
                     q_offset=0, k_offset=0):
    """Plain blockwise-dense attention in fp32 with absolute-position causal
    masking (offsets give each shard its global coordinates)."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        rows = q_offset + jax.lax.broadcasted_iota(jnp.int32, (Tq, Tk), 0)
        cols = k_offset + jax.lax.broadcasted_iota(jnp.int32, (Tq, Tk), 1)
        s = jnp.where((rows >= cols)[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# ring attention (collective form — call inside shard_map over `axis_name`)
# ---------------------------------------------------------------------------
def ring_attention_local(q, k, v, *, axis_name: str = SEQ_AXIS,
                         causal: bool = True,
                         scale: Optional[float] = None):
    """Ring attention over ``axis_name``; q/k/v are the LOCAL sequence shards
    shaped (B, S_local, H, D). Returns the local shard of the output.

    Step s: every device holds K/V chunk ``(my_index - s) mod sp`` and folds
    its partial attention into the online-softmax state, then passes the
    chunk to its right neighbor. Fully-causally-masked steps still occupy a
    ring slot (the rotation must complete) but their contribution is exactly
    zero via the mask term.
    """
    B, S_local, H, D = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    sp = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)

    qf = q.astype(jnp.float32) * scale
    q_pos = my * S_local + jax.lax.broadcasted_iota(jnp.int32, (S_local, S_local), 0)

    def step(carry, s):
        acc, m, l, k_cur, v_cur = carry
        chunk = jax.lax.rem(my - s + sp, sp)
        scores = jnp.einsum("bthd,bshd->bhts", qf, k_cur.astype(jnp.float32))
        if causal:
            k_pos = chunk * S_local + jax.lax.broadcasted_iota(
                jnp.int32, (S_local, S_local), 1)
            mask = (q_pos >= k_pos)[None, None]
            scores = jnp.where(mask, scores, NEG_INF)
            maskf = mask.astype(jnp.float32)
        else:
            maskf = None
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        if maskf is not None:
            p = p * maskf  # kills spurious exp(0)=1 on fully-masked rows
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bhts,bshd->bhtd", p, v_cur.astype(jnp.float32))
        k_nxt, v_nxt = jax.lax.ppermute(
            (k_cur, v_cur), axis_name,
            [(i, (i + 1) % sp) for i in range(sp)])
        return (acc_new, m_new, l_new, k_nxt, v_nxt), None

    acc0 = jnp.zeros((B, H, S_local, D), jnp.float32)
    m0 = jnp.full((B, H, S_local, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, S_local, 1), jnp.float32)
    # mark the fresh carries as device-varying over the same manual axes as q
    # (new-style shard_map type-checks varying-axis sets through scan)
    vma = tuple(getattr(jax.typeof(q), "vma", ()) or ())
    if vma:
        acc0, m0, l0 = (jax.lax.pcast(x, vma, to="varying")
                        for x in (acc0, m0, l0))
    (acc, m, l, _, _), _ = jax.lax.scan(
        step, (acc0, m0, l0, k, v), jnp.arange(sp))
    out = acc / jnp.maximum(l, 1e-20)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Ulysses (collective form)
# ---------------------------------------------------------------------------
def ulysses_attention_local(q, k, v, *, axis_name: str = SEQ_AXIS,
                            causal: bool = True,
                            scale: Optional[float] = None,
                            attn_fn: Optional[Callable] = None):
    """DeepSpeed-Ulysses-style attention over ``axis_name``.

    q/k/v: local shards (B, S_local, H, D) with H divisible by the axis size.
    all_to_all #1 scatters heads / gathers sequence → (B, S, H/sp, D); local
    full attention (``attn_fn`` or dense, e.g. the Pallas flash kernel);
    all_to_all #2 scatters sequence / gathers heads back.
    """
    D = q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    sp = jax.lax.psum(1, axis_name)
    H = q.shape[2]
    if H % sp != 0:
        raise ValueError(
            f"Ulysses requires the local head count ({H}) to be divisible by "
            f"the '{axis_name}' axis size ({sp}); use ring attention for "
            f"head counts that don't divide")
    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis_name, tiled=True)
    # seq-sharded → head-sharded
    qh = a2a(q, split_axis=2, concat_axis=1)
    kh = a2a(k, split_axis=2, concat_axis=1)
    vh = a2a(v, split_axis=2, concat_axis=1)
    if attn_fn is None:
        o = _dense_attention(qh, kh, vh, causal=causal, scale=scale)
    else:
        o = attn_fn(qh, kh, vh, causal=causal, scale=scale)
    # head-sharded → seq-sharded
    return a2a(o, split_axis=1, concat_axis=2)


# ---------------------------------------------------------------------------
# shard_map wrappers taking GLOBAL arrays
# ---------------------------------------------------------------------------
def _seq_specs(batch_axes, axis_name, head_axes):
    return P(batch_axes, axis_name, head_axes, None)


def ring_attention(q, k, v, *, mesh=None, axis_name: str = SEQ_AXIS,
                   causal: bool = True, scale: Optional[float] = None,
                   batch_axes=(DATA_AXIS, EXPERT_AXIS), head_axes=None):
    """Global-view ring attention: (B, S, H, D) arrays, batch sharded over
    ``batch_axes``, sequence sharded over ``axis_name``; ``head_axes`` lets
    tensor parallelism shard the head dim (composes: ring per head shard)."""
    mesh = mesh or get_mesh()
    spec = _seq_specs(batch_axes, axis_name, head_axes)
    fn = functools.partial(ring_attention_local, axis_name=axis_name,
                          causal=causal, scale=scale)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)


def ulysses_attention(q, k, v, *, mesh=None, axis_name: str = SEQ_AXIS,
                      causal: bool = True, scale: Optional[float] = None,
                      attn_fn: Optional[Callable] = None,
                      batch_axes=(DATA_AXIS, EXPERT_AXIS), head_axes=None):
    """Global-view Ulysses attention (see :func:`ulysses_attention_local`)."""
    mesh = mesh or get_mesh()
    spec = _seq_specs(batch_axes, axis_name, head_axes)
    fn = functools.partial(ulysses_attention_local, axis_name=axis_name,
                          causal=causal, scale=scale, attn_fn=attn_fn)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)


class DistributedAttention:
    """Sequence-parallel attention wrapper, API-compatible with the module
    DeepSpeed later shipped as ``deepspeed.sequence.layer.DistributedAttention``
    (post-0.10.2): wraps a *local* attention callable and handles the
    sequence↔head resharding around it.

    ``local_attn(q, k, v, *, causal, scale) -> out`` operates on
    head-sharded, full-sequence tensors (B, S, H_local, D). Only the
    "ulysses" strategy uses it; ring computes its own blockwise softmax, so
    combining ring with ``local_attn`` is rejected.
    """

    def __init__(self, local_attn: Optional[Callable] = None,
                 *, mesh=None, axis_name: str = SEQ_AXIS,
                 strategy: str = "ulysses", causal: bool = True,
                 scale: Optional[float] = None,
                 batch_axes=(DATA_AXIS, EXPERT_AXIS), head_axes=None):
        assert strategy in ("ulysses", "ring"), strategy
        if strategy == "ring" and local_attn is not None:
            raise ValueError(
                "strategy='ring' cannot use local_attn (ring attention "
                "computes blockwise softmax internally); use 'ulysses'")
        self.local_attn = local_attn
        self.mesh = mesh
        self.axis_name = axis_name
        self.strategy = strategy
        self.causal = causal
        self.scale = scale
        self.batch_axes = batch_axes
        self.head_axes = head_axes

    def __call__(self, q, k, v):
        if self.strategy == "ring":
            return ring_attention(q, k, v, mesh=self.mesh,
                                  axis_name=self.axis_name, causal=self.causal,
                                  scale=self.scale, batch_axes=self.batch_axes,
                                  head_axes=self.head_axes)
        return ulysses_attention(q, k, v, mesh=self.mesh,
                                 axis_name=self.axis_name, causal=self.causal,
                                 scale=self.scale, attn_fn=self.local_attn,
                                 batch_axes=self.batch_axes,
                                 head_axes=self.head_axes)
