"""GShard-style top-1/top-2 gating and MoE dispatch math.

Capability parity with reference ``deepspeed/moe/sharded_moe.py`` —
``top1gating`` (:179), ``top2gating`` (:277), ``MOELayer`` dispatch/combine
einsums (:420,472), ``_AllToAll`` (:90) — as pure jnp. The gating math is the
public GShard algorithm (capacity, random token priority, load-balance aux
loss) and ports directly to tensor code.

TPU-native dispatch: the reference wraps an explicit NCCL all-to-all in an
autograd Function. Here the dispatched tensor gets a *sharding constraint*
(expert axis on dim 0) and XLA inserts the all-to-all over ICI — see
``layer.py``. Expert-data-parallel gradient reduction (reference
engine.py:2304 expert-grad groups) also falls out declaratively: expert
params are sharded over the ``expert`` axis, so their grads reduce only over
the remaining (data, seq) axes.

Two dispatch materializations share one gating core (:class:`GateDecisions`):

* ``einsum`` — the reference's dense one-hot form,
  ``einsum("sec,sm->ecm")`` (sharded_moe.py:420). Costs S·E·C·M MACs each
  way and materializes the (S,E,C) combine tensor; at NLG-recipe shapes
  (S=16k, E=8, cf=1.25 top-2) that is ~2.5x the expert FFN FLOPs and a
  multi-GB intermediate.
* ``index`` (default) — TPU-native scatter/gather: tokens are scattered
  into their (expert, slot) rows and gathered back with gate weights,
  O(S·M) memory traffic and no (S,E,C) tensor. Both paths consume the SAME
  decisions, so routing is identical by construction (parity-tested in
  ``tests/unit/moe/test_moe.py``).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class GateDecisions(NamedTuple):
    """Routing decisions for a batch of S tokens under top-k gating.

    ``expert_idx``/``slot``/``gate``/``valid`` are (S, k): for each token
    and choice j, the expert it routes to, its slot in that expert's
    capacity buffer, its (top-2: renormalized) combine weight, and whether
    it survived the capacity cut. ``aux_loss`` is the load-balance loss
    (computed pre-capacity, as the reference does)."""

    aux_loss: jnp.ndarray
    expert_idx: jnp.ndarray   # (S, k) int32
    slot: jnp.ndarray         # (S, k) int32
    gate: jnp.ndarray         # (S, k) float32
    valid: jnp.ndarray        # (S, k) bool
    capacity: int


def _one_hot(x, num_classes):
    return jax.nn.one_hot(x, num_classes, dtype=jnp.float32)


def _capacity(num_tokens: int, num_experts: int, capacity_factor: float,
              min_capacity: int) -> int:
    """≅ reference _capacity (sharded_moe.py): tokens/experts × factor."""
    cap = int(math.ceil(num_tokens / num_experts * capacity_factor))
    return max(cap, min_capacity)


def _uniform_noise(rng, shape, eps: float = 1e-2):
    return jax.random.uniform(rng, shape, minval=1.0 - eps, maxval=1.0 + eps)


def _gumbel_noise(rng, shape):
    return jax.random.gumbel(rng, shape)


def top1_decisions(logits: jnp.ndarray,
                   capacity_factor: float = 1.0,
                   min_capacity: int = 4,
                   noisy_gate_policy: Optional[str] = None,
                   drop_tokens: bool = True,
                   use_rts: bool = True,
                   rng: Optional[jax.Array] = None,
                   used_token: Optional[jnp.ndarray] = None) -> GateDecisions:
    """Top-1 routing decisions (≅ reference sharded_moe.py:179).

    Random token selection (``use_rts``) breaks position bias when dropping.
    ``used_token`` (S,) masks padding tokens out of routing and the aux
    loss (reference sharded_moe.py:202-203; top-1 only, as there).
    """
    S, E = logits.shape
    capacity = _capacity(S, E, capacity_factor, min_capacity)
    if not drop_tokens:
        capacity = S

    if noisy_gate_policy == "RSample" and rng is not None:
        rng, sub = jax.random.split(rng)
        logits_for_selection = logits + _gumbel_noise(sub, logits.shape)
    else:
        logits_for_selection = logits

    gates = jax.nn.softmax(logits, axis=1)
    indices1 = jnp.argmax(logits_for_selection, axis=1)
    mask1 = _one_hot(indices1, E)  # (S, E)
    if used_token is not None:
        mask1 = mask1 * used_token.astype(mask1.dtype)[:, None]

    # load-balancing aux loss: E * mean_e(fraction_tokens_e * mean_gate_e)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    aux_loss = jnp.sum(me * ce) * E

    # random token priority: permute intra-expert ordering before capacity cut
    if use_rts and rng is not None:
        rng, sub = jax.random.split(rng)
        priority = jax.random.uniform(sub, (S,))
    else:
        priority = -jnp.arange(S, dtype=jnp.float32)  # FIFO order
    # position of each token within its expert queue, ordered by priority
    order = jnp.argsort(-priority)
    mask1_sorted = mask1[order]
    locations_sorted = jnp.cumsum(mask1_sorted, axis=0) - 1.0
    inv = jnp.argsort(order)
    locations1 = jnp.sum(locations_sorted[inv] * mask1, axis=1)  # (S,)

    keep = (locations1 < capacity) & (jnp.sum(mask1, axis=1) > 0)
    gates1 = jnp.sum(gates * mask1, axis=1)  # gate value of chosen expert

    return GateDecisions(
        aux_loss=aux_loss,
        expert_idx=indices1.astype(jnp.int32)[:, None],
        slot=locations1.astype(jnp.int32)[:, None],
        gate=gates1[:, None],
        valid=keep[:, None],
        capacity=capacity)


def top2_decisions(logits: jnp.ndarray,
                   capacity_factor: float = 1.0,
                   min_capacity: int = 4,
                   drop_tokens: bool = True,
                   rng: Optional[jax.Array] = None) -> GateDecisions:
    """Top-2 routing decisions (≅ reference sharded_moe.py:277): second
    expert chosen with gumbel noise, gates renormalized over the two picks
    (after the capacity cut, so a dropped first choice passes full weight
    to the surviving second — the reference's order of operations)."""
    S, E = logits.shape
    capacity = _capacity(S, E, 2 * capacity_factor, min_capacity)
    if not drop_tokens:
        capacity = S

    gates = jax.nn.softmax(logits, axis=1)
    indices1 = jnp.argmax(gates, axis=1)
    mask1 = _one_hot(indices1, E)

    if rng is not None:
        rng, sub = jax.random.split(rng)
        logits_w_noise = logits + _gumbel_noise(sub, logits.shape)
    else:
        logits_w_noise = logits
    logits_except1 = jnp.where(mask1 > 0, -jnp.inf, logits_w_noise)
    indices2 = jnp.argmax(logits_except1, axis=1)
    mask2 = _one_hot(indices2, E)

    locations1 = jnp.cumsum(mask1, axis=0) - 1.0
    # second-choice tokens queue after all first choices
    locations2 = jnp.cumsum(mask2, axis=0) - 1.0 + jnp.sum(mask1, axis=0)[None, :]

    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    aux_loss = jnp.sum(me * ce) * E

    loc1 = jnp.sum(locations1 * mask1, axis=1)
    loc2 = jnp.sum(locations2 * mask2, axis=1)
    valid1 = loc1 < capacity
    valid2 = loc2 < capacity

    gates1 = jnp.sum(gates * mask1, axis=1) * valid1
    gates2 = jnp.sum(gates * mask2, axis=1) * valid2
    denom = jnp.maximum(gates1 + gates2, jnp.finfo(gates.dtype).eps)
    gates1, gates2 = gates1 / denom, gates2 / denom

    return GateDecisions(
        aux_loss=aux_loss,
        expert_idx=jnp.stack([indices1, indices2], axis=1).astype(jnp.int32),
        slot=jnp.stack([loc1, loc2], axis=1).astype(jnp.int32),
        gate=jnp.stack([gates1, gates2], axis=1),
        valid=jnp.stack([valid1, valid2], axis=1),
        capacity=capacity)


def gate_decisions(logits: jnp.ndarray, k: int = 1,
                   capacity_factor: float = 1.0, min_capacity: int = 4,
                   noisy_gate_policy: Optional[str] = None,
                   drop_tokens: bool = True, use_rts: bool = True,
                   rng: Optional[jax.Array] = None,
                   used_token: Optional[jnp.ndarray] = None) -> GateDecisions:
    """Top-k routing decisions (dispatcher over top1/top2). ``used_token``
    applies to top-1 only (the reference's TopKGate likewise forwards it
    only to top1gating, sharded_moe.py:406)."""
    if k == 1:
        return top1_decisions(logits, capacity_factor, min_capacity,
                              noisy_gate_policy, drop_tokens, use_rts, rng,
                              used_token=used_token)
    if k == 2:
        return top2_decisions(logits, capacity_factor, min_capacity,
                              drop_tokens, rng)
    raise ValueError(
        f"top-{k} gating unsupported by the capacity gate (the "
        f"reference's training layer: k = 1, 2); top-k routing that "
        f"drops no token is deepspeed_tpu/moe/routed_ffn.py "
        f"(TransformerConfig.n_experts)")


def _densify(dec: GateDecisions, num_experts: int, dtype
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Decisions → dense (combine (S,E,C), dispatch (S,E,C)) one-hot form."""
    S, k = dec.expert_idx.shape
    combine = jnp.zeros((S, num_experts, dec.capacity), jnp.float32)
    for j in range(k):
        maskj = _one_hot(dec.expert_idx[:, j], num_experts) \
            * dec.valid[:, j].astype(jnp.float32)[:, None]
        loc_oh = _one_hot(dec.slot[:, j], dec.capacity)
        combine = combine + (dec.gate[:, j][:, None, None]
                             * maskj[:, :, None] * loc_oh[:, None, :])
    dispatch = combine > 0
    return combine.astype(dtype), dispatch


def top1gating(logits: jnp.ndarray,
               capacity_factor: float = 1.0,
               min_capacity: int = 4,
               noisy_gate_policy: Optional[str] = None,
               drop_tokens: bool = True,
               use_rts: bool = True,
               rng: Optional[jax.Array] = None,
               used_token: Optional[jnp.ndarray] = None,
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, int]:
    """Top-1 gating, dense form (≅ reference sharded_moe.py:179).

    Returns (aux_loss, combine_weights (S,E,C), dispatch_mask (S,E,C), capacity).
    """
    dec = top1_decisions(logits, capacity_factor, min_capacity,
                         noisy_gate_policy, drop_tokens, use_rts, rng,
                         used_token=used_token)
    combine, dispatch = _densify(dec, logits.shape[1], logits.dtype)
    return dec.aux_loss, combine, dispatch, dec.capacity


def top2gating(logits: jnp.ndarray,
               capacity_factor: float = 1.0,
               min_capacity: int = 4,
               drop_tokens: bool = True,
               rng: Optional[jax.Array] = None,
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, int]:
    """Top-2 gating, dense form (≅ reference sharded_moe.py:277)."""
    dec = top2_decisions(logits, capacity_factor, min_capacity,
                         drop_tokens, rng)
    combine, dispatch = _densify(dec, logits.shape[1], logits.dtype)
    return dec.aux_loss, combine, dispatch, dec.capacity


def dispatch_indexed(tokens: jnp.ndarray, dec: GateDecisions,
                     num_experts: int) -> jnp.ndarray:
    """tokens (S, M) → dispatched (E, C, M) by scatter-add into (expert,
    slot) rows. O(S·M) memory traffic; replaces the S·E·C·M dispatch
    einsum (reference sharded_moe.py:420). Invalid/zero-gate tokens land
    in a pad row that is sliced off (mirrors ``dispatch = combine > 0``)."""
    S, M = tokens.shape
    E, C = num_experts, dec.capacity
    flat = jnp.zeros((E * C + 1, M), tokens.dtype)
    for j in range(dec.expert_idx.shape[1]):
        p = dec.expert_idx[:, j] * C + dec.slot[:, j]
        keep = dec.valid[:, j] & (dec.gate[:, j] > 0)
        p = jnp.where(keep, p, E * C)
        flat = flat.at[p].add(tokens)
    return flat[:E * C].reshape(E, C, M)


def combine_indexed(expert_out: jnp.ndarray, dec: GateDecisions) -> jnp.ndarray:
    """expert outputs (E, C, M) → (S, M) by gathering each token's
    (expert, slot) row(s) and weighting by its gate (reference's combine
    einsum, sharded_moe.py:472, without the (S,E,C) tensor)."""
    E, C, M = expert_out.shape
    flat = expert_out.reshape(E * C, M)
    S = dec.expert_idx.shape[0]
    out = jnp.zeros((S, M), expert_out.dtype)
    for j in range(dec.expert_idx.shape[1]):
        p = jnp.where(dec.valid[:, j],
                      dec.expert_idx[:, j] * C + dec.slot[:, j], 0)
        w = (dec.gate[:, j] * dec.valid[:, j]).astype(expert_out.dtype)
        out = out + w[:, None] * flat[p]
    return out


def expert_counts(dec: GateDecisions, num_experts: int) -> jnp.ndarray:
    """Tokens dispatched per expert (the reference's ``exp_counts``)."""
    counts = jnp.zeros((num_experts,), jnp.int32)
    for j in range(dec.expert_idx.shape[1]):
        keep = dec.valid[:, j] & (dec.gate[:, j] > 0)
        counts = counts + jnp.sum(
            _one_hot(dec.expert_idx[:, j], num_experts)
            * keep.astype(jnp.float32)[:, None], axis=0).astype(jnp.int32)
    return counts


def gate_and_dispatch(tokens: jnp.ndarray, gate_logits: jnp.ndarray, k: int = 1,
                      capacity_factor: float = 1.0, min_capacity: int = 4,
                      noisy_gate_policy: Optional[str] = None,
                      drop_tokens: bool = True, use_rts: bool = True,
                      rng: Optional[jax.Array] = None,
                      used_token: Optional[jnp.ndarray] = None):
    """tokens (S, M) + logits (S, E) → (aux_loss, dispatched (E, C, M),
    combine (S, E, C)). The dispatch einsum is the reference's
    ``einsum("sec,sm->ecm")`` (sharded_moe.py:420 area). Dense form; the
    MoE layer's default is the indexed form (``gate_decisions`` +
    ``dispatch_indexed``/``combine_indexed``)."""
    if k == 1:
        aux, combine, dispatch, _ = top1gating(
            gate_logits, capacity_factor, min_capacity, noisy_gate_policy,
            drop_tokens, use_rts, rng, used_token=used_token)
    elif k == 2:
        aux, combine, dispatch, _ = top2gating(
            gate_logits, capacity_factor, min_capacity, drop_tokens, rng)
    else:
        raise ValueError(
            f"top-{k} gating unsupported by the capacity gate (the "
            f"reference's training layer: k = 1, 2); top-k routing that "
            f"drops no token is deepspeed_tpu/moe/routed_ffn.py "
            f"(TransformerConfig.n_experts)")
    dispatched = jnp.einsum("sec,sm->ecm", dispatch.astype(tokens.dtype), tokens)
    return aux, dispatched, combine


def combine_output(expert_out: jnp.ndarray, combine: jnp.ndarray) -> jnp.ndarray:
    """(E, C, M) expert outputs × (S, E, C) combine weights → (S, M)
    (reference's ``einsum("sec,ecm->sm")``, sharded_moe.py:472 area)."""
    return jnp.einsum("sec,ecm->sm", combine.astype(expert_out.dtype), expert_out)
