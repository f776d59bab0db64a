"""MoE layer (expert parallelism).

Capability parity with reference ``deepspeed/moe/layer.py:16 MoE`` +
``moe/experts.py:10 Experts``. TPU-native design:

* Experts live as one stacked parameter tree with a leading expert dimension
  (``nn.vmap``), sharded over the ``expert`` mesh axis — each device holds
  ``num_experts / ep_size`` local experts, exactly the reference's
  ``num_local_experts`` layout without per-rank module lists.
* Dispatch/combine: GShard einsums (``sharded_moe.py``); the all-to-all the
  reference issues explicitly (``_AllToAll``, moe/sharded_moe.py:90) is
  emitted by XLA from the sharding constraint that moves the dispatched
  tensor's expert dim onto the ``expert`` axis.
* Expert-group creation (``deepspeed/utils/groups.py:108,202``) is replaced
  by the mesh: ``ep_size`` is the mesh's expert-axis extent.
"""

from __future__ import annotations

from typing import Any, Optional, Type

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..parallel import mesh as mesh_mod
from .sharded_moe import (_capacity, combine_indexed, combine_output,
                          dispatch_indexed, expert_counts, gate_and_dispatch,
                          gate_decisions)


def moe_sharding_rules(prefix: str = ""):
    """TP-style rules placing stacked expert params on the expert axis."""
    E = mesh_mod.EXPERT_AXIS
    return [
        (rf"{prefix}experts/.*kernel", (E, None, None)),
        (rf"{prefix}experts/.*bias", (E, None)),
    ]


class ExpertMLP(nn.Module):
    """Default expert: 2-layer MLP (the reference's typical expert module)."""

    hidden_size: int
    intermediate_size: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        h = nn.Dense(self.intermediate_size, dtype=self.dtype, name="fc1")(x)
        h = jax.nn.gelu(h, approximate=True)
        return nn.Dense(self.hidden_size, dtype=self.dtype, name="fc2")(h)


class MoE(nn.Module):
    """Mixture-of-experts wrapper (≅ reference moe/layer.py:16).

    ``__call__(x)`` with x (..., hidden) returns ``(out, aux_loss, exp_counts)``
    like the reference's MoE.forward.
    """

    hidden_size: int
    num_experts: int = 1
    ep_size: int = 1  # informational; actual EP degree = mesh expert axis
    k: int = 1
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 4
    noisy_gate_policy: Optional[str] = None
    drop_tokens: bool = True
    use_rts: bool = True
    # Residual MoE (PR-MoE, arXiv:2201.05596; reference layer.py:77,116):
    # a dense expert-shaped MLP runs alongside the MoE and the two outputs
    # are blended by a learned per-token softmax coefficient
    use_residual: bool = False
    # "auto" (default; the per-k choice is the MoE lead of PERF.md §8):
    # "einsum" for k=1 (the dense one-hot dispatch is a bf16 MXU matmul
    # and beats the scatter at top-1 capacity) UNLESS the dense form's
    # (S,E,C) tensor would exceed ``auto_index_threshold`` elements
    # (it grows ~quadratically with S); "index" (scatter/gather, O(S·M))
    # for k>=2 — 1.19-1.21x the einsum form at the NLG recipe shape —
    # and for any k at long S. Routing is identical in all modes (both
    # forms consume the same GateDecisions).
    dispatch_mode: str = "auto"
    # max elements of the dense (S,E,C) form before "auto" forces the
    # index form. The einsum path materializes BOTH the fp32 combine and
    # the token-dtype dispatch tensor (live through backward), so budget
    # ~2x per element: 2^29 elements ≈ 2 GB combine + ~1-2 GB dispatch
    # per MoE layer
    auto_index_threshold: int = 2 ** 29
    expert_cls: Type[nn.Module] = ExpertMLP
    expert_kwargs: Optional[dict] = None
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, *, used_token=None, deterministic: bool = True):
        """x (..., hidden); ``used_token`` (keyword-only, broadcastable to
        x's token dims) masks padding tokens out of top-1 routing and the
        aux loss (reference layer.py:100 forward arg → sharded_moe.py:202)."""
        orig_shape = x.shape
        M = orig_shape[-1]
        assert M == self.hidden_size
        tokens = x.reshape(-1, M)
        if used_token is not None:
            used_token = jnp.broadcast_to(
                used_token, orig_shape[:-1]).reshape(-1)

        gate_logits = nn.Dense(self.num_experts, use_bias=False, name="gate",
                               dtype=jnp.float32)(tokens.astype(jnp.float32))

        if self.dispatch_mode not in ("auto", "index", "einsum"):
            raise ValueError(f"dispatch_mode must be 'auto', 'index' or "
                             f"'einsum', got {self.dispatch_mode!r}")
        rng = self.make_rng("gating") if self.has_rng("gating") else None
        cap_factor = self.capacity_factor if not deterministic \
            else self.eval_capacity_factor
        dispatch_mode = self.dispatch_mode
        if dispatch_mode == "auto":
            S = tokens.shape[0]
            cap = S if not self.drop_tokens else _capacity(
                S, self.num_experts, self.k * cap_factor, self.min_capacity)
            dense_elems = S * self.num_experts * cap
            dispatch_mode = "einsum" if (
                self.k == 1 and dense_elems <= self.auto_index_threshold) \
                else "index"
        if dispatch_mode == "index":
            dec = gate_decisions(
                gate_logits, k=self.k, capacity_factor=cap_factor,
                min_capacity=self.min_capacity,
                noisy_gate_policy=(self.noisy_gate_policy
                                   if not deterministic else None),
                drop_tokens=self.drop_tokens, use_rts=self.use_rts, rng=rng,
                used_token=used_token)
            aux_loss = dec.aux_loss
            dispatched = dispatch_indexed(tokens, dec, self.num_experts)
            combine = None
        else:
            dec = None
            aux_loss, dispatched, combine = gate_and_dispatch(
                tokens, gate_logits, k=self.k, capacity_factor=cap_factor,
                min_capacity=self.min_capacity,
                noisy_gate_policy=(self.noisy_gate_policy
                                   if not deterministic else None),
                drop_tokens=self.drop_tokens, use_rts=self.use_rts, rng=rng,
                used_token=used_token)

        # Move expert dim onto the expert axis: XLA emits the all-to-all here
        # (≅ reference _AllToAll before expert compute, sharded_moe.py:90)
        mesh = mesh_mod.get_mesh()
        dispatched = jax.lax.with_sharding_constraint(
            dispatched, NamedSharding(mesh, P(mesh_mod.EXPERT_AXIS, None, None)))

        kwargs = dict(self.expert_kwargs or {})
        kwargs.setdefault("hidden_size", self.hidden_size)
        kwargs.setdefault("intermediate_size", 4 * self.hidden_size)
        kwargs.setdefault("dtype", self.dtype)
        experts = nn.vmap(
            self.expert_cls,
            variable_axes={"params": 0},
            split_rngs={"params": True, "dropout": True},
            in_axes=0, out_axes=0,
            metadata_params={nn.PARTITION_NAME: "expert"},
        )(**kwargs, name="experts")
        expert_out = experts(dispatched)  # (E, C, M)

        # all-to-all back before combine
        expert_out = jax.lax.with_sharding_constraint(
            expert_out, NamedSharding(mesh, P(mesh_mod.EXPERT_AXIS, None, None)))
        if dispatch_mode == "index":
            out = combine_indexed(expert_out, dec)
            exp_counts = expert_counts(dec, self.num_experts)
        else:
            out = combine_output(expert_out, combine)
            exp_counts = jnp.sum(combine > 0, axis=(0, 2))  # tokens per expert

        if self.use_residual:
            # PR-MoE: out = coef0 * moe_out + coef1 * dense_mlp(x), with
            # coef = softmax(Linear(hidden, 2)(x)) per token
            mlp_out = self.expert_cls(**kwargs, name="residual_mlp")(tokens)
            coef = nn.Dense(2, dtype=jnp.float32, name="coefficient")(
                tokens.astype(jnp.float32))
            coef = jax.nn.softmax(coef, axis=-1).astype(out.dtype)
            out = out * coef[:, 0:1] + mlp_out.astype(out.dtype) * coef[:, 1:2]

        return out.reshape(orig_shape).astype(x.dtype), aux_loss, exp_counts
