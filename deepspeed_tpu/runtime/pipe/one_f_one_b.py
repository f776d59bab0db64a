"""1F1B pipelined gradient executor.

Executes the ``TrainSchedule`` instruction stream (schedule.py:147; reference
``deepspeed/runtime/pipe/schedule.py:189-257`` and ``engine.py:1293
_exec_schedule``) as ONE compiled SPMD loop:

* Each *tick* of a ``lax.scan`` performs, on every stage simultaneously, one
  ForwardPass (of micro ``t - stage``) and one BackwardPass (of micro
  ``t - 2(S-1) + stage``) — the steady-state 1F1B interleave. Warmup
  (forwards only valid) and drain (backwards only valid) fall out of the
  micro-id validity masks; the reference expresses the same thing as
  per-stage instruction lists.
* SendActivation/RecvActivation = one ``jnp.roll`` (+1) of the stage-sharded
  activation buffer per tick; SendGrad/RecvGrad = one roll (−1) of the
  cotangent buffer. XLA lowers both to ``collective-permute`` between
  neighboring stages over the ``pipe`` mesh axis — the reference's
  ``p2p.send/recv`` without the tensor-meta handshake (shapes are static).
* BackwardPass is a manual ``jax.vjp`` of the stage's block chain at the
  SAVED stage input (the activation-checkpointed recompute the reference
  gets from pipelined activation checkpointing). Saved inputs live in a ring
  buffer of capacity 2S−1 (+1 scratch slot for masked writes) — the 1F1B
  memory signature: outstanding activations bounded by the stage depth, NOT
  by the number of micro-batches (GPipe autodiff transpose stores one carry
  per tick ⇒ linear in M).
* LoadMicroBatch/embedding (first stage) and head+loss (last stage) are
  differentiated per tick with vjps restricted to their param subtrees; the
  loss cotangent is seeded with the fp16 loss scale.
* ReduceTiedGrads: tied params (``tied_*``) are visible to both the embed
  and head subtrees; both vjp contributions accumulate into the same slot
  and GSPMD inserts the cross-stage reduction (reference engine.py:225).
* ReduceGrads/OptimizerStep happen in the engine after this function
  returns, exactly like the reference's final-step instructions.

Total ticks = M + 2(S−1): M steady-state ticks are fully utilized (one F
and one B each, both valid); the 2(S−1) ramp ticks carry masked work — the
pipeline bubble: (2S−2)/(M+2S−2) of the ticks against GPipe's (S−1)/(M+S−1),
for ≤ 2S−1 live activations a stage against M+S−1 (PERF.md §8; the GPipe
executor is kept as ``pipeline.schedule = "gpipe"``).
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp

from ...parallel import mesh as mesh_mod
from ...parallel.mesh import PIPE_AXIS


def forward_micro_ids(t, stage_ids, num_stages):
    """ForwardPass micro id per stage at tick ``t`` (invalid outside [0, M))."""
    del num_stages
    return t - stage_ids


def backward_micro_ids(t, stage_ids, num_stages):
    """BackwardPass micro id per stage at tick ``t``."""
    return t - 2 * (num_stages - 1) + stage_ids


def total_ticks(num_micro_batches, num_stages):
    return num_micro_batches + 2 * (num_stages - 1)


def _constrain_pipe(x, mb_dim: int = 1):
    """Pin dim 0 of a (S, ...) buffer to the pipe axis and the micro-batch
    dim to the batch axes, when a mesh is active."""
    if not mesh_mod.has_mesh():
        return x
    from jax.sharding import NamedSharding, PartitionSpec

    entries: list = [PIPE_AXIS] + [None] * (mb_dim - 1)
    if x.ndim > mb_dim:
        entries.append(tuple(mesh_mod.batch_axes()))
    sh = NamedSharding(mesh_mod.get_mesh(), PartitionSpec(*entries))
    return jax.lax.with_sharding_constraint(x, sh)


def make_1f1b_grads(module) -> Callable:
    """Build ``grads_fn(params, stacked_batch, rng, scale, deterministic)``
    returning ``(loss_sum, grads, n_valid_micros)`` for a PipelineModule.

    ``grads`` is the SUM over micro-batches of loss-scale-seeded gradients
    (the engine divides by ``scale * denom`` in finalize).
    """
    S = module.num_stages
    pre_specs, block_specs, post_specs = module._split_specs()
    spec0 = block_specs[0]
    n_local = len(block_specs) // S

    from .module import block_call_mode

    call_mode = block_call_mode(spec0.typename)
    block = spec0.build()

    def chain(stage_params, x, keys, deterministic):
        """Forward through one stage's n_local blocks (scan over leaf dim 0)."""

        def body(h, xs):
            layer_params, key = xs
            rngs = {"dropout": key, "gating": jax.random.fold_in(key, 1)}
            if call_mode == "decode_det":
                # inference-capable blocks (x, decode, deterministic, ...):
                # pin decode=False for training so the deterministic flag
                # can't land in the decode slot positionally
                h = block.apply({"params": layer_params}, h, False,
                                deterministic, rngs=rngs)
            elif call_mode == "det":
                h = block.apply({"params": layer_params}, h, deterministic,
                                rngs=rngs)
            else:
                h = block.apply({"params": layer_params}, h, rngs=rngs)
            if isinstance(h, tuple):
                h = h[0]  # (x, new_cache) blocks: drop the dead aux entry
            return h, None

        h, _ = jax.lax.scan(body, x, (stage_params, keys))
        return h

    from .module import PipelineModule  # avoid cycle at import time

    def _subtree(params, prefixes):
        return {k: v for k, v in params.items()
                if any(k.startswith(p) for p in prefixes)}

    def grads_fn(params, stacked_batch, rng, scale, deterministic=True):
        leaves = jax.tree_util.tree_leaves(stacked_batch)
        M = leaves[0].shape[0]
        R = 2 * S  # ring capacity: max outstanding = 2(S-1)+1 < 2S; +scratch

        blocks_params = params["pipe"]["blocks"]["block"]
        pre_sub = _subtree(params, ("pre_", "tied_"))
        post_sub = _subtree(params, ("post_", "tied_"))

        def merged(sub):
            rest = {k: jax.lax.stop_gradient(v) for k, v in params.items()
                    if k not in sub}
            return {**rest, **sub}

        def embed_fn(sub, micro):
            return module.apply({"params": merged(sub)}, micro,
                                method=PipelineModule._embed)

        def head_fn(sub, y, micro):
            return module.apply({"params": merged(sub)}, y, micro,
                                method=PipelineModule._head_loss)

        def micro_at(i):
            i = jnp.clip(i, 0, M - 1)
            return jax.tree_util.tree_map(
                lambda x: jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False),
                stacked_batch)

        # probe shapes with an abstract embed (no FLOPs at trace time)
        feat = jax.eval_shape(embed_fn, pre_sub, micro_at(0))
        zero_f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda p: jnp.zeros(p.shape, jnp.float32), t)

        stage_ids = jnp.arange(S)
        x_roll0 = _constrain_pipe(jnp.zeros((S,) + feat.shape, feat.dtype))
        g_roll0 = _constrain_pipe(jnp.zeros((S,) + feat.shape, jnp.float32))
        ring0 = _constrain_pipe(jnp.zeros((S, R + 1) + feat.shape, feat.dtype),
                                mb_dim=2)

        carry0 = dict(
            x_roll=x_roll0, g_roll=g_roll0, ring=ring0,
            d_blocks=zero_f32(blocks_params),
            d_pre=zero_f32(pre_sub), d_post=zero_f32(post_sub),
            loss_sum=jnp.zeros((), jnp.float32))

        def micro_keys(micro_ids):
            """Per-stage rng keys derived from (micro id, stage) — NOT the
            tick — so the backward recompute of micro m at stage s re-runs
            the exact stochastic branch (dropout, MoE gating noise) its
            forward took, ticks apart."""
            return jax.vmap(lambda s, m: jax.random.split(
                jax.random.fold_in(jax.random.fold_in(rng, jnp.clip(
                    m, 0, M - 1)), s), n_local))(stage_ids, micro_ids)

        def tick(carry, t):
            f_id = forward_micro_ids(t, stage_ids, S)
            b_id = backward_micro_ids(t, stage_ids, S)
            valid_f = (f_id >= 0) & (f_id < M)
            valid_b = (b_id >= 0) & (b_id < M)
            keys_f = micro_keys(f_id)
            keys_b = micro_keys(b_id)

            # -- LoadMicroBatch + stage-0 embed (recomputed in bwd below) --
            x0 = embed_fn(jax.lax.stop_gradient(pre_sub), micro_at(f_id[0]))
            x_in = carry["x_roll"].at[0].set(x0.astype(carry["x_roll"].dtype))

            # -- ForwardPass on every stage --
            y = jax.vmap(chain, in_axes=(0, 0, 0, None))(
                blocks_params, x_in, keys_f, deterministic)

            # save stage inputs for the backward recompute; masked ticks
            # write to the scratch slot R so live slots are never clobbered
            slot = jnp.where(valid_f, f_id % R, R)
            ring = jax.vmap(
                lambda ring_s, sl, xs: ring_s.at[sl].set(xs))(
                    carry["ring"], slot, x_in)

            # -- last stage: head + loss (+ seed cotangent with loss scale) --
            h_micro = micro_at(f_id[S - 1])
            loss, head_pull = jax.vjp(
                head_fn, post_sub, y[S - 1].astype(feat.dtype), h_micro)
            seed = jnp.where(valid_f[S - 1], scale, 0.0).astype(jnp.float32)
            d_post_t, g_last, _ = head_pull(seed.astype(loss.dtype))
            loss_sum = carry["loss_sum"] + jnp.where(
                valid_f[S - 1], loss.astype(jnp.float32), 0.0)

            # -- BackwardPass: vjp of the chain at the SAVED input --
            g_in = carry["g_roll"].at[S - 1].set(g_last.astype(jnp.float32))
            b_slot = jnp.where(valid_b, b_id % R, R)
            x_saved = jax.vmap(
                lambda ring_s, sl: jax.lax.dynamic_index_in_dim(
                    ring_s, sl, 0, keepdims=False))(ring, b_slot)

            def stage_bwd(sp, xs, g, ks):
                _, pull = jax.vjp(
                    lambda sp_, x_: chain(sp_, x_, ks, deterministic), sp, xs)
                dsp, dx = pull(g.astype(xs.dtype))
                return dsp, dx

            dsp, dx = jax.vmap(stage_bwd)(blocks_params, x_saved,
                                          g_in, keys_b)
            mask = valid_b.astype(jnp.float32)
            d_blocks = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32)
                * mask.reshape((S,) + (1,) * (g.ndim - 1)),
                carry["d_blocks"], dsp)

            # -- stage 0: backward through the embed for this micro --
            g0 = dx[0].astype(jnp.float32) * mask[0]
            _, embed_pull = jax.vjp(
                lambda sub: embed_fn(sub, micro_at(b_id[0])), pre_sub)
            (d_pre_t,) = embed_pull(g0.astype(feat.dtype))
            d_pre = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), carry["d_pre"], d_pre_t)
            d_post = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32),
                carry["d_post"], d_post_t)

            # -- SendActivation (+1) / SendGrad (−1) collective permutes --
            x_roll = _constrain_pipe(jnp.roll(y, 1, axis=0))
            g_roll = _constrain_pipe(jnp.roll(
                dx.astype(jnp.float32)
                * mask.reshape((S,) + (1,) * (dx.ndim - 1)), -1, axis=0))

            new_carry = dict(carry, x_roll=x_roll, g_roll=g_roll,
                             ring=_constrain_pipe(ring, mb_dim=2),
                             d_blocks=d_blocks,
                             d_pre=d_pre, d_post=d_post, loss_sum=loss_sum)
            return new_carry, None

        final, _ = jax.lax.scan(tick, carry0, jnp.arange(total_ticks(M, S)))

        # assemble the full gradient tree: blocks + pre/post/tied subtrees
        # (tied keys get contributions from BOTH embed and head vjps)
        grads = {}
        for k in params:
            if k == "pipe":
                grads[k] = {"blocks": {"block": final["d_blocks"]}}
            else:
                g_p = final["d_pre"].get(k)
                g_q = final["d_post"].get(k)
                if g_p is not None and g_q is not None:
                    grads[k] = jax.tree_util.tree_map(
                        lambda a, b: a + b, g_p, g_q)
                else:
                    grads[k] = g_p if g_p is not None else g_q
        return final["loss_sum"] / M, grads, float(M)

    return grads_fn
