"""Model adapters for the streamed param-offload training path.

The reference's ``remote_device="cpu"|"nvme"`` works for any module built
under ``zero.Init`` (partition_parameters.py:616,288 — per-parameter
hooks). The TPU streaming runner needs slightly more structure — a
scan-stacked block to stream plus a resident embed/head — so model support
is an adapter: anything that can express

* ``split(params) -> (resident, stacked)`` / ``merge`` — which subtree
  streams layer-by-layer,
* ``embed_apply`` / ``head_loss`` — the resident computation around the
  streamed trunk (must match the module's own ``__call__`` numerics
  exactly; trajectory parity with the resident engine is asserted in
  tests),
* ``block_apply(layer_params, x, rng)`` — one streamed layer, with a
  per-layer dropout rng (lifts the round-4 dropout=0 restriction: keys are
  folded from (step, micro, layer), deterministic given the seed — note
  the rng STREAM differs from the resident engine's ``nn.scan`` rng
  split, so dropout>0 trains identically-distributed but not
  bit-identically to the resident path).

Supported families: ``TransformerLM`` (all presets) and
``GPT2LMHeadModel``. ``make_adapter`` is the registry.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp


class StreamedModelAdapter:
    """Protocol; see module docstring."""

    n_layer: int
    dropout: float
    # heterogeneous = True: layers differ structurally (Python-loop blocks
    # with per-layer param subtrees); split/merge deal in LISTS of layer
    # trees and the runner streams via HeteroLayerStore + per-layer-key
    # optimizer updates instead of stacked rows
    heterogeneous: bool = False
    # has_aux = True: block_apply returns (x, aux_loss); the runner
    # accumulates aux across layers and adds aux_weight * total to the
    # loss (the engine's tuple-return convention, engine.py:340)
    has_aux: bool = False
    aux_weight: float = 0.0

    def split(self, params: Dict) -> Tuple[Dict, Any]:
        """Full host param dict -> (resident subtree, stacked block tree
        with leading layer axis)."""
        resident = {k: v for k, v in params.items() if k != "blocks"}
        return resident, params["blocks"]["block"]

    def merge(self, resident: Dict, stacked) -> Dict:
        out = dict(resident)
        out["blocks"] = {"block": stacked}
        return out

    def embed_apply(self, resident, batch):
        raise NotImplementedError

    def block_apply(self, layer_params, x, rng, deterministic=None):
        """One streamed layer. ``deterministic=None`` means train mode
        (dropout active iff the config enables it); True forces eval."""
        raise NotImplementedError

    def head_loss(self, resident, xL, batch):
        raise NotImplementedError


class TransformerLMAdapter(StreamedModelAdapter):
    """``models/transformer_lm.TransformerLM`` — the round-4 behavior,
    plus dropout rng threading."""

    def __init__(self, module, compute_dtype):
        from ...models.transformer_lm import TransformerBlock

        self.cfg = module.config
        self.n_layer = self.cfg.n_layer
        self.dropout = self.cfg.dropout
        self.compute_dtype = compute_dtype
        self._block = TransformerBlock(self.cfg)

    def embed_apply(self, resident, batch):
        from ...models.lm_parts import _norm

        cfg = self.cfg
        ids = batch["input_ids"]
        B, T = ids.shape
        x = jnp.take(resident["embed_tokens"]["embedding"], ids, axis=0)
        if cfg.pos_emb == "learned":
            pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
            x = x + jnp.take(resident["embed_pos"]["embedding"], pos, axis=0)
        if cfg.embed_layernorm:
            x = _norm(cfg, "embed_ln").apply(
                {"params": resident["embed_ln"]}, x)
        return x.astype(self.compute_dtype)

    def block_apply(self, layer_params, x, rng, deterministic=None):
        # train mode = deterministic=False, matching the resident engine's
        # train step (rngs only when dropout actually draws)
        if deterministic is None:
            deterministic = False
        rngs = {"dropout": rng} if (not deterministic and
                                    self.dropout > 0) else None
        # TransformerBlock signature: (x, decode, deterministic, kv_cache)
        # -> (x, new_kv_cache); the training path carries no cache
        return self._block.apply({"params": layer_params}, x, False,
                                 deterministic, rngs=rngs)[0]

    def head_loss(self, resident, xL, batch):
        from ...models.lm_parts import _norm

        cfg = self.cfg
        # EXACTLY TransformerLM.__call__'s tail (shift + masked xent).
        # Tied head: Embed.attend promotes both operands to cfg.dtype, so
        # the matmul runs in compute dtype — matching it keeps bf16
        # trajectories identical to the resident engine.
        x = _norm(cfg, "ln_f").apply({"params": resident["ln_f"]}, xL)
        if cfg.tie_word_embeddings:
            emb = resident["embed_tokens"]["embedding"]
            logits = x.astype(cfg.dtype) @ emb.T.astype(cfg.dtype)
        else:
            logits = x.astype(jnp.float32) @ \
                resident["lm_head"]["kernel"].astype(jnp.float32)
        return _shifted_xent(logits, batch)


class GPT2Adapter(StreamedModelAdapter):
    """``models/gpt2.GPT2LMHeadModel``, the second model family the
    streamed engine takes. Resident: wte, wpe, ln_f; streamed: the scanned
    blocks. The embed/head reuse the model's own flax submodules so the
    numerics (including Embed.attend's dtype promotion) match
    ``GPT2LMHeadModel.logits`` exactly."""

    def __init__(self, module, compute_dtype):
        import flax.linen as nn

        from ...models.gpt2 import Block

        self.cfg = module.config
        self.n_layer = self.cfg.n_layer
        self.dropout = self.cfg.dropout
        self.compute_dtype = compute_dtype
        cfg = self.cfg
        self._block = Block(cfg)
        self._wte = nn.Embed(cfg.vocab_size, cfg.n_embd, dtype=cfg.dtype)
        self._wpe = nn.Embed(cfg.n_positions, cfg.n_embd, dtype=cfg.dtype)
        self._ln_f = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon,
                                  dtype=cfg.dtype)

    def embed_apply(self, resident, batch):
        ids = batch["input_ids"]
        B, T = ids.shape
        pos = jnp.arange(T)[None, :]
        x = self._wte.apply({"params": resident["wte"]}, ids) + \
            self._wpe.apply({"params": resident["wpe"]}, pos)
        return x.astype(self.compute_dtype)

    def block_apply(self, layer_params, x, rng, deterministic=None):
        if deterministic is None:
            deterministic = False  # train mode, like the resident engine
        rngs = {"dropout": rng} if (not deterministic and
                                    self.dropout > 0) else None
        return self._block.apply({"params": layer_params}, x, deterministic,
                                 rngs=rngs)

    def head_loss(self, resident, xL, batch):
        x = self._ln_f.apply({"params": resident["ln_f"]}, xL)
        logits = self._wte.apply({"params": resident["wte"]},
                                 x.astype(jnp.float32), method="attend")
        return _shifted_xent(logits, batch)


class GPTMoEAdapter(StreamedModelAdapter):
    """``models/gpt_moe.GPTMoEModel`` — heterogeneous trunk (alternating
    dense / MoE blocks as per-layer param subtrees ``block_i``). Blocks
    return ``(x, aux)``; the runner threads the aux sum into the loss with
    ``cfg.aux_loss_weight`` and the per-layer vjp receives the matching
    aux cotangent, so expert-router gradients flow exactly as in the
    resident engine's compiled step."""

    heterogeneous = True
    has_aux = True

    def __init__(self, module, compute_dtype):
        import flax.linen as nn

        from ...models.gpt_moe import _Block

        self.cfg = module.config
        self.module = module
        self.n_layer = self.cfg.n_layer
        self.dropout = self.cfg.dropout
        self.aux_weight = float(self.cfg.aux_loss_weight)
        self.compute_dtype = compute_dtype
        cfg = self.cfg
        self._blocks = []
        moe_index = 0
        for i in range(cfg.n_layer):
            use_moe = cfg.moe_every > 0 and \
                (i % cfg.moe_every == cfg.moe_every - 1)
            n_exp = module._experts_for_block(moe_index) if use_moe else 0
            if use_moe:
                moe_index += 1
            self._blocks.append(_Block(cfg, use_moe, n_exp))
        self._wte = nn.Embed(cfg.vocab_size, cfg.n_embd, dtype=cfg.dtype)
        self._wpe = nn.Embed(cfg.n_positions, cfg.n_embd, dtype=cfg.dtype)
        self._ln_f = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon,
                                  dtype=cfg.dtype)

    def split(self, params: Dict) -> Tuple[Dict, Any]:
        resident = {k: v for k, v in params.items()
                    if not k.startswith("block_")}
        layers = [params[f"block_{i}"] for i in range(self.n_layer)]
        return resident, layers

    def merge(self, resident: Dict, layers) -> Dict:
        out = dict(resident)
        for i, tree in enumerate(layers):
            out[f"block_{i}"] = tree
        return out

    def layer_key(self, i: int) -> str:
        return f"block_{i}"

    def embed_apply(self, resident, batch):
        ids = batch["input_ids"]
        B, T = ids.shape
        pos = jnp.arange(T)[None, :]
        x = self._wte.apply({"params": resident["wte"]}, ids) + \
            self._wpe.apply({"params": resident["wpe"]}, pos)
        return x.astype(self.compute_dtype)

    def block_apply_layer(self, i, layer_params, x, rng,
                          deterministic=None):
        if deterministic is None:
            deterministic = False  # train mode: MoE capacity/gating differ
        rngs = None
        if not deterministic:
            # gating rng drives RTS / noisy-gate draws (seed-deterministic;
            # the STREAM differs from the resident engine's, so use_rts
            # trains identically-distributed but not bit-identically —
            # parity tests pin use_rts=False)
            rngs = {"gating": jax.random.fold_in(
                jnp.asarray(rng, jnp.uint32), 1)}
            if self.dropout > 0:
                rngs["dropout"] = jnp.asarray(rng, jnp.uint32)
        return self._blocks[i].apply({"params": layer_params}, x,
                                     deterministic, rngs=rngs)

    def head_loss(self, resident, xL, batch):
        # EXACTLY GPTMoEModel.__call__'s tail: ln_f + tied attend +
        # UNMASKED mean shifted NLL (gpt_moe.py:132-143); aux is added by
        # the runner
        x = self._ln_f.apply({"params": resident["ln_f"]}, xL)
        logits = self._wte.apply({"params": resident["wte"]},
                                 x.astype(jnp.float32), method="attend")
        ids = batch["input_ids"]
        labels = batch.get("labels", ids) if hasattr(batch, "get") else ids
        targets = labels[:, 1:]
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        token_ll = jnp.take_along_axis(logp, targets[..., None],
                                       axis=-1)[..., 0]
        return -jnp.mean(token_ll)


def _shifted_xent(logits, batch):
    """The shared GPT-family tail: causal shift + masked mean xent
    (mirrors GPT2LMHeadModel.__call__ / TransformerLM.__call__)."""
    input_ids = batch["input_ids"]
    labels = batch.get("labels", input_ids) if hasattr(batch, "get") \
        else input_ids
    logits = logits[:, :-1]
    targets = labels[:, 1:]
    mask = (targets >= 0).astype(jnp.float32)
    targets = jnp.maximum(targets, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def make_adapter(module, compute_dtype) -> StreamedModelAdapter:
    """Adapter registry for offload_param streaming; raises with the
    supported-family list for anything else."""
    from ...models.gpt2 import GPT2LMHeadModel
    from ...models.gpt_moe import GPTMoEModel
    from ...models.transformer_lm import TransformerLM

    if isinstance(module, TransformerLM):
        return TransformerLMAdapter(module, compute_dtype)
    if isinstance(module, GPT2LMHeadModel):
        return GPT2Adapter(module, compute_dtype)
    if isinstance(module, GPTMoEModel):
        return GPTMoEAdapter(module, compute_dtype)
    raise ValueError(
        "offload_param streaming supports TransformerLM and "
        f"GPT2LMHeadModel and GPTMoEModel modules (got "
        f"{type(module).__name__}); the module must expose a streamable "
        "per-layer trunk (scan-stacked blocks or per-layer block_i "
        "subtrees) plus a resident embed/head")
