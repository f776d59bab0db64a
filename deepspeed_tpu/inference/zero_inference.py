"""ZeRO-Inference — weight streaming for models larger than HBM.

Capability parity with reference ZeRO-Inference
(docs/_posts/2022-09-10-zero-inference.md; engine hooks at
inference/engine.py:336,449): model weights live in HOST memory (or a
memory-mapped checkpoint) and stream to the device one transformer layer
at a time, so the device-resident footprint is O(2 layers), not O(model).
Throughput-oriented by design — with a large token batch each layer's
matmuls amortize its weight transfer (the reference's "7 TFLOPs per
GPT3-layer per token-batch" argument).

TPU-native mechanics: the per-layer apply is ONE jitted function reused
for every layer (identical shapes → single compile), and JAX's async
dispatch gives upload/compute overlap for free — ``device_put`` of layer
``i+1`` is enqueued before the compute of layer ``i`` blocks (double
buffering without streams, the role pinned-buffer prefetch plays in the
reference's AIO pipeline).

Works with :class:`deepspeed_tpu.models.transformer_lm.TransformerLM`
params (scan-stacked blocks with a leading layer axis).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.kv_cache_spec import make_kv_cache_spec
from ..models.lm_config import TransformerConfig
from ..models.transformer_lm import TransformerBlock
from ..utils.logging import log_dist
from ..utils.streaming import LayerWireFormat


def _slice_layer(stacked: Any, i: int) -> Any:
    """Layer ``i`` of scan-stacked params (leading layer axis per leaf)."""
    return jax.tree_util.tree_map(lambda a: np.asarray(a[i]), stacked)


class ZeroInferenceEngine:
    """Full-context scoring with layer-streamed weights.

    ``params_host``: the TransformerLM param pytree, host-resident
    (numpy arrays or np.memmap views into a checkpoint file).
    """

    def __init__(self, config: TransformerConfig, params_host: Dict,
                 dtype=jnp.bfloat16, prefetch: int = 1, pack: bool = True,
                 int8: bool = False):
        why = make_kv_cache_spec(config).refusal("zero_inference")
        if why:
            raise ValueError(why)
        if int8 and not config.int8_weights:
            # int8 ZeRO-Inference: quantize the Dense kernels host-side
            # (QuantDense layout) so each streamed layer is ~half the
            # bytes AND the dequant runs inside the Pallas GEMM on chip.
            # The head lives in the always-resident tier and stays full
            # precision unless ``config.int8_head`` opts it in (same tier
            # shape as the resident engine); head_fn dequantizes it.
            import dataclasses

            from ..ops.quantization.convert import DENSE_KEYS, quantize_lm_params

            head_keys = set() if config.int8_head else {"lm_head"}
            params_host, n_dense = quantize_lm_params(
                params_host, dense_keys=DENSE_KEYS - head_keys)
            config = dataclasses.replace(config, int8_weights=True)
            log_dist(f"ZeroInference int8 tier: {n_dense} Dense kernels -> "
                     "QuantDense (streamed int8-at-rest)", ranks=[0])
        self.config = config
        self.dtype = dtype
        self.prefetch = max(0, prefetch)
        self._host = params_host
        self._stacked = params_host["blocks"]["block"]
        self.n_layer = config.n_layer
        # pack: ship each layer as ONE contiguous buffer instead of one
        # transfer per leaf — per-transfer latency (host↔device link
        # round-trips) would otherwise dominate the stream for trees with
        # many small leaves; leaves are re-sliced on device by a jitted
        # unpack (an HBM-local copy)
        self.pack = pack
        # the packed buffer is raw BYTES, so any leaf-dtype mix ships as
        # one transfer (bf16 checkpoints, int8 QuantDense kernels with
        # f32 scales, ...). Float leaves are converted to the engine
        # compute dtype at stage time — except "scale" leaves, which are
        # per-channel quantization/norm scales that stay full precision
        # (utils/streaming.py holds the shared wire format).
        self._wire = LayerWireFormat(
            _slice_layer(self._stacked, 0), dtype,
            keep_dtype=lambda path, leaf:
            getattr(path[-1], "key", None) == "scale")
        self._layer_treedef = self._wire.treedef
        self._leaf_shapes = self._wire.shapes
        self._leaf_wire_dtypes = self._wire.wire_dtypes
        self._leaf_nbytes = self._wire.nbytes

        # small always-resident pieces: embeddings, final norm, head
        def put_small(name):
            if name not in params_host:
                return None
            return jax.device_put(jax.tree_util.tree_map(
                lambda a: jnp.asarray(a, dtype) if jnp.issubdtype(
                    np.asarray(a).dtype, jnp.floating) else jnp.asarray(a),
                params_host[name]))

        self._small = {name: put_small(name)
                       for name in ("embed_tokens", "embed_pos", "embed_ln",
                                    "ln_f", "lm_head")
                       if name in params_host}

        cfg = config
        block = TransformerBlock(cfg)

        def block_fn(layer_params, x):
            if self.pack:
                layer_params = self._unpack(layer_params)
            return block.apply({"params": layer_params}, x, False, True)[0]

        # NOTE: no input donation here (neither the layer buffer nor the
        # activation). Buffers are freed by refcount (`buffers.pop` +
        # `del`); in isolated A/B tests under the rounds 1-5 runtime
        # (not re-measured on today's), put->consume loops with a
        # donated consumed input degraded subsequent host->device
        # transfers ~100x after ~15 iterations, while the identical loop
        # without donation held ~1.5 GB/s.
        self._jit_block = jax.jit(block_fn)

        from ..models.kv_cache_spec import make_layer_kv_cache

        def cached_block_init_fn(layer_params, x):
            # first (prefill) pass: build this layer's zeroed cache and
            # thread it explicitly — the block takes/returns the cache as
            # a value (carry-DUS design; layout/dtype stay the model's
            # concern via make_layer_kv_cache). "prefill" mode (the
            # start == 0 contract this fn guarantees) attends over the
            # fresh prompt k/v — O(T) memory, never the (B, H, T, S)
            # allocated-cache score tensor that OOMs at long prompts.
            if self.pack:
                layer_params = self._unpack(layer_params)
            cache = dict(make_layer_kv_cache(cfg, x.shape[0]),
                         start=jnp.zeros((), jnp.int32))
            out, new_cache = block.apply({"params": layer_params}, x,
                                         "prefill", True, cache)
            new_cache.pop("start", None)
            return out, new_cache

        def cached_block_fn(layer_params, cache, x, start):
            if self.pack:
                layer_params = self._unpack(layer_params)
            out, new_cache = block.apply(
                {"params": layer_params}, x, True, True,
                dict(cache, start=start))
            new_cache.pop("start", None)
            return out, new_cache

        self._jit_cached_block_init = jax.jit(cached_block_init_fn)
        # the cache IS donated: it is device-resident and round-trips
        # through this same jit (in-place update, no full-cache copy per
        # layer per token). The no-donation NOTE above concerns
        # host->device-transferred buffers only.
        self._jit_cached_block = jax.jit(cached_block_fn,
                                         donate_argnums=(1,))

        from ..models.lm_parts import _norm

        def embed_fn(emb, pos_emb, emb_ln, ids, start):
            B, T = ids.shape
            table = emb["embedding"]
            x = jnp.take(table, ids, axis=0)
            if pos_emb is not None:
                pos = jnp.broadcast_to(start + jnp.arange(T)[None], (B, T))
                x = x + jnp.take(pos_emb["embedding"], pos, axis=0)
            if emb_ln is not None:
                # bloom-family embedding layernorm (transformer_lm.py:332)
                x = _norm(cfg, "embed_ln").apply({"params": emb_ln}, x)
            return x

        self._jit_embed = jax.jit(embed_fn)

        def head_fn(emb, ln_f_params, lm_head, x):
            ln = _norm(cfg, "ln_f")
            x = ln.apply({"params": ln_f_params}, x)
            if lm_head is not None:
                kern = lm_head["kernel"].astype(jnp.float32)
                if "scale" in lm_head:
                    # int8_head tier: QuantDense layout (padded int8 kernel
                    # + per-column scale); dequant on the resident copy and
                    # slice off the lane padding
                    kern = (kern * lm_head["scale"])[:, :cfg.vocab_size]
                return x.astype(jnp.float32) @ kern
            return x.astype(jnp.float32) @ \
                emb["embedding"].T.astype(jnp.float32)

        self._jit_head = jax.jit(head_fn)
        total = sum(np.asarray(l).nbytes for l in
                    jax.tree_util.tree_leaves(params_host))
        per_layer = sum(np.asarray(l).nbytes for l in
                        jax.tree_util.tree_leaves(self._stacked)) \
            // max(self.n_layer, 1)
        log_dist(f"ZeroInference: {total / 1e9:.2f} GB weights host-resident,"
                 f" streaming {per_layer / 1e6:.1f} MB/layer "
                 f"(prefetch={self.prefetch})", ranks=[0])

    def _put_layer(self, i: int):
        layer = _slice_layer(self._stacked, i)
        if not self.pack:
            # same wire-dtype rule as the packed path (floats -> compute
            # dtype, "scale" leaves and non-floats keep storage dtype)
            leaves = jax.tree_util.tree_leaves(layer)
            conv = [np.asarray(l, wdt) for l, wdt in
                    zip(leaves, self._leaf_wire_dtypes)]
            return jax.device_put(jax.tree_util.tree_unflatten(
                self._layer_treedef, conv))
        leaves = jax.tree_util.tree_leaves(layer)
        # rotating staging buffers, NOT a fresh array per layer: (a) the
        # runtime retains a host reference per staged transfer, so fresh
        # buffers grow RSS by the whole model per pass (observed OOM at
        # 48 GB streamed); (b) re-put of the same host buffer rides the
        # pinned-transfer fast path (~1.6 GB/s vs ~0.6 GB/s first-put
        # under the rounds 1-5 runtime; not re-measured). prefetch+2
        # buffers guarantee no in-flight transfer shares a buffer with
        # the layer being staged.
        if not hasattr(self, "_staging"):
            n_buf = self.prefetch + 2
            total = sum(self._leaf_nbytes)
            self._staging = [np.empty(total, np.uint8) for _ in range(n_buf)]
            self._staging_dev = [None] * n_buf
            self._staging_i = 0
        slot = self._staging_i
        self._staging_i = (self._staging_i + 1) % len(self._staging)
        if self._staging_dev[slot] is not None:
            # the slot's previous transfer must be on-device before its
            # host buffer is overwritten (dispatch runs ahead of execution)
            self._staging_dev[slot].block_until_ready()
        # release guard refs for transfers that already landed, so the
        # device footprint stays O(prefetch+1 layers): the consumer
        # (`forward`'s buffers dict) is the only remaining owner
        for s, dev in enumerate(self._staging_dev):
            if dev is not None and s != slot:
                try:
                    if dev.is_ready():
                        self._staging_dev[s] = None
                except AttributeError:
                    break  # runtime without is_ready: keep refs as guards
        buf = self._staging[slot]
        self._wire.pack_into(
            jax.tree_util.tree_unflatten(self._layer_treedef, leaves), buf)
        # CPU backend: device_put ZERO-COPIES host numpy, so a reused
        # staging buffer would alias a live device array — hand it a
        # private copy there (tests-only path; real accelerators copy on
        # transfer and keep the rotating-buffer RSS/pinning wins)
        uni = self._wire.uniform_dtype
        if uni is not None:
            # dtype-uniform layer (plain bf16 checkpoints): ship TYPED and
            # unpack by slice+reshape — the byte-path's (N, itemsize)
            # bitcast reshape tiles catastrophically on real TPUs
            buf = buf.view(uni)
        payload = buf.copy() if jax.default_backend() == "cpu" else buf
        dev = jax.device_put(payload)
        self._staging_dev[slot] = dev
        return dev

    def _unpack(self, flat):
        """Traced: packed buffer -> leaf tree (HBM-local)."""
        if self._wire.uniform_dtype is not None:
            return self._wire.unpack_typed(flat)
        return self._wire.unpack(flat)

    def forward(self, input_ids, layer_times: Optional[list] = None
                ) -> jnp.ndarray:
        """Full-context logits with layer streaming.

        ``layer_times``: optional list; when given, each layer's
        stage+dispatch+execute wall time is appended (the benchmark's
        per-layer instrumentation hook — synchronizes per layer, so only
        pass it when measuring)."""
        import time as _time

        ids = jnp.asarray(input_ids, jnp.int32)
        if ids.ndim == 1:
            ids = ids[None]
        x = self._jit_embed(self._small["embed_tokens"],
                            self._small.get("embed_pos"),
                            self._small.get("embed_ln"), ids,
                            jnp.zeros((), jnp.int32))
        if layer_times is not None:
            x.block_until_ready()
        # pipeline: enqueue next layers' uploads before blocking on compute
        buffers = {}
        for j in range(min(self.prefetch + 1, self.n_layer)):
            buffers[j] = self._put_layer(j)
        for i in range(self.n_layer):
            t0 = _time.perf_counter()
            layer = buffers.pop(i)
            nxt = i + self.prefetch + 1
            if nxt < self.n_layer:
                buffers[nxt] = self._put_layer(nxt)  # async upload
            x = self._jit_block(layer, x)
            # the engine's ref is dropped here; the buffer is freed once
            # the block consumes it and the staging guard's transfer ref
            # is released (see _put_layer)
            del layer
            if layer_times is not None:
                x.block_until_ready()
                layer_times.append(_time.perf_counter() - t0)
        return self._jit_head(self._small["embed_tokens"],
                              self._small["ln_f"],
                              self._small.get("lm_head"), x)

    __call__ = forward

    def score(self, input_ids) -> np.ndarray:
        """Per-sequence mean log-likelihood (throughput-style batch
        scoring, the ZeRO-Inference serving mode). The tail is one jitted
        program, not eager op-by-op dispatch over the (B, T, V) logits."""
        ids = jnp.asarray(input_ids, jnp.int32)
        if ids.ndim == 1:
            ids = ids[None]
        return self.score_logits(self.forward(ids), ids)

    def generate(self, input_ids, max_new_tokens: int = 32,
                 temperature: float = 0.0, seed: int = 0) -> np.ndarray:
        """Autoregressive generation under weight streaming — the serving
        mode of the reference's ZeRO-Inference (BLOOM-176B generation,
        docs/_posts/2022-09-10-zero-inference.md): weights stay
        host-resident and stream through the chip per step, while the KV
        caches (which DO fit — O(L·B·S·D), not O(params)) stay
        device-resident across the whole generation.

        ``temperature`` 0 = greedy. Returns (B, T_prompt + new) int32.
        """
        cfg = self.config
        ids = jnp.asarray(input_ids, jnp.int32)
        if ids.ndim == 1:
            ids = ids[None]
        B, T = ids.shape
        S = cfg.max_seq_len
        if max_new_tokens <= 0:
            return np.asarray(ids)
        if T + max_new_tokens > S:
            raise ValueError(f"prompt({T}) + max_new_tokens"
                             f"({max_new_tokens}) exceeds max_seq_len({S})")
        caches = [None] * self.n_layer

        if not hasattr(self, "_jit_sample"):
            def sample(logits, rng, temperature):
                last = logits[:, -1, :].astype(jnp.float32)
                greedy = jnp.argmax(last, axis=-1)
                sampled = jax.random.categorical(
                    rng, last / jnp.maximum(temperature, 1e-6), axis=-1)
                return jnp.where(temperature > 0, sampled, greedy) \
                    .astype(jnp.int32)

            self._jit_sample = jax.jit(sample)

        rng = jax.random.PRNGKey(seed)
        temp = jnp.asarray(temperature, jnp.float32)

        def stream_pass(tokens, start, first=False):
            x = self._jit_embed(self._small["embed_tokens"],
                                self._small.get("embed_pos"),
                                self._small.get("embed_ln"), tokens,
                                jnp.asarray(start, jnp.int32))
            buffers = {j: self._put_layer(j)
                       for j in range(min(self.prefetch + 1, self.n_layer))}
            for i in range(self.n_layer):
                layer = buffers.pop(i)
                nxt = i + self.prefetch + 1
                if nxt < self.n_layer:
                    buffers[nxt] = self._put_layer(nxt)
                if first:
                    x, caches[i] = self._jit_cached_block_init(layer, x)
                else:
                    x, caches[i] = self._jit_cached_block(
                        layer, caches[i], x, jnp.asarray(start, jnp.int32))
                del layer
            return self._jit_head(self._small["embed_tokens"],
                                  self._small["ln_f"],
                                  self._small.get("lm_head"), x)

        logits = stream_pass(ids, 0, first=True)  # prefill builds caches
        rng, sub = jax.random.split(rng)
        token = self._jit_sample(logits, sub, temp)
        out = [token]
        for step in range(max_new_tokens - 1):
            logits = stream_pass(token[:, None], T + step)
            rng, sub = jax.random.split(rng)
            token = self._jit_sample(logits, sub, temp)
            out.append(token)
        return np.concatenate([np.asarray(ids)] +
                              [np.asarray(t)[:, None] for t in out], axis=1)

    def score_logits(self, logits, input_ids) -> np.ndarray:
        """The scoring tail over already-computed logits (one jitted
        program + the readback). Split out so callers that must control
        readback ordering (to time the pass without the fetch) reuse
        the shipped tail instead of re-deriving it."""
        ids = jnp.asarray(input_ids, jnp.int32)
        if not hasattr(self, "_jit_score_tail"):
            def tail(logits, ids):
                logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
                token_ll = jnp.take_along_axis(
                    logp, ids[:, 1:][..., None], axis=-1)[..., 0]
                return jnp.mean(token_ll, axis=-1)

            self._jit_score_tail = jax.jit(tail)
        return np.asarray(self._jit_score_tail(logits, ids))
