"""Inference engine (≅ reference ``deepspeed/inference/engine.py:89
InferenceEngine``), TPU-first.

The reference's pipeline — policy/container kernel injection, TP weight
slicing (``engine.py:259,314``), CUDA-graph capture (``:532,551``), KV-cache
workspace (inference_context.h) — maps to:

* injection → :func:`module_inject.replace_module` produces sharding rules;
  TP slicing is a ``NamedSharding`` placement, XLA inserts the allreduces;
* CUDA graphs → whole-step ``jax.jit`` (always on; ``enable_cuda_graph``
  accepted and ignored);
* KV cache → the model's flax ``cache`` collection, statically shaped at
  the model's ``max_seq_len``, donated through the decode step so updates
  are in-place in HBM (``max_out_tokens`` is accepted for config
  compatibility; capacity is the model's, and generate() enforces it).

``generate()`` runs a jitted prefill then a jitted single-token decode loop
with greedy/temperature/top-k/top-p sampling.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from .. import comm as dist
from ..module_inject import replace_module
from ..parallel import mesh as mesh_mod
from ..parallel.axis_rules import physical_spec
from ..runtime.zero.policy import ShardingRules, _path_str
from ..utils.logging import log_dist
from .config import DeepSpeedInferenceConfig


def _filter_logits(last, temperature, top_k, top_p):
    """Temperature/top-k/top-p filtering on raw fp32 logits (masked-out
    entries at -1e30). Shared between the sampling path and speculative
    verification (serving/spec_decode) — acceptance probabilities must be
    computed under EXACTLY the distribution the sampler draws from.
    ``last`` is (..., V); temperature traced, top_k/top_p static."""
    V = last.shape[-1]
    scaled = last / jnp.maximum(temperature, 1e-6)
    top_k = min(top_k, V)
    if top_k > 0:
        kth = jnp.sort(scaled, axis=-1)[..., -top_k][..., None]
        scaled = jnp.where(scaled < kth, -1e30, scaled)
    if top_p < 1.0:
        sorted_ = jnp.sort(scaled, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # smallest prefix with mass >= top_p
        cutoff_idx = jnp.sum(cum < top_p, axis=-1)
        cutoff = jnp.take_along_axis(sorted_, cutoff_idx[..., None], axis=-1)
        scaled = jnp.where(scaled < cutoff, -1e30, scaled)
    return scaled


def pack_chunk_args(ids, slot, start, length, last_idx, *rows):
    """A prefill chunk's host-built arguments as ONE int32 vector, so that
    they reach the device in one transfer whatever their number:
    ``[slot, start, length, last_idx]``, the chunk's ``C`` token ids, then
    any further rows (a paged pool's table rows of the slot). The chunk
    programs take it apart again with :func:`unpack_chunk_args`."""
    return np.concatenate(
        [np.asarray([slot, start, length, last_idx], np.int32),
         np.asarray(ids, np.int32).reshape(-1)]
        + [np.asarray(row, np.int32).reshape(-1) for row in rows])


def unpack_chunk_args(packed, row_width: int = 0, rows: int = 0):
    """Traced twin of :func:`pack_chunk_args`: ``(ids (1, C), slot, start,
    length, last_idx, rows)`` with ``rows`` trailing rows of ``row_width``
    (static; what is left of the vector is the chunk)."""
    tail = rows * row_width
    width = packed.shape[0] - 4 - tail
    ids = packed[4:4 + width][None]
    more = tuple(packed[4 + width + i * row_width:
                        4 + width + (i + 1) * row_width]
                 for i in range(rows))
    return (ids, packed[0], packed[1], packed[2], packed[3]) + more


class InferenceEngine:
    """Construct via :func:`deepspeed_tpu.init_inference`."""

    def __init__(self, model: Any = None,
                 config: Union[str, Dict, DeepSpeedInferenceConfig, None] = None,
                 model_parameters: Any = None, mesh=None, **kwargs):
        dist.init_distributed()
        if isinstance(config, DeepSpeedInferenceConfig):
            self._config = config
        else:
            cfg_dict = dict(config or {})
            cfg_dict.update(kwargs)
            # reference accepts mp_size= at top level
            if "mp_size" in cfg_dict:
                cfg_dict.setdefault("tensor_parallel", {})
                if isinstance(cfg_dict["tensor_parallel"], dict):
                    cfg_dict["tensor_parallel"].setdefault(
                        "tp_size", cfg_dict.pop("mp_size"))
                else:
                    cfg_dict.pop("mp_size")
            self._config = DeepSpeedInferenceConfig(**cfg_dict)

        if mesh is not None:
            mesh_mod.set_mesh(mesh)
        elif not mesh_mod.has_mesh():
            mesh_mod.initialize_mesh(model=self._config.mp_size)
        self.mesh = mesh_mod.get_mesh()
        self.mp_world_size = mesh_mod.get_model_parallel_world_size()

        self.module = model
        self.dtype = self._config.jnp_dtype()
        self._params_host = model_parameters
        self.params = None
        self._param_shardings = None
        self._rules: Optional[ShardingRules] = None
        self._jit_logits = None
        self._jit_prefill = None
        self._jit_decode = None
        self._jit_prefill_gen = None
        self._jit_decode_scan = None
        self._jit_sample = None
        self._decode_fn = None
        self._jit_verify_k = None
        self._jit_prefill_chunk = None
        self._decode_scan_execs = {}  # aval-keyed AOT decode executables
        self._cache = None
        self._cache_batch = None
        log_dist(f"InferenceEngine: tp={self.mp_world_size} dtype={self._config.dtype}",
                 ranks=[0])

    # ------------------------------------------------------------------
    def _ensure_params(self, input_ids) -> None:
        if self.params is not None:
            return
        if self._params_host is None:
            if not hasattr(self.module, "init"):
                raise ValueError("pass model_parameters= for non-flax models")
            rng = jax.random.PRNGKey(0)
            variables = self.module.init(
                {"params": rng},
                jnp.asarray(input_ids[:1]), method=self.module.logits)
            self._params_host = variables["params"]
        self._finalize_params()

    def _finalize_params(self) -> None:
        def cast(p):
            p = jnp.asarray(p)
            return p.astype(self.dtype) if jnp.issubdtype(p.dtype, jnp.floating) \
                else p

        params = jax.tree_util.tree_map(cast, self._params_host)
        self._rules = replace_module(
            self.module, params=params, tp_size=self.mp_world_size,
            injection_policy=self._config.injection_policy)

        def leaf_sharding(path, leaf):
            spec = self._rules.spec_for(_path_str(path))
            if spec is None or len(spec) != np.ndim(leaf):
                spec = PartitionSpec(*([None] * np.ndim(leaf)))
            # canonicalize through the axis-rules guard: size-1 mesh axes
            # and axes that don't divide the dim collapse to replicated,
            # and trailing Nones are stripped so equivalent placements
            # produce IDENTICAL NamedShardings (P() vs P(None,'model') on
            # a 1-wide axis would otherwise fork jit executables)
            spec = physical_spec(tuple(spec), np.shape(leaf), self.mesh)
            return NamedSharding(self.mesh, spec)

        if self._use_int8_compute():
            params = self._quantize_structured(params)
            self._quant_scales = None
        else:
            params, self._quant_scales = self._maybe_quantize(params)
        self._param_shardings = jax.tree_util.tree_map_with_path(leaf_sharding, params)
        self.params = jax.device_put(params, self._param_shardings)
        if hasattr(self.module, "logits"):
            self._build_jits()

    # ------------------------------------------------------------------
    # weight-only int8 (quant.enabled or dtype=int8): kernels live in HBM
    # as int8 + per-group fp32 scales; every compiled function dequantizes
    # IN-JIT, so XLA fuses the int8→bf16 convert + scale into the consuming
    # matmul's operand read (≅ the reference's int8 inference tier,
    # csrc/quantization + weight_quantizer.py). Where weights are read once
    # per dispatch (per-step decode, prefill) this halves weight HBM
    # traffic (~1.5x, PERF.md §8 lead); inside the whole-loop decode
    # scan XLA hoists the dequant, so the win there is at-rest/transport
    # footprint, not bandwidth.
    # ------------------------------------------------------------------
    def _quant_enabled(self) -> bool:
        return self._config.quant.enabled or \
            "int8" in str(self._config.dtype)

    # -- int8 COMPUTE tier -------------------------------------------------
    # When the served module is the unified TransformerLM family, int8
    # doesn't stop at storage: the Dense layers are swapped for QuantDense
    # (int8 kernel + f32 per-output-channel scale) and every matmul runs
    # the Pallas dequant-GEMM (ops/quantization/int8_matmul.py) — the
    # reference's fused csrc/transformer/inference dequantize path.
    # Weights stream from HBM as int8 even inside the whole-loop decode
    # scan, where the storage tier's XLA dequant would be hoisted into a
    # materialized bf16 copy. TP>1 keeps the storage tier (the Pallas call
    # is not yet partition-annotated for GSPMD).
    # ----------------------------------------------------------------------
    # single source of truth for which modules are QuantDense-convertible
    from ..ops.quantization.convert import DENSE_KEYS as _INT8_DENSE_KEYS

    def _use_int8_compute(self) -> bool:
        cfg = getattr(self.module, "config", None)
        return (self._quant_enabled()
                and self._config.quant.bits == 8
                and self.mp_world_size == 1
                # QuantDense computes in bf16; honor an explicit f32
                # request by keeping the dequant storage tier instead
                and self.dtype == jnp.bfloat16
                and hasattr(cfg, "int8_weights")
                and not getattr(cfg, "int8_weights"))

    def _quantize_structured(self, params):
        """bf16 param tree -> QuantDense tree (int8 kernel, f32 scale) for
        every Dense in the LM; rebuilds the serving module with
        ``int8_weights=True``."""
        import dataclasses

        from ..ops.quantization.convert import quantize_lm_params

        # the vocab projection stays full precision (int8_head defaults
        # off) — same tier shape as ZeroInferenceEngine, so dtype=int8
        # yields identical output-head numerics in both engines
        head_keys = {"lm_head"} if not getattr(
            self.module.config, "int8_head", False) else set()
        qparams, n_dense = quantize_lm_params(
            params, dense_keys=self._INT8_DENSE_KEYS - head_keys)
        self._serve_module = self.module.clone(config=dataclasses.replace(
            self.module.config, int8_weights=True))
        log_dist(f"inference int8 compute tier: {n_dense} Dense kernels -> "
                 "QuantDense (Pallas dequant-GEMM)", ranks=[0])
        return qparams

    def _maybe_quantize(self, params):
        if not self._quant_enabled():
            return params, None
        from ..runtime.weight_quantizer import WeightQuantization

        qcfg = self._config.quant
        wq = WeightQuantization(num_bits=qcfg.bits)
        scales: dict = {}

        def visit(path, leaf):
            if np.ndim(leaf) < 2 or not jnp.issubdtype(
                    jnp.asarray(leaf).dtype, jnp.floating):
                return leaf
            size = int(np.prod(np.shape(leaf)))
            groups = size // qcfg.group_size \
                if qcfg.group_size and size % qcfg.group_size == 0 else 1
            q, s = wq.quantize_value(np.asarray(leaf, np.float32), groups)
            scales[_path_str(path)] = jnp.asarray(s)
            return jnp.asarray(q)

        qparams = jax.tree_util.tree_map_with_path(visit, params)
        log_dist(f"inference weight quantization: int{qcfg.bits}, "
                 f"{len(scales)} kernels, group_size={qcfg.group_size}",
                 ranks=[0])
        return qparams, scales

    def _dequant(self, params):
        """Traced: restore compute-dtype kernels from int8 + scales."""
        if self._quant_scales is None:
            return params
        scales = self._quant_scales
        dtype = self.dtype

        def visit(path, leaf):
            key = _path_str(path)
            if key not in scales:
                return leaf
            s = scales[key]
            flat = leaf.astype(jnp.float32).reshape(s.shape[0], -1) * s
            return flat.reshape(leaf.shape).astype(dtype)

        return jax.tree_util.tree_map_with_path(visit, params)

    def _build_jits(self) -> None:
        module = getattr(self, "_serve_module", None) or self.module
        dequant = self._dequant

        def logits_fn(params, input_ids):
            return module.apply({"params": dequant(params)}, input_ids,
                                method=module.logits)

        def prefill_fn(params, input_ids):
            out, vars_ = module.apply(
                {"params": dequant(params)}, input_ids, method=module.prefill,
                mutable=["cache"])
            return out, vars_["cache"]

        # generation-only prefill: last-position logits (the full
        # (B, T, V) fp32 prompt logits are the largest prefill buffer
        # and bound the servable batch at long context)
        prefill_gen = getattr(module, "prefill_last", None)

        def prefill_last_fn(params, input_ids):
            out, vars_ = module.apply(
                {"params": dequant(params)}, input_ids,
                method=prefill_gen, mutable=["cache"])
            return out, vars_["cache"]

        def prefill_at_fn(params, input_ids, last_pos):
            # serving-path prefill: prompts are right-padded to a shape
            # bucket (bounds recompiles across arbitrary prompt lengths)
            # and ``last_pos`` projects the true last prompt position
            out, vars_ = module.apply(
                {"params": dequant(params)}, input_ids, last_pos,
                method=prefill_gen, mutable=["cache"])
            return out, vars_["cache"]

        chunk_gen = getattr(module, "prefill_chunk", None)
        spec = self.kv_cache_spec()
        state_leaves = tuple(getattr(spec, "state_leaves", ()))
        why = spec.refusal("tensor_parallel") \
            if spec is not None and self.mp_world_size > 1 else None
        if why:
            raise ValueError(why)

        def prefill_chunk_fn(params, cache, packed):
            """One bounded prefill chunk DIRECTLY into slot ``slot`` of
            the slot-pooled cache: dynamic-slice the target row out
            (batch axis 1 of the (L, B, ...) leaves), run the (1, C)
            chunked forward against it at offset ``start``, and
            dynamic-update-slice the row back with the slot's index set
            to ``start + length`` (the TRUE new prefill offset — the
            chunk ran at padded width C). Only the target row is ever
            written, so live neighbours can't be clobbered by the
            chunk's C-wide writes, and slot/start/length are traced —
            ONE compiled program covers every slot at every offset. They
            arrive with the chunk's ids as one vector
            (:func:`pack_chunk_args`): one transfer a chunk.

            A recurrent state (``KVCacheSpec.state_leaves``) is not
            sliced: the stacked leaves go in whole with the row's number,
            the chunk kernel rewrites that row's blocks in place and no
            other row is read or written (a slice would copy one row's
            state of every layer out and back, 0.27 GB each way at the
            served size). K/V leaves beside a state group are sliced as
            ever."""
            cs = cache["cache_store"]
            ids, slot, start, length, last_idx = unpack_chunk_args(packed)
            row = {k: jax.lax.dynamic_slice_in_dim(v, slot, 1, 1)
                   for k, v in cs.items()
                   if k != "index" and k not in state_leaves}
            whole = {k: cs[k] for k in state_leaves}
            rows = (slot[None],) if state_leaves else ()
            out, vars_ = module.apply(
                {"params": dequant(params),
                 "cache": {"cache_store": dict(row, **whole,
                                               index=start[None])}},
                ids, start[None], last_idx, *rows, method=chunk_gen,
                mutable=["cache"])
            new = vars_["cache"]["cache_store"]

            def write(dst, src):
                idx = (jnp.zeros((), jnp.int32), slot) + \
                    (jnp.zeros((), jnp.int32),) * (dst.ndim - 2)
                return jax.lax.dynamic_update_slice(
                    dst, src.astype(dst.dtype), idx)

            merged = {k: write(cs[k], new[k]) for k in row}
            merged.update({k: new[k] for k in whole})
            merged["index"] = cs["index"].at[slot].set(start + length)
            return out, {"cache_store": merged}

        capacity = self._declared_kv_capacity()

        def decode_fn(params, cache, token, pos=None, rows=None):
            # a server hands in what the device already holds and no
            # more: its (B,) current-token twin as it is (the axis is
            # added here) and no ``pos``, which is then the cache's own
            # ``index`` held inside the allocation, as
            # ``SlotPool.positions`` does it on the host.
            # ``rows``: only for a model with a recurrent state, from a
            # caller some of whose rows do not run (the server: free
            # slots, slots in mid-prefill). (B,) int32, the cache row of
            # each entry, out of range for one that does not run: that
            # row's state comes back bit for bit. A K/V model is never
            # given it: its program is what it was.
            more = {} if rows is None else {"rows": rows}
            if token.ndim == 1:
                token = token[:, None]
            if pos is None:
                pos = cache["cache_store"]["index"]
                if capacity is not None:
                    pos = jnp.minimum(pos, capacity - 1)
            out, vars_ = module.apply(
                {"params": dequant(params), "cache": cache}, token, pos,
                method=module.decode, mutable=["cache"], **more)
            return out, vars_["cache"]

        def sample_fn(logits, rng, temperature, top_k, top_p, greedy):
            """``(rng', tokens)``: the key is split HERE (the caller
            keeps what comes back and passes it to its next call), so no
            caller runs a program of its own for the split."""
            rng, sub = jax.random.split(rng)
            last = logits[:, -1, :].astype(jnp.float32)
            scaled = _filter_logits(last, temperature, top_k, top_p)
            sampled = jax.random.categorical(sub, scaled, axis=-1)
            return rng, jnp.where(greedy, jnp.argmax(last, axis=-1),
                                  sampled)

        def decode_scan_fn(params, cache, token, pos, rng, temperature,
                           greedy, n_steps, top_k, top_p):
            """The whole decode loop as ONE compiled program — the TPU
            equivalent of the reference's CUDA-graph capture/replay
            (inference/engine.py:532,551): a single dispatch generates
            ``n_steps`` tokens, so per-step host/dispatch latency vanishes."""

            def body(carry, _):
                cache, token, pos, rng = carry
                logits, cache = decode_fn(params, cache, token, pos)
                rng, nxt = sample_fn(logits, rng, temperature, top_k, top_p,
                                     greedy)
                nxt = nxt.astype(jnp.int32)
                return (cache, nxt, pos + 1, rng), nxt

            (cache, token, pos, rng), toks = jax.lax.scan(
                body, (cache, token, pos, rng), None, length=n_steps)
            return cache, toks.T  # (B, n_steps)

        # the traced decode body is kept for composition: the speculative
        # verify program (serving/spec_decode) closes over it
        self._decode_fn = decode_fn
        self._jit_logits = jax.jit(logits_fn)
        self._jit_prefill = jax.jit(prefill_fn)
        self._jit_prefill_gen = jax.jit(prefill_last_fn) \
            if prefill_gen is not None else self._jit_prefill
        self._jit_prefill_at = jax.jit(prefill_at_fn) \
            if prefill_gen is not None else None
        self._jit_prefill_chunk = jax.jit(prefill_chunk_fn,
                                          donate_argnums=(1,)) \
            if chunk_gen is not None else None
        self._jit_decode = jax.jit(decode_fn, donate_argnums=(1,))
        # a key that a program hands back is pinned to ONE placement,
        # the one its first holder commits it to (``key_sharding``): left
        # to the partitioner it differed from program to program on a
        # mesh, and the next program that took it compiled again
        self._jit_sample = jax.jit(sample_fn, static_argnums=(3, 4),
                                   out_shardings=(self.key_sharding, None))
        self._jit_decode_scan = jax.jit(decode_scan_fn,
                                        donate_argnums=(1,),
                                        static_argnums=(7, 8, 9))

    # ------------------------------------------------------------------
    def forward(self, input_ids, *args, **kwargs):
        """Full-context logits for LM modules; non-LM modules (no
        ``logits`` method — e.g. the diffusion family) run a generic
        compiled apply over the given arguments (≅ reference
        engine.forward, inference/engine.py:592, which serves any wrapped
        module)."""
        if not hasattr(self.module, "logits"):
            return self._generic_forward(input_ids, *args, **kwargs)
        input_ids = jnp.asarray(input_ids)
        if input_ids.ndim == 1:
            input_ids = input_ids[None]
        self._ensure_params(input_ids)
        return self._jit_logits(self.params, input_ids)

    def _generic_forward(self, *args, **kwargs):
        args = tuple(jnp.asarray(a) for a in args)
        if self.params is None:
            if self._params_host is None:
                if not hasattr(self.module, "init"):
                    raise ValueError(
                        "pass model_parameters= for non-flax models")
                self._params_host = self.module.init(
                    {"params": jax.random.PRNGKey(0)}, *args,
                    **kwargs)["params"]
            self._finalize_params()
        # kwargs are threaded into the compiled apply (keys are static; a
        # new key set recompiles)
        kw_keys = tuple(sorted(kwargs))
        if getattr(self, "_jit_generic_keys", None) != kw_keys:
            self._jit_generic_keys = kw_keys
            self._jit_generic = jax.jit(
                lambda p, a, kv: self.module.apply(
                    {"params": self._dequant(p)}, *a,
                    **dict(zip(kw_keys, kv))))
        return self._jit_generic(self.params, args,
                                 tuple(kwargs[k] for k in kw_keys))

    __call__ = forward

    def _compile_decode_scan(self, cache_aval, batch, n_steps, top_k, top_p):
        """AOT-compile the whole-decode program from avals only (no cache
        buffer live), caching the executable per signature. Returns None
        only under tensor parallelism (below), where generate() uses the
        plain jit dispatch; a failed compile raises."""
        if self.mp_world_size != 1:
            # TP caches come out of prefill sharded over the model axis;
            # lowering with replicated avals would produce an executable
            # that can never match (an expensive dead compile) — skip and
            # use the plain jit dispatch
            return None
        leaves = jax.tree_util.tree_leaves(cache_aval)
        key = (jax.tree_util.tree_structure(cache_aval),
               tuple((l.shape, str(l.dtype)) for l in leaves),
               batch, n_steps, top_k, top_p)
        if key in self._decode_scan_execs:
            return self._decode_scan_execs[key]
        rep = NamedSharding(mesh_mod.get_mesh(), PartitionSpec())
        p_sds = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding),
            self.params)
        c_sds = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=rep), cache_aval)
        rng_shape = jax.eval_shape(jax.random.PRNGKey, 0)
        # a failed lower/compile is a bug and raises; nothing here
        # retries it as a slower plain-jit success
        compiled = self._jit_decode_scan.lower(
            p_sds, c_sds,
            jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct(rng_shape.shape, rng_shape.dtype,
                                 sharding=rep),
            jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
            jax.ShapeDtypeStruct((), jnp.bool_, sharding=rep),
            n_steps, top_k, top_p).compile()
        self._decode_scan_execs[key] = compiled
        return compiled

    @property
    def key_sharding(self) -> NamedSharding:
        """Where a sampling key lives between the programs that split it
        (the sampler, ``verify_k``): replicated on the engine's mesh."""
        return NamedSharding(self.mesh, PartitionSpec())

    def kv_cache_spec(self):
        """The served module's declared KV-cache contract, or None when it
        doesn't declare one (foreign modules). The serving subsystem sizes
        its slot pool from this."""
        module = getattr(self, "_serve_module", None) or self.module
        spec_fn = getattr(module, "kv_cache_spec", None)
        if not callable(spec_fn):
            return None
        try:
            return spec_fn()
        except Exception:  # noqa: BLE001 — foreign modules may need state
            return None

    def _declared_kv_capacity(self) -> Optional[int]:
        spec = self.kv_cache_spec()
        cap = getattr(spec, "max_seq_len", None)
        return int(cap) if cap is not None else None

    # ------------------------------------------------------------------
    def prefill_chunk(self, cache, input_ids, slot, start, length,
                      last_idx):
        """Process one fixed-width prefill chunk into row ``slot`` of the
        slot-pooled ``cache`` at offset ``start`` (see the jitted body in
        ``_build_jits``). ``input_ids`` is (1, C) int32 right-padded,
        ``length`` the TRUE token count in the chunk, ``last_idx`` the
        position (within the chunk) to project — only meaningful on the
        final chunk, whose logits seed the first sampled token. Returns
        ``(logits (1, 1, V), cache)``; the cache operand is donated
        (updated in place in HBM) and comes back with the slot's index
        at ``start + length``."""
        if self._jit_prefill_chunk is None:
            raise ValueError("prefill_chunk requires a module exposing "
                             "prefill_chunk(input_ids, start_pos, "
                             "last_idx); the unified TransformerLM "
                             "family does")
        return self._jit_prefill_chunk(
            self.params, cache,
            pack_chunk_args(input_ids, slot, start, length, last_idx))

    def verify_k(self, cache, cur, draft, draft_len, rng, temperature,
                 greedy, top_k: int, top_p: float):
        """Speculative verification: score K draft positions for every
        row in ONE fixed-shape chunked-decode forward and run acceptance
        in the same compiled program (greedy accept-prefix, or lossless
        rejection sampling under the serving sampler's filtered
        distribution for ``do_sample``).

        ``cur`` is (B,) int32, each row's current token (the rows scored
        are [current_token, draft_0..K-1] at the cache's own per-slot
        ``index``: nothing about them is sent that the device holds);
        ``draft`` (B, K); ``draft_len`` (B,) int32 in [0, K] (0 = plain
        decode for that row: dead or non-speculating slots ride along
        masked); ``rng`` the caller's key, split inside. The cache
        operand is donated (updated in place in HBM) and comes back with
        all K+1 positions written for every row — the caller rolls back
        rejected positions by per-slot ``index`` masking
        (:meth:`SlotPool.advance`), never a reshape.

        Returns ``(cache, out (B, K+1) int32, n_emit (B,) int32, rng')``:
        row ``i`` emits ``out[i, :n_emit[i]]`` — the accepted draft
        prefix plus the bonus/correction token (always >= 1 per step);
        ``rng'`` is the key for the caller's next call.
        """
        if self._decode_fn is None:
            raise ValueError("verify_k requires an LM module with a "
                             "decode() method (build jits first)")
        if self._jit_verify_k is None:
            from ..serving.spec_decode.verify import make_verify_fn

            self._jit_verify_k = jax.jit(
                make_verify_fn(self._decode_fn, _filter_logits),
                donate_argnums=(1,), static_argnums=(8, 9),
                out_shardings=(None, None, None, self.key_sharding))
        return self._jit_verify_k(self.params, cache, cur, draft, draft_len,
                                  rng, temperature, greedy, int(top_k),
                                  float(top_p))

    def generate(self, input_ids, max_new_tokens: int = 32,
                 do_sample: bool = False, temperature: Optional[float] = None,
                 top_k: Optional[int] = None, top_p: Optional[float] = None,
                 eos_token_id: Optional[int] = None, seed: int = 0,
                 **kwargs):
        """Autoregressive generation with KV cache (≅ reference
        engine._generate, inference/engine.py:620).

        Returns int32 array (B, T_prompt + n_generated) — prompt + new
        tokens, truncated at ``eos_token_id`` if every row finished early.
        """
        cfg = self._config
        input_ids = jnp.asarray(input_ids, jnp.int32)
        if input_ids.ndim == 1:
            input_ids = input_ids[None]
        B, T = input_ids.shape
        if max_new_tokens <= 0:
            return np.asarray(input_ids)
        max_len = getattr(self.module.config, "max_seq_len", None)
        if max_len is not None and T + max_new_tokens > max_len:
            raise ValueError(
                f"prompt({T}) + max_new_tokens({max_new_tokens}) exceeds the "
                f"model's max_seq_len({max_len}) KV-cache capacity")
        self._ensure_params(input_ids)

        temperature = cfg.temperature if temperature is None else temperature
        top_k = cfg.top_k if top_k is None else top_k
        top_p = cfg.top_p if top_p is None else top_p
        greedy = jnp.asarray(not do_sample)

        # Cache avals from a shape-only prefill: the decode-program compile
        # happens BEFORE any cache buffer lives. The allocated KV capacity
        # comes from the module's DECLARED kv_cache_spec when it has one
        # (the allocation contract; the serving slot pool
        # consumes the same spec), falling back to the last dim of ndim>=4
        # cache leaves (positions-minor layout) only for foreign modules
        # that declare nothing. Steps past capacity would write out of
        # bounds (silently clamped by JAX today, but fragile); fail loudly.
        _, cache_aval = jax.eval_shape(self._jit_prefill_gen, self.params,
                                       input_ids)
        cache_cap = self._declared_kv_capacity()
        if cache_cap is None:
            cache_cap = max((x.shape[-1]
                             for x in jax.tree_util.tree_leaves(cache_aval)
                             if getattr(x, "ndim", 0) >= 4), default=None)
        caps = [c for c in (max_len, cache_cap) if c is not None]
        capacity = min(caps) if caps else None
        if capacity is not None and T + max_new_tokens > capacity:
            raise ValueError(
                f"prompt({T}) + max_new_tokens({max_new_tokens}) exceeds the "
                f"allocated KV-cache capacity({capacity})")

        decode_exec = None
        if eos_token_id is None:
            # whole-loop compile (CUDA-graph analog): ONE dispatch for the
            # entire decode — per-token host dispatch latency disappears.
            # n_steps is static, so bucket it (next power of two, capped by
            # the KV capacity) to bound recompiles across varying budgets;
            # the extra steps' outputs are sliced off.
            n_steps = max_new_tokens - 1
            bucket = 1
            while bucket < n_steps:
                bucket *= 2
            if capacity is not None:
                bucket = min(bucket, capacity - T - 1)
            bucket = max(bucket, n_steps)
            # AOT-compile the decode program NOW, before the prefill cache
            # exists: the compiler checks the program's HBM budget
            # against FREE memory without crediting the dispatch-time
            # donation of the cache carries, so compiling with buffers
            # live needs transient 2x-cache headroom (the
            # kv_capacity_results.json boundary finding). Donation is part
            # of the lowering, so the dispatch itself aliases as usual.
            decode_exec = self._compile_decode_scan(
                cache_aval, B, bucket, int(top_k), float(top_p))

        logits, cache = self._jit_prefill_gen(self.params, input_ids)
        temperature = jnp.asarray(temperature, jnp.float32)
        rng, token = self._jit_sample(
            logits, jax.device_put(jax.random.PRNGKey(seed),
                                   self.key_sharding),
            temperature, int(top_k), float(top_p), greedy)

        if eos_token_id is None:
            args = (self.params, cache, token.astype(jnp.int32),
                    jnp.asarray(T, jnp.int32), rng, temperature, greedy)
            rest = None
            if decode_exec is not None:
                # small args must match the replicated shardings the
                # executable was lowered with; the cache comes straight
                # from prefill — if its layout disagrees (e.g. TP-sharded
                # caches), fall back to the plain jit dispatch
                rep = NamedSharding(mesh_mod.get_mesh(), PartitionSpec())
                try:
                    placed = (args[0], args[1]) + tuple(
                        jax.device_put(a, rep) for a in args[2:])
                    _, rest = decode_exec(*placed)
                except ValueError:
                    rest = None
            if rest is None:
                _, rest = self._jit_decode_scan(
                    *args, bucket, int(top_k), float(top_p))
            toks = np.concatenate([np.asarray(token)[:, None],
                                   np.asarray(rest)[:, :n_steps]], axis=1)
        else:
            # eager loop with pipelined eos check: step j+1 is DISPATCHED
            # before step j's tokens are pulled to the host, so the eos
            # fetch overlaps the in-flight decode instead of serializing
            # every iteration on a device round-trip. When the check says
            # everyone finished, the just-dispatched step's token is
            # dropped — output width and values match the serial loop
            # bitwise (the speculative step consumed one rng split, but
            # nothing after the break reads the stream).
            dev_out = [token]
            finished = np.zeros((np.shape(input_ids)[0],), bool)
            pos = T
            for _ in range(max_new_tokens - 1):
                logits, cache = self._jit_decode(
                    self.params, cache, token[:, None],
                    jnp.asarray(pos, jnp.int32))
                rng, nxt = self._jit_sample(
                    logits, rng, temperature, int(top_k), float(top_p),
                    greedy)
                # host sync on the PREVIOUS token while this step runs
                finished |= np.asarray(token) == eos_token_id
                if finished.all():
                    break
                token = nxt
                dev_out.append(token)
                pos += 1
            toks = np.stack([np.asarray(t) for t in dev_out], axis=1)
        if eos_token_id is not None:
            # clamp everything after each row's first eos to eos
            hit = np.cumsum(toks == eos_token_id, axis=1) > 0
            after = np.roll(hit, 1, axis=1)
            after[:, 0] = False
            toks = np.where(after, eos_token_id, toks)
        return np.concatenate([np.asarray(input_ids), toks], axis=1)

    # ------------------------------------------------------------------
    def throughput(self, input_ids, max_new_tokens: int = 64) -> Dict[str, float]:
        """Decode-throughput probe (tokens/s) used by bench/autotuning."""
        t0 = time.perf_counter()
        toks = self.generate(input_ids, max_new_tokens=max_new_tokens)
        dt = time.perf_counter() - t0
        n_new = toks.shape[1] - np.shape(input_ids)[-1]
        return {"tokens_per_sec": n_new * toks.shape[0] / dt, "elapsed_s": dt}
