"""deepspeed_tpu — a TPU-native training & inference framework with the
capabilities of DeepSpeed (reference v0.9.5), built on JAX/XLA/pjit/Pallas.

Public API parity with ``deepspeed/__init__.py``: :func:`initialize` (:58),
:func:`init_inference` (:260), :func:`add_config_arguments` (:237), plus the
``comm``/``zero``/``monitor``/``ops`` subpackages.
"""

from __future__ import annotations

import time as _time

_IMPORT_T0_NS = _time.perf_counter_ns()   # closed as ``setup/import`` below

from typing import Any, Dict, Optional, Union  # noqa: E402

__version__ = "0.1.0"

from . import comm  # noqa: F401
from . import parallel  # noqa: F401
from .runtime import zero  # noqa: F401  (deepspeed.zero namespace parity)
from .runtime.activation_checkpointing import checkpointing  # noqa: F401
from .accelerator import get_accelerator  # noqa: F401
from .runtime.config import DeepSpeedConfig  # noqa: F401
from .runtime.engine import DeepSpeedEngine  # noqa: F401
from .utils.logging import log_dist, logger  # noqa: F401
from .comm.comm import init_distributed  # noqa: F401  (≅ reference
# deepspeed.init_distributed, deepspeed/__init__.py:303 re-export)
from .telemetry import default_tracer as _default_tracer


def initialize(args=None,
               model: Any = None,
               optimizer=None,
               model_parameters: Any = None,
               training_data=None,
               lr_scheduler=None,
               mpu=None,
               dist_init_required: Optional[bool] = None,
               collate_fn=None,
               config: Union[str, Dict, None] = None,
               config_params: Union[str, Dict, None] = None,
               loss_fn=None,
               sharding_rules=None,
               mesh=None):
    """Build the engine (≅ reference ``deepspeed.initialize``,
    deepspeed/__init__.py:58).

    Returns ``(engine, optimizer, training_dataloader, lr_scheduler)``.

    TPU-native notes: ``model`` is a flax Module (``__call__(batch) -> loss``)
    or a pure ``loss_fn(params, batch, rng)``; ``optimizer`` comes from the
    JSON config (``optimizer.type``); ``mpu`` is superseded by the mesh —
    pass ``mesh`` or config["mesh"] degrees instead.
    """
    if config is None and config_params is not None:
        config = config_params
    if config is None and args is not None:
        config = getattr(args, "deepspeed_config", None)
    if config is None:
        raise ValueError("DeepSpeed requires --deepspeed_config or config=")
    with _default_tracer().span("setup/build", entry="initialize"):
        engine = _build_engine(model, model_parameters, training_data,
                               lr_scheduler, collate_fn, config, loss_fn,
                               sharding_rules, mesh)
    return engine, engine.optimizer_def, engine.training_dataloader, engine.lr_scheduler


def _build_engine(model, model_parameters, training_data, lr_scheduler,
                  collate_fn, config, loss_fn, sharding_rules, mesh):
    """The engine ``initialize`` returns, by the kind of model and config."""

    # PipelineModule → PipelineEngine dispatch (reference __init__.py:151-189)
    try:
        from .runtime.pipe.module import PipelineModule
    except ImportError:
        PipelineModule = None

    cfg_probe = config
    if isinstance(config, str):
        import json as _json

        with open(config) as _fh:
            cfg_probe = _json.load(_fh)
    hybrid = isinstance(cfg_probe, dict) and \
        cfg_probe.get("hybrid_engine", {}).get("enabled", False)
    if not hybrid and hasattr(cfg_probe, "hybrid_engine"):
        hybrid = bool(cfg_probe.hybrid_engine.enabled)
    if PipelineModule is not None and isinstance(model, PipelineModule):
        from .runtime.pipe.engine import PipelineEngine

        engine = PipelineEngine(model=model, config=config,
                                model_parameters=model_parameters,
                                training_data=training_data,
                                lr_scheduler=lr_scheduler, collate_fn=collate_fn,
                                mesh=mesh, sharding_rules=sharding_rules)
    elif hybrid:
        from .runtime.hybrid_engine import DeepSpeedHybridEngine

        engine = DeepSpeedHybridEngine(model=model, loss_fn=loss_fn,
                                       model_parameters=model_parameters,
                                       config=config,
                                       sharding_rules=sharding_rules,
                                       training_data=training_data,
                                       lr_scheduler=lr_scheduler,
                                       collate_fn=collate_fn, mesh=mesh)
    else:
        engine = DeepSpeedEngine(model=model, loss_fn=loss_fn,
                                 model_parameters=model_parameters,
                                 config=config, sharding_rules=sharding_rules,
                                 training_data=training_data,
                                 lr_scheduler=lr_scheduler, collate_fn=collate_fn,
                                 mesh=mesh)
    return engine


def init_inference(model: Any = None, config: Union[str, Dict, None] = None, **kwargs):
    """Build the inference engine (≅ reference ``deepspeed.init_inference``,
    deepspeed/__init__.py:260)."""
    try:
        from .inference.engine import InferenceEngine
    except ImportError as e:
        raise NotImplementedError(
            "inference engine not built yet in this round") from e

    return InferenceEngine(model=model, config=config, **kwargs)


def init_serving(model: Any = None, config: Union[str, Dict, None] = None,
                 num_slots: int = 4, max_queue_depth: int = 64, **kwargs):
    """Build a continuous-batching server: :func:`init_inference` for the
    engine, then wrap it in :class:`serving.ServingEngine` (slot-pooled KV
    cache, FIFO admission, per-request SLO metrics, optional speculative
    decoding).

    Knobs split into two scopes. **Server-global** (fixed at construction,
    shared by every request — they shape the compiled programs): the
    serving-only keys ``do_sample``, ``temperature``,
    ``top_k``, ``top_p``, ``seed``, ``monitor``, ``spec_decode``,
    ``prefill_chunk`` and ``prefill_token_budget`` (stall-free chunked
    admission; 0 disables), the telemetry keys ``tracer`` (a
    :class:`telemetry.Tracer` of the server's own, or ``True`` for a
    default-capacity one; given none the server records into the
    process-wide ``telemetry.default_tracer()``, which is on, and
    ``Tracer(enabled=False)`` silences the ring),
    ``registry``, ``strict_recompile`` (raise at the step boundary on
    any post-warmup recompile), which pass
    through to ServingEngine, plus
    ``num_slots`` / ``max_queue_depth``. **Per-request** (ride on each ``submit()``):
    ``max_new_tokens`` and ``eos_token_id`` — nothing else varies per
    request, so slot churn never changes a compiled shape. Everything
    else configures the inference engine.

    ``spec_decode`` enables draft–verify speculative decoding: ``True``
    for defaults (n-gram drafter, k=4), a dict such as
    ``{"drafter": "ngram", "k": 8, "max_ngram": 3}`` or
    ``{"drafter": "model", "draft_engine": small_engine}``, or a
    :class:`serving.SpecDecodeConfig`. Greedy output stays bitwise
    identical to ``spec_decode=None``; admission control tightens to
    ``prompt + max_new_tokens <= capacity - k`` (the verify headroom).

    The fault-tolerance keys (all optional, all server-global):
    ``deadline_default_ms`` (TTL applied to every submit that doesn't
    carry its own ``deadline_ms``), ``step_wall_budget_ms`` (per-step
    wall-time watchdog), ``guard_numerics`` (NaN/inf logits guard that
    fails only the poisoned slot), ``degradation`` (``True``, a dict of
    :class:`serving.resilience.DegradationConfig` overrides, or an
    instance — the HEALTHY/PRESSURED/OVERLOADED ladder),
    ``preempt_queue_threshold`` / ``preempt_min_run_steps`` (automatic
    pressure preemption), and ``fault_injector`` (a
    :class:`serving.resilience.FaultInjector` for chaos testing).
    Per-request ``deadline_ms`` rides on ``submit()``.

    ``paged_kv`` replaces the per-slot contiguous KV rows with a
    :class:`serving.PagedKVPool` — fixed-size refcounted pages behind a
    static per-slot page table, with radix-trie prefix caching and
    copy-on-write sharing (vLLM PagedAttention + SGLang RadixAttention;
    greedy output stays bitwise identical). ``True`` for defaults (page
    size = the prefill chunk, ``num_pages`` = worst-case), or a dict
    ``{"num_pages": int, "page_size": int, "prefix_cache": bool,
    "kernel": "auto"|"on"|"off"}`` — ``num_pages`` below
    ``num_slots * max_seq_len / page_size`` oversubscribes HBM;
    pressure is drained by trie eviction, then automatic preemption.
    ``kernel`` selects the fused Pallas paged-attention decode/verify
    path (``"auto"`` arms it on real TPU hardware only; the dense
    gather path stays the bitwise-parity oracle).

    ``step()`` runs one step ahead of the host: it queues its programs,
    then settles the step before it (one wait, one fetch, the replay of
    its tokens) and leaves its own in flight; ``settle()`` brings the host
    up to date. No option: it is how the server steps (a speculative
    server and the two roles of a disaggregated pair settle inside the
    step, because they need the values).

    The efficiency/goodput observability keys (all server-global):
    ``cost_model`` (``True``, a :class:`telemetry.ProgramCostModel`
    kwargs dict, or an instance — harvests XLA ``cost_analysis()`` per
    program and derives live MFU / bandwidth-utilization / KV-HBM-drift
    gauges; off by default because the lazy AOT harvest compiles each
    program once more), ``slo`` (``True``, a dict, or a
    :class:`telemetry.SLOConfig` — windowed quantile digests, goodput
    and burn-rate alerting), ``flight_recorder`` (on by default;
    ``False``, an int capacity, a kwargs dict, or a
    :class:`telemetry.FlightRecorder`), and ``dump_dir`` (where fatal
    raises drop their post-mortem JSON; ``srv.debug_dump()`` serves the
    same snapshot live).

    The multi-tenant front-end keys (server-global): ``priority``
    (``True`` for the default interactive/standard/batch classes, a
    :class:`serving.PriorityConfig` kwargs dict — ``classes``,
    ``shares``, ``default_class``, ``tenants`` — or an instance; swaps
    the FIFO scheduler for :class:`serving.PriorityScheduler` with
    fair-share token budgets, per-tenant rate limits/quotas, and
    burn-rate-driven shedding/preemption when ``slo`` is also on) and
    ``clock`` (a monotonic ``() -> float`` callable shared by EVERY
    time-dependent decision — deadlines, queue expiry, SLO latencies,
    rate buckets; defaults to ``time.perf_counter``; never wall
    clock). Per-request ``priority`` / ``tenant`` ride on ``submit()``.
    The HTTP/SSE server wraps the returned engine:
    ``serving.ServingFrontend(srv, port=...)``."""
    import inspect

    from .serving.engine import ServingEngine

    # every option of the server's constructor but those this function
    # passes itself, in the constructor's order (a set's order would
    # differ from one process to the next); whatever is left configures
    # the inference engine
    options = inspect.signature(ServingEngine.__init__).parameters
    serve_keys = [k for k in options if k not in (
        "self", "engine", "num_slots", "max_queue_depth")]
    serve_kwargs = {k: kwargs.pop(k) for k in serve_keys if k in kwargs}
    tracer = _default_tracer()
    with tracer.span("setup/build", entry="init_serving"):
        engine = init_inference(model=model, config=config, **kwargs)
        with tracer.span("setup/init_serving", slots=num_slots):
            return ServingEngine(engine, num_slots=num_slots,
                                 max_queue_depth=max_queue_depth,
                                 **serve_kwargs)


def add_config_arguments(parser):
    """Inject --deepspeed / --deepspeed_config CLI args (≅ reference
    deepspeed/__init__.py:237)."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed (helper flag, parity only)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to DeepSpeed json configuration file")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help="Deprecated alias of --deepspeed")
    group.add_argument("--deepscale_config", default=None, type=str,
                       help="Deprecated alias of --deepspeed_config")
    return parser


_default_tracer().complete("setup/import", _IMPORT_T0_NS,
                           _time.perf_counter_ns() - _IMPORT_T0_NS)
