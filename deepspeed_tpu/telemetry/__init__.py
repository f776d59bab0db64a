"""Telemetry: structured tracing, per-request timelines, recompile
watchdog, and a Prometheus-exportable metrics registry.

This package is the TPU-idiomatic analogue of the reference
DeepSpeed's observability stack, mapped feature-for-feature:

* reference ``utils/timer.py`` (SynchronizedWallClockTimer) →
  :class:`Tracer` spans, the one way the program times host work. The
  reference synchronizes CUDA before reading the clock; here the
  analogous hazard is JAX *async dispatch* — a host-side timer around
  a jitted call measures dispatch, not compute. Spans record honest
  host time; the device's time is the ``serving/sync`` /
  ``train/sync`` span around the step's ``block_until_ready``.
* reference ``monitor/`` (TensorBoard/WandB/csv scalar sinks) →
  :class:`MetricsRegistry` publishing ``(tag, value, step)`` events
  through the same ``MonitorMaster`` fan-out, plus the new machine-
  readable ``JSONLMonitor`` sink and Prometheus text exposition via
  :meth:`MetricsRegistry.to_prometheus`.
* reference ``flops_profiler`` (per-module latency breakdown) →
  step-phase spans inside ``ServingEngine.step()`` /
  ``DeepSpeedEngine.train_batch()`` exported as a Chrome
  trace-event / Perfetto JSON timeline (:meth:`Tracer.export`),
  including per-request lifecycle lanes (:class:`TimelineStore`) —
  per-iteration attribution rather than per-module FLOPs, because on
  TPU the profiler of record for intra-step FLOPs is XLA's own.
* reference ``profiling/flops_profiler`` (module-walk MAC counting,
  ``get_model_profile``) → :class:`ProgramCostModel`
  (``telemetry/costs.py``). Where DeepSpeed re-derives flops from
  module hooks, XLA already knows: every warm executable's
  ``lowered.compile().cost_analysis()`` / ``memory_analysis()`` is
  harvested once per abstract signature through the ``_WatchedJit``
  seam and charged per call, yielding live MFU /
  bandwidth-utilization / tokens-per-flop gauges plus KV-HBM
  reconciliation (predicted page math vs actual device bytes,
  ``telemetry/hbm_drift``).
* no reference analogue: :class:`SLOTracker` (``telemetry/slo.py``) —
  O(1)-memory mergeable quantile digests over sliding windows,
  per-window goodput (finished-within-SLO ÷ admitted), and
  multi-window burn-rate alerting (``ok``/``warn``/``page``), the
  sensor suite the ROADMAP's SLO-aware scheduler consumes.
* no reference analogue: :class:`FlightRecorder`
  (``telemetry/flight_recorder.py``) — a bounded ring of per-step
  records that becomes a self-contained post-mortem JSON when the
  engine raises (invariant violation, stall, strict recompile), and a
  live ``srv.debug_dump()`` statusz snapshot.
* reference ``monitor/`` + flops profiler, fleet edition:
  :class:`FleetTelemetry` (``telemetry/fleet.py``). Where the
  reference fans ONE engine's scalars out to its sinks and profiles
  ONE module tree, the serving fleet needs the transpose — N replicas'
  registries merged into one Prometheus exposition with
  ``replica=``/``role=`` labels, per-replica quantile digests merged
  bucketwise into fleet p50/p99, goodput/burn computed over SUMMED
  admission windows, and ONE fleet post-mortem aligning every
  replica's flight-recorder ring on the shared injected clock.
  Cross-replica request *journeys* (minted by the router, stamped by
  each home's :class:`TimelineStore`, stitched by
  ``ReplicaRouter.journey``) play the flops profiler's attribution
  role at fleet scope: where a latency went, per hop, per replica —
  exported as a multi-process Perfetto document via
  :func:`merge_chrome` (one process lane per replica, flow arrows
  across handoff/transfer/failover boundaries).
* no reference analogue: :class:`RecompileWatchdog`. XLA recompilation
  is the TPU-specific production hazard (a shape-churned serving step
  silently costs seconds); the watchdog attributes every recompile to
  a jitted program + abstract shape signature, and ``strict`` mode
  turns the tests' "zero recompiles under churn" invariant into a
  runtime guarantee.

What is on by default: :func:`default_tracer`, the process-wide
:class:`Tracer`, enabled. Both engines record their step phases, request
events and set-up (``setup/import``, ``setup/build``, one
``setup/compile`` per compile) into it when no ``tracer=`` is passed. A
span takes ~1 us to time itself and open its annotation, and its event in
the ring ~0.4 us more (a flat record, no lock; ``events()`` builds the
dicts when it is read). The ring holds 131,072 events: ~11,900 plain
decode steps of a server (11 events each), a window of 30 s while a step
takes 2.5 ms or more; ``dropped`` / ``events_total`` say when it wrapped
(the gauges ``telemetry/tracer_dropped`` / ``telemetry/tracer_events_total``
in a server's registry). A server's counter tracks
(``serving/occupancy``, ``paging/pages``, ``serving/load_state``) take a
sample when a level changes and at every 256th step. ``serving/step``
carries ``dry`` = 1 where the step found the device done with the step
before when it called its first program (``serving/steps_device_dry`` in
the registry): only there is the step's ``exposed`` phase idle time of
the chip. What a profiler session adds: every span is also a
``jax.profiler.TraceAnnotation``, so under ``jax.profiler.start_trace``
(xprof, ``chip_smoke.py``, the benchmark's ``--trace 1``) the same spans
lie in the xplane's ``/host:CPU`` plane on the profiler's clock, beside
the device's operations. Export, any time::

    from deepspeed_tpu.telemetry import default_tracer
    default_tracer().export("/tmp/trace.json")   # ui.perfetto.dev

Quick start::

    from deepspeed_tpu.telemetry import Tracer
    tracer = Tracer()
    with tracer.span("serving/step", step=3):
        ...
    tracer.export("/tmp/trace.json")   # open in ui.perfetto.dev

Serving integration (all knobs on ``ds.init_serving``)::

    srv = ds.init_serving(engine, tracer=Tracer(),   # a ring of its own;
                          strict_recompile=True)     # Tracer(enabled=False)
    srv.end_warmup()            # after warmup traffic   silences the ring
    srv.timeline(request_id)    # per-request lifecycle events
    srv.publish_telemetry()     # registry -> monitor sinks
"""

from .tracer import Tracer, default_tracer, export_merged, merge_chrome
from .registry import Counter, Gauge, Histogram, MetricsRegistry
from .timeline import TimelineStore
from .watchdog import (RecompileAfterWarmupError, RecompileWatchdog,
                       abstract_signature, fast_signature,
                       suppress_compile_events)
from .costs import (ProgramCostModel, device_memory_report,
                    kv_hbm_report, resolve_peaks)
from .slo import (QuantileDigest, SLOConfig, SLOTargets, SLOTracker,
                  WindowedQuantiles)
from .flight_recorder import FlightRecorder, POST_MORTEM_KEYS
from .fleet import (FleetTelemetry, FLEET_POST_MORTEM_KEYS,
                    FLEET_SCHEMA_VERSION)

__all__ = [
    "Tracer",
    "default_tracer",
    "merge_chrome",
    "export_merged",
    "FleetTelemetry",
    "FLEET_POST_MORTEM_KEYS",
    "FLEET_SCHEMA_VERSION",
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "TimelineStore",
    "RecompileWatchdog",
    "RecompileAfterWarmupError",
    "abstract_signature",
    "fast_signature",
    "suppress_compile_events",
    "ProgramCostModel",
    "kv_hbm_report",
    "device_memory_report",
    "resolve_peaks",
    "QuantileDigest",
    "WindowedQuantiles",
    "SLOConfig",
    "SLOTargets",
    "SLOTracker",
    "FlightRecorder",
    "POST_MORTEM_KEYS",
]
