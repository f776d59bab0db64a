"""ServingEngine: request-level continuous batching over InferenceEngine.

``InferenceEngine.generate()`` is whole-batch synchronous — every
request must arrive together and the batch holds its slots until the
slowest member finishes. This front-end turns the same compiled
machinery (the jitted ``prefill_last`` and donated single-step decode)
into a server: requests arrive one at a time via :meth:`submit`, each
:meth:`step` admits queued prompts into free slots of the fixed-shape
:class:`~deepspeed_tpu.serving.slot_pool.SlotPool` and runs ONE decode
step for all live slots, and finished sequences retire immediately so
their slot goes back to work (Orca-style iteration-level scheduling;
PAPERS.md).

Shape discipline is what keeps this fast on TPU: the decode step always
runs at batch = ``num_slots`` with per-slot (B,) cache offsets, so slot
churn never changes a compiled program — dead slots ride along as
masked padding. Prompt prefills are right-padded to power-of-two
buckets and the true last position is projected via
``prefill_last(input_ids, last_pos)``, bounding prefill recompiles at
log2(max_seq_len) for arbitrary prompt lengths.

Admission is STALL-FREE by default (``prefill_chunk > 0``,
Sarathi-style; PAPERS.md): each step spends at most a prefill token
budget before the decode dispatch, so a burst of arrivals can no
longer stall every live slot behind an unbounded prefill wave.
Prompts longer than the chunk width are seated ``PREFILLING`` and
stream into their slot's cache row one bounded
``prefill_chunk(input_ids, start_pos, last_idx)`` dispatch per step
(window-masked attention against the already-written positions — the
jitted program slices the target row out and writes only it back, so
live neighbours are untouched); shorter prompts waiting at the same
bucket width are prefilled in ONE batched dispatch (batch dim bucketed
to powers of two) and scattered into their slots by a single jitted
multi-row admit. Compile count stays bounded by
log2(num_slots) x log2(max_seq_len) admission programs plus one chunk
program; greedy outputs remain bitwise identical to serial admission.
``prefill_chunk=0`` restores the serial one-prompt-per-dispatch
admission (the benchmark's baseline arm).

With a ``spec_decode`` config the decode step becomes draft–verify
speculative decoding over the same fixed shapes: a host-side
:class:`~deepspeed_tpu.serving.spec_decode.Drafter` proposes up to K
tokens per live slot, one jitted ``verify_k`` forward scores all
``(num_slots, K+1)`` positions at once, and each slot keeps its
accepted prefix plus the target model's bonus/correction token — up to
K+1 tokens per slot per step, bitwise identical to plain greedy decode.
Rejected draft positions are rolled back by the per-slot cache ``index``
(:meth:`SlotPool.advance`), never by reshaping, so speculation adds
exactly one more compiled program regardless of churn.

The step RUNS ONE AHEAD of what the host has read: ``step()`` number n
queues its programs and then settles the bundle of step n - 1 (one wait,
one fetch, the replay of its tokens), leaving its own in flight, so the
device holds a step queued while the host replays, does its telemetry and
prepares the next. A token is visible in ``Request.output_tokens`` when
its step is settled: by the next ``step()``, or by :meth:`settle`. A
request whose end the host can count (``max_new_tokens``, the slot's
capacity) takes no row past it; an end only the value tells (EOS, the
finite guard) costs one dead row. Whatever takes a seated request out by
another road (preemption, ``cancel``, a deadline, an aborted step, the
audit, a handoff) settles first. Speculative decoding (the drafter reads
the host's histories) and the two roles of a disaggregated pair (the
handoff is a value) settle inside the step.

FAULT TOLERANCE (the :mod:`.resilience` package) hardens the loop
without ever changing a compiled shape:

* per-request deadlines (``submit(..., deadline_ms=...)``) expire
  queued requests before they cost a prefill and retire seated ones
  through the same slot-release/index-masking rollback speculation
  uses (``finish_reason="deadline"``);
* ``preempt()`` evicts a seated request and re-queues it carrying its
  generated-so-far tokens; re-admission prefills prompt + outputs
  through the existing bucketed/chunked paths (fixed shapes, zero new
  programs) and greedy output is bitwise identical to an un-preempted
  run. Automatic victim selection (youngest first) kicks in when the
  queue exceeds ``preempt_queue_threshold`` — those victims re-queue at
  the BACK (round-robin time-slicing), or the very next grant would
  hand each victim its own freed slot forever;
* a HEALTHY/PRESSURED/OVERLOADED load-state machine progressively
  shrinks the prefill token budget, suspends speculative drafting
  (zero-length drafts through the SAME verify program — no recompile),
  and finally sheds new submissions with ``retry_after``;
* an optional NaN/inf logits guard (``guard_numerics``) fails ONLY the
  poisoned slot (``finish_reason="numerical_error"``); the other slots'
  tokens from the same dispatch are kept;
* a seeded :class:`~deepspeed_tpu.serving.resilience.FaultInjector`
  threads deterministic failures through five named points for the
  chaos suite (``tests/unit/serving/test_resilience.py``).
"""

from __future__ import annotations

import time
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..models.kv_cache_spec import page_lanes
from ..ops.attention.sparse_index import (index_rows, pages_most,
                                            tokens_read)
from ..telemetry import (FlightRecorder, MetricsRegistry, ProgramCostModel,
                         RecompileAfterWarmupError, RecompileWatchdog,
                         SLOTracker, TimelineStore, Tracer, default_tracer)
from ..telemetry.tracer import (NO_SPAN, _Span, _StepSpan, gc_ns_total,
                                watch_gc)
from ..utils.logging import log_dist
from .metrics import ServingMetrics
from .paged_pool import PagedKVPool, PagePoolExhausted
from .request import FinishReason, RejectReason, Request, RequestState
from .resilience import (DegradationConfig, FaultInjectingDrafter,
                         InvariantViolation, LoadState, LoadStateMachine,
                         ServingStalledError, select_victims)
from .scheduler import FIFOScheduler
from .slot_pool import SlotPool

# jitted entry points the recompile watchdog wraps; verify_k is created
# lazily on first use, so _ensure_watch re-checks the list every step.
# The paged entries only exist on a PagedKVPool (attach skips absentees).
_WATCHED_ENGINE_JITS = ("_jit_prefill_at", "_jit_decode",
                        "_jit_prefill_chunk", "_jit_sample",
                        "_jit_verify_k", "_jit_decode_scan")
_WATCHED_POOL_JITS = ("_admit_jit", "_admit_rows_jit",
                      "_paged_decode_jit", "_paged_verify_jit",
                      "_paged_decode_kernel_jit",
                      "_paged_verify_kernel_jit",
                      "_paged_chunk_jit", "_paged_chunk_decode_jit",
                      "_jit_copy_page",
                      "_jit_gather_pages", "_jit_scatter_pages")
_WATCHED_SERVING_JITS = ("_jit_finite", "_jit_cur_scatter", "_jit_spec_cur")
# the model drafter jits its own last-token argmax (lazily, on the
# first propose); unwatched it was the one serving-side jit that could
# recompile post-warmup without attribution — found by the graftlint
# jit inventory, pinned by tests/unit/analysis/test_inventory.py
_WATCHED_DRAFTER_JITS = ("_argmax",)

_MIN_PREFILL_BUCKET = 16


class _Enqueue(_Span):
    """``serving/enqueue`` around ONE call of a jitted program, in the
    server's tracer (:func:`~deepspeed_tpu.telemetry.tracer.enqueue_span`
    says which calls get the span). At its close the step's account takes
    it, from the clock reads the span made: its time under ``enqueue``
    and, if it is the step's first and the previous step ended in a sync,
    ``exposed``: from the end of that sync to now, the host's serial
    stretch between two steps' programs. With the step run one ahead the
    device works through that stretch on the step before; it is idle time
    of the chip only on a step marked ``dry`` (:meth:`ServingEngine.
    _enqueue`: the bundle in flight was ready before this program was
    called), and on one with nothing in flight."""

    __slots__ = ("_srv",)

    def __init__(self, srv: "ServingEngine", program: str):
        super().__init__(srv.tracer, "serving/enqueue",
                         {"program": program, "kind": "program"})
        self._srv = srv

    def __exit__(self, exc_type, exc, tb):
        super().__exit__(exc_type, exc, tb)
        srv = self._srv
        phases = srv._phase_ns
        phases["enqueue"] = phases.get("enqueue", 0) + self.dur_ns
        if srv._exposed_from_ns is not None:
            phases["exposed"] = self.t0_ns + self.dur_ns \
                - srv._exposed_from_ns
            srv._exposed_from_ns = None
        return False


class _Phase(_Span):
    """A span of the step whose time OUTSIDE its ``serving/enqueue``
    children goes to one phase of the step's account: the phases then
    add up to no more than the step, whatever device calls a phase
    holds."""

    __slots__ = ("_srv", "_phase", "_enqueued")

    def __init__(self, srv: "ServingEngine", phase: str, name: str,
                 attrs: Optional[dict] = None):
        super().__init__(srv.tracer, name, attrs)
        self._srv, self._phase, self._enqueued = srv, phase, 0

    def __enter__(self):
        self._enqueued = self._srv._phase_ns.get("enqueue", 0)
        return super().__enter__()

    def __exit__(self, exc_type, exc, tb):
        super().__exit__(exc_type, exc, tb)
        phases = self._srv._phase_ns
        phases[self._phase] = phases.get(self._phase, 0) + self.dur_ns \
            - (phases.get("enqueue", 0) - self._enqueued)
        return False


class _PagesPhase(_Phase):
    """``serving/pages``: the scheduler -> KV pool boundary (seating the
    granted requests, making a dispatch's write columns writable,
    publishing a prompt's pages), with what it cost the pool: pages
    ``allocated``, copy-on-write pages ``forked``, requests ``preempted``
    under page pressure."""

    __slots__ = ("_before",)

    def __init__(self, srv: "ServingEngine"):
        super().__init__(srv, "pages", "serving/pages")
        self._before = (0, 0, 0)

    def _counts(self) -> tuple:
        srv = self._srv
        if not srv._paged:
            return (0, 0, srv.metrics.preempted)
        return (srv.pool.pages_allocated, srv.pool.cow_copies,
                srv.metrics.preempted)

    def __enter__(self):
        self._before = self._counts()
        return super().__enter__()

    def __exit__(self, exc_type, exc, tb):
        allocated, forked, preempted = self._counts()
        allocated0, forked0, preempted0 = self._before
        self.args = {"allocated": allocated - allocated0,
                     "forked": forked - forked0,
                     "preempted": preempted - preempted0}
        return super().__exit__(exc_type, exc, tb)


class _Bundle:
    """What one step left on the device for the host to read: the
    deferred ``(arrays, callback)`` pairs in dispatch order, the routed
    FFN's counters of its programs, and where the values that only a
    settled step knows are written (``attrs``: the step's own account
    while the step is open, the attributes of its ``serving/step`` span
    once that has closed)."""

    __slots__ = ("step_id", "pending", "moe_stats", "attrs")

    def __init__(self, step_id: int, pending: list, moe_stats: list,
                 attrs: dict):
        self.step_id, self.pending = step_id, pending
        self.moe_stats, self.attrs = moe_stats, attrs

    def ready(self) -> bool:
        """Whether the device has finished the step: its last-queued
        array is ready (the device runs a step's programs in order). No
        wait and no device call."""
        last = self.pending[-1][0][-1] if self.pending \
            else self.moe_stats[-1]
        return last.is_ready()


class ServingEngine:
    """Continuous-batching server over a built
    :class:`~deepspeed_tpu.inference.engine.InferenceEngine`.

    Construct via :func:`deepspeed_tpu.init_serving`. Sampling knobs
    default to the inference config's (greedy unless ``do_sample``);
    they are server-global — per-request ``max_new_tokens`` and
    ``eos_token_id`` ride on the :class:`Request`.
    """

    def __init__(self, engine: Any, num_slots: int = 4,
                 max_queue_depth: int = 64,
                 do_sample: bool = False,
                 temperature: Optional[float] = None,
                 top_k: Optional[int] = None, top_p: Optional[float] = None,
                 seed: int = 0, monitor: Optional[Any] = None,
                 spec_decode: Optional[Any] = None,
                 prefill_chunk: int = 64,
                 prefill_token_budget: Optional[int] = None,
                 tracer: Optional[Any] = None,
                 registry: Optional[Any] = None,
                 strict_recompile: bool = False,
                 deadline_default_ms: Optional[float] = None,
                 step_wall_budget_ms: Optional[float] = None,
                 guard_numerics: bool = False,
                 degradation: Optional[Any] = None,
                 preempt_queue_threshold: Optional[int] = None,
                 preempt_min_run_steps: int = 2,
                 fault_injector: Optional[Any] = None,
                 paged_kv: Any = False,
                 cost_model: Any = False,
                 slo: Any = None,
                 flight_recorder: Any = True,
                 dump_dir: Optional[str] = None,
                 priority: Any = None,
                 clock: Optional[Any] = None,
                 role: str = "both"):
        self.engine = engine
        # ONE monotonic clock for every time-dependent decision —
        # deadline stamps, queue expiry, SLO latencies, degradation
        # cooldowns AND the front end's rate buckets all read this
        # callable. Injectable so tests drive a fake clock through all
        # of them at once, and so the front end can share it; wall-clock
        # time.time() must never leak into deadline paths (NTP steps
        # would fire or defer deadlines arbitrarily).
        self._now = clock if clock is not None else time.perf_counter
        # materialize params + jits before sizing anything off the module
        engine._ensure_params(jnp.zeros((1, 2), jnp.int32))
        spec = engine.kv_cache_spec()
        if spec is None:
            raise ValueError(
                "serving requires the module to declare kv_cache_spec() "
                "(the slot pool allocates through it); the unified "
                "TransformerLM family does")
        if getattr(engine, "_jit_prefill_at", None) is None:
            raise ValueError(
                "serving requires the module to expose prefill_last("
                "input_ids, last_pos) for bucketed slot prefill")
        cfg = engine._config
        # pin the pool to the axis-rules placement for the engine's mesh
        # so the cold cache matches the committed arrays its jitted
        # steps hand back (otherwise the first admission compiles a
        # second executable). The per-leaf resolver shards k/v over
        # (data, model) where the mesh and shapes allow it and resolves
        # to the historical replicated placement everywhere else — on a
        # TP=1/DP=1 mesh every leaf is replicated, which is how the
        # single-chip path stays the bitwise oracle.
        rep = None
        if getattr(engine, "mesh", None) is not None:
            from ..parallel.axis_rules import cache_leaf_sharding
            rep = cache_leaf_sharding(
                "paged" if paged_kv else "stacked", mesh=engine.mesh)
        # kept for the current-token twin: every host-built slots-shaped
        # array is committed through the same resolver (key "index") so
        # its placement always matches the pool's per-slot index leaf
        self._pool_sharding = rep
        # -- paged KV (ISSUE 7): page-pooled storage + prefix cache ----
        # paged_kv: False (contiguous rows), True (paged, defaults), or a
        # dict {"num_pages": int, "page_size": int, "prefix_cache": bool}
        capacity = int(spec.max_seq_len)
        # what the cache's kind does not run with yet refuses here, in the
        # table's words, before anything is allocated
        # (models/cache_kinds.py, CACHE_REFUSALS); the pool asks for
        # prefix_cache itself
        mesh = getattr(engine, "mesh", None)
        asked = {
            "spec_decode": spec_decode,
            "paged_kv": paged_kv,
            "roles": role != "both",
            "tensor_parallel_serving":
                mesh is not None and mesh.shape.get("model", 1) > 1,
            "prefill_chunk_wider_than_window": paged_kv,
        }
        for feature, on in asked.items():
            why = spec.refusal(feature, prefill_chunk) if on else None
            if why:
                raise ValueError(why)
        # the bytes of a row's recurrent state (power_retention layers) and
        # of a token's one latent row over the layers (KVCacheSpec.latent),
        # for the counters; 0 for K/V a head
        self._state_row_bytes = int(getattr(spec, "state_bytes_per_row", 0))
        # (a state group's prefill runs its real tokens through the chunk
        # form of its kind's scan: the span attribute that counts them)
        self._chunk_tokens_key = next(
            (f"{kind}_chunk_tokens"
             for kind in ("ssm", "kda", "gdn", "conv", "lightning")
             if kind in getattr(spec, "kinds", ())), None)
        # learned sparse attention's sizes (``KVCacheSpec.sparse``): what
        # the equations read for a query at a position, for the counters
        self._sparse = getattr(spec, "sparse", None)
        self._latent_token_bytes = int(getattr(spec, "latent", 0)) \
            * np.dtype(spec.dtype).itemsize \
            * int(getattr(spec, "kv_layers", spec.n_layer))
        if paged_kv:
            knobs = dict(paged_kv) if isinstance(paged_kv, dict) else {}
            page_size = knobs.pop("page_size", None)
            if page_size is None:
                # default: the prefill chunk width (ISSUE 7) — one chunk
                # fills one page — auto-halved the same way the chunk is
                # until it divides the capacity
                page_size = int(prefill_chunk) if prefill_chunk > 0 else 64
                page_size = max(1, min(page_size, capacity))
                while page_size > 1 and capacity % page_size != 0:
                    page_size //= 2
            num_pages = knobs.pop("num_pages", None)
            use_prefix = bool(knobs.pop("prefix_cache", True))
            # paged_kernel: "off" (dense gather/scatter composition — the
            # bitwise oracle), "on" (fused in-place paged-attention
            # kernel, interpret mode off-TPU), "auto" (kernel on TPU)
            paged_kernel = str(knobs.pop("kernel", "auto"))
            if knobs:
                raise ValueError(f"unknown paged_kv keys: {sorted(knobs)}; "
                                 f"expected num_pages/page_size/"
                                 f"prefix_cache/kernel")
            self.pool = PagedKVPool(spec, num_slots, num_pages=num_pages,
                                    page_size=int(page_size), sharding=rep,
                                    prefix_cache=use_prefix,
                                    kernel=paged_kernel)
        else:
            # (a model with sliding-window layers keeps full-length rows
            # here: what left a window is masked, not freed)
            self.pool = SlotPool(spec, num_slots, sharding=rep)
        self._paged = isinstance(self.pool, PagedKVPool)
        self._spec = None
        self._drafter = None
        sched_capacity = self.pool.capacity
        if spec_decode is not None:
            from .spec_decode import SpecDecodeConfig, make_drafter
            sc = SpecDecodeConfig.from_value(spec_decode)
            if sc is not None and sc.enabled:  # False / enabled=False: off
                sc.validate(self.pool.capacity)
                self._spec = sc
                self._drafter = make_drafter(sc)
                # verify writes k+1 positions past a slot's live offset
                # (rejected tail = masked padding). Reserving k columns of
                # headroom at admission keeps even a fully-rejected chunk
                # inside the allocation, so the dynamic-slice writes can
                # never clamp into another request's live columns.
                sched_capacity = self.pool.capacity - sc.k
        sched_kw = dict(
            max_queue_depth=max_queue_depth, capacity=sched_capacity,
            # page-denominated admission (oversubscription makes row
            # capacity a fiction): reject what the whole pool could
            # never hold; spec decode's k-past-the-index verify writes
            # are headroom columns, mirroring the row-capacity reserve
            page_size=self.pool.page_size if self._paged else None,
            num_pages=self.pool.num_pages if self._paged else None,
            page_headroom=(self._spec.k if self._spec is not None else 0))
        # priority: None/False (plain FIFO), True (default classes), a
        # PriorityConfig kwargs dict, or an instance. Imported lazily:
        # frontend/ imports serving modules, so a top-level import here
        # would be circular.
        if priority:
            from .frontend.priority import PriorityScheduler
            self.scheduler = PriorityScheduler(
                num_slots, priority=priority, clock=self._now, **sched_kw)
        else:
            self.scheduler = FIFOScheduler(num_slots, **sched_kw)
        self._priority = getattr(self.scheduler, "config", None) \
            if priority else None
        # -- telemetry -------------------------------------------------
        # given none, the server records into the process-wide tracer,
        # which is ON (an event ~0.4 us over the ~1 us its span takes to
        # time itself, 11 events a plain decode step; the ring holds
        # 131,072: telemetry/tracer.py); an explicit
        # Tracer(enabled=False) silences the ring
        if tracer is True:
            tracer = Tracer()
        self.tracer = tracer if tracer is not None else default_tracer()
        if self.tracer is default_tracer():
            watch_gc()      # host/gc: a full collection inside a step
        self.registry = registry if registry is not None else MetricsRegistry()
        self.step_id = 0                 # monotonic scheduler-step counter
        self.timelines = TimelineStore(tracer=self.tracer)
        self.watchdog = RecompileWatchdog(
            registry=self.registry, tracer=self.tracer, monitor=monitor,
            strict=strict_recompile, step_fn=lambda: self.step_id)
        self.metrics = ServingMetrics(monitor, registry=self.registry,
                                      step_fn=lambda: self.step_id)
        # -- efficiency & goodput telemetry (ISSUE 8) ------------------
        # cost_model: False (off), True (defaults), a ProgramCostModel
        # kwargs dict, or an instance. Off by default: the lazy AOT
        # harvest compiles each program once more, a warmup cost test
        # suites constructing many servers shouldn't pay.
        if cost_model is True:
            cost_model = ProgramCostModel(registry=self.registry)
        elif isinstance(cost_model, dict):
            cost_model = ProgramCostModel(registry=self.registry,
                                          **cost_model)
        elif not cost_model:
            cost_model = None
        self.costs = cost_model
        # _ensure_watch subscribes the cost model to every watched jit
        self.watchdog.cost_model = self.costs
        # slo: None/False (off), True (default SLOConfig), dict/SLOConfig
        self.slo = (SLOTracker(slo, registry=self.registry,
                               tracer=self.tracer, monitor=monitor)
                    if slo else None)
        # flight_recorder: True (defaults), int capacity, kwargs dict,
        # an instance, or False. Default ON — one deque append per step.
        if flight_recorder is True:
            flight_recorder = FlightRecorder(dump_dir=dump_dir)
        elif isinstance(flight_recorder, bool):
            flight_recorder = None
        elif isinstance(flight_recorder, int):
            flight_recorder = FlightRecorder(capacity=flight_recorder,
                                             dump_dir=dump_dir)
        elif isinstance(flight_recorder, dict):
            flight_recorder = FlightRecorder(
                **{"dump_dir": dump_dir, **flight_recorder})
        elif flight_recorder is not None and dump_dir is not None \
                and flight_recorder.dump_dir is None:
            flight_recorder.dump_dir = dump_dir
        self.recorder = flight_recorder
        self.dump_dir = dump_dir
        self._tokens_emitted = 0        # lifetime tokens (all paths)
        self._tokens_prev = 0           # snapshot for per-step deltas
        self._after_step_ns = 0         # total of serving/after_step
        # the step in flight, from its spans: when it opened, host
        # nanoseconds by phase (measured, each from spans that closed:
        # boundary, grant, pages, prepare, enqueue, sync, replay add up
        # to the step less its after-step; exposed lies across them),
        # the calls that handed the device work, and the programs it
        # dispatched (the serving/step span's attributes at its close)
        self._step_t0_ns = 0
        self._phase_ns: dict = {}
        self._device_calls = 0
        self._dispatched: dict = {}
        # when the last sync of the step before ended, until the step in
        # flight has queued its first program (None: that step had no
        # sync, or this is the first: the device is not known idle)
        self._sync_end_ns: Optional[int] = None
        self._exposed_from_ns: Optional[int] = None
        # every device call of the pool goes through the same account
        self.pool.enqueue = self._enqueue
        # fleet identity: assigned by ReplicaRouter at join time, stamped
        # onto every timeline event so cross-replica journeys stitch
        self.replica_id: Optional[int] = None
        # accumulated step wall — the overhead_pct denominator when no
        # cost model is attached (the fleet aggregator's fallback)
        self.step_wall_s = 0.0
        self.registry.add_collector(self._collect_telemetry_health)
        if self._state_row_bytes:
            self.registry.gauge("serving/state_bytes_resident").set(
                float(self._state_row_bytes * num_slots))
        if self._paged:
            # pool-internal events (CoW copies, trie evictions) land in
            # the same registry as the engine-side paging/* series
            self.pool.registry = self.registry
            if self._latent_token_bytes:
                self.registry.add_collector(
                    lambda: self.registry.gauge(
                        "serving/latent_pages_mapped").set(float(
                            self.pool.num_pages - self.pool.free_page_count)))
        # -- resilience ------------------------------------------------
        if deadline_default_ms is not None and deadline_default_ms <= 0:
            raise ValueError(f"deadline_default_ms must be > 0, got "
                             f"{deadline_default_ms}")
        if step_wall_budget_ms is not None and step_wall_budget_ms <= 0:
            raise ValueError(f"step_wall_budget_ms must be > 0, got "
                             f"{step_wall_budget_ms}")
        if preempt_queue_threshold is not None and preempt_queue_threshold < 1:
            raise ValueError(f"preempt_queue_threshold must be >= 1, got "
                             f"{preempt_queue_threshold}")
        self.deadline_default_ms = deadline_default_ms
        self.step_wall_budget_ms = step_wall_budget_ms
        self.preempt_queue_threshold = preempt_queue_threshold
        self.preempt_min_run_steps = int(preempt_min_run_steps)
        self._degradation = DegradationConfig.from_value(degradation)
        self._load = (LoadStateMachine(self._degradation)
                      if self._degradation is not None else None)
        self.faults = fault_injector
        if self.faults is not None and self._drafter is not None:
            # surface drafter faults exactly where a real drafter throws
            self._drafter = FaultInjectingDrafter(self._drafter, self.faults)
        # one tiny always-fixed-shape program: (num_slots,) bool of "is
        # every logit in this row finite". Guarding decode logits (not
        # every intermediate) catches poisoned rows before their token
        # is committed, at one watched jit and zero recompiles.
        if guard_numerics:
            self._jit_finite = jax.jit(
                lambda l: jnp.all(jnp.isfinite(l),
                                  axis=tuple(range(1, l.ndim))))
        else:
            self._jit_finite = None
        # -- stall-free admission config -------------------------------
        if prefill_chunk < 0:
            raise ValueError(f"prefill_chunk must be >= 0, got "
                             f"{prefill_chunk}")
        chunk = min(int(prefill_chunk), self.pool.capacity)
        # chunk starts are multiples of the chunk width, so requiring
        # capacity % chunk == 0 guarantees start + chunk <= capacity for
        # every chunk — the row's dynamic-update-slice can never clamp
        # and smear the final columns. Auto-halve rather than error:
        # chunk width is a latency knob, not a correctness contract.
        while chunk > 1 and self.pool.capacity % chunk != 0:
            chunk //= 2
        self._stall_free = (chunk > 0 and
                            getattr(engine, "_jit_prefill_chunk", None)
                            is not None)
        self.prefill_chunk = chunk if self._stall_free else 0
        if self._stall_free:
            budget = (2 * chunk if prefill_token_budget is None
                      else int(prefill_token_budget))
            if budget < chunk:
                raise ValueError(
                    f"prefill_token_budget ({budget}) must be >= "
                    f"prefill_chunk ({chunk}); a smaller budget could "
                    f"never schedule the in-flight chunk")
            self.prefill_token_budget = budget
        else:
            self.prefill_token_budget = None
        # prefix-hit seating rides the chunked-prefill path (a hit seats
        # PREFILLING at its uncached suffix), so it needs stall-free mode
        self._use_prefix = (self._paged and self.pool.prefix is not None
                            and self._stall_free)
        if self._paged:
            # build the paged gather/scatter jits now so _ensure_watch
            # wraps them before any traffic
            self.pool.bind_engine(engine)
        # FIFO of seated PREFILLING requests whose prompts are still
        # streaming in chunk by chunk; step() advances the head only
        self._prefill_queue: List[Request] = []
        # the chunk a step has prepared and left to its decode dispatch:
        # (request, ids, position, length, when its preparation began)
        self._chunk_beside: Optional[tuple] = None
        self.temperature = cfg.temperature if temperature is None else temperature
        self.top_k = cfg.top_k if top_k is None else top_k
        self.top_p = cfg.top_p if top_p is None else top_p
        self._greedy = jnp.asarray(not do_sample)
        # the sampler's key lives on the device: the sampler and verify
        # programs take it, split it inside and hand the next one back
        self._rng = jax.device_put(jax.random.PRNGKey(seed),
                                   engine.key_sharding)
        # device twins of what the host rarely changes, each beside the
        # host value it was put from: the temperature, and a state
        # model's ``rows`` with the running slots it names
        self._temperature_dev: tuple = (None, None)
        self._rows_dev: tuple = (None, None)
        self._slot_req: dict = {}                      # slot -> Request
        self._current = np.zeros((num_slots,), np.int32)  # last token per slot
        # device twin of _current: decode/spec dispatch read it so a step
        # never blocks on the previous step's sampled token reaching the
        # host. The host copy is refreshed at the single end-of-step fetch.
        # device_put with the mesh's replicated sharding (not jnp.zeros)
        # so the array is COMMITTED and placed exactly like the jit
        # outputs that later replace it — an uncommitted or
        # single-device first arg would give _jit_cur_scatter a second
        # cache entry for the same shapes, a recompile the watchdog
        # rightly flags.
        # canonical placement for the twin: the pool's resolved ``index``
        # sharding (slots over `data` when the mesh and count allow,
        # replicated otherwise — so TP=1/DP=1 keeps today's placement
        # bitwise). EVERY producer of _cur_dev is pinned to it; GSPMD is
        # otherwise free to hand back the sampler's batch-sharded layout
        # and fork _jit_cur_scatter the first time an admission lands
        # after a decode (warmup can't sweep that ordering).
        self._cur_sharding = (
            self._pool_sharding("index", np.zeros((num_slots,), np.int32))
            if callable(self._pool_sharding) else self._rep_sharding())
        self._cur_dev = jax.device_put(
            np.zeros((num_slots,), np.int32), self._cur_sharding)
        self._jit_cur_scatter = jax.jit(
            lambda cur, tok, slots: cur.at[slots].set(tok, mode="drop"),
            out_shardings=self._cur_sharding)
        # after a verify step the new current token for row b is the last
        # *emitted* token: out[b, n_emit[b]-1] (n_emit >= 1 for live rows;
        # the max() guards masked rows, whose value is never surfaced)
        self._jit_spec_cur = jax.jit(
            lambda out, n_emit: jnp.take_along_axis(
                out, jnp.maximum(n_emit - 1, 0)[:, None],
                axis=1)[:, 0].astype(jnp.int32),
            out_shardings=self._cur_sharding)
        # -- disaggregated prefill/decode role (ISSUE 19) --------------
        # "both" is the classic colocated engine. "prefill" runs
        # admission/chunked prefill only and parks each request once its
        # pages are full and its first token sampled (see
        # pending_handoffs); "decode" additionally accepts adopted
        # requests whose prefill ran elsewhere. Roles change NO jit
        # signature — every program is built and warmed identically, a
        # prefill engine simply never dispatches the decode ones.
        if role not in ("both", "prefill", "decode"):
            raise ValueError(f"role must be both|prefill|decode, "
                             f"got {role!r}")
        if role != "both" and not self._paged:
            raise ValueError("prefill/decode roles require paged_kv: "
                             "pages are the cross-replica handoff unit")
        self.role = role
        # prefill role: seated RUNNING requests whose prompts are fully
        # paged in and first token sampled, awaiting transfer to a
        # decode replica (they hold their slot+pages until adopted)
        self._handoff_ready: Optional[List[Request]] = \
            [] if role == "prefill" else None
        # pre-warm every reachable cur-scatter width NOW, before the
        # watchdog attaches below: singles scatter (1,) and batched
        # admissions scatter the power-of-two group buckets, a bounded
        # family warmup traffic cannot be relied on to sweep (an engine
        # warmed on sequential requests would otherwise compile its
        # first batched bucket under load)
        rep = self._rep_sharding()
        nb = 1
        while True:
            self._jit_cur_scatter(
                self._cur_dev,
                self._cur_commit(np.zeros((nb,), np.int32)),
                jnp.asarray(np.full((nb,), num_slots, np.int32)))
            if nb >= num_slots:
                break
            nb *= 2
        if self._spec is not None:
            self._jit_spec_cur(
                jax.device_put(np.zeros((num_slots, self._spec.k + 1),
                                        np.int32), rep),
                jax.device_put(np.ones((num_slots,), np.int32), rep))
        # deferred host work: (device_arrays, callback) pairs queued at
        # dispatch time and replayed, in dispatch order, when their step
        # is settled. ``_deferred`` is what the step being queued has
        # gathered; sealed with the routed FFN's counters it becomes the
        # bundle ``_in_flight``, which the NEXT step settles after it has
        # queued its own programs (:meth:`_settle`)
        self._deferred: List[Any] = []
        self._in_flight: Optional[_Bundle] = None
        # tokens queued on the device that the host has not read, by
        # request id, and the requests whose LAST token by the host's own
        # count (max_new_tokens, the slot's capacity) is among them: such
        # a request takes no further row (:meth:`_runs`)
        self._unread: dict = {}
        self._closing: set = set()
        # requests that finished since the last step() or settle()
        # handed its list out
        self._finished: List[Request] = []
        # whether the step's first program was queued with the step
        # before unsettled (None: it has queued none yet), and whether
        # that step was done by then: the device had run dry
        self._ahead: Optional[bool] = None
        self._dry = False
        # a counter track's last recorded sample, by name
        self._track_last: dict = {}
        self._next_id = 0
        self._ensure_watch()
        log_dist(f"ServingEngine: slots={num_slots} "
                 f"capacity={self.pool.capacity} "
                 f"max_queue_depth={max_queue_depth} "
                 f"admission={'stall-free chunk=%d budget=%d' % (self.prefill_chunk, self.prefill_token_budget) if self._stall_free else 'serial'}",
                 ranks=[0])

    # ------------------------------------------------------------------
    def _ensure_watch(self) -> None:
        """(Re-)attach the recompile watchdog to every jitted entry point.

        Idempotent and cheap (a handful of getattr/isinstance checks);
        called once per step because ``_jit_verify_k`` is created lazily
        on the first speculative verify and tests swap jits in and out."""
        wd = self.watchdog
        for attr in _WATCHED_ENGINE_JITS:
            wd.attach(self.engine, attr, name=f"InferenceEngine.{attr}")
        for attr in _WATCHED_POOL_JITS:
            wd.attach(self.pool, attr, name=f"SlotPool.{attr}")
        for attr in _WATCHED_SERVING_JITS:
            wd.attach(self, attr, name=f"ServingEngine.{attr}")
        if self._drafter is not None:
            # unwrap the fault-injection shim; the jit lives on the
            # real drafter
            drafter = getattr(self._drafter, "inner", self._drafter)
            for attr in _WATCHED_DRAFTER_JITS:
                wd.attach(drafter, attr, name=f"Drafter.{attr}")

    @property
    def _fuses_chunks(self) -> bool:
        """Whether a chunk beside running slots goes with the decode rows
        as ONE program, by what this server is: a paged pool whose chunk
        reads its pages in place (``PagedKVPool.fuses``), plain decoding
        and a role that decodes."""
        return (self._paged and self._spec is None
                and self.role != "prefill" and self.prefill_chunk > 0
                and self.pool.fuses(self.prefill_chunk))

    @property
    def _runs_ahead(self) -> bool:
        """Whether a step leaves its bundle in flight for the next to
        settle, by what this server is: plain decoding (a drafter reads
        the host's histories, so a speculative step needs its own tokens)
        on a colocated engine (of a disaggregated pair the prefill side
        parks a request on its first token's VALUE, and the decode side
        adopts mid-stream)."""
        return self._spec is None and self.role == "both"

    def end_warmup(self) -> None:
        """Declare warmup traffic over: from here on, any recompile counts
        against :attr:`watchdog` ``.recompiles`` (and raises in strict
        mode at the next step boundary). A server that fuses a chunk with
        the decode rows first brings that program in itself
        (``PagedKVPool.warm_chunk_decode``): warm-up traffic that drains
        a request at a time never puts a chunk beside a running slot."""
        if self._fuses_chunks:
            rows = (self._cur_commit(np.full((self.pool.num_slots,), -1,
                                             np.int32)),) \
                if self._state_row_bytes else ()
            self.pool.warm_chunk_decode(self.engine, self.prefill_chunk,
                                        self._cur_dev, *rows)
        self.watchdog.end_warmup()

    # -- warmup signature manifest (graftcheck witness) -----------------
    def _signature_env(self) -> dict:
        """The serving config knobs that determine the reachable jit
        signature set — the ``configs`` entry graftcheck re-enumerates
        under when diffing a manifest (analysis/interp.py drivers)."""
        pool = self.pool
        return {
            "num_slots": int(pool.num_slots),
            "capacity": int(pool.capacity),
            "prefill_chunk": int(self.prefill_chunk or 0),
            "prefill_token_budget": int(self.prefill_token_budget or 0),
            "paged": bool(self._paged),
            "paged_kernel": str(getattr(pool, "kernel", "off"))
            if self._paged else "off",
            "paged_kernel_active": bool(getattr(pool, "kernel_active",
                                                False)),
            "page_size": int(getattr(pool, "page_size", 0) or 0),
            "num_pages": int(getattr(pool, "num_pages", 0) or 0),
            "pages_per_slot": int(getattr(pool, "pages_per_slot", 0) or 0),
            "top_k": int(self.top_k or 0),
            "top_p": float(self.top_p),
            "temperature": float(self.temperature),
            "greedy": bool(np.asarray(self._greedy)),
            "spec_k": int(self._spec.k) if self._spec is not None else 0,
            "guard_numerics": self._jit_finite is not None,
            "use_prefix": bool(self._use_prefix),
            "stall_free": bool(self._stall_free),
            # role never moves a traced shape (same warmups, same
            # programs; a prefill engine just skips the decode
            # dispatch) — recorded for arm attribution like the mesh
            "role": str(self.role),
            # mesh shape the caches/params were committed under. The
            # jitted entries keep their signatures across mesh shapes
            # (the tentpole invariant — only in/out shardings move), so
            # the interp drivers ignore these keys; they are recorded so
            # a manifest diff can attribute a mismatch to the arm that
            # produced it.
            "mesh_data": int(self._mesh_axis_size("data")),
            "mesh_model": int(self._mesh_axis_size("model")),
        }

    def _mesh_axis_size(self, axis: str) -> int:
        mesh = getattr(self.engine, "mesh", None)
        if mesh is None:
            return 1
        return int(dict(mesh.shape).get(axis, 1))

    def export_signatures(self, path: str, merge: bool = False,
                          extra: Optional[dict] = None) -> dict:
        """Write (or merge into) a ``signatures.json`` warmup manifest:
        ``{"version": 1, "configs": [env...], "programs": {name:
        [sorted sigs]}}``.

        ``merge=True`` unions with an existing file — a caller may run
        several servers against one shared inference engine, so
        the shared engine jits see every arm's traffic and the manifest
        is only meaningful as the union.  ``extra`` adds workload keys
        the config alone cannot know (vocab size, prompt-length sweep
        bounds)."""
        import json
        import os

        env = self._signature_env()
        if extra:
            env.update(extra)
        programs = self.watchdog.signature_manifest()
        doc = {"version": 1, "configs": [env], "programs": programs}
        if merge and os.path.exists(path):
            with open(path, encoding="utf-8") as fh:
                old = json.load(fh)
            configs = [c for c in old.get("configs", []) if c != env]
            doc["configs"] = configs + [env]
            merged = {k: set(v) for k, v in old.get("programs", {}).items()}
            for name, sigs in programs.items():
                merged.setdefault(name, set()).update(sigs)
            doc["programs"] = {name: sorted(sigs)
                               for name, sigs in sorted(merged.items())}
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(doc, fh, indent=2, sort_keys=True)
            fh.write("\n")
        return doc

    def set_tracer(self, tracer) -> None:
        """Swap the tracer in post-construction (e.g. a traced replay on
        an already-warmed server)."""
        self.tracer = tracer
        self._track_last = {}       # its counter tracks start afresh
        self.timelines.tracer = tracer
        self.watchdog.tracer = tracer
        if self.slo is not None:
            self.slo.tracer = tracer

    def timeline(self, request_id: int):
        """Lifecycle events recorded for one request id (oldest first),
        or None if the id is unknown/evicted."""
        return self.timelines.get(request_id)

    def publish_telemetry(self) -> int:
        """Flush the metrics registry as ``telemetry/*`` monitor events
        on the current step axis; returns the number of events."""
        return self.registry.publish(self.metrics.monitor, self.step_id)

    # -- efficiency / goodput / flight recorder (ISSUE 8) --------------
    def _collect_telemetry_health(self) -> None:
        """Registry collector (runs at every snapshot/Prometheus
        scrape): pull-time counters that would be wasteful to push from
        the hot path — tracer ring totals/drops, JSONL sink write
        errors, flight-recorder activity."""
        g = self.registry.gauge
        g("telemetry/tracer_events_total").set(float(self.tracer.events_total))
        g("telemetry/tracer_dropped").set(float(self.tracer.dropped))
        mon = self.metrics.monitor
        jm = getattr(mon, "jsonl_monitor", None)
        if jm is None and hasattr(mon, "write_errors"):
            jm = mon          # a bare JSONLMonitor passed as the sink
        if jm is not None:
            g("monitor/jsonl_write_errors").set(
                float(getattr(jm, "write_errors", 0)))
        if self.recorder is not None:
            g("telemetry/flight_recorder_records").set(
                float(self.recorder.records_total))
            g("telemetry/postmortem_dumps").set(
                float(self.recorder.dump_count))

    @property
    def telemetry_overhead_s(self) -> float:
        """Host seconds spent in the instrumentation: the total of the
        ``serving/after_step`` spans (paging gauges, SLO / cost-model /
        flight-recorder bookkeeping, the recompile gate) plus the cost
        model's per-call accounting and the SLO tracker's observe/on_step
        work (one-time AOT harvests are excluded — they are warmup,
        reported separately in ``costs.summary()['harvest_s']``)."""
        total = self._after_step_ns / 1e9
        if self.costs is not None:
            total += self.costs.overhead_s
        if self.slo is not None:
            total += self.slo.overhead_s
        return total

    def _telemetry_step(self, wall: float, running_at_entry: int,
                        granted: List[Request]) -> None:
        """Step-boundary efficiency/SLO/flight-recorder bookkeeping
        (timed by the ``serving/after_step`` span it runs under)."""
        costs, slo, rec = self.costs, self.slo, self.recorder
        if costs is None and slo is None and rec is None:
            return
        tokens = self._tokens_emitted - self._tokens_prev
        self._tokens_prev = self._tokens_emitted
        if slo is not None:
            if running_at_entry:
                slo.observe_gap(wall)
            slo.on_step(self.step_id)
        if costs is not None:
            costs.step_update(wall, tokens=tokens)
            if self.step_id % costs.kv_every == 0:
                costs.reconcile_kv(self.pool, monitor=self.metrics.monitor,
                                   step=self.step_id, tracer=self.tracer)
        if rec is not None:
            rec.record(self._step_record(granted))

    def _step_record(self, granted: List[Request]) -> dict:
        rec = {
            "step_id": self.step_id,
            "t_unix": time.time(),
            # the shared injected clock: fleet post-mortems align every
            # replica's ring on this axis, not the per-replica step_id
            "t": self._now(),
            "replica": self.replica_id,
            # where the step went, from its spans: the time since
            # serving/step opened, and the phases closed so far
            "wall_ms": (time.perf_counter_ns() - self._step_t0_ns) / 1e6,
            "phases_ms": {k: v / 1e6 for k, v in self._phase_ns.items()},
            # whether the device had finished the step before when this
            # one's first program was called: `exposed` was the chip's
            # idle time on this step
            "dry": int(self._dry),
            "dispatched": dict(self._dispatched),
            "live": len(self._slot_req),
            "pending": self.scheduler.pending,
            "prefilling": len(self._prefill_queue),
            "free_slots": self.pool.free_count,
            "granted": [r.request_id for r in granted],
            "finished": [r.request_id for r in self._finished],
            "tokens_total": self._tokens_emitted,
            "load_state": (self._load.state.name
                           if self._load is not None else None),
            "alert_state": (self.slo.alert_state
                            if self.slo is not None else None),
        }
        if self._paged:
            rec["free_pages"] = self.pool.free_page_count
        return rec

    def _post_mortem(self, reason: str, error: Any = None,
                     extra: Optional[dict] = None) -> Optional[str]:
        """Write a flight-recorder post-mortem dump (no-op without a
        recorder or ``dump_dir``); never raises — the caller is already
        unwinding the real failure."""
        if self.recorder is None:
            return None
        try:
            return self.recorder.dump(
                reason, error=error, timelines=self.timelines,
                registry=self.registry, tracer=self.tracer, extra=extra)
        except Exception:       # pragma: no cover - defensive
            return None

    def debug_dump(self) -> dict:
        """Live statusz snapshot: the flight-recorder ring, open
        request timelines, registry, watchdog summary, every
        non-terminal request's host state, and (when enabled) the SLO
        and cost-model summaries — the same payload a post-mortem file
        wraps, served from a healthy process."""
        rec = self.recorder if self.recorder is not None \
            else FlightRecorder(capacity=1)
        out = rec.snapshot(timelines=self.timelines,
                           registry=self.registry, tracer=self.tracer)
        out.update(step_id=self.step_id, live=self.live_count,
                   pending=self.scheduler.pending,
                   requests=self._stuck_dump(),
                   load_state=(self._load.state.name
                               if self._load is not None else None),
                   watchdog=self.watchdog.summary(),
                   telemetry_overhead_s=self.telemetry_overhead_s)
        if self.slo is not None:
            out["slo"] = self.slo.snapshot()
        if self.costs is not None:
            out["costs"] = self.costs.summary()
        return out

    def efficiency_snapshot(self) -> dict:
        """Bench-facing rollup: cost-model MFU/bandwidth, SLO goodput +
        digest percentiles, KV HBM reconciliation, and instrumentation
        overhead (as a fraction of accumulated step wall)."""
        out: dict = {"telemetry_overhead_s": self.telemetry_overhead_s}
        wall = None
        if self.costs is not None:
            # pull-time freshness: the loop reconciles only every
            # kv_every steps, a snapshot should never serve stale drift
            self.costs.reconcile_kv(self.pool, step=self.step_id)
            cs = self.costs.summary()
            wall = cs["wall_s"]
            out["costs"] = cs
            out["mfu"] = cs["mfu"]
            out["bandwidth_util"] = cs["bandwidth_util"]
            hbm = cs["hbm"]
            out["hbm_drift"] = hbm.get("hbm_drift")
            out["hbm_peak_bytes"] = hbm.get("hbm_peak_bytes")
        if self.slo is not None:
            ss = self.slo.snapshot()
            out["slo"] = ss
            out["goodput_slo"] = ss["goodput_slo"]
            out["ttft_p99_ms"] = ss["ttft_p99_ms"]
            out["gap_p99_ms"] = ss["gap_p99_ms"]
            out["alert_state"] = ss["alert_state"]
        if not wall:
            # no cost model: fall back to the accumulated step wall so
            # overhead_pct is still honest on SLO-only configurations
            wall = self.step_wall_s
        if wall:
            out["overhead_pct"] = 100.0 * out["telemetry_overhead_s"] / wall
        return out

    def reset_efficiency_window(self) -> None:
        """Zero cost-model totals, SLO windows, and overhead clocks
        (harvested program costs are kept) — benches call this after
        warmup so efficiency numbers cover only the measured run."""
        if self.costs is not None:
            self.costs.reset_totals()
        if self.slo is not None:
            self.slo.reset()
        self._after_step_ns = 0
        self.step_wall_s = 0.0
        self._tokens_prev = self._tokens_emitted

    def _chaos_corrupt_state(self) -> None:
        """Chaos-only (the ``state_corruption`` fault point):
        deliberately corrupt slot bookkeeping — a seated slot marked
        free, or a free slot dropped — so the ``check_invariants``
        audit and the flight recorder behind it are proven against REAL
        corruption. Only reachable through an armed FaultInjector."""
        if self._slot_req:
            self.pool._free_set.add(min(self._slot_req))
        elif self.pool._free_set:
            self.pool._free_set.discard(min(self.pool._free_set))
        self.tracer.instant("chaos/state_corruption")

    @property
    def live_count(self) -> int:
        return len(self._slot_req)

    @property
    def pending(self) -> int:
        return self.scheduler.pending

    def submit(self, prompt, max_new_tokens: int = 32,
               eos_token_id: Optional[int] = None,
               deadline_ms: Optional[float] = None,
               priority: Optional[str] = None,
               tenant: Optional[str] = None) -> Request:
        """Enqueue one generation request. Never raises on load: admission
        control marks the returned request ``REJECTED`` with a
        ``reject_reason`` (``"queue_full"``, ``"prompt_too_long"``,
        ``"rate_limited"``/``"tenant_quota"`` under tenant policies, or
        ``"retry_after"`` when overload or burn-rate shedding is active
        — then ``req.retry_after_s`` carries the backoff hint) so
        callers can shed or retry.

        ``priority``/``tenant`` (priority scheduling only) pick the
        request's class and rate-limit bucket; an unknown class raises
        ``ValueError``. Burn-rate shedding: when a class's SLO burn
        alert is at warn/page, submissions of STRICTLY LOWER classes are
        shed with ``retry_after`` — the error budget of a paying tier is
        defended by refusing work that would preempt it anyway.

        ``deadline_ms`` (or the engine-wide ``deadline_default_ms``)
        arms a TTL from submission: a request that can't finish in time
        retires with ``finish_reason="deadline"`` — out of the queue
        before ever costing a prefill, or out of its slot via the usual
        release/masking rollback."""
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{max_new_tokens}")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        req = Request(self._next_id, prompt, max_new_tokens, eos_token_id)
        self._next_id += 1
        if self._priority is not None:
            req.priority_class = (priority if priority is not None
                                  else self._priority.default_class)
            self.scheduler.rank_of(req.priority_class)  # loud on unknown
        elif priority is not None:
            raise ValueError("priority classes require a priority-enabled "
                             "engine (pass priority=True/config to "
                             "ServingEngine / init_serving)")
        if tenant is not None:
            req.tenant = str(tenant)
        req.submit_time = self._now()
        ttl = deadline_ms if deadline_ms is not None \
            else self.deadline_default_ms
        if ttl is not None:
            if ttl <= 0:
                raise ValueError(f"deadline_ms must be > 0, got {ttl}")
            req.deadline_ms = float(ttl)
            req.deadline_time = req.submit_time + float(ttl) / 1e3
        if self._load is not None and self._load.state is LoadState.OVERLOADED:
            # overload shedding: stop feeding the queue before it melts;
            # rejected-with-retry_after is cheaper for everyone than an
            # accepted request that will blow its deadline anyway
            accepted, reason = False, RejectReason.RETRY_AFTER
            req.retry_after_s = self._degradation.retry_after_s
        elif self._shed_by_burn(req):
            accepted, reason = False, RejectReason.RETRY_AFTER
            if req.retry_after_s is None:
                req.retry_after_s = (
                    self._degradation.retry_after_s
                    if self._degradation is not None else 1.0)
        else:
            accepted, reason = self.scheduler.submit(req)
        self.timelines.record(req.request_id, "submitted",
                              prompt_len=req.prompt_len,
                              max_new_tokens=max_new_tokens,
                              priority_class=req.priority_class,
                              tenant=req.tenant)
        if not accepted:
            req.state = RequestState.REJECTED
            req.reject_reason = reason
            self.metrics.record_rejection(req)
            self.timelines.record(req.request_id, "rejected", terminal=True,
                                  reason=reason.value,
                                  retry_after_s=req.retry_after_s)
        elif self.slo is not None:
            # goodput denominator: every ADMITTED request counts against
            # the window, whether or not it ever finishes in time
            self.slo.observe_admitted(cls=req.priority_class)
        return req

    def _shed_floor(self) -> Optional[int]:
        """The lowest class rank still admitted under burn-rate
        shedding, or None when nothing is burning (or priority/SLO
        tracking is off). When class ``k``'s burn alert is warn/page,
        every class ranked strictly below ``k`` is shed — the floor is
        the highest-priority burning class's own rank."""
        if self._priority is None or self.slo is None:
            return None
        floor = None
        for cls, alert in self.slo.class_alerts.items():
            if alert in ("warn", "page"):
                try:
                    k = self.scheduler.rank_of(cls)
                except ValueError:
                    continue  # SLO classes need not all be sched classes
                floor = k if floor is None else min(floor, k)
        return floor

    def _shed_by_burn(self, req: Request) -> bool:
        floor = self._shed_floor()
        return floor is not None \
            and self.scheduler.rank_of(req.priority_class) > floor

    # ------------------------------------------------------------------
    @staticmethod
    def _rep_sharding():
        """Replicated NamedSharding on the global mesh — the placement
        every serving jit output carries, so host-built device arrays
        (the current-token twin) share a jit cache entry with them."""
        from ..parallel import mesh as mesh_mod
        return NamedSharding(mesh_mod.get_mesh(), PartitionSpec())

    def _cur_commit(self, arr):
        """Commit a current-token-family array (any width) to the same
        resolved slots placement the pool's ``index`` leaf carries —
        shape-aware, so a (1,) single-admission token stays replicated
        while a full-width batch shards with the pool. Pinning every
        producer keeps ``_jit_cur_scatter`` at one executable per width
        no matter what layout GSPMD picked for the sampler output."""
        if callable(self._pool_sharding):
            # the resolver reads the shape alone, so it gets the device
            # array itself: np.asarray(arr) would fetch the tokens, a
            # blocking sync in the middle of the step's dispatches
            sh = self._pool_sharding(
                "index", arr if hasattr(arr, "shape") else np.asarray(arr))
        else:
            sh = self._rep_sharding()
        with self._enqueue("cur_commit", "transfer"):
            return jax.device_put(arr, sh)

    def _enqueue(self, program: str, kind: str = "program"):
        """Count one device call of the step and, for a jitted program,
        open the span around it (see :class:`_Enqueue`); the pool makes
        its calls through this too. A put or an eager operation is
        counted and nothing else, and so is every call while the tracer's
        ring is off: no span, no annotation, no clock read."""
        self._device_calls += 1
        if kind != "program":
            return NO_SPAN
        if self._ahead is None:     # the step's first program
            bundle = self._in_flight
            self._ahead = bundle is not None
            # the step before is done already and its successor not yet
            # called: the chip idles until this call lands. One
            # non-blocking query of the bundle's last-queued array (a
            # step with nothing in flight is dry by construction, and
            # says so through `in_flight`)
            self._dry = bundle is not None and bundle.ready()
        if not self.tracer.enabled:
            return NO_SPAN
        return _Enqueue(self, program)

    def _track(self, name: str, **values) -> None:
        """A counter track's sample, recorded when a level differs from
        the track's last recorded one and every 256th step besides (so an
        exported ring that has wrapped still shows the level): the
        staircase Perfetto draws is the one a sample a step drew."""
        if not self.tracer.enabled:
            return
        if self._track_last.get(name) != values or not self.step_id % 256:
            self._track_last[name] = values
            self.tracer.counter(name, **values)

    def _phase(self, phase: str, name: str, **attrs) -> _Phase:
        """``tracer.span(name, **attrs)`` whose time outside its enqueue
        children the step's account takes under ``phase``."""
        return _Phase(self, phase, name, attrs or None)

    def _temperature(self):
        """``self.temperature`` as a device scalar, put again only when
        the attribute has another value than the one it was put from."""
        value, dev = self._temperature_dev
        if value != self.temperature:
            value = self.temperature
            with self._enqueue("temperature", "transfer"):
                dev = jax.device_put(np.float32(value))
            self._temperature_dev = (value, dev)
        return dev

    def _sample_dev(self, logits):
        """Dispatch the sampler and return the token *device* array: ONE
        device call, the program. What it needs beside the logits the
        device holds already: the key, which the program splits itself
        and hands back for the next call (the same threefry split the
        host used to run as a program of its own, so the same sub-keys
        and tokens), and the temperature's device scalar
        (:meth:`_temperature`).

        No host sync happens here: callers stash the array (plus a
        closure that needs its host value) via :meth:`_defer`, and the
        single blocking fetch at the end of :meth:`step` replays every
        closure in dispatch order. Per-row sampling is independent
        (``categorical``/``argmax`` act row-wise on one split key), so
        batching rows from different call sites cannot change values."""
        temperature = self._temperature()
        with self._enqueue("sample"):
            self._rng, tokens = self.engine._jit_sample(
                logits, self._rng, temperature, int(self.top_k),
                float(self.top_p), self._greedy)
        return tokens

    def _defer(self, arrays, callback) -> None:
        """Queue ``callback(*host_values)`` until the step is settled.

        ``arrays`` is a list of device arrays; the callback receives the
        same list as host arrays, out of the bundle's one fetch."""
        self._deferred.append((list(arrays), callback))

    def _queued_token(self, req: Request) -> None:
        """A token of ``req`` is queued on the device (its slot's position
        has moved already). If the host can count that it is the last, by
        ``max_new_tokens`` or by the slot's capacity, the request is
        closing: it takes no row after this one, and
        :meth:`_maybe_retire` finds it counted out when the token is
        read."""
        rid = req.request_id
        unread = self._unread[rid] = self._unread.get(rid, 0) + 1
        if len(req.output_tokens) + unread >= req.max_new_tokens \
                or int(self.pool.starts[req.slot]) >= self.pool.capacity:
            self._closing.add(rid)

    def _read_token(self, req: Request) -> None:
        """The replay has the value of one queued token of ``req``."""
        rid = req.request_id
        self._unread[rid] -= 1
        if not self._unread[rid]:
            del self._unread[rid]

    def _runs(self, req: Request) -> bool:
        """Whether a seated request takes a row of the next decode
        program: it is RUNNING and the host has not counted its end among
        the tokens in flight."""
        return req.state is RequestState.RUNNING \
            and req.request_id not in self._closing

    def _seal(self) -> Optional[_Bundle]:
        """What the step has queued for the host, as one bundle (None:
        nothing). The routed FFN's counters ride with the tokens."""
        moe_stats = []
        if self._paged and self.pool.moe_stats:
            moe_stats, self.pool.moe_stats = self.pool.moe_stats, []
        if not self._deferred and not moe_stats:
            return None
        pending, self._deferred = self._deferred, []
        return _Bundle(self.step_id, pending, moe_stats, self._dispatched)

    def _settle(self, bundle: Optional[_Bundle]) -> None:
        """Bring the host up to one bundle: ONE wait for its arrays
        (``serving/sync`` times exactly that), one fetch of them all, the
        replay of the queued host bookkeeping in dispatch order, and the
        routed FFN's counters onto the step that ran them."""
        if bundle is None:
            return
        arrays = [a for arrs, _ in bundle.pending for a in arrs]
        arrays += bundle.moe_stats
        phases = self._phase_ns
        with self.tracer.span("serving/sync", arrays=len(arrays),
                              step=bundle.step_id) as sp:
            # the step's ONE deliberate sync: every deferred token/flag
            # fetch collapses onto this block
            jax.block_until_ready(arrays)
        phases["sync"] = phases.get("sync", 0) + sp.dur_ns
        self._sync_end_ns = sp.t0_ns + sp.dur_ns
        with self._phase("replay", "serving/replay",
                         callbacks=len(bundle.pending)):
            host = jax.device_get(arrays)
            at = 0
            for arrs, callback in bundle.pending:
                callback(*host[at:at + len(arrs)])
                at += len(arrs)
            if bundle.moe_stats:
                self._note_moe_stats(host[at:], bundle.attrs)

    def _settle_all(self) -> bool:
        """Settle the bundle in flight and then what the step being
        queued has deferred so far (its routed counters stay with the
        pool, for the step's own bundle). Returns whether there was
        anything."""
        if self._in_flight is None and not self._deferred:
            return False
        bundle, self._in_flight = self._in_flight, None
        self._settle(bundle)
        if self._deferred:
            pending, self._deferred = self._deferred, []
            self._settle(_Bundle(self.step_id, pending, [],
                                 self._dispatched))
        return True

    def _settle_early(self, reason: str) -> bool:
        """The forced settle: before a seated request leaves by another
        road than the steady step (``reason``), the host reads everything
        the device holds for it. Counted, by reason."""
        if not self._settle_all():
            return False
        self.registry.counter("serving/settled_early").inc()
        self.registry.counter(f"serving/settled_early/{reason}").inc()
        return True

    def settle(self) -> List[Request]:
        """Bring the host up to date with the device: wait for the step in
        flight and replay its tokens, so that every token queued so far is
        in its request's ``output_tokens``. Returns the requests that
        finished since the last ``step()`` or ``settle()`` returned."""
        self._settle_all()
        return self._take_finished()

    def _take_finished(self) -> List[Request]:
        out, self._finished = self._finished, []
        return out

    def _note_moe_stats(self, stats: list, attrs: dict) -> None:
        """What the routed FFN counted in one step's programs (each hands
        back ``moe.routed_ffn.call_stats``; ``stats`` are those arrays out
        of the bundle's fetch): registry counters, and attributes of the
        ``serving/step`` span of the step that ran them (``attrs``)."""
        from ..moe.routed_ffn import CALL_STATS

        calls = np.stack(stats)
        step = {name: float(col.max() if name.startswith("load_max")
                            else col.sum())
                for name, col in zip(CALL_STATS, calls.T)}
        reg = self.registry
        for name in ("assignments", "experts_touched", "layer_calls",
                     "routed_assignments"):
            if name in step:    # (the last: a layer that holds a share)
                reg.counter(f"serving/moe_{name}").inc(step[name])
                step[name] = int(step[name])
        reg.gauge("serving/moe_load_max").set(step["load_max"])
        attrs.update({f"moe_{name}": val for name, val in step.items()})

    def _note_state_rows(self, sp, rows: int, tokens: int = 0) -> None:
        """``state_rows`` on a dispatch's span, for a model with a
        recurrent state: the rows whose state the program reads and
        writes, from the host's own running set (no device read). The
        step's span gathers them, with the bytes they stand for over the
        layers (a row's state read once and written once). ``tokens``, a
        prefill dispatch's REAL tokens (the host's own positions): a model
        of state-space, KDA or short-convolution layers runs them through
        the chunk form (``ssm_chunk_tokens`` / ``kda_chunk_tokens`` /
        ``gdn_chunk_tokens`` / ``conv_chunk_tokens`` on the span, summed on
        the step's)."""
        if not self._state_row_bytes:
            return
        sp.set(state_rows=rows)
        d = self._dispatched
        d["state_rows"] = d.get("state_rows", 0) + rows
        d["state_bytes"] = 2 * self._state_row_bytes * d["state_rows"]
        key = self._chunk_tokens_key
        if tokens and key:
            sp.set(**{key: tokens})
            d[key] = d.get(key, 0) + tokens

    def _note_latent(self, sp, read: int, written: int) -> None:
        """``latent_tokens_read`` / ``latent_rows_written`` on a dispatch's
        span, for a model with latent attention: the cached rows that the
        program's real query rows see (a running slot's, up to and with
        its own token; a chunk's slot, up to the chunk's last real token)
        and the rows it adds to the cache, a layer, from the host's own
        positions (no device read). What rides along (a free slot, one in
        mid-prefill, a chunk's padding) is not counted: nobody reads its
        result. The step's span gathers both, with the bytes the read
        stands for over the layers."""
        if not self._latent_token_bytes:
            return
        sp.set(latent_tokens_read=int(read), latent_rows_written=int(written))
        d = self._dispatched
        d["latent_tokens_read"] = d.get("latent_tokens_read", 0) + int(read)
        d["latent_rows_written"] = d.get("latent_rows_written", 0) \
            + int(written)
        d["latent_bytes_read"] = \
            self._latent_token_bytes * d["latent_tokens_read"]

    def _note_sparse(self, sp, positions, decode: bool = False) -> None:
        """``sparse_rows`` / ``sparse_tokens_read`` / ``sparse_index_rows``
        on a dispatch's span, for a model with learned sparse attention:
        its real query rows, the tokens the EQUATIONS read for them (a KV
        head a layer: all of a context under ``dense_len``, else the
        window's and the chosen blocks') and the compressed keys visible to
        them, from the host's own positions (no device read: which blocks
        were chosen is the device's to know). The ``decode`` rows of a page
        pool also say how their read's blocks engage, a layer:
        ``sparse_pages_most``, the most pages their (row, KV head)s can
        list, and ``sparse_blocks_most``, the grid steps of ``sparse_read.
        pages_a_step`` pages those make (exact under ``dense_len``, bounds
        past it). The step's span gathers them."""
        if self._sparse is None:
            return
        positions = np.asarray(positions, np.int64).reshape(-1)
        new = {"sparse_rows": int(positions.size),
               "sparse_tokens_read": int(
                   tokens_read(positions, self._sparse).sum()),
               "sparse_index_rows": int(
                   index_rows(positions, self._sparse).sum())}
        if decode and self._paged:
            from ..ops.attention.sparse_read import pages_a_step

            spec, page_size = self.pool.spec, self.pool.page_size
            most = pages_most(positions, self._sparse, page_size)
            G = pages_a_step(spec.rep, spec.cache_d, page_lanes(page_size),
                             spec.dtype)
            new["sparse_pages_most"] = int(spec.kv_heads * most.sum())
            new["sparse_blocks_most"] = int(
                spec.kv_heads * (-(-most // G)).sum())
        sp.set(**new)
        d = self._dispatched
        for key, val in new.items():
            d[key] = d.get(key, 0) + val

    def _note_admit(self, rows: int, padded_tokens: int) -> None:
        """An admission program of this step: requests seated, and the
        tokens it computes (rows x bucket width, padding included)."""
        d = self._dispatched
        d["admit"] = d.get("admit", 0) + rows
        d["admit_tokens"] = d.get("admit_tokens", 0) + padded_tokens

    @staticmethod
    def _bucket(n: int, cap: int) -> int:
        b = _MIN_PREFILL_BUCKET
        while b < n:
            b *= 2
        return min(b, cap)

    def _prefill_at(self, ids, last_pos):
        """The bucketed prefill program over host-built ``ids`` and the
        position(s) to project: one put, one call."""
        eng = self.engine
        with self._enqueue("prefill_at", "transfer"):
            args = jax.device_put((ids, last_pos))
        with self._enqueue("prefill_at"):
            return eng._jit_prefill_at(eng.params, *args)

    def _cur_scatter(self, tokens_dev, slots) -> None:
        """Write freshly sampled first tokens into the current-token
        twin at ``slots`` (host ids)."""
        with self._enqueue("cur_scatter", "transfer"):
            slots = jax.device_put(np.asarray(slots, np.int32))
        with self._enqueue("cur_scatter"):
            self._cur_dev = self._jit_cur_scatter(self._cur_dev, tokens_dev,
                                                  slots)

    def _admit(self, req: Request) -> None:
        slot = self.pool.alloc()
        # rollback snapshot: a PREEMPTED request arrives carrying its
        # generated-so-far tokens and first-token stamp — a failed
        # re-admission must restore exactly that state, never wipe it
        n0 = len(req.output_tokens)
        admit0, first0 = req.admit_time, req.first_token_time
        try:
            if self.faults is not None:
                self.faults.check("admit_oom")
            seed = req.seed_tokens        # prompt, + outputs when resumed
            T = req.seed_len
            width = self._bucket(T, self.pool.capacity)
            ids = np.zeros((1, width), np.int32)
            ids[0, :T] = seed
            running_before = self._running_count()
            req.admit_time = self._now()
            self._note_admit(1, width)
            with self._phase("prepare", "serving/admit", rid=req.request_id,
                             tokens=T, width=width) as sp:
                self._note_state_rows(sp, 1, T)
                self._note_latent(sp, 0, T)
                logits, pre_cache = self._prefill_at(ids, np.int32(T - 1))
                self.pool.admit(pre_cache, slot, T)
                if self._paged:
                    sp.set(pool_writes=self.pool.pages_touched(
                        slot, 0, self.pool.capacity))
                with self.tracer.span("serving/sample"):
                    # dispatch only; the host value arrives at the
                    # end-of-step fetch
                    tok_dev = self._cur_commit(self._sample_dev(logits))
                self._cur_scatter(tok_dev, [slot])
            now = self._now()
            self.metrics.record_prefill(T, now - req.admit_time,
                                        blocking=running_before > 0)
            req.slot = slot
            self._slot_req[slot] = req
            req.state = RequestState.RUNNING
            req.last_admit_step = self.step_id
            self.timelines.record(req.request_id, "admitted", slot=slot,
                                  mode="bucketed")
            self.tracer.flow("s", "req", req.request_id)
            self._queued_token(req)

            def _on_first_token(tok, req=req, slot=slot, n0=n0):
                self._read_token(req)
                token = int(tok[0])
                if req.first_token_time is None:
                    req.first_token_time = self._now()
                req.output_tokens.append(token)
                self._tokens_emitted += 1
                self._current[slot] = token
                if n0 == 0:
                    self.timelines.record(req.request_id, "first_token")
                self._maybe_retire(req, token)

            self._defer([tok_dev], _on_first_token)
        except Exception:
            # undo the partial admission so the request can be re-queued
            # with no trace: the slot goes back and timing/output state
            # reverts to the pre-admission snapshot, so _abort_step sees
            # a clean QUEUED request (resumed ones keep their tokens)
            self._slot_req.pop(slot, None)
            self.pool.release(slot)
            req.state = RequestState.QUEUED
            req.slot = None
            req.admit_time = admit0
            req.first_token_time = first0
            del req.output_tokens[n0:]
            raise
        if self._use_prefix:
            # publish the freshly-prefilled full prompt pages (refcounted
            # past this slot's lifetime) for the next same-prefix request
            with _PagesPhase(self):
                self.pool.cache_prefix(slot, seed)

    def _running_count(self) -> int:
        return sum(1 for r in self._slot_req.values() if self._runs(r))

    def _admission_cost(self, req: Request) -> int:
        """Prefill tokens this grant charges against the step budget: the
        padded bucket width for a whole-seed admission, one chunk for a
        long seed (only its first chunk can run this step). Preempted
        requests are charged for prompt + generated-so-far — that is
        what re-admission actually prefills."""
        T = req.seed_len
        if T <= self.prefill_chunk:
            return self._bucket(T, self.pool.capacity)
        return self.prefill_chunk

    # -- paged KV: page accounting and prefix-hit seating --------------
    def _prefix_plan(self, hit_tokens: int, seed_len: int) -> int:
        """Where a prefix-hit admission starts prefilling. A full hit
        still re-prefills the LAST chunk (the final-chunk logits sample
        the first token, exactly like a cold chunked admission — bitwise
        parity); the start is aligned DOWN to a chunk multiple so every
        chunk keeps the start+chunk <= capacity invariant the chunk
        program's update-slice relies on."""
        C = max(self.prefill_chunk, 1)
        if hit_tokens >= seed_len:
            pos0 = seed_len - min(C, seed_len)
        else:
            pos0 = min(hit_tokens, seed_len)
        return (pos0 // C) * C

    def _page_cost(self, req: Request) -> int:
        """FRESH pages seating this request allocates right now: the
        pages covering its uncached suffix (CoW forks included; shared
        prefix pages are free — a refcount bump). Decode-time growth is
        deliberately NOT charged — that is the oversubscription bet,
        underwritten by trie eviction + pressure preemption."""
        ps = self.pool.page_size
        seed = req.seed_len
        pos0 = 0
        if self._use_prefix:
            hit = self.pool.prefix.peek(req.seed_tokens) * ps
            pos0 = self._prefix_plan(hit, seed)
        return (seed - 1) // ps - pos0 // ps + 1

    def _grant_page_budget(self) -> int:
        """Pages the grant may promise this step: free now, plus what
        trie eviction could reclaim without preempting anyone."""
        return self.pool.free_page_count + self.pool.evictable_page_count()

    def _grantable_slots(self) -> int:
        """Slots the grant may fill this step. Pages are counted by
        group: the budget above is the full group's; of the window group
        a request never holds more than one ring (``sliding_window /
        page_size + 1`` pages, one more while an unaligned chunk is
        written), so it admits as many requests as it has whole rings
        free."""
        free = self.pool.free_count
        ring = getattr(self.pool, "ring", None)
        if ring is not None:
            free = min(free, ring.free_count
                       // (-(-ring.window // ring.page_size) + 2))
        return free

    def _ensure_pages(self, req: Request, start: int, end: int,
                      sync: bool = True) -> None:
        """ensure_writable of ``req``'s slot with the pressure valve: on
        PagePoolExhausted (free list empty AND trie eviction dry), settle
        the step in flight (a request that has ended gives its pages
        back), then preempt the youngest OTHER seated request — its pages
        come back to the free list — and retry. Only when no victim
        remains does the exhaustion propagate (a sizing bug: one request's
        footprint exceeds the whole pool, which the submit-time page
        check rejects). The forced settle may end ``req`` itself (its
        token in flight was an EOS or a poisoned row): its slot is free
        then and nothing is mapped into it. ``sync`` is
        ``ensure_writable``'s: a chunk passes ``False``, its program
        publishes the row."""
        slot = req.slot
        while True:
            try:
                self.pool.ensure_writable(slot, start, end, sync=sync)
                return
            except PagePoolExhausted:
                if self._settle_early("preempt"):
                    if self._slot_req.get(slot) is not req:
                        return      # ended in there: free slots map nothing
                    continue
                victims = [
                    r for r in select_victims(
                        list(self._slot_req.values()),
                        n=len(self._slot_req), current_step=self.step_id,
                        min_run_steps=0, class_rank=self._class_rank)
                    if r.slot != slot]
                if not victims:
                    raise
                self._preempt_req(victims[0], auto=True)

    def _ensure_decode_pages(self, width: int) -> None:
        """Back every RUNNING slot's next ``width`` write columns with
        exclusively-owned pages before the decode/verify dispatch.
        PREFILLING slots are skipped on purpose: their masked garbage
        writes hit unmapped entries (scatter drops them) or pages the
        seating already CoW-forked — allocating for garbage would waste
        pages under pressure. So are closing slots (:meth:`_runs`): what
        their row writes, nobody reads. (An earlier slot's pages may have
        settled the step in flight or preempted: a request that left by
        either is no longer running when its turn comes.)"""
        for slot, req in list(self._slot_req.items()):
            if self._runs(req):
                idx = int(self.pool.starts[slot])
                self._ensure_pages(req, idx, idx + width)

    def _admit_prefix_hit(self, req: Request) -> bool:
        """Try to seat ``req`` through the prefix cache: walk the trie,
        map the cached pages into a fresh slot for free, and enter the
        chunked-prefill path at the first uncached position. Returns
        False on a miss (caller falls through to the cold paths)."""
        pool = self.pool
        seed = req.seed_tokens
        seed_len = req.seed_len
        pages = pool.prefix.match(seed)
        hit = len(pages) * pool.page_size
        pos0 = self._prefix_plan(hit, seed_len)
        self.metrics.record_prefix(pos0, seed_len)
        if pos0 <= 0:
            return False     # nothing actually skipped: cold path
        now = self._now()    # before alloc: nothing may fail while the
        slot = pool.alloc()  # slot is held but not yet seated
        try:
            if self.faults is not None:
                self.faults.check("admit_oom")
            pool.reset_row(slot)
            pool.seat_prefix(slot, pages, pos0)
        except PagePoolExhausted:
            # the uncached suffix needs more fresh pages than remain:
            # release (unmapping anything seated so far) and retry next
            # step once eviction/preemption has freed pages
            pool.release(slot)
            req.state = RequestState.QUEUED
            req.slot = None
            self.scheduler.requeue_front([req])
            self.timelines.record(req.request_id, "requeued",
                                  reason="page_pressure")
            return True
        except Exception:
            pool.release(slot)
            req.state = RequestState.QUEUED
            req.slot = None
            raise
        req.admit_time = now
        req.slot = slot
        req.prefill_pos = pos0
        req.prefix_hit_tokens = pos0
        req.state = RequestState.PREFILLING
        req.last_admit_step = self.step_id
        self._slot_req[slot] = req
        self._prefill_queue.append(req)
        self.timelines.record(req.request_id, "admitted", slot=slot,
                              mode="prefix_hit")
        self.timelines.record(req.request_id, "prefix_hit",
                              hit_tokens=pos0, seed_len=seed_len)
        self.tracer.flow("s", "req", req.request_id)
        return True

    def _admit_stall_free(self, granted: List[Request]) -> list:
        """Seat every granted request: long prompts become PREFILLING
        (their cache rows fill chunk by chunk in later steps); short
        prompts are grouped by padded bucket width and handed back as
        ``(width, group)`` pairs, each of which the step prefills +
        scatters in ONE batched dispatch."""
        groups: dict = {}
        for req in granted:
            if self._use_prefix and self._admit_prefix_hit(req):
                continue          # seated PREFILLING at its uncached
            #                       suffix (or re-queued under pressure)
            T = req.seed_len
            if T > self.prefill_chunk:
                now = self._now()
                slot = self.pool.alloc()
                try:
                    self.pool.reset_row(slot)
                except Exception:
                    # nothing seated yet: hand the slot straight back so
                    # a row-scrub failure cannot strand it
                    self.pool.release(slot)
                    raise
                req.admit_time = now
                req.slot = slot
                req.prefill_pos = 0
                req.state = RequestState.PREFILLING
                req.last_admit_step = self.step_id
                self._slot_req[slot] = req
                self._prefill_queue.append(req)
                self.timelines.record(req.request_id, "admitted", slot=slot,
                                      mode="chunked")
                self.tracer.flow("s", "req", req.request_id)
            else:
                groups.setdefault(self._bucket(T, self.pool.capacity),
                                  []).append(req)
        return sorted(groups.items())

    def _admit_batch(self, group: List[Request], width: int) -> None:
        """Batched bucketed admission: ``len(group)`` same-bucket prompts
        prefilled in one ``prefill_last`` dispatch at a power-of-two
        batch, then scattered into their slots by one jitted multi-row
        admit. Compile count: log2(num_slots) batch buckets x
        log2(max_seq_len) width buckets. Padding rows carry the slot
        sentinel ``num_slots`` (scatter drop-mode discards them)."""
        n = len(group)
        nB = 1
        while nB < n:
            nB *= 2
        ids = np.zeros((nB, width), np.int32)
        last_pos = np.zeros((nB,), np.int32)
        slots = np.full((nB,), self.pool.num_slots, np.int32)
        lengths = np.zeros((nB,), np.int32)
        running_before = self._running_count()
        # rollback snapshots (preempted group members keep their tokens
        # and stamps if this dispatch dies — see _admit)
        n0s = [len(r.output_tokens) for r in group]
        stamps = [(r.admit_time, r.first_token_time) for r in group]
        try:
            if self.faults is not None:
                self.faults.check("admit_oom")
            for i, req in enumerate(group):
                T = req.seed_len
                ids[i, :T] = req.seed_tokens
                last_pos[i] = T - 1
                slots[i] = self.pool.alloc()
                lengths[i] = T
                req.admit_time = self._now()
            t0 = self._now()
            self._note_admit(n, nB * width)
            with self._phase("prepare", "serving/prefill_batch", n=n,
                             width=width, batch=nB) as sp:
                self._note_state_rows(sp, n, int(lengths.sum()))
                # (a prompt admitted whole attends to its own fresh rows)
                self._note_latent(sp, 0, int(lengths.sum()))
                logits, pre_cache = self._prefill_at(ids, last_pos)
                self.pool.admit_rows(pre_cache, slots, lengths)
                if self._paged:
                    sp.set(pool_writes=self.pool.pages_touched(
                        slots[:n], np.zeros((n,)), self.pool.capacity))
                with self.tracer.span("serving/sample"):
                    # dispatch only; host values arrive at the
                    # end-of-step fetch
                    tokens_dev = self._cur_commit(self._sample_dev(logits))
                self._cur_scatter(tokens_dev, slots)
            now = self._now()
            self.metrics.record_prefill(int(lengths.sum()), now - t0,
                                        blocking=running_before > 0)
            for i, req in enumerate(group):
                slot = int(slots[i])
                req.slot = slot
                self._slot_req[slot] = req
                req.state = RequestState.RUNNING
                req.last_admit_step = self.step_id
                self.timelines.record(req.request_id, "admitted", slot=slot,
                                      mode="batched")
                self.tracer.flow("s", "req", req.request_id)
                self._queued_token(req)
                if self._use_prefix:
                    self.pool.cache_prefix(slot, req.seed_tokens)

            def _on_batch_tokens(tokens, group=group, slots=slots, n0s=n0s):
                now = self._now()
                for i, req in enumerate(group):
                    self._read_token(req)
                    token = int(tokens[i])
                    slot = int(slots[i])
                    if req.first_token_time is None:
                        req.first_token_time = now
                    req.output_tokens.append(token)
                    self._tokens_emitted += 1
                    self._current[slot] = token
                    if n0s[i] == 0:
                        self.timelines.record(req.request_id, "first_token")
                    self._maybe_retire(req, token)

            self._defer([tokens_dev], _on_batch_tokens)
        except Exception:
            # roll the whole group back to clean QUEUED requests so
            # _abort_step re-queues them with no trace (resumed members
            # revert to their pre-admission snapshots)
            for i, req in enumerate(group):
                slot = int(slots[i])
                if slot < self.pool.num_slots:
                    self._slot_req.pop(slot, None)
                    self.pool.release(slot)
                self._unread.pop(req.request_id, None)
                self._closing.discard(req.request_id)
                req.state = RequestState.QUEUED
                req.slot = None
                req.admit_time, req.first_token_time = stamps[i]
                del req.output_tokens[n0s[i]:]
            raise

    def _prefill_chunk_step(self) -> None:
        """Run AT MOST one bounded prefill chunk — for the head of the
        prefill queue — so per-step latency stays bounded by the token
        budget no matter how long the queued prompts are. The final
        chunk projects the prompt's true last position, samples the
        first token, and flips the request to RUNNING."""
        if not self._prefill_queue:
            return
        req = self._prefill_queue[0]
        slot = req.slot
        C = self.prefill_chunk
        pos = req.prefill_pos
        seed = req.seed_tokens            # prompt, + outputs when resumed
        seed_len = req.seed_len
        L = min(C, seed_len - pos)
        ids = np.zeros((1, C), np.int32)
        ids[0, :L] = seed[pos:pos + L]
        running_before = self._running_count()
        t0 = self._now()
        if self._paged:
            # the chunk's write window must land in owned pages BEFORE
            # the dispatch (allocating / CoW-forking under pressure may
            # preempt a victim — host work, so it happens outside jit).
            # The mappings stay in the host's mirror: the chunk program
            # writes the slot's row into the device table itself
            with _PagesPhase(self):
                self._ensure_pages(req, pos, pos + L, sync=False)
        self._dispatched["chunk"] = L
        # a chunk that does not end its prompt, beside running slots, is
        # queued by this step's decode dispatch, with the decode rows in
        # ONE program (``_decode_step``); a prompt's last chunk keeps its
        # own: the slot it finishes decodes in this same step, from the
        # token this chunk's head has yet to choose. The running slots are
        # counted AFTER the pages above: paging the chunk in may have
        # preempted the last of them, and then no decode follows
        beside = (pos + L < seed_len and self._fuses_chunks
                  and self._running_count() > 0)
        with self._phase("prepare", "serving/prefill_chunk",
                         rid=req.request_id, pos=pos, len=L) as sp:
            self._note_state_rows(sp, 1, L)
            self._note_latent(sp, pos + L, L)
            self._note_sparse(sp, pos + np.arange(L))
            if self._paged:
                if beside:
                    self._chunk_beside = (req, ids, pos, L, t0)
                    logits = None       # (the decode dispatch's to compute)
                else:
                    logits = self.pool.run_prefill_chunk(
                        self.engine, ids, slot, pos, L, L - 1)
                sp.set(pool_writes=self.pool.pages_touched(slot, pos, C))
                self._set_pool_reads(sp, C, [slot], [pos])
            else:
                # what only the host knows goes as host values:
                # ``prefill_chunk`` packs them into ONE vector and hands
                # it to the jitted call itself, the one transfer of a
                # chunk (a put of its own before the call cost 0.14 ms
                # more on the chip's host, a tuple of five arrays 1.05:
                # PERF.md §6, PR 35)
                self._enqueue("chunk", "transfer")
                with self._enqueue("chunk"):
                    logits, cache = self.engine.prefill_chunk(
                        self.pool.cache, ids, slot, pos, L, L - 1)
                self.pool.cache = cache
        self.pool.starts[slot] = pos + L  # device index moved in-program
        req.prefill_pos = pos + L
        req.chunks += 1
        self.timelines.record(req.request_id, "prefill_chunk", pos=pos,
                              len=L)
        if logits is not None and req.prefill_pos >= seed_len:
            with self._phase("prepare", "serving/sample"):
                # dispatch only; host value arrives at the end-of-step
                # fetch
                tok_dev = self._cur_commit(self._sample_dev(logits))
            self._cur_scatter(tok_dev, [slot])
            self.metrics.record_prefill(L, self._now() - t0,
                                        blocking=running_before > 0)
            self._prefill_queue.pop(0)
            req.state = RequestState.RUNNING
            req.last_admit_step = self.step_id
            self._queued_token(req)
            if self._use_prefix:
                with _PagesPhase(self):
                    self.pool.cache_prefix(slot, seed)

            def _on_chunk_token(tok, req=req, slot=slot):
                self._read_token(req)
                token = int(tok[0])
                first = req.first_token_time is None
                if first:
                    req.first_token_time = self._now()
                req.output_tokens.append(token)
                self._tokens_emitted += 1
                self._current[slot] = token
                if first:
                    self.timelines.record(req.request_id, "first_token")
                self._maybe_retire(req, token)

            self._defer([tok_dev], _on_chunk_token)
        elif not beside:
            # no sync: the chunk is enqueued and this step's decode
            # dispatch overlaps its host-side latency — the device
            # serializes them anyway, and step_gap captures the real
            # wall cost. Recorded time is therefore enqueue-side only
            # (a chunk left to the decode dispatch is recorded there,
            # once its program is enqueued)
            self.metrics.record_prefill(L, self._now() - t0,
                                        blocking=running_before > 0)

    def _maybe_retire(self, req: Request, token: int) -> None:
        rid = req.request_id
        if req.eos_token_id is not None and token == req.eos_token_id:
            req.finish_reason = FinishReason.EOS
        elif len(req.output_tokens) >= req.max_new_tokens:
            req.finish_reason = FinishReason.LENGTH
        elif rid in self._closing and rid not in self._unread:
            # counted out when this token was queued, and not by its
            # budget: the slot's cache row was full (``_queued_token``).
            # Retire rather than silently clamp-overwrite the last column
            # on the next decode write
            req.finish_reason = FinishReason.LENGTH_CAP
        else:
            if self._handoff_ready is not None and \
                    req.state is RequestState.RUNNING:
                # prefill role: pages full, first token sampled — the
                # request now belongs to a decode replica. It stays
                # seated (slot + page references held) until the router
                # transfers it or a rollback path retires it.
                self._handoff_ready.append(req)
                # parked: prefill done but no decode home yet — the
                # completeness probe must not count this as done even
                # though the timeline is still open
                self.timelines.record(req.request_id, "handoff_ready",
                                      parked=True, slot=req.slot,
                                      journey=req.journey_id)
            return
        req.state = RequestState.FINISHED
        req.finish_time = self._now()
        self.pool.release(req.slot)
        del self._slot_req[req.slot]
        self._closing.discard(rid)
        self._finish_record(req)
        self._finished.append(req)

    def _finish_record(self, req: Request) -> None:
        """Shared terminal bookkeeping for every FINISHED retirement
        (normal, length-capped, or deadline-expired): metrics, the flow
        arrow, and the terminal timeline event."""
        self.metrics.record_finish(req)
        if self.slo is not None:
            if req.finish_reason is FinishReason.CANCELLED:
                # a client cancellation is neither good nor bad service:
                # withdraw the admission instead of judging latencies
                self.slo.observe_cancel(cls=req.priority_class)
            else:
                ok = req.finish_reason in (FinishReason.EOS,
                                           FinishReason.LENGTH,
                                           FinishReason.LENGTH_CAP)
                e2e = (req.finish_time - req.submit_time
                       if req.finish_time is not None and
                       req.submit_time is not None else None)
                self.slo.observe_finish(ttft_s=req.ttft,
                                        per_token_s=req.per_token_latency,
                                        e2e_s=e2e, ok=ok,
                                        cls=req.priority_class)
        self.tracer.flow("f", "req", req.request_id)
        self.timelines.record(req.request_id, "finished", terminal=True,
                              reason=FinishReason.of(req.finish_reason).value,
                              new_tokens=len(req.output_tokens),
                              chunks=req.chunks,
                              spec_drafted=req.spec_drafted,
                              spec_accepted=req.spec_accepted)

    # -- disaggregated prefill/decode handoff (ISSUE 19) ---------------
    def pending_handoffs(self) -> List[Request]:
        """Prefill role: the seated RUNNING requests whose prefill is
        complete and first token sampled, ready for a decode replica.
        Non-destructive — a successful :meth:`adopt` on the destination
        followed by :meth:`finish_handoff` here removes an entry, so a
        request the router cannot place this step is simply retried."""
        return list(self._handoff_ready or ())

    def adopt(self, req: Request, src: "ServingEngine") -> dict:
        """Seat a request whose prefill ran on ANOTHER replica: copy its
        live pages across pools (one fixed-shape jitted transfer — see
        :meth:`PagedKVPool.import_pages`), seat them, and resume decode
        at the source's exact position. Pages the local prefix trie
        already holds for the request's prompt are mapped for free (a
        refcount bump) and only the uncached tail is moved — the
        prefix-affine dispatch payoff. The transferred pages are the
        same bits the source produced and the first token was already
        sampled from them, so greedy output is bitwise identical to a
        colocated run.

        On any failure nothing stays seated here (allocated pages are
        unwound on both pools) and the exception propagates — the
        router re-homes the request through the failover scrub.
        Returns transfer accounting: ``{"pages", "hit_pages", "bytes",
        "seconds"}``."""
        if self.role == "prefill":
            raise ValueError("adopt() needs a decode-capable replica "
                             "(role 'decode' or 'both')")
        if not self._paged or not getattr(src, "_paged", False):
            raise ValueError("adopt() requires paged KV on both replicas")
        # both ends read what their devices hold first: the source's last
        # token is the one decoding resumes from
        src._settle_early("handoff")
        self._settle_early("handoff")
        if req.state is not RequestState.RUNNING or req.slot is None:
            raise ValueError(f"adopt() needs a seated RUNNING request; "
                             f"req {req.request_id} is {req.state.value}")
        pool, spool = self.pool, src.pool
        src_slot = req.slot
        seed = req.seed_tokens
        pos = int(spool.starts[src_slot])
        n_live = -(-pos // spool.page_size)
        src_pages = [int(p) for p in spool.table[src_slot, :n_live]]
        t0 = self._now()
        slot = pool.alloc()
        hit_pages: List[int] = []
        try:
            pool.reset_row(slot)
            if self._use_prefix:
                # local trie hit: map the cached prefix pages in place
                # of transferring them (their bits are identical — they
                # came off an earlier transfer or colocated prefill)
                hit_pages = pool.prefix.match(seed)[:n_live]
            if hit_pages:
                pool.map_prefix(slot, hit_pages, sync=False)
            dst_pages = pool.import_pages(spool, src_pages[len(hit_pages):])
        except Exception:
            # import_pages already unwound its own failure, so only
            # the slot (and any mapped prefix pages) needs releasing
            pool.release(slot)
            raise
        try:
            pool.seat_pages(slot, dst_pages, pos,
                            first_entry=len(hit_pages))
        except Exception:
            # seat_pages is atomic: on failure it took NONE of the
            # batch, so the whole import is ours to hand back
            pool.unref_pages(dst_pages)
            pool.release(slot)
            raise
        now = self._now()
        req.slot = slot
        req.last_admit_step = self.step_id
        if req.admit_time is None:
            req.admit_time = now
        self._slot_req[slot] = req
        # current-token twin: the source's last sampled token resumes
        # the decode loop here (width-1 scatter — a pre-warmed program)
        tok = int(req.output_tokens[-1])
        self._current[slot] = tok
        self._cur_dev = self._jit_cur_scatter(
            self._cur_dev,
            self._cur_commit(np.asarray([tok], np.int32)),
            jnp.asarray([slot]))
        if self._use_prefix:
            # publish the adopted prompt's full pages into THIS pool's
            # trie: the next same-prefix handoff routed here skips the
            # transfer for those pages entirely
            pool.cache_prefix(slot, seed)
        self.timelines.record(req.request_id, "adopted", slot=slot,
                              pages=len(dst_pages),
                              hit_pages=len(hit_pages),
                              src_replica=src.replica_id,
                              dst_replica=self.replica_id,
                              journey=req.journey_id)
        self.tracer.flow("s", "req", req.request_id)
        return {"pages": len(dst_pages), "hit_pages": len(hit_pages),
                "bytes": len(dst_pages) * pool.page_nbytes,
                "seconds": now - t0}

    def finish_handoff(self, req: Request, slot: int,
                       dst_replica: Optional[int] = None) -> None:
        """Prefill role: release the source seat AFTER a decode replica
        adopted the request. ``slot`` is the source slot (``req.slot``
        already points at the destination). The slot and its page
        references go back through the standard rollback — trie-cached
        prompt pages stay warm for the next same-prefix prompt — and
        the request's timeline HERE closes with a terminal hand-off
        event (it finishes on the adopting replica's timeline)."""
        self._settle_early("handoff")
        if self._slot_req.get(slot) is not req:
            raise ValueError(f"finish_handoff: slot {slot} does not seat "
                             f"req {req.request_id}")
        del self._slot_req[slot]
        self.pool.release(slot)
        if self._handoff_ready:
            self._handoff_ready[:] = [r for r in self._handoff_ready
                                      if r is not req]
        self.timelines.record(req.request_id, "handed_off", terminal=True,
                              slot=slot, src_replica=self.replica_id,
                              dst_replica=dst_replica,
                              journey=req.journey_id)

    # -- resilience: eviction, deadlines, preemption -------------------
    def _evict_slot(self, req: Request) -> None:
        """Reclaim a seated request's slot through the rollback path:
        release the slot (its stale KV becomes masked padding, exactly
        like a rejected draft tail) and detach all seat state. The
        caller decides what the request becomes next (FINISHED on
        deadline, QUEUED on preemption, FAILED on poisoned logits)."""
        slot = req.slot
        del self._slot_req[slot]
        self.pool.release(slot)
        req.slot = None
        self._closing.discard(req.request_id)
        # identity filter, not remove(): value equality on requests would
        # elementwise-compare their numpy prompts
        self._prefill_queue[:] = [r for r in self._prefill_queue
                                  if r is not req]
        if self._handoff_ready:
            # a parked handoff that retires (deadline/cancel/preempt)
            # before any decode replica adopts it leaves the launchpad
            self._handoff_ready[:] = [r for r in self._handoff_ready
                                      if r is not req]

    def _expire_deadlines(self) -> None:
        """Retire every request whose deadline has passed: queued ones
        before they cost a prefill, seated ones via slot eviction (the
        step in flight is settled first: the request keeps the tokens it
        was owed, or has ended with them). Runs at the step boundary so a
        mid-step expiry can never interleave with a dispatch."""
        now = self._now()
        expired = self.scheduler.expire(now)
        if any(req.expired(now) for req in self._slot_req.values()):
            self._settle_early("deadline")
        for slot, req in list(self._slot_req.items()):
            if req.expired(now):
                self._evict_slot(req)
                expired.append(req)
        for req in expired:
            req.state = RequestState.FINISHED
            req.finish_reason = FinishReason.DEADLINE
            req.finish_time = now
            self._finish_record(req)
            self._finished.append(req)

    def preempt(self, request_id: int) -> Request:
        """Evict a seated (RUNNING or PREFILLING) request and re-queue it
        at the FRONT of the admission queue carrying its generated-so-far
        tokens. Re-admission prefills prompt + outputs through the
        existing bucketed/chunked paths — fixed shapes, zero new
        programs — and greedy output is bitwise identical to never having
        been preempted (see ``Request.seed_tokens``). The step in flight
        is settled first, so the request carries every token queued for
        it. Raises ``ValueError`` if the id is not currently seated (or
        no longer: its last token was in flight)."""
        self._settle_early("preempt")
        for req in self._slot_req.values():
            if req.request_id == request_id:
                self._preempt_req(req, auto=False)
                return req
        raise ValueError(f"request {request_id} is not seated in a slot "
                         f"(only RUNNING/PREFILLING requests can be "
                         f"preempted)")

    def cancel(self, request_id: int) -> Optional[Request]:
        """Cancel a request by id — the client hung up or sent
        ``DELETE /v1/requests/{id}``. A QUEUED request is removed from
        the admission queue before it ever costs a prefill; a seated
        (RUNNING/PREFILLING) one is evicted through the preemption
        rollback (slot released, pages refcount-decremented, prefill
        queue filtered) and NOT re-queued. Either way the request
        retires ``FINISHED``/``cancelled`` with a terminal timeline
        event, and SLO accounting withdraws the admission (cancellation
        is neither good nor bad service). Returns the request, or None
        when the id is unknown or already terminal — a cancel racing
        the final token is normal, not an error."""
        for r in self.scheduler.queue:
            if r.request_id == request_id:
                # identity filter: deque.remove would still work (eq=False
                # means identity ==), but stay explicit like _evict_slot
                self.scheduler.queue = type(self.scheduler.queue)(
                    x for x in self.scheduler.queue if x is not r)
                return self._finish_cancel(r)
        if any(r.request_id == request_id for r in self._slot_req.values()):
            # (it may end in there: then the cancel raced the final token)
            self._settle_early("cancel")
        for r in list(self._slot_req.values()):
            if r.request_id == request_id:
                slot = r.slot
                self._evict_slot(r)
                self.tracer.instant("serving/cancel", rid=r.request_id,
                                    slot=slot)
                return self._finish_cancel(r)
        return None

    def _finish_cancel(self, req: Request) -> Request:
        req.state = RequestState.FINISHED
        req.finish_reason = FinishReason.CANCELLED
        req.finish_time = self._now()
        self._finish_record(req)
        return req

    def _preempt_req(self, req: Request, auto: bool) -> None:
        slot = req.slot
        self._evict_slot(req)
        req.state = RequestState.QUEUED
        req.prefill_pos = 0       # a partial chunked prefill restarts
        req.admit_time = None
        req.preemptions += 1
        if auto:
            # pressure victims go to the BACK: re-queueing at the head
            # would hand the victim its own freed slot at the very next
            # grant — an infinite preempt/re-admit swap that generates
            # nothing. Tail requeue yields round-robin time-slicing with
            # the arrivals that caused the pressure.
            self.scheduler.requeue_back([req])
        else:
            self.scheduler.requeue_front([req])
        self.metrics.record_preemption(req)
        self.timelines.record(req.request_id, "preempted", slot=slot,
                              auto=auto, generated=len(req.output_tokens))
        self.tracer.instant("serving/preempt", rid=req.request_id,
                            slot=slot, auto=auto)

    def _auto_preempt(self) -> None:
        """Pressure valve: when the queue has outgrown the threshold and
        every slot is taken, evict ONE victim per step (youngest /
        least-progress first; must have held its slot for
        ``preempt_min_run_steps``). One per step is deliberate — paced
        eviction keeps the batch mostly busy while pressure drains.

        With paged KV, page starvation counts as pressure too: free
        slots are no help when the queue head's uncached suffix exceeds
        every page the pool could free without a preemption."""
        if (self.preempt_queue_threshold is None
                or self.scheduler.pending <= self.preempt_queue_threshold):
            return
        self._preempt_one(self.scheduler.head(),
                          lambda: list(self._slot_req.values()))

    def _preempt_one(self, head: Optional[Request], candidates) -> None:
        """While ``head`` is starved, evict ONE victim of ``candidates()``
        (seated requests; ``select_victims`` chooses). The step in flight
        is settled only once there is a victim to take, so a starved
        server whose residents are all too young (or too high a class)
        keeps running ahead; after the settle the question is asked again:
        a request whose last token was in flight has given its slot back,
        and the victim may be that one."""
        def victims():
            return select_victims(
                candidates(), n=1, current_step=self.step_id,
                min_run_steps=self.preempt_min_run_steps,
                class_rank=self._class_rank)

        if not self._starved(head):
            return
        chosen = victims()
        if chosen and self._settle_early("preempt"):
            chosen = victims() if self._starved(head) else []
        for req in chosen:
            self._preempt_req(req, auto=True)

    def _starved(self, head: Optional[Request]) -> bool:
        """No free slot, or (paged) the queue's ``head`` needs more pages
        than a grant could allocate without a preemption."""
        if self.pool.free_count == 0:
            return True
        return (self._paged and head is not None
                and self._page_cost(head) > self._grant_page_budget())

    def _class_rank(self, req: Request) -> int:
        """Victim-selection key: a request's priority rank (0 = highest)
        under priority scheduling, 0 for everyone under plain FIFO."""
        if self._priority is None:
            return 0
        return self.scheduler.rank_of(req.priority_class)

    def _burn_preempt(self) -> None:
        """Burn-rate-driven preemption, the seated half of class
        shedding: while a class's burn alert is at warn/page
        (``_shed_floor``), requests of STRICTLY LOWER classes are not
        just refused at submit — if a protected-class request is
        waiting and the pool is starved (no free slot, or its pages
        exceed what a grant could allocate), one shed-class resident is
        evicted per step (paced like ``_auto_preempt``; tail-requeued so
        it resumes once the burn clears)."""
        floor = self._shed_floor()
        if floor is None:
            return
        head = self.scheduler.head_within(floor)
        if head is None:
            return  # nobody protected is waiting
        # (not starved: normal admission will seat the protected head)
        self._preempt_one(head, lambda: [
            r for r in self._slot_req.values()
            if self._class_rank(r) > floor])

    # ------------------------------------------------------------------
    def step(self) -> List[Request]:
        """One scheduler iteration: admit into free slots, queue one decode
        (or draft+verify) step for every running slot, then settle the
        step BEFORE this one and leave this one's bundle in flight
        (:attr:`_runs_ahead`; a server that cannot settles its own too).
        A step with nothing to queue settles and returns. Returns the
        requests that finished since the last ``step()`` or ``settle()``
        returned: a token, and an end, is visible when its step is
        settled.

        Exception-safe: if the engine throws mid-step, no slot leaks —
        granted-but-unadmitted requests go back to the head of the queue,
        requests whose KV state is unrecoverable are FAILED (reason
        ``"error"``), the pool is reset, and the error propagates."""
        self.step_id += 1
        self._ensure_watch()      # _jit_verify_k materializes lazily
        tracer = self.tracer
        t_step = self._now()
        running_at_entry = self._running_count()
        tokens_at_entry = self._tokens_emitted
        self._dispatched = {}
        phases = self._phase_ns = {}
        self._device_calls = 0
        self._ahead, self._dry = None, False
        table_puts0 = self.pool.table_puts if self._paged else 0
        # from the previous step's sync on the host is on its own
        # (serving/enqueue closes the interval: `exposed`)
        self._exposed_from_ns, self._sync_end_ns = self._sync_end_ns, None
        gc_ns0 = gc_ns_total()
        with _StepSpan(tracer, "serving/step", {
                "step": self.step_id,
                "in_flight": int(self._in_flight is not None)}) as sp_step:
            self._step_t0_ns = sp_step.t0_ns
            # boundary work first, outside the abort scope: expiring a
            # deadline or walking the load ladder touches no device
            # state, so a failure here must not FAIL innocent requests
            with self._phase("boundary", "serving/boundary"):
                self._expire_deadlines()
                self._update_load_state()
                self._auto_preempt()
                self._burn_preempt()
            self._track("serving/occupancy", live=self.live_count,
                        pending=self.scheduler.pending)
            with tracer.span("serving/grant") as sp:
                page_budget = self._grant_page_budget() if self._paged \
                    else None
                page_cost = self._page_cost if self._paged else None
                if self._stall_free:
                    # one chunk for the prefill-queue head will run this
                    # step; pre-charge it so admissions + chunk stay
                    # within budget
                    spent = self.prefill_chunk if self._prefill_queue else 0
                    granted = self.scheduler.grant(
                        self._grantable_slots(),
                        token_budget=self._effective_prefill_budget(),
                        cost=self._admission_cost, spent=spent,
                        page_budget=page_budget, page_cost=page_cost)
                else:
                    granted = self.scheduler.grant(
                        self._grantable_slots(),
                        page_budget=page_budget, page_cost=page_cost)
            phases["grant"] = sp.dur_ns
            try:
                if self._stall_free:
                    batches = ()
                    if granted:
                        with _PagesPhase(self):
                            batches = self._admit_stall_free(granted)
                    for width, group in batches:
                        if len(group) == 1:
                            # singleton: the per-request path (no
                            # sentinel padding, no scatter program) is
                            # strictly cheaper — the batched dispatch
                            # only pays off when it coalesces ≥2 prompts
                            self._admit(group[0])
                        else:
                            self._admit_batch(group, width)
                    self._prefill_chunk_step()
                else:
                    for req in granted:
                        self._admit(req)
                if self.faults is not None:
                    # the host-exception and slow-dispatch points sit
                    # between admission and decode: requests are seated
                    # (worst case for the abort path) but no decode
                    # state has moved yet
                    self.faults.maybe_sleep("slow_dispatch")
                    self.faults.check("step_host_error")
                if self._running_count() and self.role != "prefill":
                    t0 = self._now()
                    if self._spec is not None:
                        self._spec_decode_step(t0)
                    else:
                        self._decode_step(t0)
                # a chunk prepared for the decode dispatch went with it
                # or was dropped by it: its mirrors have moved, so one
                # left behind would be columns never written
                assert self._chunk_beside is None, "chunk never dispatched"
                # the step's ONE device sync, for the step BEFORE this
                # one: its every token/flag/counter in one fetch, then
                # the host bookkeeping replayed in dispatch order, while
                # the device runs what this step has just queued
                before, self._in_flight = self._in_flight, self._seal()
                self._settle(before)
                if not self._runs_ahead:
                    self._settle_all()
            except Exception:
                self._abort_step(granted)
                raise
            if self._ahead:
                self.registry.counter("serving/steps_run_ahead").inc()
            if self._dry:
                self.registry.counter("serving/steps_device_dry").inc()
            # the SLO tracker times its own methods: its part of the
            # after-step is taken out again, so telemetry_overhead_s
            # (which adds slo.overhead_s) never counts it twice
            slo_ns0 = self.slo.overhead_ns if self.slo is not None else 0
            self._dispatched["device_calls"] = self._device_calls
            if self._paged:
                # whole-table republications (a chunk that only mapped
                # its own row: 0; a release, a preemption, a decode slot
                # crossing a page boundary: 1 each)
                self._dispatched["table_puts"] = \
                    self.pool.table_puts - table_puts0
            with tracer.span("serving/after_step") as sp:
                wall = self._after_step(t_step, running_at_entry, granted)
            self._after_step_ns += sp.dur_ns
            if self.slo is not None:
                self._after_step_ns -= self.slo.overhead_ns - slo_ns0
            # the step's account, measured: each figure from spans that
            # closed inside the step (telemetry/tracer.py, PERF.md §3)
            account = {f"{k}_ns": phases[k] for k in (
                "enqueue", "prepare", "pages", "exposed") if k in phases}
            if gc_ns_total() > gc_ns0:
                account["gc_ns"] = gc_ns_total() - gc_ns0
            sp_step.set(dry=int(self._dry),
                        tokens=self._tokens_emitted - tokens_at_entry,
                        **self._dispatched, **account)
            if self._in_flight is not None:
                # what only the settled step knows (the routed FFN's
                # counters) is written onto this span when it is: the
                # step they are reported on is the step that ran them
                self._in_flight.attrs = sp_step.args
        if running_at_entry:
            # a running request waited through this WHOLE step for its
            # next token — the user-visible inter-token gap, admission
            # work included (what stall-free admission bounds)
            self.metrics.record_step_gap(wall)
        return self._take_finished()

    def _after_step(self, t_step: float, running_at_entry: int,
                    granted: List[Request]) -> float:
        """The serial host work after the step's sync and replay: paging
        gauges, telemetry, the recompile gate; no array of the step in
        flight is touched. Returns the step's wall on the injected
        clock."""
        tracer = self.tracer
        if self._paged:
            # per-step paging gauges (Prometheus export + dashboards):
            # occupancy and sharing level of the page pool
            free = self.pool.free_page_count
            shared = int(np.sum(self.pool.page_refs > 1))
            self.registry.gauge("paging/free_pages").set(float(free))
            self.registry.gauge("paging/pages_in_use").set(
                float(self.pool.num_pages - free))
            self.registry.gauge("paging/refcounted_pages").set(float(shared))
            self._track("paging/pages", free=free,
                        in_use=self.pool.num_pages - free, shared=shared)
            if self.pool.ring is not None:
                ring = self.pool.ring
                self.registry.gauge("paging/pages_mapped_full").set(
                    float(self.pool.num_pages - free))
                self.registry.gauge("paging/pages_mapped_window").set(
                    float(ring.mapped_count))
                self._dispatched.update(window_pages=ring.mapped_count,
                                        window_pages_total=ring.num_pages)
        if self.faults is not None and self.faults.fires("state_corruption"):
            # chaos: corrupt our own slot bookkeeping at the boundary so
            # check_invariants + the flight recorder face REAL damage
            self._chaos_corrupt_state()
        wall = self._now() - t_step
        self.step_wall_s += wall
        self._telemetry_step(wall, running_at_entry, granted)
        # strict-mode recompile gate sits at the step boundary: raising
        # mid-step would trigger _abort_step and FAIL innocent in-flight
        # requests, when the state is actually perfectly consistent
        try:
            self.watchdog.check()
        except RecompileAfterWarmupError as e:
            self._post_mortem("recompile_after_warmup", e)
            raise
        if self.step_wall_budget_ms is not None and \
                wall * 1e3 > self.step_wall_budget_ms:
            # per-step wall-time watchdog: flag, don't kill — one slow
            # step is an observability event; sustained slowness shows
            # up in step_gap p99 and drives the load-state machine
            self.metrics.record_step_overrun(wall, self.step_wall_budget_ms)
            tracer.instant("serving/step_overrun", wall_ms=wall * 1e3,
                           budget_ms=self.step_wall_budget_ms,
                           device_calls=self._device_calls,
                           dry=int(self._dry),
                           phases_ms={k: v / 1e6
                                      for k, v in self._phase_ns.items()})
        return wall

    def _effective_prefill_budget(self) -> Optional[int]:
        """The step's prefill token budget after degradation: PRESSURED
        halves it (floor: one chunk), OVERLOADED pins it at one chunk —
        admission slows before live decode latency does."""
        budget = self.prefill_token_budget
        if budget is None or self._load is None:
            return budget
        if self._load.state is LoadState.OVERLOADED:
            return max(self.prefill_chunk, 1)
        if self._load.state is LoadState.PRESSURED:
            return max(self.prefill_chunk, budget // 2)
        return budget

    def _update_load_state(self) -> None:
        if self._load is None:
            return
        cfg = self._degradation
        gaps = self.metrics.step_gaps[-cfg.window:]
        p99 = float(np.percentile(np.asarray(gaps), 99) * 1e3) \
            if gaps else None
        pending = self.scheduler.pending
        head = self.scheduler.head()
        if self._paged and head is not None and \
                self._page_cost(head) \
                > self._grant_page_budget():
            # page starvation is load even when the queue is short: an
            # oversubscribed pool that can't seat the queue head should
            # trip the ladder (and its retry_after shedding) just like
            # queue depth does, so degradation stays meaningful when
            # pages — not slots — are the scarce resource
            pending = max(pending, cfg.queue_pressured)
        moved = self._load.update(pending, p99, step=self.step_id)
        self._track("serving/load_state", level=int(self._load.state))
        if moved is not None:
            old, new = moved
            self.metrics.record_load_state(old, new)
            self.tracer.instant("serving/load_transition", old=old.name,
                                new=new.name, queue=self.scheduler.pending,
                                gap_p99_ms=p99)
            log_dist(f"ServingEngine: load {old.name} -> {new.name} "
                     f"(queue={self.scheduler.pending}, "
                     f"gap_p99_ms={p99})", ranks=[0])

    def _fail_slot(self, req: Request, reason: FinishReason) -> None:
        """Fail ONE seated request (poisoned logits): evict its slot via
        the rollback path and mark it FAILED, leaving every other slot's
        tokens from the same dispatch untouched."""
        self._evict_slot(req)
        req.state = RequestState.FAILED
        req.finish_reason = reason
        req.finish_time = self._now()
        self.metrics.record_failure(req)
        self.tracer.flow("f", "req", req.request_id)
        self.timelines.record(req.request_id, "failed", terminal=True,
                              reason=reason.value,
                              new_tokens=len(req.output_tokens))

    def _guard_rows(self, finite, running):
        """Replay half of the NaN/inf guard: given the fetched (B,) bool
        of per-row finiteness, return the survivors of ``running`` and
        fail the poisoned rows. Runs inside the deferred drain — the
        finite vector rode the step's one fetch instead of buying its
        own sync."""
        if finite is None:
            return running
        ok = [(slot, req) for slot, req in running if bool(finite[slot])]
        for slot, req in running:
            if not bool(finite[slot]) and \
                    req.state is RequestState.RUNNING:
                self._fail_slot(req, FinishReason.NUMERICAL_ERROR)
        return ok

    def _set_pool_reads(self, sp, rows: int, slots=None,
                        starts=None) -> None:
        """``pool_reads`` / ``read_slots`` / ``pool_read_pages`` on a
        decode, verify or chunk span whose dispatch read the pool through
        the kernel: the grid steps of its work list for one layer of each
        page group, the slots in it and the pages the steps fold (more
        than the steps where a step is a block: the latent read; counted
        like ``pool_writes``: after the dispatch, from the host's
        mirror). A chunk names its one slot and where it starts."""
        work = self.pool.pages_read(rows, slots, starts)
        if work is None:
            return
        sp.set(pool_reads=work[0], read_slots=work[1],
               pool_read_pages=work[2])

    def _state_rows(self, running) -> tuple:
        """For a model with a recurrent state, the decode program's
        ``rows``: the rows that run; every other row (free, or seated and
        still prefilling) is out of range, no step of the state kernels,
        and keeps its state bit for bit (the index rollback is what hides
        such a row's K/V column; it does nothing for a state). Put when
        the running set changes, not every step: the device array stays
        beside the slots it was built from."""
        if not self._state_row_bytes:
            return ()
        slots = tuple(slot for slot, _ in running)
        if self._rows_dev[0] != slots:
            rows = np.full((self.pool.num_slots,), -1, np.int32)
            rows[list(slots)] = slots
            self._rows_dev = (slots, self._cur_commit(rows))
        return (self._rows_dev[1],)

    def _decode_step(self, t0: float) -> None:
        """One decode program for every slot. Nothing is sent before it
        that the device holds: the program takes the (B,) current-token
        twin as it is and its positions from the cache's own ``index``
        (equal to the host's ``starts`` whenever a decode is queued: a
        chunk program sets its slot's entry, and the rollback below
        republishes it after a decode beside a prefilling slot). The host
        publishes: the page table when a running slot crosses a page
        boundary (``_ensure_decode_pages``), a state model's ``rows``
        when the running set changed, and the index after the program
        when a prefilling slot rode along. Where this step's chunk was
        left to this dispatch (``_prefill_chunk_step``), the one program
        is the chunk's and the decode's together
        (``PagedKVPool.run_chunk_decode``): it leaves the device where the
        two leave it, so everything after the call is the same."""
        eng = self.engine
        if self._paged:
            # page the write column in BEFORE snapshotting the running
            # set: under pressure this can preempt a victim out of it
            with _PagesPhase(self):
                self._ensure_decode_pages(1)
        running = [(slot, req) for slot, req in self._slot_req.items()
                   if self._runs(req)]
        self._dispatched["decode"] = len(running)
        more = self._state_rows(running)
        # the chunk this step left to this dispatch, unless paging the
        # write column in just preempted its request (the slot is free and
        # its pages are back in the heap: nothing of it may run)
        beside = self._chunk_beside
        self._chunk_beside = None
        if beside is not None and self._slot_req.get(beside[0].slot) \
                is not beside[0]:
            beside = None
        self._dispatched["fused"] = int(beside is not None)
        with self._phase("prepare", "serving/decode",
                         live=len(running)) as sp:
            self._note_state_rows(sp, len(running))
            self._note_sparse(sp, self.pool.starts[
                [slot for slot, _ in running]], decode=True)
            if self._latent_token_bytes:
                slots = [slot for slot, _ in running]
                self._note_latent(
                    sp, int(self.pool.starts[slots].sum()) + len(slots),
                    len(slots))
            if self._paged:
                if beside is not None:
                    req, ids, pos, L, t_chunk = beside
                    _, logits = self.pool.run_chunk_decode(
                        eng, ids, req.slot, pos, L, L - 1, self._cur_dev,
                        *more)
                    self.registry.counter("serving/fused_steps").inc()
                    self.metrics.record_prefill(L, self._now() - t_chunk,
                                                blocking=True)
                else:
                    logits = self.pool.run_decode(eng, self._cur_dev, *more)
                # counted after the dispatch, from a mirror the dispatch
                # does not move: every slot's row is in the program's
                # work list, the live ones map a page at their index
                sp.set(pool_writes=self.pool.pages_touched(
                    np.arange(self.pool.num_slots), self.pool.starts, 1))
                self._set_pool_reads(sp, 1)
            else:
                with self._enqueue("decode"):
                    logits, cache = eng._jit_decode(
                        eng.params, self.pool.cache, self._cur_dev, None,
                        *more)
        if self.faults is not None:
            logits, _ = self.faults.corrupt_logits(
                logits, [slot for slot, _ in running])
        # dispatch the finite check; the (B,) bool rides the step fetch
        finite_dev = None
        if self._jit_finite is not None and running:
            with self._enqueue("finite"):
                finite_dev = self._jit_finite(logits)
        if not self._paged:
            self.pool.cache = cache
        if self._prefill_queue:
            # PREFILLING slots rode along as masked padding: the decode
            # program advanced every device index by 1, so overwrite from
            # the mirror (running rows +1, prefilling rows unchanged) —
            # same index-rollback trick speculative decoding uses
            deltas = np.zeros((self.pool.num_slots,), np.int32)
            for slot, _ in running:
                deltas[slot] = 1
            self.pool.advance(deltas)
        else:
            self.pool.advance(1)
        with self._phase("prepare", "serving/sample"):
            nxt_dev = self._sample_dev(logits)
        # full-batch overwrite: every row's next current token IS this
        # decode's sample for that row (non-running rows hold garbage a
        # masked decode row can never surface, and any later admission
        # scatter overwrites them); re-committed to the canonical slots
        # placement — a free transfer when GSPMD already chose it
        self._cur_dev = self._cur_commit(nxt_dev)
        for _, req in running:
            self._queued_token(req)

        def _on_decode(nxt, finite=None, running=running):
            for _, req in running:
                self._read_token(req)
            live = self._guard_rows(finite, running)
            emitted = 0
            for slot, req in live:
                if req.state is not RequestState.RUNNING:
                    # ended by a token read before this one (an EOS or a
                    # poisoned row of the step before, whose value the
                    # host had not read when this row was queued): the
                    # one dead row such an end costs. It wrote a column
                    # the slot owned
                    continue
                token = int(nxt[slot])
                req.output_tokens.append(token)
                self._current[slot] = token
                emitted += 1
                self._maybe_retire(req, token)
            self._tokens_emitted += emitted
            self.metrics.record_decode_step(emitted, len(running),
                                            step_s=self._now() - t0)

        self._defer([nxt_dev] if finite_dev is None
                    else [nxt_dev, finite_dev], _on_decode)

    def _spec_decode_step(self, t0: float) -> None:
        """Draft K tokens per live slot, verify them all in ONE fixed-shape
        (num_slots, K+1) forward, keep each slot's accepted prefix + bonus
        token, and roll back rejected KV via the per-slot index."""
        eng = self.engine
        K = self._spec.k
        B = self.pool.num_slots
        if self._paged:
            # verify writes K+1 columns past every RUNNING slot's index;
            # page them in first (may preempt under pressure, so it runs
            # before the drafter snapshots the live set)
            with _PagesPhase(self):
                self._ensure_decode_pages(K + 1)

        # PREFILLING slots keep histories[slot] = None: the drafter
        # proposes nothing for them (draft_len 0) and their deltas stay
        # 0 below, so verify's masked garbage writes are rolled back by
        # the index overwrite and later overwritten by their next chunk
        if self._load is not None and \
                self._load.state is LoadState.OVERLOADED:
            # degradation: suspend speculation WITHOUT changing a shape —
            # zero-length drafts flow through the same verify_k program
            # (draft_len 0 reduces it to plain decode per row), so the
            # suspension and the recovery are both recompile-free
            draft = np.zeros((B, K), np.int32)
            draft_len = np.zeros((B,), np.int32)
            t_draft = 0.0
        else:
            # admissions sampled first tokens earlier THIS step: the
            # drafter's host-side histories need them, so settle them
            # now. Steady-state decode steps never take this early
            # settle, keeping the hot loop at exactly one sync per step.
            self._settle_all()
            histories: List[Optional[np.ndarray]] = [None] * B
            for slot, req in self._slot_req.items():
                if self._runs(req):
                    histories[slot] = req.tokens()
            with self._phase("prepare", "serving/draft", k=K):
                # (a model drafter's own programs and its fetch lie in
                # here, unmarked: no serving/enqueue of theirs)
                draft, draft_len = self._drafter.propose(histories, K)
            draft = np.asarray(draft, np.int32)
            draft_len = np.clip(np.asarray(draft_len, np.int32), 0, K)
            t_draft = self._now() - t0

        self._dispatched["decode"] = self._running_count()
        with self._phase("prepare", "serving/verify_k", k=K) as sp:
            # what only the host knows, in one put: the drafts and their
            # lengths. The program takes the current tokens from the
            # device twin, the positions from the cache's own index, and
            # the key, which it splits and hands back (as the sampler)
            with self._enqueue("verify_k", "transfer"):
                draft_dev, lens = jax.device_put((draft, draft_len))
            args = (self._cur_dev, draft_dev, lens, self._rng,
                    self._temperature(), self._greedy, int(self.top_k),
                    float(self.top_p))
            if self._paged:
                out_dev, n_emit_dev, self._rng = self.pool.run_verify(
                    eng, *args)
                sp.set(pool_writes=self.pool.pages_touched(
                    np.arange(self.pool.num_slots), self.pool.starts,
                    K + 1))
                self._set_pool_reads(sp, K + 1)
            else:
                with self._enqueue("verify_k"):
                    cache, out_dev, n_emit_dev, self._rng = eng.verify_k(
                        self.pool.cache, *args)
                self.pool.cache = cache
        # next step's current token per row is the last EMITTED one:
        # out[b, n_emit[b]-1] (n_emit >= 1 always for live rows)
        with self._enqueue("spec_cur"):
            self._cur_dev = self._jit_spec_cur(out_dev, n_emit_dev)
        live = [(slot, req) for slot, req in self._slot_req.items()
                if self._runs(req)]

        def _on_verify(out, n_emit, live=live, draft_len=draft_len):
            deltas = np.zeros((B,), np.int32)
            emitted = drafted = accepted = 0
            for slot, req in live:
                if req.state is not RequestState.RUNNING:
                    # retired by an earlier replay in this same drain;
                    # its verify row was masked padding
                    continue
                e = int(n_emit[slot])
                if int(self.pool.starts[slot]) >= self.pool.capacity:
                    # (how many tokens a verify emits is a value, so the
                    # full row is found here and not when it was queued)
                    self._closing.add(req.request_id)
                # the cache row holds e new positions regardless of how
                # many tokens the request actually consumes below: if
                # eos/budget truncates the emission, the request retires
                # this step, so the surplus becomes dead padding in a
                # freed slot
                deltas[slot] = e
                drafted += int(draft_len[slot])
                accepted += e - 1
                req.spec_drafted += int(draft_len[slot])
                req.spec_accepted += e - 1
                for token in out[slot, :e].tolist():
                    req.output_tokens.append(token)
                    self._current[slot] = token
                    emitted += 1
                    self._maybe_retire(req, token)
                    if req.state is not RequestState.RUNNING:
                        break
            self.pool.advance(deltas)      # per-slot KV rollback
            self._tokens_emitted += emitted
            self.metrics.record_decode_step(
                emitted, len(live), drafted=drafted, accepted=accepted,
                draft_s=t_draft, step_s=self._now() - t0)

        self._defer([out_dev, n_emit_dev], _on_verify)

    def _abort_step(self, granted: List[Request]) -> None:
        """Mid-step exception recovery: never leak a slot. Requests the
        failed admission already rolled back to QUEUED re-join the queue
        head; PREFILLING requests lose only cache state that can be
        rebuilt from the prompt, so they are scrubbed and re-queued too
        (ahead of the granted ones — they are older); running requests
        lose their (possibly donated-away) KV state and are FAILED; the
        pool restarts from a fresh cache. The step BEFORE the failed one,
        if it is still in flight, is settled first: its tokens are owed,
        and a request that ended with them has ended."""
        before, self._in_flight = self._in_flight, None
        if before is not None and before.step_id < self.step_id:
            try:
                self._settle(before)
            except Exception:   # (the device is what failed: the step's
                pass            #  own error is the one to propagate)
        requeued = [r for r in granted if r.state is RequestState.QUEUED]
        self.scheduler.requeue_front(requeued)
        for req in requeued:
            self.timelines.record(req.request_id, "requeued",
                                  reason="admit_error")
        prefilling = sorted(
            (r for r in self._slot_req.values()
             if r.state is RequestState.PREFILLING),
            key=lambda r: r.request_id)
        for req in prefilling:
            del self._slot_req[req.slot]
            req.slot = None
            req.admit_time = None
            req.prefill_pos = 0
            # output_tokens are NOT cleared: a preempted request mid-
            # re-prefill owns real generated tokens — they are its seed,
            # rebuilt from scratch on the next admission
            self.timelines.record(req.request_id, "requeued",
                                  reason="step_error")
        self.scheduler.requeue_front(prefilling)
        self._prefill_queue[:] = []
        self._chunk_beside = None
        for req in self._slot_req.values():
            req.state = RequestState.FAILED
            req.finish_reason = FinishReason.ERROR
            req.finish_time = self._now()
            self.metrics.record_failure(req)
            self.timelines.record(req.request_id, "failed", terminal=True,
                                  reason=FinishReason.ERROR.value)
        self._slot_req.clear()
        if self._handoff_ready:
            self._handoff_ready.clear()  # every member was seated -> FAILED
        self._current[:] = 0
        # drop queued-but-unfetched host bookkeeping: its device arrays
        # belong to the aborted step's state, and its requests are now
        # FAILED/requeued either way
        self._deferred.clear()
        self._unread.clear()
        self._closing.clear()
        self._cur_dev = jax.device_put(
            np.zeros((self.pool.num_slots,), np.int32),
            self._cur_sharding)
        self.pool.reset()

    def run_until_drained(self, max_steps: Optional[int] = None,
                          stall_patience: int = 32) -> List[Request]:
        """Step until the queue and every slot are empty (or ``max_steps``).
        Every healthy step with live work either emits a token, advances
        a prefill by a full chunk, or changes the queue/slot population,
        and every prompt and budget is finite — so a progress signature
        that sits IDENTICAL for ``stall_patience`` consecutive steps can
        only mean a livelock (scheduler bug, budget deadlock, preemption
        thrash). Rather than hang forever, that raises
        :class:`~deepspeed_tpu.serving.resilience.ServingStalledError`
        carrying a dump of every stuck request's state. The host is up
        to date when this returns (:meth:`settle`), at ``max_steps`` too."""
        out: List[Request] = []
        steps = 0
        last_sig = None
        still = 0
        while self.scheduler.pending or self._slot_req:
            out.extend(self.step())
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
            sig = self._progress_signature()
            if sig == last_sig:
                still += 1
                if still >= stall_patience:
                    dump = self._stuck_dump()
                    err = ServingStalledError(
                        f"no progress for {still} consecutive steps "
                        f"(step_id={self.step_id}, pending="
                        f"{self.scheduler.pending}, live="
                        f"{self.live_count}); stuck requests: {dump}",
                        dump=dump)
                    self._post_mortem("stalled", err,
                                      extra={"stuck": dump})
                    raise err
            else:
                still = 0
                last_sig = sig
        # (an EOS leaves its one dead row in flight behind an empty server)
        out.extend(self.settle())
        return out

    def _progress_signature(self) -> tuple:
        """Everything that must move for the drain loop to be making
        progress: queue/slot population, finished/failed totals, tokens
        generated and prefill positions of every seated request."""
        return (self.scheduler.pending, len(self._slot_req),
                len(self.metrics.finished), self.metrics.failed,
                tuple(sorted(
                    (r.request_id, r.state.value, len(r.output_tokens),
                     r.prefill_pos)
                    for r in self._slot_req.values())))

    def _stuck_dump(self) -> List[dict]:
        """Host-side state of every non-terminal request, for the
        ServingStalledError payload."""
        reqs = list(self._slot_req.values()) + list(self.scheduler.queue)
        return [{"request_id": r.request_id, "state": r.state.value,
                 "slot": r.slot, "prefill_pos": r.prefill_pos,
                 "seed_len": r.seed_len,
                 "new_tokens": len(r.output_tokens),
                 "max_new_tokens": r.max_new_tokens,
                 "preemptions": r.preemptions,
                 "deadline_ms": r.deadline_ms} for r in reqs]

    def check_invariants(self) -> None:
        """Audit the engine/pool/scheduler cross-bookkeeping; raises
        :class:`~deepspeed_tpu.serving.resilience.InvariantViolation`
        listing every violation (never just the first) if any state is
        inconsistent. The chaos suite calls this after every injected
        fault — the contract is that NO fault, wherever injected, may
        leak a slot or strand a request. The step in flight is settled
        first: the audit judges a host that has read what it queued."""
        self._settle_early("audit")
        errors = list(self.pool.consistency_errors())
        if self._unread or self._closing:
            errors.append(f"tokens counted in flight with nothing in "
                          f"flight: unread {self._unread}, closing "
                          f"{sorted(self._closing)}")
        seated = set(self._slot_req.keys())
        free = set(self.pool._free_set)
        overlap = seated & free
        if overlap:
            errors.append(f"slots both seated and free: {sorted(overlap)}")
        missing = set(range(self.pool.num_slots)) - seated - free
        if missing:
            errors.append(f"slots leaked (neither seated nor free): "
                          f"{sorted(missing)}")
        for slot, req in self._slot_req.items():
            if req.slot != slot:
                errors.append(f"slot map disagrees: _slot_req[{slot}] has "
                              f"req {req.request_id} with req.slot="
                              f"{req.slot}")
            if req.state not in (RequestState.RUNNING,
                                 RequestState.PREFILLING):
                errors.append(f"seated req {req.request_id} in state "
                              f"{req.state.value}")
        prefilling_ids = sorted(
            r.request_id for r in self._slot_req.values()
            if r.state is RequestState.PREFILLING)
        queue_ids = sorted(r.request_id for r in self._prefill_queue)
        if prefilling_ids != queue_ids:
            errors.append(f"PREFILLING seated requests {prefilling_ids} != "
                          f"prefill queue {queue_ids}")
        for r in self.scheduler.queue:
            if r.state is not RequestState.QUEUED:
                errors.append(f"queued req {r.request_id} in state "
                              f"{r.state.value}")
            if r.slot is not None:
                errors.append(f"queued req {r.request_id} still holds "
                              f"slot {r.slot}")
        # seated slots only: a free slot's start is whatever the decode
        # program's uniform +1 counted it up to since it was last seated
        # (SlotPool.positions clamps it), past the capacity after 2,048
        # idle steps — 25 s of serving at 12 ms a step
        seated = sorted(s for s in self._slot_req
                        if 0 <= s < self.pool.num_slots)
        starts = self.pool.starts[seated]
        if np.any(starts < 0) or np.any(starts > self.pool.capacity):
            errors.append(f"cache starts of seated slots {seated} out of "
                          f"[0, {self.pool.capacity}]: {starts.tolist()}")
        for r in (self._handoff_ready or ()):
            # a parked handoff must still be a live seat HERE — anything
            # else means a retire/transfer path forgot to purge it
            if r.state is not RequestState.RUNNING or r.slot is None \
                    or self._slot_req.get(r.slot) is not r:
                errors.append(f"handoff-ready req {r.request_id} not "
                              f"seated RUNNING (state={r.state.value}, "
                              f"slot={r.slot})")
        if errors:
            err = InvariantViolation(errors)
            self._post_mortem("invariant_violation", err,
                              extra={"violations": errors})
            raise err

    def stats(self) -> dict:
        """Aggregate SLO snapshot (see ServingMetrics.snapshot); with
        paged KV a ``"paging"`` sub-dict carries the page-pool and
        prefix-cache counters (see PagedKVPool.page_stats)."""
        snap = self.metrics.snapshot()
        if self._paged:
            snap["paging"] = self.pool.page_stats()
        return snap
