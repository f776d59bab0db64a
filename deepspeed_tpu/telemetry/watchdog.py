"""Recompile watchdog: turn "zero recompiles under churn" into a
runtime counter.

The serving tests pin the invariant that slot churn never triggers XLA
recompilation by diffing ``jitted._cache_size()`` before/after a wave.
This module promotes that into production telemetry:

* a process-global ``jax.monitoring`` duration listener counts every
  backend compile (``backend_compiles``, unattributed — JAX fires it
  for any program in the process) and leaves one ``setup/compile`` span
  per compile in the process-wide tracer: the program's name (the
  ``_WatchedJit``'s where the compile fell inside one, else JAX's own),
  the abstract signature where known, the seconds of each stage
  (tracing, lowering, the backend compile, which holds the retrieval
  from the persistent cache) and whether that cache hit;
* :class:`_WatchedJit` proxies wrap the named jitted entry points
  (``InferenceEngine._jit_*``, ``SlotPool._admit*_jit``); a call during
  which the global compile counter advanced is attributed a recompile
  under the program name plus the abstract shape signature of the
  offending call (``recompiles`` — the headline counter, counted after
  warmup).

Detection deliberately keys on the *backend compile* event, not on
``jitted._cache_size()`` growth: the C++ fastpath cache adds entries
for identical avals (e.g. numpy-backed vs device-resident inputs)
without lowering or compiling anything, so cache growth over-reports.
The compile-window attribution assumes watched programs are not called
concurrently from multiple threads (true for the serving/step loop);
a concurrent unrelated compile would at worst mislabel, never
undercount.

Each detection emits a ``telemetry/recompile`` event into the tracer,
registry, and monitor sinks. ``strict`` mode arms
:meth:`RecompileWatchdog.check` to raise
:class:`RecompileAfterWarmupError` — callers invoke it *between*
steps so an unexpected recompile aborts cleanly instead of corrupting
in-flight state.

``jax.monitoring`` listeners are global and cannot be removed
individually, so exactly one module-level listener is registered and
dispatches to a ``WeakSet`` of live watchdogs.
"""

from __future__ import annotations

import contextlib
import threading
import time
import weakref
from typing import Any, Dict, List, Optional

try:  # pragma: no cover - jax is always present in this repo
    from jax import monitoring as _jax_monitoring
    from jax import tree_util as _jax_tree_util
except Exception:  # pragma: no cover
    _jax_monitoring = None
    _jax_tree_util = None


class RecompileAfterWarmupError(RuntimeError):
    """Raised by strict-mode watchdogs when a warmed program recompiles."""


# ----------------------------------------------------------------------
# shape signatures
# ----------------------------------------------------------------------
def _sig_one(x: Any) -> str:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return f"{dtype}[{','.join(map(str, shape))}]"
    if isinstance(x, (list, tuple, dict)):
        leaves: List[Any] = []
        if _jax_tree_util is not None:
            try:
                leaves = _jax_tree_util.tree_leaves(x)
            except Exception:
                leaves = []
        if leaves:
            return f"tree({len(leaves)} leaves, first={_sig_one(leaves[0])})"
        return f"{type(x).__name__}()"
    if isinstance(x, (bool, int, float, str)) or x is None:
        return repr(x)
    return type(x).__name__


def abstract_signature(args: tuple, kwargs: Dict[str, Any]) -> str:
    """Cheap human-readable abstraction of a call's arg shapes.

    Only computed when a recompile was already detected, so it can
    afford the pytree walk.
    """
    parts = [_sig_one(a) for a in args]
    parts += [f"{k}={_sig_one(v)}" for k, v in sorted(kwargs.items())]
    return "(" + ", ".join(parts) + ")"


def _manifest_one(x: Any) -> str:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return f"{dtype}[{','.join(map(str, shape))}]"
    if isinstance(x, (dict, list, tuple)):
        return "*"
    return repr(x)


def manifest_signature(args: tuple, kwargs: Dict[str, Any]) -> str:
    """The warmup-manifest rendering of one watched call: top-level
    only — arrays as ``dtype[d1,d2]``, pytree containers as ``*``,
    python scalars (static_argnums operands here) by ``repr``.

    This grammar is the runtime twin of
    ``deepspeed_tpu.analysis.absdomain.expand_signatures``; graftcheck
    diffs the two sets byte-for-byte, so any change here must be
    mirrored there (pinned by tests/unit/analysis/test_signatures.py).
    """
    parts = [_manifest_one(a) for a in args]
    parts += [f"{k}={_manifest_one(v)}" for k, v in sorted(kwargs.items())]
    return "(" + ", ".join(parts) + ")"


def _fast_one(x: Any) -> str:
    shape = getattr(x, "shape", None)
    if shape is not None:
        return f"{getattr(x, 'dtype', '?')}[{','.join(map(str, shape))}]"
    if isinstance(x, dict):
        return f"dict{len(x)}"
    if isinstance(x, (list, tuple)):
        return f"{type(x).__name__}{len(x)}"
    if isinstance(x, bool) or x is None or isinstance(x, str):
        return repr(x)
    if isinstance(x, (int, float)):
        # jit traces python scalars as weak-typed values, so the *value*
        # does not change the executable; keying on it would explode the
        # signature space (e.g. a chunk position argument).
        return type(x).__name__
    return type(x).__name__


def fast_signature(args: tuple, kwargs: Dict[str, Any]) -> str:
    """Value-independent top-level signature, cheap enough for every
    call: arrays by dtype/shape, containers by length, scalars by type.
    Unlike :func:`abstract_signature` this never walks pytrees, so it
    can key the per-call cost accounting inside the ≤3% telemetry
    overhead budget."""
    parts = [_fast_one(a) for a in args]
    if kwargs:
        parts += [f"{k}={_fast_one(v)}" for k, v in sorted(kwargs.items())]
    return "|".join(parts)


def _key_one(x: Any):
    # tuple-atom twin of _fast_one: raw shape/dtype objects are hashable
    # and skip every f-string, which matters at one key per watched call
    shape = getattr(x, "shape", None)
    if shape is not None:
        return (shape, getattr(x, "dtype", None))
    if isinstance(x, (dict, list, tuple)):
        return (type(x).__name__, len(x))
    if isinstance(x, (bool, str)) or x is None:
        return x
    return type(x).__name__


def fast_key(args: tuple, kwargs: Dict[str, Any]) -> tuple:
    """Hashable tuple equivalent of :func:`fast_signature` — same
    abstraction, no string formatting; the per-call cost-accounting key."""
    if kwargs:
        return (tuple(map(_key_one, args)),
                tuple((k, _key_one(v)) for k, v in sorted(kwargs.items())))
    return tuple(map(_key_one, args))


# ----------------------------------------------------------------------
# per-program proxies
# ----------------------------------------------------------------------
class _WatchedJit:
    """Transparent wrapper over a jitted callable: a call during which
    the process-wide backend-compile counter advanced is reported to
    the watchers as a recompile of this program.

    Attribute access falls through to the wrapped function, so
    existing ``fn._cache_size()`` call sites keep working whether or
    not the attribute has been wrapped. Non-jit callables (tests
    inject plain lambdas) trigger no compiles and pass through
    without bookkeeping.
    """

    def __init__(self, fn, name: str):
        self._fn = fn
        self._name = name
        self._watchers: "weakref.WeakSet[RecompileWatchdog]" = \
            weakref.WeakSet()
        # ProgramCostModel instances accounting flops/bytes per call
        # (telemetry/costs.py); weak so dead servers drop off
        self._cost_models: "weakref.WeakSet" = weakref.WeakSet()
        # warmup signature manifest: every distinct manifest_signature
        # seen while recording (warmup); end_warmup() freezes it and the
        # frozen set is the runtime witness graftcheck diffs against
        self._manifest: set = set()
        self._recording = True
        _ensure_listener()

    def __call__(self, *args, **kwargs):
        global _in_watched_call
        if self._recording:
            self._manifest.add(manifest_signature(args, kwargs))
        start = _compile_events
        # compiles of this call wait here for the name and the signature
        held, _in_watched_call = _in_watched_call, []
        try:
            out = self._fn(*args, **kwargs)
        finally:
            compiles, _in_watched_call = _in_watched_call, held
            sig = abstract_signature(args, kwargs) if compiles else None
            for rec in compiles:
                _emit_compile(rec, self._name, sig)
        if _compile_events > start and self._watchers:
            sig = sig or abstract_signature(args, kwargs)
            for w in list(self._watchers):
                w.record(self._name, sig)
        if self._cost_models:
            for cm in list(self._cost_models):
                cm.account(self._name, self._fn, args, kwargs)
        return out

    def __getattr__(self, item):
        return getattr(self._fn, item)

    def __repr__(self):
        return f"_WatchedJit({self._name}, {self._fn!r})"


# ----------------------------------------------------------------------
# global jax.monitoring listener
# ----------------------------------------------------------------------
_active_watchdogs: "weakref.WeakSet[RecompileWatchdog]" = weakref.WeakSet()
_listener_lock = threading.Lock()
_listener_registered = False
# process-wide backend-compile tick; _WatchedJit snapshots it around
# each call to attribute compiles to the program that triggered them
_compile_events = 0
# depth of suppress_compile_events() scopes: AOT cost harvesting
# (telemetry/costs.py) compiles the same program out-of-band, which
# must not register as a serving recompile
_suppress_compiles = 0


@contextlib.contextmanager
def suppress_compile_events():
    """Hide backend compiles from the watchdogs for the scope, e.g. the
    AOT ``lower().compile()`` the cost model runs to harvest
    ``cost_analysis()`` for an already-warm executable."""
    global _suppress_compiles
    _suppress_compiles += 1
    try:
        yield
    finally:
        _suppress_compiles -= 1


# the stages of one compile, in the order JAX reports them; the backend
# compile closes it (a hit in the persistent cache is retrieved inside it)
_COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}
# seconds of the stages heard since the last backend compile. A stage
# overwrites: tracing a program traces the jits inside it first, and the
# outermost, whose time holds theirs, reports last
_stages_heard: Dict[str, float] = {}
# compile records of the _WatchedJit call in flight (None outside one)
_in_watched_call: Optional[List[Dict[str, Any]]] = None


def _emit_compile(rec: Dict[str, Any], program: Optional[str],
                  signature: Optional[str]) -> None:
    """One ``setup/compile`` span in the process-wide tracer."""
    from .tracer import default_tracer

    stages = rec["stages"]
    dur_ns = int(sum(v for k, v in stages.items()
                     if k != "cache_retrieval_s") * 1e9)
    default_tracer().complete(
        "setup/compile", rec["end_ns"] - dur_ns, dur_ns,
        program=program or rec["fun"], fun=rec["fun"], signature=signature,
        cache="hit" if "cache_retrieval_s" in stages else "miss", **stages)


def _on_event_duration(event: str, duration: float, **kw) -> None:
    global _compile_events
    stage = _COMPILE_STAGES.get(event)
    if stage is None:
        return
    _stages_heard[stage] = float(duration)
    if stage != "backend_s":
        return
    stages = dict(_stages_heard)
    _stages_heard.clear()
    if _suppress_compiles:
        return
    _compile_events += 1
    for w in list(_active_watchdogs):
        w._record_backend_compile(event, duration)
    rec = {"fun": kw.get("fun_name"), "end_ns": time.perf_counter_ns(),
           "stages": stages}
    if _in_watched_call is not None:
        _in_watched_call.append(rec)
    else:
        _emit_compile(rec, None, None)


def _ensure_listener() -> None:
    global _listener_registered
    with _listener_lock:
        if _listener_registered or _jax_monitoring is None:
            return
        _jax_monitoring.register_event_duration_secs_listener(
            _on_event_duration)
        _listener_registered = True


# registered with the package, not with the first watchdog: a trainer has
# no watchdog, and its compiles are set-up time all the same
_ensure_listener()


# ----------------------------------------------------------------------
# watchdog
# ----------------------------------------------------------------------
class RecompileWatchdog:
    """Counts and attributes recompiles; optionally raises after warmup.

    Lifecycle: construct → :meth:`attach` the jitted entry points →
    run warmup traffic → :meth:`end_warmup` → steady state. Recompiles
    recorded before ``end_warmup()`` land in ``warmup_recompiles``;
    after it they land in the headline ``recompiles`` counter, and in
    ``strict`` mode the next :meth:`check` raises.
    """

    def __init__(self, registry=None, tracer=None, monitor=None,
                 strict: bool = False, step_fn=None, name: str = "",
                 cost_model=None):
        self.registry = registry
        self.tracer = tracer
        self.monitor = monitor
        self.strict = strict
        self.name = name
        # optional ProgramCostModel; attach() subscribes it to every
        # proxy so per-call flops/bytes accounting rides the same seam
        self.cost_model = cost_model
        self._step_fn = step_fn or (lambda: 0)
        self._warmed = False
        self.warmup_recompiles = 0
        self._post_warmup = 0
        self._raised_at = 0
        self.backend_compiles = 0
        self.events: List[Dict[str, Any]] = []
        # proxies this watchdog attached: the source of the warmup
        # signature manifest (weak — shared proxies outlive no owner)
        self._proxies: "weakref.WeakSet[_WatchedJit]" = weakref.WeakSet()
        _active_watchdogs.add(self)
        _ensure_listener()

    # -- wiring --------------------------------------------------------
    def attach(self, owner: Any, attr: str,
               name: Optional[str] = None) -> Optional[_WatchedJit]:
        """Wrap ``owner.attr`` (idempotent; proxies are shared across
        watchdogs so a jitted entry is never double-wrapped)."""
        fn = getattr(owner, attr, None)
        if fn is None:
            return None
        if isinstance(fn, _WatchedJit):
            proxy = fn
        else:
            proxy = _WatchedJit(
                fn, name or f"{type(owner).__name__}.{attr}")
            setattr(owner, attr, proxy)
        proxy._watchers.add(self)
        self._proxies.add(proxy)
        if self.cost_model is not None:
            proxy._cost_models.add(self.cost_model)
        return proxy

    def attach_all(self, owner: Any, attrs) -> None:
        for attr in attrs:
            self.attach(owner, attr)

    # -- recording -----------------------------------------------------
    def record(self, program: str, signature: str) -> None:
        warmup = not self._warmed
        self.events.append({
            "program": program, "signature": signature,
            "warmup": warmup, "time": time.time(),
        })
        if warmup:
            self.warmup_recompiles += 1
        else:
            self._post_warmup += 1
        if self.registry is not None:
            key = "telemetry/recompiles_warmup" if warmup \
                else "telemetry/recompiles"
            self.registry.counter(key).inc()
        if self.tracer is not None:
            self.tracer.instant("telemetry/recompile", program=program,
                                signature=signature, warmup=warmup)
        mon = self.monitor
        if mon is not None and getattr(mon, "enabled", False) and not warmup:
            mon.write_events([("telemetry/recompile",
                               float(self._post_warmup),
                               int(self._step_fn()))])

    def _record_backend_compile(self, event: str, duration: float) -> None:
        self.backend_compiles += 1
        if self.registry is not None:
            self.registry.counter("telemetry/backend_compiles").inc()

    # -- lifecycle -----------------------------------------------------
    def end_warmup(self) -> None:
        self._warmed = True
        # freeze the warmup manifest: signatures seen from here on are
        # post-warmup traffic, which the static checker must already
        # cover via the warmup set (that is the invariant under test)
        for p in list(self._proxies):
            p._recording = False

    def signature_manifest(self) -> Dict[str, List[str]]:
        """program name → sorted warmup signatures, across every proxy
        this watchdog attached (the runtime half of the graftcheck
        manifest diff)."""
        out: Dict[str, set] = {}
        for p in list(self._proxies):
            if p._manifest:  # a never-dispatched proxy has no warmup set
                out.setdefault(p._name, set()).update(p._manifest)
        return {name: sorted(sigs) for name, sigs in sorted(out.items())}

    @property
    def warmed(self) -> bool:
        return self._warmed

    @property
    def recompiles(self) -> int:
        """Attributed recompiles observed after :meth:`end_warmup`."""
        return self._post_warmup

    def check(self) -> None:
        """Raise (strict mode only) if a warmed program recompiled since
        the last check. Call between steps, never inside a step."""
        if (self.strict and self._warmed
                and self._post_warmup > self._raised_at):
            new = self.events[-1] if self.events else {}
            self._raised_at = self._post_warmup
            raise RecompileAfterWarmupError(
                f"recompile after warmup ({self._post_warmup} total): "
                f"{new.get('program', '?')} {new.get('signature', '')}")

    def summary(self) -> Dict[str, Any]:
        return {
            "recompiles": self._post_warmup,
            "warmup_recompiles": self.warmup_recompiles,
            "backend_compiles": self.backend_compiles,
            "warmed": self._warmed,
            "programs": sorted({e["program"] for e in self.events}),
        }
