"""Low-overhead span tracer with Chrome trace-event / Perfetto export.

``Tracer.span`` is the ONE way the program times host work, and every
span has three sinks:

* a ``jax.profiler.TraceAnnotation`` opened under it, so whenever a
  profiler session runs (an xprof capture, ``chip_smoke.py``, the
  benchmark's ``--trace 1``) the span lies in the xplane's ``/host:CPU``
  plane on the profiler's clock, beside the device's operations. With no
  session the annotation costs ~0.4 us;
* an event in a bounded ring buffer on ``time.perf_counter_ns``
  (monotonic, ns resolution): the clock of ``Request.*_time``, of
  :class:`TimelineStore` and of a harness that reads
  ``time.perf_counter``. Each event notes under ``profiled`` whether a
  profiler session was active, so a reader can pick the traced stretch;
* Chrome trace-event / Perfetto export of that ring.

The tracer is deliberately dumb: nesting is never tracked explicitly —
Chrome's trace viewer infers nesting of complete ("X") events from
ts/dur containment per thread track, so a span stack on the host would
only add overhead.

What an event costs (PR 52). The ring holds one flat tuple an event
(:func:`_as_dict` names its fields), appended to a
``collections.deque(maxlen=capacity)``: the append is atomic under the
interpreter's lock, so the record path takes no lock, builds no dict,
and shares one ``tid`` object a thread. The dicts that
:meth:`Tracer.events`, :meth:`Tracer.to_chrome`, :func:`merge_chrome`
and the flight recorder hand out are built when they are READ, key for
key what the ring used to store; an event's ``args`` is the caller's own
dict, held by reference, so what is written onto it after the span
closed (the routed FFN's ``moe_*`` on a ``serving/step``, a step late)
is in every later export. Measured on the chip's host (``PERF.md`` §6,
PR 52): an event's ring part is 0.4 us inside a ``serving/step`` (which
asks the profiler once for all its events, :class:`_StepSpan`) and
0.5-0.7 us outside one, where the dict, the lock and the calls it
replaces took 1.2; the span that times it (two clock reads and the
annotation) takes ~1.2 us with the ring on or off. A record holds 172 B
where the dict took 381 B before its ``args``: with the ``args`` of a
server's plain decode step a full default ring of 131,072 holds 43.5 MB
where the 65,536 dicts held 35.3 MB, 3.6 KB a step of history where it
was 7.0. A plain decode step of a server leaves 11 events, so the
default ring holds ~11,900 of them: a window of 30 s fits while a step
takes 2.5 ms or more. A ring that wrapped says so: ``dropped`` here and
in every export, ``events_total`` beside it (a server's registry carries
both as the gauges ``telemetry/tracer_dropped`` /
``telemetry/tracer_events_total``).

:func:`default_tracer` is the process-wide tracer, ENABLED: engines use
it when no ``tracer=`` is passed. An explicit ``Tracer(enabled=False)``
silences the ring (its spans still time themselves and still open the
annotation). Events whose name starts with ``setup/`` (import, build,
one per compile) are kept outside the ring, so a long run's wrap-around
never loses how the process started.

Event kinds emitted (Chrome trace-event ``ph`` codes):

* ``X`` — complete span (``span()`` context manager / ``trace()``
  decorator), with ``ts``/``dur`` in ns internally, µs on export.
* ``i`` — instant event (``instant()``).
* ``C`` — counter sample (``counter()``).
* ``b``/``n``/``e`` — async nestable events keyed by ``(cat, id)``;
  used for per-request lifecycle tracks (``async_begin`` /
  ``async_instant`` / ``async_end``).
* ``s``/``f`` — flow start/finish (``flow()``), drawing arrows from a
  request's track into the engine-step spans that serviced it.

Fleet export: :func:`merge_chrome` renders SEVERAL tracers into one
Chrome/Perfetto document — one *process* lane per tracer (pid = fleet
position, ``process_name`` metadata from the label), all timestamps
normalized to the fleet-wide earliest event. Because the tracers share
one host ``perf_counter_ns`` clock, cross-replica ordering is exact,
and a flow pair emitted on two different tracers with the same
``(cat, id)`` renders as an arrow ACROSS process lanes — the journey
arrows the router draws at every handoff/transfer/failover boundary.
"""

from __future__ import annotations

import contextlib
import functools
import gc
import itertools
import json
import time
from collections import deque
from threading import get_ident
from time import perf_counter_ns
from typing import Any, Dict, List, Optional, Sequence, Tuple


try:  # pragma: no cover - jax is always present in this repo
    from jax.profiler import TraceAnnotation as _Annotation
except Exception:  # pragma: no cover
    _Annotation = None

# events whose name starts with this are kept outside the ring
SETUP_PREFIX = "setup/"
_SETUP_CAPACITY = 4096


def profiler_active() -> bool:
    """Whether a profiler session is recording TraceAnnotations now."""
    return _Annotation is not None and bool(_Annotation.is_enabled())


def _as_dict(rec: tuple) -> Dict[str, Any]:
    """The dict an event is read as: the keys of its kind, in the order
    the ring stored them when it stored dicts. One event of the ring is a
    flat record: ``(name, ph, ts, dur, tid, args, profiled)``, and ``cat``,
    ``id`` after them on an async or flow event. ``dur`` is None on all but
    a complete span; what a kind always carries (an instant's ``s``, a flow
    finish's ``bp``) is added here."""
    name, ph, ts, dur, tid, args, profiled = rec[:7]
    if ph == "X":
        return {"name": name, "ph": ph, "ts": ts, "dur": dur, "tid": tid,
                "args": args, "profiled": profiled}
    if ph == "C":
        return {"name": name, "ph": ph, "ts": ts, "tid": tid,
                "args": args, "profiled": profiled}
    if ph == "i":
        return {"name": name, "ph": ph, "ts": ts, "tid": tid, "s": "t",
                "args": args, "profiled": profiled}
    ev = {"name": name, "ph": ph, "cat": rec[7], "id": rec[8], "ts": ts,
          "tid": tid}
    if ph == "f":
        ev["bp"] = "e"  # bind to enclosing slice
    elif ph != "s":     # (a flow carries no args)
        ev["args"] = args
    ev["profiled"] = profiled
    return ev


class _Span:
    """Context manager timing a block: opens a profiler annotation,
    and records one complete ("X") event on exit if the tracer's ring
    is on. ``t0_ns`` / ``dur_ns`` stay readable after the block."""

    __slots__ = ("_tracer", "name", "args", "t0_ns", "dur_ns", "_ann")

    def __init__(self, tracer: "Tracer", name: str,
                 args: Optional[Dict[str, Any]]):
        self._tracer = tracer
        self.name = name
        self.args = args
        self.t0_ns = 0
        self.dur_ns = 0
        self._ann = None

    def set(self, **attrs):
        """Attach attributes to the span (visible in the trace viewer)."""
        if self.args is None:
            self.args = {}
        self.args.update(attrs)
        return self

    def __enter__(self):
        if _Annotation is not None:
            self._ann = _Annotation(self.name)
            self._ann.__enter__()
        self.t0_ns = perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.dur_ns = perf_counter_ns() - self.t0_ns
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        if self._tracer.enabled:
            args = self.args
            if exc_type is not None:
                args = dict(args) if args else {}
                args["error"] = exc_type.__name__
            self._tracer._record(self.name, "X", self.t0_ns, self.dur_ns,
                                 args)
        return False


class _StepSpan(_Span):
    """A span that many events close inside (``serving/step``): it asks
    ONCE, at its opening, whether a profiler session is recording, and the
    tracer marks every event up to its close with that answer instead of
    asking an event. A tracer used outside such a span keeps asking."""

    __slots__ = ()

    def __enter__(self):
        if self._tracer.enabled:
            self._tracer._profiled = profiler_active()
        return super().__enter__()

    def __exit__(self, exc_type, exc, tb):
        try:
            return super().__exit__(exc_type, exc, tb)
        finally:
            self._tracer._profiled = None


class Tracer:
    """Thread-safe bounded span recorder.

    ``capacity`` bounds host memory: once full, the oldest events are
    overwritten (ring buffer). ``events_total`` keeps counting, so
    ``events_total > capacity`` (``dropped`` > 0) tells you the window
    wrapped. The default holds ~11,900 plain decode steps of a server
    (11 events each): 30 s of steps of 2.5 ms.
    """

    def __init__(self, capacity: int = 131072, enabled: bool = True,
                 process_name: str = "deepspeed_tpu"):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.enabled = enabled
        self.process_name = process_name
        # flat records (_as_dict), oldest first; the append is atomic
        # under the interpreter's lock, so recording takes none
        self._ring: "deque[tuple]" = deque(maxlen=capacity)
        self._setup: List[tuple] = []   # setup/* events, kept
        # thread ident -> [the one int object its events share, how many
        # it has recorded]: each thread writes its own entry alone
        self._threads: Dict[int, list] = {}
        # what a step's owner knows of the profiler for the step's events
        # (ServingEngine.step asks once a step); None: ask an event
        self._profiled: Optional[bool] = None
        # wall-clock anchor so exports can be correlated across files
        self.epoch_ns = perf_counter_ns()
        self.epoch_unix = time.time()

    # ------------------------------------------------------------------
    # recording
    # ------------------------------------------------------------------
    def _record(self, name: str, ph: str, ts: int, dur: Optional[int],
                args: Optional[Dict[str, Any]], *ids) -> None:
        if not self.enabled:
            return
        profiled = self._profiled
        if profiled is None:
            profiled = profiler_active()
        ident = get_ident()
        mine = self._threads.get(ident)
        if mine is None:
            mine = self._threads.setdefault(ident, [ident, 0])
        rec = (name, ph, ts, dur, mine[0], args, profiled)
        if ids:
            rec += ids
        if name.startswith(SETUP_PREFIX) \
                and len(self._setup) < _SETUP_CAPACITY:
            self._setup.append(rec)
            return
        self._ring.append(rec)
        mine[1] += 1

    @property
    def events_total(self) -> int:
        """Events the ring has taken since it was made or cleared, the
        overwritten ones included (the kept ``setup/*`` aside)."""
        return sum(n for _, n in list(self._threads.values()))

    def span(self, name: str, **args):
        """Context manager timing a block: ``with tracer.span("x"): ...``"""
        return _Span(self, name, args or None)

    def complete(self, name: str, t0_ns: int, dur_ns: int, **args) -> None:
        """Record a span that is already over (its start and length on
        ``perf_counter_ns``): work a listener hears of only at its end,
        such as a compile, or that began before this module existed,
        such as the package's import."""
        self._record(name, "X", int(t0_ns), int(dur_ns), args or None)

    def trace(self, name: Optional[str] = None):
        """Decorator form of :meth:`span`."""
        def deco(fn):
            label = name or fn.__qualname__

            @functools.wraps(fn)
            def wrapper(*a, **kw):
                with self.span(label):
                    return fn(*a, **kw)
            return wrapper
        return deco

    def instant(self, name: str, **args) -> None:
        if self.enabled:
            self._record(name, "i", perf_counter_ns(), None, args or None)

    def counter(self, name: str, **values) -> None:
        """Counter track sample, e.g. ``counter("slots", live=3)``."""
        if self.enabled:
            self._record(name, "C", perf_counter_ns(), None, values)

    # --- async (per-request) tracks -----------------------------------
    def async_begin(self, cat: str, name: str, aid, **args) -> None:
        self._async("b", cat, name, aid, args)

    def async_instant(self, cat: str, name: str, aid, **args) -> None:
        self._async("n", cat, name, aid, args)

    def async_end(self, cat: str, name: str, aid, **args) -> None:
        self._async("e", cat, name, aid, args)

    def _async(self, ph: str, cat: str, name: str, aid,
               args: Dict[str, Any]) -> None:
        if self.enabled:
            self._record(name, ph, perf_counter_ns(), None, args or None,
                         cat, aid)

    def flow(self, ph: str, name: str, fid, cat: str = "flow") -> None:
        """Flow event: ``ph`` is ``"s"`` (start) or ``"f"`` (finish)."""
        if self.enabled:
            self._record(name, ph, perf_counter_ns(), None, None, cat, fid)

    # ------------------------------------------------------------------
    # inspection / export
    # ------------------------------------------------------------------
    @property
    def dropped(self) -> int:
        """Events overwritten by ring wrap-around (never exported)."""
        return max(0, self.events_total - self.capacity)

    def _snapshot(self, last: Optional[int] = None) -> List[tuple]:
        """The ring's records oldest first, or its ``last`` newest. A
        thread that appends meanwhile makes the deque's iterator raise:
        read again."""
        while True:
            try:
                if last is None:
                    return list(self._ring)
                return list(itertools.islice(reversed(self._ring),
                                             last))[::-1]
            except RuntimeError:
                continue

    def events(self) -> List[Dict[str, Any]]:
        """Snapshot of the kept ``setup/*`` events, then of the buffered
        events, each oldest first. The dicts are built here; an event's
        ``args`` is the recorder's own object. The collector is paused
        meanwhile: none of a full ring's dicts is garbage, and a full
        collection set off by the read would leave its ``host/gc`` span
        in the ring being read."""
        paused = gc.isenabled()
        gc.disable()
        try:
            return [_as_dict(r) for r in itertools.chain(
                list(self._setup), self._snapshot())]
        finally:
            if paused:
                gc.enable()

    def tail(self, n: int) -> List[Dict[str, Any]]:
        """``events()[-n:]`` without reading the ring's other events."""
        recs = self._snapshot(n) if n > 0 else []
        if len(recs) < n:
            recs = list(self._setup)[len(recs) - n:] + recs
        return [_as_dict(r) for r in recs]

    def clear(self) -> None:
        self._ring.clear()
        self._setup = []
        self._threads = {}

    def to_chrome(self) -> Dict[str, Any]:
        """Render the buffer as a Chrome trace-event JSON object.

        Timestamps are normalized to µs relative to the earliest
        buffered event; thread idents are remapped to small tids so
        Perfetto's track names stay readable.
        """
        evs = self.events()
        base = min((e["ts"] for e in evs), default=0)
        tids: Dict[int, int] = {}
        out: List[Dict[str, Any]] = []
        for ev in evs:
            tid = tids.setdefault(ev.get("tid", 0), len(tids))
            o = {"name": ev["name"], "ph": ev["ph"], "pid": 0, "tid": tid,
                 "ts": (ev["ts"] - base) / 1e3}
            if "dur" in ev:
                o["dur"] = ev["dur"] / 1e3
            for k in ("cat", "id", "s", "bp"):
                if k in ev:
                    o[k] = ev[k]
            if ev.get("args"):
                o["args"] = ev["args"]
            out.append(o)
        meta = [{"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
                 "args": {"name": self.process_name}}]
        for ident, tid in tids.items():
            meta.append({"name": "thread_name", "ph": "M", "pid": 0,
                         "tid": tid, "args": {"name": f"host-{tid}"}})
        return {
            "traceEvents": meta + out,
            "displayTimeUnit": "ms",
            "otherData": {
                "epoch_unix": self.epoch_unix,
                "events_total": self.events_total,
                "dropped": self.dropped,
            },
        }

    def export(self, path: str) -> int:
        """Write the Perfetto/Chrome JSON trace; returns event count."""
        trace = self.to_chrome()
        with open(path, "w") as f:
            json.dump(trace, f)
        return len(trace["traceEvents"])


_DEFAULT_TRACER = Tracer()


def default_tracer() -> Tracer:
    """The process-wide tracer, enabled: what an engine records into when
    it is given no ``tracer=``."""
    return _DEFAULT_TRACER


# what a device call that gets no span is wrapped in
NO_SPAN = contextlib.nullcontext()


def enqueue_span(program: str, kind: str = "program"):
    """``serving/enqueue`` around ONE call that hands the device work.
    ``kind`` ``program`` (a jitted program) gets the span, which ends when
    the call returns: that is when the work is queued. ``transfer`` (host
    arrays put on the device, an eager operation on a device array) gets
    none: a serving step makes four to eight of them, and a span each
    cost more than the account may (PERF.md §6, PR 34); a server counts
    them in ``device_calls``. This is what a pool without a server opens,
    in the process-wide tracer; a ``ServingEngine`` hands its pool its own
    (``ServingEngine._enqueue``), which opens the same span in the
    server's tracer and keeps the step's account besides."""
    if kind != "program":
        return NO_SPAN
    return _DEFAULT_TRACER.span("serving/enqueue", program=program,
                                kind=kind)


# ---------------------------------------------------------------------------
# full garbage collections
# ---------------------------------------------------------------------------
class _GcWatch:
    """The ``gc.callbacks`` entry behind :func:`watch_gc`."""

    def __init__(self):
        self.total_ns = 0
        self._span: Optional[_Span] = None

    def __call__(self, phase: str, info: Dict[str, Any]) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._span = _DEFAULT_TRACER.span("host/gc", generation=2)
            self._span.__enter__()
        elif self._span is not None:
            sp, self._span = self._span, None
            sp.set(collected=info["collected"])
            sp.__exit__(None, None, None)
            self.total_ns += sp.dur_ns


_GC_WATCH = _GcWatch()


def watch_gc() -> None:
    """Leave a ``host/gc`` span (``generation``, ``collected``) in the
    process-wide tracer for every FULL collection from now on: a
    generation-2 pass stops the interpreter for tens of milliseconds, and
    a step that held one is otherwise a stall with no name. Collections
    of generations 0 and 1 return at once. Installed once a process, by
    the first engine that records into :func:`default_tracer`."""
    if _GC_WATCH not in gc.callbacks:
        gc.callbacks.append(_GC_WATCH)


def gc_ns_total() -> int:
    """Nanoseconds spent in the full collections :func:`watch_gc` has
    seen: read at both ends of a stretch to know what fell inside."""
    return _GC_WATCH.total_ns


# ---------------------------------------------------------------------------
# multi-process (fleet) merge
# ---------------------------------------------------------------------------
def merge_chrome(tracers: Sequence[Tuple[str, "Tracer"]]) -> Dict[str, Any]:
    """Merge several tracers into ONE Chrome trace-event document.

    ``tracers`` is an ordered ``(label, tracer)`` sequence; position in
    the sequence becomes the Perfetto *pid* and ``label`` its
    ``process_name`` — a DP fleet renders as one lane per replica (plus
    the router's own lane). Timestamps are normalized to the earliest
    event ACROSS the whole fleet: every tracer reads the same
    process-wide ``perf_counter_ns`` clock, so relative ordering
    between lanes is exact, and a flow ``s``/``f`` pair whose halves
    were recorded on two different tracers (same ``cat`` + ``id``)
    draws its arrow across the process boundary — how a request's
    handoff/transfer/failover hops stay visually connected.
    """
    snap = [(str(label), tr.events()) for label, tr in tracers]
    base = min((e["ts"] for _, evs in snap for e in evs), default=0)
    out: List[Dict[str, Any]] = []
    meta: List[Dict[str, Any]] = []
    events_total = 0
    dropped = 0
    for pid, ((label, evs), (_, tr)) in enumerate(zip(snap, tracers)):
        meta.append({"name": "process_name", "ph": "M", "pid": pid,
                     "tid": 0, "args": {"name": label}})
        tids: Dict[int, int] = {}
        for ev in evs:
            tid = tids.setdefault(ev.get("tid", 0), len(tids))
            o = {"name": ev["name"], "ph": ev["ph"], "pid": pid,
                 "tid": tid, "ts": (ev["ts"] - base) / 1e3}
            if "dur" in ev:
                o["dur"] = ev["dur"] / 1e3
            for k in ("cat", "id", "s", "bp"):
                if k in ev:
                    o[k] = ev[k]
            if ev.get("args"):
                o["args"] = ev["args"]
            out.append(o)
        for tid in tids.values():
            meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                         "tid": tid, "args": {"name": f"host-{tid}"}})
        events_total += tr.events_total
        dropped += tr.dropped
    return {
        "traceEvents": meta + out,
        "displayTimeUnit": "ms",
        "otherData": {
            "processes": {str(i): label
                          for i, (label, _) in enumerate(snap)},
            "events_total": events_total,
            "dropped": dropped,
        },
    }


def export_merged(path: str,
                  tracers: Sequence[Tuple[str, "Tracer"]]) -> int:
    """Write a :func:`merge_chrome` fleet trace; returns event count."""
    trace = merge_chrome(tracers)
    with open(path, "w") as f:
        json.dump(trace, f)
    return len(trace["traceEvents"])
