"""The serving step's account (PR 34), as its five readers see it
(``perf/STEP_ACCOUNT.md``). Since PR 34 the program opens a
``serving/enqueue`` span around every call of a jitted program and says at
the close of ``serving/step`` how many calls handed the device work, the
puts and eager operations among them (``device_calls``); this file lays
those spans over the window that
``program_spans.place_window`` finds and gives, for each step of it:

* ``exposed_ms``: end of the LAST ``serving/sync`` of the step before to
  the end of this step's FIRST ``serving/enqueue`` of kind ``program``:
  the host time during which the device had nothing queued, as far as the
  host can know. ``None`` for the window's first step, after a step that
  ended in no sync (it only queued a chunk: the device was still busy) and
  for a step that queued no program;
* ``enqueue_ms``: the step's ``serving/enqueue`` spans, summed;
* ``prepare_ms``: the dispatch spans and ``serving/sample``, less the
  enqueue spans inside them (a ``serving/sample`` that lies inside an
  admission's span is that span's, not counted twice);
* ``device_calls``: the step's own count, from its attributes;
* ``profiled``: whether a profiler session was recording when the step
  closed.

A ring without ``serving/enqueue`` (a parent commit from before PR 34, a
program without the tracer) gives ``None``: every reader then returns
``None`` and the metric is left out of the line."""

from __future__ import annotations

from typing import List, Optional, Sequence

from perf import program_spans

ENQUEUE = "serving/enqueue"
SYNC = "serving/sync"
SAMPLE = "serving/sample"
# the spans under which the step prepares and queues its programs
PREPARE_SPANS = tuple(n for n in program_spans.DISPATCH_SPANS
                      if n.startswith("serving/")) + ("serving/draft",
                                                      SAMPLE)
# a traced stretch shorter than this has no interior to speak of
MIN_PROFILED_STEPS = 4


def inside(outer: dict, inner: dict) -> bool:
    return outer["t0"] <= inner["t0"] and inner["t1"] <= outer["t1"]


def top_level(spans: Sequence[dict]) -> List[dict]:
    """The spans that lie inside no other of ``spans``, by start."""
    return sorted((s for s in spans if not any(
        o is not s and inside(o, s) for o in spans)),
        key=lambda s: s["t0"])


def _outside_children(span: dict, enqueues: Sequence[dict]) -> float:
    return (span["t1"] - span["t0"]) - sum(
        e["t1"] - e["t0"] for e in enqueues if inside(span, e))


def window_rows(record: dict, events: Optional[Sequence[dict]] = None
                ) -> Optional[List[dict]]:
    """One row a step of the window (see the module's text), or None."""
    if events is None:
        events = program_spans.program_events()
    window = program_spans.place_window(record, events)
    if window is None:
        return None
    steps = window["steps"]
    inside = program_spans.children(events, steps,
                                    (ENQUEUE, SYNC) + PREPARE_SPANS)
    if not any(c[ENQUEUE] for c in inside):
        return None
    profiled = {(e.get("args") or {}).get("step"): bool(e.get("profiled"))
                for e in events
                if e.get("ph") == "X" and e["name"] == "serving/step"}
    rows, sync_end = [], None
    for step, kids in zip(steps, inside):
        enqueues = kids[ENQUEUE]
        programs = [e for e in enqueues
                    if e["args"].get("kind") == "program"]
        top = top_level([s for name in PREPARE_SPANS for s in kids[name]])
        rows.append({
            "step": step,
            "exposed_ms": (programs[0]["t1"] - sync_end) * 1e3
            if sync_end is not None and programs else None,
            "enqueue_ms": sum(e["t1"] - e["t0"] for e in enqueues) * 1e3,
            "prepare_ms": sum(_outside_children(s, enqueues)
                              for s in top) * 1e3,
            "device_calls": step["args"].get("device_calls"),
            "profiled": profiled.get(step["args"].get("step"), False),
        })
        sync_end = kids[SYNC][-1]["t1"] if kids[SYNC] else None
    return rows


def traced_interior(rows: Sequence[dict]) -> Optional[List[dict]]:
    """The steps of the traced stretch without its first and its last (the
    profiler's start stalls the loop before the first, and its stop after
    the last: ``PERF.md`` §7), or None where the window holds no traced
    stretch to speak of."""
    traced = [r for r in rows if r["profiled"]]
    if len(traced) < MIN_PROFILED_STEPS:
        return None
    return traced[1:-1]
